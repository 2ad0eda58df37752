"""Bounded-staleness async aggregation (DESIGN.md §14): the weighting law.

Fast tier-1 coverage of the unified round/staleness policy
(utils/rounds.py) at both deployment scales it serves: the pure weight
law (decay, hard cutoff, exact identity at tau=0), its composition into
the folded-attack fast path (parallel/fold.py ``row_weights`` — the Gram
algebra must equal weighting the rows), the in-graph emulation on the
aggregathor topology (``staleness=``; --max_staleness 0 is BITWISE the
synchronous program), convergence under a slow Byzantine rank, and the
telemetry v4 staleness plumbing (suspicion folding, schema validation,
Prometheus histogram). The multi-process host-plane twins live in
tests/test_async_cluster.py (slow).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu import data as data_lib
from garfield_tpu.aggregators import gars
from garfield_tpu.attacks import apply_gradient_attack
from garfield_tpu.models import select_model
from garfield_tpu.parallel import aggregathor, core, fold
from garfield_tpu.utils import rounds, selectors


class TestWeights:
    def test_decay_and_cutoff(self):
        w = rounds.staleness_weights(
            np.array([0, 1, 2, 3, 4, 5, 9]), decay=0.5, max_staleness=4
        )
        np.testing.assert_array_equal(
            w, np.array([1.0, 0.5, 0.25, 0.125, 0.0625, 0.0, 0.0],
                        np.float32),
        )
        assert w.dtype == np.float32

    def test_tau_zero_is_exactly_one(self):
        # The --max_staleness 0 bitwise contract rests on this: a fresh
        # row's weight is EXACTLY 1.0, whatever the decay.
        for decay in (0.3, 0.5, 0.9, 1.0):
            w = rounds.staleness_weights(
                np.array([0]), decay=decay, max_staleness=8
            )
            assert w[0] == np.float32(1.0)

    def test_negative_tau_clamps(self):
        # A frame tagged AHEAD of the consumer (catch-up race) is fresh.
        w = rounds.staleness_weights(
            np.array([-3, 0]), decay=0.5, max_staleness=2
        )
        np.testing.assert_array_equal(w, [1.0, 1.0])

    def test_jnp_matches_np_and_jits(self):
        taus = np.array([0, 1, 3, 7])
        w_np = rounds.staleness_weights(taus, decay=0.7, max_staleness=5)
        w_j = jax.jit(
            lambda t: rounds.staleness_weights(
                t, decay=0.7, max_staleness=5
            )
        )(jnp.asarray(taus))
        np.testing.assert_array_equal(np.asarray(w_j), w_np)

    def test_discount_rows(self):
        stack = np.arange(12, dtype=np.float32).reshape(4, 3)
        w = np.array([1.0, 0.5, 0.25, 0.0], np.float32)
        out = rounds.discount_rows(stack, w)
        np.testing.assert_array_equal(out, stack * w[:, None])
        # w == 1 everywhere is a bitwise no-op (IEEE multiply).
        ones = np.ones(4, np.float32)
        assert np.array_equal(rounds.discount_rows(stack, ones), stack)

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            rounds.StalenessPolicy(-1, 0.5)
        with pytest.raises(ValueError):
            rounds.StalenessPolicy(2, 0.0)
        with pytest.raises(ValueError):
            rounds.StalenessPolicy(2, 1.5)

    def test_resolve_env_defaults(self, monkeypatch):
        class A:
            async_agg = True
            max_staleness = None
            staleness_decay = None

        monkeypatch.setenv("GARFIELD_MAX_STALENESS", "7")
        monkeypatch.setenv("GARFIELD_STALENESS_DECAY", "0.8")
        p = rounds.resolve(A())
        assert (p.max_staleness, p.decay) == (7, 0.8)

        class B:
            async_agg = False

        assert rounds.resolve(B()) is None


def _tiny_tree(key, n=8):
    """A small stacked gradient tree (two leaves) for fold tests."""
    k1, k2 = jax.random.split(key)
    return {
        "a": jax.random.normal(k1, (n, 6, 3), jnp.float32),
        "b": jax.random.normal(k2, (n, 5), jnp.float32),
    }


class TestWeightedFold:
    def _reference(self, gar, tree, w, byz_mask, f, attack="lie"):
        """Where-path reference: poison the flat stack, weight the rows,
        aggregate — the semantics the Gram composition must reproduce."""
        flat = core.flatten_rows(tree)
        poisoned = apply_gradient_attack(attack, flat, byz_mask)
        weighted = poisoned * jnp.asarray(w)[:, None]
        return gar.unchecked(weighted, f=f)

    def test_fold_row_weights_match_weighted_rows(self):
        n, f = 8, 2
        gar = gars["krum"]
        byz_mask = core.default_byz_mask(n, f)
        tree = _tiny_tree(jax.random.PRNGKey(0), n)
        w = rounds.staleness_weights(
            np.array([0, 0, 1, 0, 2, 0, 3, 1]), decay=0.5, max_staleness=4
        )
        plan = fold.plan_for(gar, "lie", byz_mask, {})
        assert plan is not None
        got = fold.folded_tree_aggregate(
            gar, plan, tree, f=f, row_weights=jnp.asarray(w)
        )
        got_flat = jnp.concatenate(
            [l.reshape(-1) for l in jax.tree.leaves(got)]
        )
        ref = self._reference(gar, tree, w, byz_mask, f)
        np.testing.assert_allclose(
            np.asarray(got_flat), np.asarray(ref), rtol=2e-5, atol=1e-6
        )

    def test_fold_row_weights_bitwise_deterministic(self):
        n, f = 8, 2
        gar = gars["krum"]
        byz_mask = core.default_byz_mask(n, f)
        tree = _tiny_tree(jax.random.PRNGKey(1), n)
        w = jnp.asarray(rounds.staleness_weights(
            np.array([0, 1, 0, 2, 0, 0, 4, 3]), decay=0.5, max_staleness=4
        ))
        a = fold.folded_tree_aggregate(gar, plan := fold.plan_for(
            gar, "lie", byz_mask, {}
        ), tree, f=f, row_weights=w)
        b = fold.folded_tree_aggregate(
            gar, plan, tree, f=f, row_weights=w
        )
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_row_weights_rejected_off_gram_rules(self):
        n, f = 8, 2
        gar = gars["median"]  # tree_aggregate_ext fold, no gram_select
        byz_mask = core.default_byz_mask(n, f)
        plan = fold.plan_for(gar, "lie", byz_mask, {})
        assert plan is not None
        with pytest.raises(ValueError, match="row_weights"):
            fold.folded_tree_aggregate(
                gar, plan, _tiny_tree(jax.random.PRNGKey(2), n), f=f,
                row_weights=jnp.ones((n,)),
            )


def _pima_setup():
    module = select_model("pimanet", "pima")
    loss = selectors.select_loss("bce")
    opt = selectors.select_optimizer(
        "sgd", lr=0.05, momentum=0.0, weight_decay=0.0
    )
    return module, loss, opt


def _pima_batches(n, bsz):
    m = data_lib.DatasetManager("pima", bsz, n, n, 0)
    m.num_ps = 0
    xs, ys = m.sharded_train_batches()
    return xs, jnp.asarray(xs[:, 0]), jnp.asarray(ys[:, 0])


def _run(step_fn, state, x, y, iters):
    losses = []
    for _ in range(iters):
        state, m = step_fn(state, x, y)
        losses.append(float(m["loss"]))
    return state, losses


def _flat_params(state):
    return np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree.leaves(state.params)]
    )


class TestEmulation:
    def test_max_staleness_zero_is_bitwise_synchronous(self):
        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        runs = []
        for staleness in (None, {"max_staleness": 0, "decay": 0.5}):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie", staleness=staleness,
            )
            state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
            state, losses = _run(step_fn, state, x, y, 6)
            runs.append((losses, _flat_params(state)))
        assert runs[0][0] == runs[1][0]
        np.testing.assert_array_equal(runs[0][1], runs[1][1])

    def test_all_zero_taus_is_bitwise_synchronous(self):
        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        runs = []
        for staleness in (
            None,
            {"max_staleness": 3, "decay": 0.5, "taus": [0] * 8},
        ):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, "median", num_workers=8, f=1,
                attack="reverse", staleness=staleness,
            )
            state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
            state, losses = _run(step_fn, state, x, y, 5)
            runs.append(losses)
        assert runs[0] == runs[1]

    def test_weighted_tree_matches_flat_path(self):
        # The fold composition (tree path, Gram algebra) and the flat
        # path (rows weighted explicitly) must train identically.
        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        staleness = {
            "max_staleness": 4, "decay": 0.5,
            "taus": [0, 0, 1, 0, 2, 0, 3, 4],
        }
        states = []
        for tree_path in (True, False):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie", staleness=staleness, tree_path=tree_path,
            )
            state = init_fn(jax.random.PRNGKey(1), xs[0, 0])
            state, losses = _run(step_fn, state, x, y, 4)
            assert all(np.isfinite(l) for l in losses)
            states.append(_flat_params(state))
        np.testing.assert_allclose(
            states[0], states[1], rtol=2e-5, atol=1e-6
        )

    def test_random_taus_deterministic_and_finite(self):
        # Seeded per-step draws: two identical runs agree bitwise.
        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        runs = []
        for _ in range(2):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie",
                staleness={"max_staleness": 3, "decay": 0.7},
            )
            state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
            state, losses = _run(step_fn, state, x, y, 5)
            runs.append(losses)
        assert runs[0] == runs[1]
        assert all(np.isfinite(l) for l in runs[0])

    def test_lie_attack_converges_with_slow_byzantine_rank(self):
        # The acceptance smoke at unit scale: the Byzantine rank is ALSO
        # the straggler (max staleness — its lie rows enter the GAR
        # discounted), krum at f=1 must train through it.
        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        init_fn, step_fn, _ = aggregathor.make_trainer(
            module, loss, opt, "krum", num_workers=8, f=1, attack="lie",
            staleness={
                "max_staleness": 4, "decay": 0.5,
                # Rank 7 is the Byzantine slot (core.default_byz_mask
                # marks the LAST f ranks) — and the slow one.
                "taus": [0, 0, 0, 0, 0, 0, 0, 4],
            },
        )
        state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
        state, losses = _run(step_fn, state, x, y, 40)
        assert losses[-1] < losses[0] * 0.7, losses[::8]

    def test_bad_staleness_config_rejected(self):
        module, loss, opt = _pima_setup()
        with pytest.raises(ValueError, match="unknown staleness"):
            aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie", staleness={"max_stale": 3},
            )
        with pytest.raises(ValueError, match="shape"):
            aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie",
                staleness={"max_staleness": 3, "taus": [0, 1]},
            )


class TestTelemetryV4:
    def test_hub_folds_staleness_into_suspicion(self):
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=4)
        for step in range(10):
            hub.record_event(
                "staleness", who="t", step=step,
                ranks=[0, 1, 3], staleness=[0, 1, 4],
                weights=[1.0, 0.5, 0.0625], reused=2,
            )
        susp = hub.suspicion()
        # Rank 0 fresh (deficit 0), rank 1 deficit 0.5, rank 3 ~0.94;
        # rank 2 never observed.
        assert susp[0] == pytest.approx(0.0)
        assert susp[1] == pytest.approx(0.5)
        assert susp[3] == pytest.approx(1 - 0.0625)
        st = hub.staleness_stats()
        assert st["count"] == 30 and st["max"] == 4
        assert st["hist"] == {0: 10, 1: 10, 4: 10}
        assert st["mean"] == pytest.approx(5 / 3)

    def test_summary_staleness_block_validates(self):
        from garfield_tpu.telemetry import exporters
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=3)
        hub.record_event(
            "staleness", who="t", step=0, ranks=[0, 1],
            staleness=[0, 2], weights=[1.0, 0.25], reused=1,
        )
        rec = hub.summary()
        exporters.validate_record(rec)
        assert rec["staleness"]["count"] == 2
        # Synchronous hubs stay v3-shaped (staleness None).
        rec2 = MetricsHub(num_ranks=3).summary()
        exporters.validate_record(rec2)
        assert rec2["staleness"] is None

    def test_validate_staleness_event(self):
        from garfield_tpu.telemetry import exporters

        good = exporters.make_record(
            "event", event="staleness", step=3, ranks=[0, 1],
            staleness=[0, 2], weights=[1.0, 0.25],
        )
        exporters.validate_record(good)
        bad = dict(good, weights=[1.0])  # length mismatch
        with pytest.raises(ValueError):
            exporters.validate_record(bad)
        bad2 = dict(good, step=-1)
        with pytest.raises(ValueError):
            exporters.validate_record(bad2)

    def test_prometheus_staleness_histogram(self):
        from garfield_tpu.telemetry import exporters
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=2)
        hub.record_event(
            "staleness", who="t", step=0, ranks=[0, 1],
            staleness=[0, 3], weights=[1.0, 0.125],
        )
        text = exporters.prometheus_text(hub)
        assert 'garfield_staleness_rounds_bucket{le="0"} 1' in text
        assert 'garfield_staleness_rounds_bucket{le="+Inf"} 2' in text
        assert "garfield_staleness_rounds_count 2" in text
        assert "garfield_staleness_rounds_max" in text
        # Synchronous hubs expose no staleness family at all.
        assert "garfield_staleness" not in exporters.prometheus_text(
            MetricsHub(num_ranks=2)
        )


class TestLearnEmulation:
    """LEARN per-phase staleness emulation (parallel/learn ``staleness=``,
    DESIGN.md §15): the decentralized half of the ms=0 bitwise contract
    plus the weighted fold-vs-flat equivalence on every exchange phase
    (phase-2 gradients, agreement rounds, model gossip)."""

    def _learn(self, staleness, *, tree_path=True, gar="krum", subset=None,
               non_iid=False, steps=4, f=2):
        from garfield_tpu.parallel import learn

        module, loss, opt = _pima_setup()
        xs, x, y = _pima_batches(8, 16)
        init_fn, step_fn, _ = learn.make_trainer(
            module, loss, opt, gar, num_nodes=8, f=f, attack="lie",
            staleness=staleness, tree_path=tree_path, subset=subset,
            non_iid=non_iid,
        )
        state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
        state, losses = _run(step_fn, state, x, y, steps)
        return losses, _flat_params(state)

    def test_max_staleness_zero_is_bitwise_synchronous(self):
        l0, f0 = self._learn(None)
        l1, f1 = self._learn({"max_staleness": 0, "decay": 0.5})
        assert l0 == l1
        np.testing.assert_array_equal(f0, f1)

    def test_all_zero_taus_is_bitwise_synchronous(self):
        l0, f0 = self._learn(None, gar="median", f=1)
        l1, f1 = self._learn(
            {"max_staleness": 3, "decay": 0.5, "taus": [0] * 8},
            gar="median", f=1,
        )
        assert l0 == l1
        np.testing.assert_array_equal(f0, f1)

    def test_weighted_fold_matches_flat_per_phase(self):
        # Subsets + agreement rounds + gossip all active: the Gram
        # row-weight composition (folded_tree_aggregate_multi) must
        # train like the flat path that weights rows explicitly.
        st = {"max_staleness": 4, "decay": 0.5,
              "taus": [0, 0, 1, 0, 2, 0, 3, 4]}
        lt, ft = self._learn(st, tree_path=True, subset=7, non_iid=True)
        lf, ff = self._learn(st, tree_path=False, subset=7, non_iid=True)
        assert all(np.isfinite(v) for v in lt + lf)
        np.testing.assert_allclose(ft, ff, rtol=2e-5, atol=1e-6)

    def test_weighted_fold_matches_flat_full_participation(self):
        st = {"max_staleness": 4, "decay": 0.5,
              "taus": [0, 0, 1, 0, 2, 0, 3, 4]}
        lt, ft = self._learn(st, tree_path=True)
        lf, ff = self._learn(st, tree_path=False)
        np.testing.assert_allclose(ft, ff, rtol=2e-5, atol=1e-6)

    def test_seeded_per_phase_draws_deterministic(self):
        a = self._learn({"max_staleness": 3, "decay": 0.7})
        b = self._learn({"max_staleness": 3, "decay": 0.7})
        assert a[0] == b[0]
        np.testing.assert_array_equal(a[1], b[1])
        assert all(np.isfinite(v) for v in a[0])

    def test_bad_config_rejected(self):
        from garfield_tpu.parallel import learn

        module, loss, opt = _pima_setup()
        with pytest.raises(ValueError, match="unknown staleness"):
            learn.make_trainer(
                module, loss, opt, "krum", num_nodes=8, f=2,
                staleness={"max_stale": 3},
            )
        with pytest.raises(ValueError, match="shape"):
            learn.make_trainer(
                module, loss, opt, "krum", num_nodes=8, f=2,
                staleness={"max_staleness": 3, "taus": [0, 1]},
            )


class TestMultiFoldRowWeights:
    def test_multi_observer_weights_match_per_observer_reference(self):
        # folded_tree_aggregate_multi(row_weights=) vs each observer's
        # explicit weighted where-path aggregate over its subset.
        n, f, q = 8, 2, 7
        gar = gars["krum"]
        byz_mask = core.default_byz_mask(n, f)
        tree = _tiny_tree(jax.random.PRNGKey(3), n)
        w = jnp.asarray(rounds.staleness_weights(
            np.array([0, 1, 0, 2, 0, 0, 3, 4]), decay=0.5, max_staleness=4
        ))
        plan = fold.plan_for(gar, "lie", byz_mask, {})
        sels = jnp.stack([
            core.subset_indices(jax.random.PRNGKey(10 + m), n, q)
            for m in range(3)
        ])
        got = fold.folded_tree_aggregate_multi(
            gar, plan, tree, f=f, subset_sels=sels, row_weights=w
        )
        flat = core.flatten_rows(tree)
        poisoned = apply_gradient_attack("lie", flat, byz_mask)
        weighted = poisoned * w[:, None]
        got_rows = core.flatten_rows(got)
        for m in range(3):
            ref = gar.unchecked(weighted[sels[m]], f=f)
            np.testing.assert_allclose(
                np.asarray(got_rows[m]), np.asarray(ref),
                rtol=2e-5, atol=1e-6,
            )


class TestTelemetryV6:
    def test_autoscale_event_validates(self):
        from garfield_tpu.telemetry import exporters

        good = exporters.make_record(
            "event", event="autoscale", who="cluster-ps", step=4,
            action="spawn", rank=3, active=5, rate=12.5, target=20.0,
        )
        exporters.validate_record(good)
        with pytest.raises(ValueError):
            exporters.validate_record(dict(good, action="explode"))
        with pytest.raises(ValueError):
            exporters.validate_record(dict(good, active=-1))
        with pytest.raises(ValueError):
            exporters.validate_record(dict(good, rate="fast"))

    def test_hub_folds_autoscale_and_summary_validates(self):
        from garfield_tpu.telemetry import exporters
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=4)
        assert hub.autoscale_stats() is None
        assert hub.active_workers() is None
        hub.record_event("autoscale", action="spawn", rank=2, active=3)
        hub.record_event("autoscale", action="spawn", rank=3, active=4)
        hub.record_event("autoscale", action="retire", rank=3, active=3)
        st = hub.autoscale_stats()
        assert st == {"spawns": 2, "retires": 1, "active_workers": 3}
        assert hub.active_workers() == 3
        rec = hub.summary()
        exporters.validate_record(rec)
        assert rec["autoscale"] == st
        # Fixed-membership hubs stay v5-shaped (autoscale None).
        rec2 = MetricsHub(num_ranks=4).summary()
        exporters.validate_record(rec2)
        assert rec2["autoscale"] is None

    def test_prometheus_active_workers_gauge(self):
        from garfield_tpu.telemetry import exporters
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=4)
        hub.record_event("autoscale", action="spawn", rank=1, active=2)
        text = exporters.prometheus_text(hub)
        assert "garfield_active_workers 2" in text
        assert 'garfield_autoscale_actions_total{action="spawn"} 1' in text
        assert "garfield_active_workers" not in exporters.prometheus_text(
            MetricsHub(num_ranks=4)
        )

    def test_plane_labelled_wire_counters(self):
        from garfield_tpu.telemetry import exporters
        from garfield_tpu.telemetry.hub import MetricsHub

        hub = MetricsHub(num_ranks=2)
        hub.record_event(
            "wire", who="t", step=0, bytes_out=100, bytes_in=50,
            frames_in=2, encode_s=0.0, decode_s=0.0,
            planes={"1": {"bytes_out": 60, "bytes_in": 50},
                    "2": {"bytes_out": 40, "bytes_in": 0}},
        )
        hub.record_event(
            "wire", who="t", step=1, bytes_out=10, bytes_in=0,
            frames_in=0, encode_s=0.0, decode_s=0.0,
            planes={"1": {"bytes_out": 10, "bytes_in": 0}},
        )
        planes = hub.wire_plane_counters()
        assert planes["1"] == {"bytes_out": 70, "bytes_in": 50}
        assert planes["2"] == {"bytes_out": 40, "bytes_in": 0}
        text = exporters.prometheus_text(hub)
        assert ('garfield_wire_plane_bytes_total'
                '{plane="1",direction="out"} 70') in text
        rec = hub.summary()
        from garfield_tpu.telemetry import exporters as _e
        _e.validate_record(rec)
        assert rec["wire_planes"]["2"]["bytes_out"] == 40

    def test_plane_tagged_exchange_wait_and_staleness_validate(self):
        from garfield_tpu.telemetry import exporters

        exporters.validate_record(exporters.make_record(
            "event", event="exchange_wait", step=2, q=3, arrived=3,
            wait_s=0.01, timed_out=False, plane=1,
        ))
        exporters.validate_record(exporters.make_record(
            "event", event="staleness", who="cluster-node-0", step=2,
            plane="model", ranks=[0, 1], staleness=[0, 2],
            weights=[1.0, 0.25], reused=1,
        ))
