"""Telemetry plane tests (ISSUE 2): in-graph taps, hub, exporters.

Pins the three contracts the subsystem makes:
  1. taps are pure OBSERVERS — taps-on vs taps-off TrainState
     trajectories are BITWISE identical (aggregathor, learn, byzsgd;
     krum/cclip x lie/none, with and without wait-n-f subsets);
  2. tap correctness — krum's selection mask equals the rule's own
     ``selection_indices`` / ``influence`` on the same poisoned stack;
  3. the JSONL schema round-trips and malformed records fail loudly,
     and the derived
     suspicion score ranks the Byzantine ranks above every honest rank
     on the 8-worker aggregathor run under the lie attack.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu import models
from garfield_tpu.aggregators import krum as krum_rule
from garfield_tpu.attacks import apply_gradient_attack
from garfield_tpu.parallel import aggregathor, byzsgd, core, learn
from garfield_tpu.telemetry import (
    JsonlExporter,
    MetricsHub,
    exporters,
    make_record,
    prometheus_text,
    validate_jsonl,
    validate_record,
)
from garfield_tpu.telemetry import taps as taps_lib
from garfield_tpu.utils import selectors


def _pima_setup():
    module = models.select_model("pimanet", "pima")
    loss = selectors.select_loss("bce")
    opt = selectors.select_optimizer("sgd", lr=0.05, momentum=0.9)
    return module, loss, opt


def _pima_batches(num, bsz, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(num, bsz, 8)).astype(np.float32)
    y = (x.sum(-1, keepdims=True) > 0).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _run(step_fn, state, x, y, iters):
    metrics = None
    for _ in range(iters):
        state, metrics = step_fn(state, x, y)
    return state, metrics


class TestTrajectoryEquivalence:
    """Taps-on must be BITWISE the taps-off trajectory: the taps read the
    same poisoned stack and keys the GAR consumed and write nothing back,
    so enabling telemetry cannot move a single bit of TrainState."""

    @pytest.mark.parametrize("gar,attack,f", [
        ("krum", "lie", 2),
        ("krum", None, 2),
        ("cclip", "lie", 2),
        ("cclip", None, 2),
    ])
    @pytest.mark.parametrize("subset", [None, 7])
    def test_aggregathor_bitwise(self, gar, attack, f, subset):
        module, loss, opt = _pima_setup()
        x, y = _pima_batches(8, 16)
        states, taps = [], []
        for tele in (True, False):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, gar, num_workers=8, f=f, attack=attack,
                subset=subset, telemetry=tele,
            )
            state = init_fn(jax.random.PRNGKey(0), x[0])
            state, metrics = _run(step_fn, state, x, y, 5)
            states.append(state)
            taps.append(metrics.get("tap"))
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            states[0], states[1],
        )
        assert taps[0] is not None and taps[1] is None
        assert set(taps[0]) == set(taps_lib.TAP_KEYS)
        assert taps[0]["selected"].shape == (8,)

    @pytest.mark.parametrize("gar,attack,f", [
        ("krum", "lie", 2),
        ("krum", None, 2),
        ("cclip", "lie", 2),
        ("cclip", None, 2),
    ])
    @pytest.mark.parametrize("subset", [None, 7])
    def test_learn_bitwise(self, gar, attack, f, subset):
        module, loss, opt = _pima_setup()
        x, y = _pima_batches(8, 16)
        states, taps = [], []
        for tele in (True, False):
            init_fn, step_fn, _ = learn.make_trainer(
                module, loss, opt, gar, num_nodes=8, f=f, attack=attack,
                subset=subset, telemetry=tele,
            )
            state = init_fn(jax.random.PRNGKey(0), x[0])
            state, metrics = _run(step_fn, state, x, y, 5)
            states.append(state)
            taps.append(metrics.get("tap"))
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            states[0], states[1],
        )
        assert taps[0] is not None and taps[1] is None
        if subset is not None:
            # Observer-mean semantics: each rank is observed by the
            # fraction of nodes whose q-subset contained it.
            obs = np.asarray(taps[0]["observed"])
            assert np.all(obs <= 1.0) and np.all(obs > 0.0)
            np.testing.assert_allclose(obs.mean(), subset / 8, atol=1e-6)

    @pytest.mark.parametrize("subset", [None, 7])
    def test_byzsgd_bitwise(self, subset):
        module, loss, opt = _pima_setup()
        x, y = _pima_batches(8, 16)
        states, taps = [], []
        for tele in (True, False):
            # median: feasible on BOTH planes (krum cannot aggregate the
            # 2 PS models — its check needs n >= 2f+3).
            init_fn, step_fn, _ = byzsgd.make_trainer(
                module, loss, opt, "median", num_workers=8, num_ps=2,
                fw=2, attack="lie", subset=subset, telemetry=tele,
            )
            state = init_fn(jax.random.PRNGKey(0), x[0])
            state, metrics = _run(step_fn, state, x, y, 3)
            states.append(state)
            taps.append(metrics.get("tap"))
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            states[0], states[1],
        )
        assert taps[0] is not None and taps[1] is None

    def test_layer_granularity_rejected(self):
        module, loss, opt = _pima_setup()
        with pytest.raises(ValueError, match="granularity"):
            aggregathor.make_trainer(
                module, loss, opt, "median", num_workers=8, f=1,
                granularity="layer", telemetry=True,
            )


class TestTapCorrectness:
    def test_krum_mask_pins_selection_indices(self):
        """The tap's selection mask must equal krum's own selection on
        the SAME poisoned stack — and its Byzantine fraction must equal
        the rule's ``influence`` statistic."""
        rng = np.random.default_rng(7)
        n, f, d = 8, 2, 40
        stack = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        mask = core.default_byz_mask(n, f)
        poisoned = apply_gradient_attack("lie", stack, jnp.asarray(mask))
        bundle = taps_lib.compute_flat("krum", poisoned, f)
        sel = np.asarray(krum_rule.selection_indices(poisoned, f))
        m = n - f - 2
        want = np.zeros(n, np.float32)
        want[sel] = 1.0
        np.testing.assert_array_equal(
            np.asarray(bundle["selected"]), want
        )
        np.testing.assert_array_equal(
            np.asarray(bundle["observed"]), np.ones(n, np.float32)
        )
        # influence = Byzantine fraction among the m selected.
        infl = krum_rule.influence(
            np.asarray(poisoned[:n - f]), np.asarray(poisoned[n - f:]), f
        )
        got_frac = float(np.asarray(bundle["selected"])[n - f:].sum()) / m
        assert abs(infl - got_frac) < 1e-9
        # The tap's score is the rule's krum score: selected ranks hold
        # the m smallest scores.
        score = np.asarray(bundle["score"])
        assert set(np.argsort(score)[:m]) == set(sel.tolist())

    def test_cclip_tap_reports_tau_and_clip(self):
        rng = np.random.default_rng(3)
        stack = rng.normal(size=(8, 30)).astype(np.float32)
        stack[7] *= 50.0  # one huge outlier must be clipped hard
        bundle = taps_lib.compute_flat("cclip", jnp.asarray(stack), 1)
        sel = np.asarray(bundle["selected"])
        assert float(bundle["tau"]) > 0.0
        assert 0.0 < float(bundle["clip_frac"]) <= 1.0
        assert sel[7] < 0.2 and sel[7] == sel.min()

    def test_median_share_collapses_for_outlier(self):
        rng = np.random.default_rng(4)
        stack = rng.normal(size=(8, 200)).astype(np.float32)
        stack[6:] += 40.0  # two colluding far-off rows never win a median
        bundle = taps_lib.compute_flat("median", jnp.asarray(stack), 2)
        sel = np.asarray(bundle["selected"])
        assert sel[6:].max() < 0.05
        assert sel[:6].min() > 0.5

    def test_scatter_marks_unobserved(self):
        rng = np.random.default_rng(5)
        stack = jnp.asarray(rng.normal(size=(6, 10)).astype(np.float32))
        bundle = taps_lib.compute_flat("average", stack, 0)
        out = taps_lib.scatter(bundle, jnp.asarray([0, 2, 3, 4, 6, 7]), 8)
        np.testing.assert_array_equal(
            np.asarray(out["observed"]),
            np.asarray([1, 0, 1, 1, 1, 0, 1, 1], np.float32),
        )


class TestExporters:
    def test_jsonl_round_trip(self, tmp_path):
        path = tmp_path / "t.jsonl"
        records = [
            make_record("run", meta={"tag": "test"}),
            make_record("step", step=0, loss=0.5, step_time_s=None, tap={
                "observed": [1.0, 1.0], "selected": [1.0, 0.0],
                "score": [0.1, 9.0], "tau": 0.0, "clip_frac": 0.0,
            }),
            make_record("event", event="exchange_wait", step=0, q=6,
                        arrived=6, wait_s=0.01, timed_out=False),
            make_record("summary", steps=1, events=1,
                        suspicion=[0.0, 1.0]),
            make_record("span", phase="grad", t_wall=1.0, dur_s=0.01),
            make_record("soak_bench", check="steady", rounds=4, d=256,
                        shards=2, cohort=16, p50_s=0.001),
        ]
        with JsonlExporter(path) as exp:
            for rec in records:
                exp.write(rec)
        assert validate_jsonl(path) == len(records)
        with open(path) as fp:
            back = [json.loads(line) for line in fp]
        assert back == records

    @pytest.mark.parametrize("bad", [
        {"kind": "step", "step": 0},                      # no schema
        {"schema": "garfield-telemetry", "v": 1, "kind": "nope"},
        {"schema": "garfield-telemetry", "v": 0, "kind": "step", "step": 0},
        {"schema": "garfield-telemetry", "v": 1, "kind": "step",
         "step": -1},
        {"schema": "garfield-telemetry", "v": 1, "kind": "step", "step": 0,
         "tap": {"observed": [1.0], "selected": [1.0, 0.0],
                 "score": [0.0], "tau": 0, "clip_frac": 0}},
        {"schema": "garfield-telemetry", "v": 1, "kind": "span"},
        {"schema": "garfield-telemetry", "v": 1, "kind": "fed_bench",
         "check": "scaling", "gar": "hier-krum", "n": "8", "d": 10,
         "shards": 1},
        # a harness row's envelope and typed columns must be well-formed.
        {"schema": "garfield-telemetry", "v": 13, "kind": "soak_bench",
         "check": "steady", "rounds": 4, "d": 256, "shards": 2,
         "cohort": 0},
        {"schema": "garfield-telemetry", "v": 7, "kind": "defense_bench",
         "cell": "grad/lie/off", "gar": "krum", "steps": "4"},
        # schema v2: step-time percentiles must be well-typed when present.
        {"schema": "garfield-telemetry", "v": 2, "kind": "summary",
         "steps": 1, "events": 0, "step_time": [0.1]},
        {"schema": "garfield-telemetry", "v": 2, "kind": "summary",
         "steps": 1, "events": 0,
         "step_time": {"mean_s": 0.1, "p95_s": "fast"}},
    ])
    def test_validate_rejects_malformed(self, bad):
        with pytest.raises(ValueError, match="schema violation"):
            validate_record(bad)

    def test_v2_step_time_percentiles_validate(self):
        validate_record(make_record(
            "summary", steps=3, events=0,
            step_time={"count": 3, "mean_s": 0.1, "p50_s": 0.09,
                       "p95_s": 0.2, "p99_s": 0.3},
        ))

    @pytest.mark.parametrize("line", [
        {"kind": "bench", "metric": "m", "value": 1.5, "unit": "steps/s"},
        {"kind": "gar_bench", "gar": "krum", "n": 8, "f": 2, "d": 1000,
         "latency_s": 0.001},
        {"kind": "hier_bench", "gar": "hier-krum", "n": 1024, "f": 8,
         "d": 1000, "bucket_size": 32, "levels": 2, "num_buckets": 32,
         "latency_s": 0.1},
        {"kind": "transfer_bench", "devices": 8, "d": 1000,
         "latency_s": 0.001},
        {"kind": "exchange_bench", "n": 4, "d": 1000, "wire": "f32",
         "round_s": 0.01},
        {"kind": "trans_bench", "check": "ab", "model": "vit_tiny",
         "slots": 8, "d": 1000, "path": "fused", "per_slot_grad_s": 0.01},
    ], ids=lambda line: line["kind"])
    def test_removed_kind_is_refused(self, line):
        """v16: a well-formed v15 line of a kind whose program went is
        refused like any unknown kind, and the message names it."""
        rec = {"schema": "garfield-telemetry", "v": 15, **line}
        with pytest.raises(ValueError, match=repr(line["kind"])):
            validate_record(rec)
        with pytest.raises(ValueError, match=repr(line["kind"])):
            make_record(line["kind"])

    def test_malformed_jsonl_fails_loudly(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text('{"schema": "garfield-telemetry"\nnot json\n')
        with pytest.raises(ValueError):
            validate_jsonl(path)

    def test_hub_records_validate_and_prometheus_renders(self):
        hub = MetricsHub(num_ranks=4, meta={"tag": "t"})
        tap = {
            "observed": np.ones(4, np.float32),
            "selected": np.asarray([1, 1, 0, 0], np.float32),
            "score": np.zeros(4, np.float32),
            "tau": np.float32(0.5),
            "clip_frac": np.float32(0.25),
        }
        validate_record(hub.record_step(0, loss=1.0, tap=tap))
        validate_record(hub.record_event("exchange_wait", step=0, q=3,
                                         arrived=3, wait_s=0.02))
        validate_record(hub.summary())
        text = prometheus_text(hub)
        assert 'garfield_rank_suspicion{rank="2"} 1' in text
        assert "garfield_steps_total 1" in text
        np.testing.assert_allclose(hub.suspicion(), [0, 0, 1, 1])


class TestSuspicionAudit:
    def test_lie_attack_ranks_byzantine_ranks_top(self, tmp_path):
        """The acceptance criterion: 8-worker CPU-mesh aggregathor under
        the lie attack, telemetry on — the JSONL holds per-step selection
        masks whose cumulative exclusion frequency ranks the f Byzantine
        ranks above every honest rank."""
        module, loss, opt = _pima_setup()
        x, y = _pima_batches(8, 16)
        f = 2
        init_fn, step_fn, _ = aggregathor.make_trainer(
            module, loss, opt, "median", num_workers=8, f=f, attack="lie",
            telemetry=True,
        )
        state = init_fn(jax.random.PRNGKey(0), x[0])
        hub = MetricsHub(num_ranks=8, meta={"tag": "audit-test"})
        path = tmp_path / "telemetry.jsonl"
        with JsonlExporter(path) as exp:
            exp.write(make_record("run", meta=hub.meta))
            for i in range(25):
                state, metrics = step_fn(state, x, y)
                exp.write(hub.record_step(
                    i, loss=float(metrics["loss"]), tap=metrics["tap"]
                ))
            exp.write(hub.summary())
        assert validate_jsonl(path) == 27
        with open(path) as fp:
            steps = [json.loads(l) for l in fp if '"kind": "step"' in l]
        assert all(len(rec["tap"]["selected"]) == 8 for rec in steps)
        susp = hub.suspicion()
        assert susp is not None
        assert susp[8 - f:].min() > susp[:8 - f].max(), susp


@pytest.mark.slow
def test_cli_telemetry_end_to_end(tmp_path):
    """--telemetry on the real aggregathor CLI: JSONL + Prometheus
    artifacts appear, validate, and carry per-step taps."""
    from garfield_tpu.apps import aggregathor as app_aggregathor

    tdir = tmp_path / "tele"
    app_aggregathor.main([
        "--dataset", "mnist", "--model", "convnet", "--loss", "nll",
        "--batch", "8", "--num_iter", "3", "--train_size", "256",
        "--acc_freq", "0", "--num_workers", "8", "--fw", "2",
        "--gar", "krum", "--attack", "lie", "--telemetry", str(tdir),
    ])
    jsonl = tdir / "telemetry.jsonl"
    prom = tdir / "metrics.prom"
    assert validate_jsonl(jsonl) == 5  # run + 3 steps + summary
    with open(jsonl) as fp:
        kinds = [json.loads(l)["kind"] for l in fp]
    assert kinds[0] == "run" and kinds[-1] == "summary"
    assert kinds.count("step") == 3
    assert "garfield_rank_suspicion" in prom.read_text()
