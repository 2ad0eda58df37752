"""Federated round engine (garfield_tpu/federated/, DESIGN.md §19).

Fast tier-1 coverage: shard planning/reassembly + capacity guards, the
seeded cohort sampler (determinism pin, f pricing, staleness
composition), cohort-level f composition (budget covers the realized
Byzantine count => robustness-matrix-style tolerance; budget exceeded
=> the documented failure mode), the S=1 full-participation bitwise
anchor against the unsharded streaming path, sharded checkpoint
round-trip at pima scale, and the client-id-keyed suspicion the
rotation/resampling attack cannot launder. The multi-process wire
deployment (real shard planes over PeerExchange + the autoscaled client
fleet) lives in tests/test_fed_cluster.py (slow, conftest._RUN_LAST).
"""

import numpy as np
import pytest

from garfield_tpu import federated as fed
from garfield_tpu.aggregators import hierarchy
from garfield_tpu.telemetry import exporters, hub as tele_hub
from garfield_tpu.utils import rounds as rounds_lib, wire

RNG = np.random.default_rng(20260805)


def honest_rows(n, d, mu=None, sigma=0.1):
    mu = RNG.normal(size=d).astype(np.float32) if mu is None else mu
    return (mu[None, :] + sigma * RNG.normal(size=(n, d))).astype(
        np.float32
    ), mu


# ---------------------------------------------------------------------------
# sharding


class TestSharding:
    def test_spans_partition_and_reassemble_bitwise(self):
        for d, s in [(101, 4), (16, 16), (10 ** 5, 7), (9, 1)]:
            spec = fed.plan_shards(d, s)
            assert spec.spans[0][0] == 0 and spec.spans[-1][1] == d
            widths = [hi - lo for lo, hi in spec.spans]
            assert max(widths) - min(widths) <= 1  # balanced
            v = RNG.normal(size=d).astype(np.float32)
            parts = [spec.slice_rows(v, k) for k in range(s)]
            assert np.array_equal(fed.reassemble(spec, parts), v)

    def test_capacity_guards(self):
        with pytest.raises(ValueError, match="nibble"):
            fed.plan_shards(100, fed.MAX_SHARDS + 1)
        with pytest.raises(ValueError):
            fed.plan_shards(2, 4)  # more shards than parameters
        spec = fed.plan_shards(64, 4)
        with pytest.raises(ValueError):
            fed.shard_plane(4, spec.num_shards)
        with pytest.raises(TypeError):
            fed.shard_plane(1.5)
        # shard id == wire plane: the stamp and the slot agree.
        assert fed.shard_plane(3, 4) == 3

    def test_reassemble_rejects_mismatched_parts(self):
        spec = fed.plan_shards(10, 2)
        with pytest.raises(ValueError):
            fed.reassemble(spec, [np.zeros(5, np.float32)])
        with pytest.raises(ValueError):
            fed.reassemble(
                spec, [np.zeros(4, np.float32), np.zeros(6, np.float32)]
            )


# ---------------------------------------------------------------------------
# sampler


class TestSampler:
    def test_seeded_determinism_pin(self):
        """The cohort is a pure function of (seed, round): same seed +
        round => identical ids in identical order (order is bucket
        assignment, so it is part of the contract); different rounds or
        seeds diverge."""
        s = fed.CohortSampler(10_000, 256, seed=11)
        a, b = s.cohort(7), s.cohort(7)
        assert np.array_equal(a, b)
        assert a.dtype == np.int64 and np.unique(a).size == a.size
        assert not np.array_equal(s.cohort(7), s.cohort(8))
        s2 = fed.CohortSampler(10_000, 256, seed=12)
        assert not np.array_equal(s.cohort(7), s2.cohort(7))
        # Pinned bytes: a committed FEDBENCH row must be reproducible.
        assert s.cohort(0)[:4].tolist() == \
            fed.CohortSampler(10_000, 256, seed=11).cohort(0)[:4].tolist()

    def test_full_participation_is_identity_order(self):
        s = fed.CohortSampler(64, 64, seed=3)
        assert np.array_equal(s.cohort(5), np.arange(64))

    def test_f_budget_prices_the_cohort_not_the_population(self):
        s = fed.CohortSampler(10 ** 6, 1024, seed=0, byz_frac=0.01)
        f = s.f_budget()
        mean = 1024 * 0.01
        assert f >= mean  # at least the expectation
        assert f <= s.capacity()
        # Zero threat => zero budget; any threat => at least 1.
        assert fed.CohortSampler(100, 50, byz_frac=0.0).f_budget() == 0
        tiny = fed.CohortSampler(10 ** 6, 512, byz_frac=1e-6)
        assert tiny.f_budget() >= 1

    def test_f_budget_refuses_uncomposable_threat(self):
        s = fed.CohortSampler(10 ** 4, 64, byz_frac=0.3)
        with pytest.raises(ValueError, match="capacity"):
            s.f_budget()

    def test_realized_byzantine_counts_global_ids(self):
        s = fed.CohortSampler(1000, 100, seed=5)
        cohort = s.cohort(0)
        byz = set(cohort[:7].tolist()) | {999_999}
        assert s.realized_byzantine(cohort, byz) == 7

    def test_staleness_composition_drops_cutoff_members(self):
        pol = rounds_lib.StalenessPolicy(max_staleness=2, decay=0.5)
        s = fed.CohortSampler(100, 8, seed=1, staleness=pol)
        cohort = s.cohort(4)
        tags = {
            int(cohort[0]): 3,   # tau 1 -> weight 0.5
            int(cohort[1]): 4,   # fresh
            int(cohort[2]): 0,   # tau 4 > cutoff -> dropped
        }
        active, w, dropped = s.cohort_weights(4, cohort, tags)
        assert int(cohort[2]) in dropped.tolist()
        assert active.size == 7 and dropped.size == 1
        wmap = dict(zip(active.tolist(), w.tolist()))
        assert wmap[int(cohort[0])] == 0.5
        assert wmap[int(cohort[1])] == 1.0  # exactly 1.0: bitwise no-op
        # No tags / no policy: everyone fresh at exactly 1.0.
        a2, w2, d2 = s.cohort_weights(4, cohort, None)
        assert a2.size == 8 and np.all(w2 == 1.0) and d2.size == 0


# ---------------------------------------------------------------------------
# cohort-level f composition (ISSUE 13 satellite)


class TestCohortComposition:
    """plan_hierarchy over sampled cohorts: budget >= realized Byzantine
    count => the aggregate stays within the robustness-matrix-style
    tolerance of the honest mean; budget exceeded => the documented
    failure mode (the bound is void — and measurably so)."""

    def _attack_rows(self, n, d, n_byz, mu):
        rows, _ = honest_rows(n - n_byz, d, mu=mu)
        # Reverse-and-amplify: the classic divergence attack.
        bad = np.tile(-8.0 * mu, (n_byz, 1)).astype(np.float32)
        return np.concatenate([rows, bad], axis=0)

    def test_budget_covers_realized_count_bounds_aggregate(self):
        n, d = 96, 64
        s = fed.CohortSampler(10 ** 4, n, seed=2, byz_frac=0.02)
        f = s.f_budget()
        plan = hierarchy.plan_hierarchy(n, f, "krum")
        assert plan.n == n  # the cohort composes at the priced budget
        mu = RNG.normal(size=d).astype(np.float32)
        g = self._attack_rows(n, d, f, mu)  # realized == budget
        agg = np.asarray(hierarchy.aggregate(g, f, bucket_gar="krum"))
        # Within the honest spread: the rule kept the adversary out.
        assert np.linalg.norm(agg - mu) < 1.0

    def test_budget_exceeded_documented_failure(self):
        """The OTHER side of the contract: realized Byzantine count past
        the priced budget voids the bound — the reverse cohort drags
        the aggregate an order of magnitude off the honest mean. This
        is the failure mode the per-cohort pricing exists to prevent,
        recorded (not hidden) per DESIGN.md §19."""
        n, d = 96, 64
        f = 3  # deliberately under-priced
        mu = RNG.normal(size=d).astype(np.float32)
        mu /= np.float32(np.linalg.norm(mu) / 8.0)  # strong signal
        # Realized 60 >> budget 3: a majority of bucket summaries is
        # Byzantine, so the top krum's tightest cluster IS the attack.
        g = self._attack_rows(n, d, 60, mu)
        agg = np.asarray(hierarchy.aggregate(g, f, bucket_gar="krum"))
        honest_dist = np.linalg.norm(agg - mu)
        assert honest_dist > 2.0  # the bound is measurably void

    def test_engine_flags_budget_exceeded(self):
        n, d = 64, 32
        sampler = fed.CohortSampler(n, n, seed=4, byz_frac=0.02)
        eng = fed.FedRoundEngine(
            np.zeros(d, np.float32), 2, sampler, lr=0.1
        )
        ids, f = eng.begin_round()
        g, _ = honest_rows(n, d)
        eng.ingest_rows(g)
        info = eng.finish_round(byz_ids=set(ids[: f + 1].tolist()))
        assert info["realized_byz"] == f + 1
        assert info["budget_exceeded"] is True
        eng.begin_round()
        eng.ingest_rows(g)
        info = eng.finish_round(byz_ids=set(ids[:f].tolist()))
        assert info["budget_exceeded"] is False


# ---------------------------------------------------------------------------
# the engine


class TestEngine:
    def test_s1_full_participation_bitwise_unsharded(self):
        """The anchor: S=1 full participation over several rounds IS the
        existing unsharded single-PS streaming path, bit for bit — same
        StreamingAggregator programs, same arrival order, same SGD
        update."""
        n, d, rounds = 128, 96, 3
        sampler = fed.CohortSampler(n, n, seed=9, byz_frac=0.02)
        model0 = RNG.normal(size=d).astype(np.float32)
        eng = fed.FedRoundEngine(model0, 1, sampler, lr=0.05)
        ref = model0.copy()
        for r in range(rounds):
            ids, f = eng.begin_round()
            g = np.random.default_rng([13, r]).normal(
                size=(n, d)).astype(np.float32)
            eng.ingest_rows(g)
            eng.finish_round()
            red = hierarchy.StreamingAggregator(n, f)
            red.push_many(g)
            ref = (ref - np.float32(0.05) * red.finalize()).astype(
                np.float32
            )
        assert np.array_equal(eng.model, ref)

    def test_sharded_rounds_deterministic_and_agree_on_clean_data(self):
        """S>1 folds per-shard (selection may differ per span — the
        documented semantics), but the engine is deterministic, and on
        clean concentrated data every shard keeps the same inliers, so
        S=1 and S=2 land on the same aggregate to fold precision."""
        n, d = 64, 64
        sampler = fed.CohortSampler(n, n, seed=6)
        g, mu = honest_rows(n, d, sigma=0.01)
        outs = []
        for s in (1, 2, 4):
            eng = fed.FedRoundEngine(
                np.zeros(d, np.float32), s, sampler, lr=1.0
            )
            eng.begin_round()
            eng.ingest_rows(g)
            eng.finish_round()
            outs.append(eng.model.copy())
            eng2 = fed.FedRoundEngine(
                np.zeros(d, np.float32), s, sampler, lr=1.0
            )
            eng2.begin_round()
            eng2.ingest_rows(g)
            eng2.finish_round()
            assert np.array_equal(eng.model, eng2.model)  # deterministic
        for o in outs[1:]:
            # Per-shard selection may pick different (equally honest)
            # inliers per span, so agreement is to the honest spread,
            # not bitwise — the documented S>1 semantics.
            np.testing.assert_allclose(o, outs[0], atol=0.1)

    def test_partial_participation_round_and_telemetry(self):
        hub = tele_hub.MetricsHub(suspicion_halflife=8)
        tele_hub.install(hub)
        try:
            sampler = fed.CohortSampler(256, 32, seed=3, byz_frac=0.02)
            eng = fed.FedRoundEngine(
                np.zeros(48, np.float32), 2, sampler, lr=0.1,
                audit=True, telemetry=True,
            )
            ids, f = eng.begin_round()
            assert ids.size == 32
            g, _ = honest_rows(32, 48)
            eng.ingest_rows(g)
            info = eng.finish_round()
            assert info["active"] == 32 and info["f_budget"] == f
            assert set(info["per_shard"]) == {"0", "1"}
            fedstats = hub.federated_stats()
            assert fedstats["rounds"] == 1
            assert fedstats["last_cohort"] == 32
            assert hub.client_suspicion_decayed() is not None
            summ = hub.summary()
            exporters.validate_record(summ)
            assert summ["federated"]["rounds"] == 1
        finally:
            tele_hub.uninstall()

    def test_staleness_discounts_compose_into_rows(self):
        """A straggler's row enters every shard scaled by decay**tau —
        the same law as the async cluster plane (utils/rounds.py)."""
        n, d = 16, 24
        pol = rounds_lib.StalenessPolicy(max_staleness=3, decay=0.5)
        sampler = fed.CohortSampler(n, n, seed=1, staleness=pol)
        eng = fed.FedRoundEngine(
            np.zeros(d, np.float32), 2, sampler, lr=1.0,
            bucket_gar="average",
        )
        eng.round = 5
        g = np.ones((n, d), np.float32)
        tags = {0: 4}  # client 0 is one round stale -> weight 0.5
        active, f = eng.begin_round(tags=tags)
        assert active.size == n
        for cid in active.tolist():
            eng.ingest(cid, g[cid])
        eng.finish_round()
        # average over rows: (15 * 1.0 + 0.5) / 16 per coordinate.
        expect = -(15.0 + 0.5) / 16.0
        np.testing.assert_allclose(eng.model, expect, rtol=1e-6)

    def test_shard_server_wire_ingest_and_cross_shard_reject(self):
        spec = fed.plan_shards(32, 2)
        sv = fed.ShardServer(1, spec, bucket_gar="average")
        sv.begin_round(0, 4, 0)
        rows = RNG.normal(size=(4, 32)).astype(np.float32)
        sliced = spec.slice_rows(rows, 1)
        # A multi-row frame stamped for THIS shard ingests...
        sv.push_frame(wire.encode(sliced.ravel(), plane=1))
        agg = sv.finish_round()
        np.testing.assert_allclose(
            agg, sliced.mean(axis=0), rtol=1e-5, atol=1e-6
        )
        # ...a frame stamped for the OTHER shard is ban evidence.
        sv.begin_round(1, 4, 0)
        with pytest.raises(wire.WireError, match="cross-shard"):
            sv.push_frame(
                wire.encode(spec.slice_rows(rows, 0).ravel(), plane=0)
            )
        # ...and a non-whole-row frame too.
        with pytest.raises(wire.WireError, match="whole number"):
            sv.push_frame(wire.encode(np.ones(7, np.float32), plane=1))

    def test_shard_server_bounds_sparse_elems_claim(self):
        """REVIEW fix: a cohort member's CRC-valid topk frame claiming a
        huge dense size must reject on the shard's n*d_shard bound
        BEFORE the scatter allocates (np.zeros(elems) at 2^40 is a 4 TB
        allocation the sender controls) — same attributable WireError
        ban path as a cross-shard stamp. Honest sparse frames inside
        the bound still ingest."""
        import struct
        import zlib

        spec = fed.plan_shards(32, 2)
        sv = fed.ShardServer(1, spec, bucket_gar="average")
        sv.begin_round(0, 4, 0)
        pairs = np.zeros(2, np.dtype([("i", "<u4"), ("v", "<f4")]))
        pairs["i"] = [0, 1]
        pairs["v"] = [3.0, -3.0]
        payload = pairs.tobytes()
        giant = struct.pack(
            "!2sBBQI", b"GW", 1, (1 << 4) | 4, 2 ** 40,
            zlib.crc32(payload),
        ) + payload
        with pytest.raises(wire.WireError, match="bound"):
            sv.push_frame(giant)
        assert sv.arrived() == 0
        # An honest multi-row sparse frame (4 rows x d_shard=16 = 64
        # elems, exactly the bound) ingests fine.
        rows = RNG.normal(size=(4, 32)).astype(np.float32)
        sliced = spec.slice_rows(rows, 1)
        sv.push_frame(
            wire.encode(sliced.ravel(), "topk", k=64, plane=1)
        )
        assert sv.arrived() == 4
        assert sv.finish_round().shape == (16,)


# ---------------------------------------------------------------------------
# suspicion survives sampling (ISSUE 13 satellite)


class TestClientSuspicion:
    def test_rotating_sampled_attacker_tops_decayed_suspicion(self):
        """Regression: a Byzantine client resampled into a DIFFERENT
        cohort position every round must still top the hub's decayed
        suspicion — the score is keyed by stable global id, so cohort-
        index reshuffling (the sampling-scale laundering channel)
        buys nothing."""
        hub = tele_hub.MetricsHub(suspicion_halflife=6)
        tele_hub.install(hub)
        try:
            # Small population + many rounds: every honest client is
            # observed often enough that its exclusion frequency
            # converges to the rule's honest-exclusion rate (krum keeps
            # m = n - f - 2 per fold), leaving no one-observation ties
            # at 1.0 with the attacker.
            pop, n, d = 32, 16, 32
            byz = 7  # the one Byzantine global id
            sampler = fed.CohortSampler(pop, n, seed=21, byz_frac=0.05)
            eng = fed.FedRoundEngine(
                np.zeros(d, np.float32), 2, sampler, lr=0.01,
                audit=True, telemetry=True,
            )
            mu = RNG.normal(size=d).astype(np.float32)
            seen = 0
            for r in range(40):
                ids, f = eng.begin_round()
                rows, _ = honest_rows(ids.size, d, mu=mu, sigma=0.05)
                if byz in ids:
                    pos = int(np.where(ids == byz)[0][0])
                    rows[pos] = -50.0 * mu  # the reverse attack
                    seen += 1
                eng.ingest_rows(rows)
                eng.finish_round()
            assert seen >= 5, "sampler never drew the attacker"
            susp = hub.client_suspicion_decayed()
            assert susp is not None and byz in susp
            top = max(susp, key=susp.get)
            assert top == byz, (
                f"attacker {byz} (s={susp[byz]:.3f}) not on top — "
                f"got {top} (s={susp[top]:.3f})"
            )
            # And resampling cannot LAUNDER it: the attacker's score
            # strictly dominates every honest client's.
            honest_max = max(
                v for c, v in susp.items() if c != byz
            )
            assert susp[byz] > honest_max
        finally:
            tele_hub.uninstall()


# ---------------------------------------------------------------------------
# sharded checkpoints (ISSUE 13 satellite)


class TestShardedCheckpoint:
    def test_round_trip_bitwise_at_pima_scale(self, tmp_path):
        # pima-scale vector (the tabular model's parameter count is a
        # few hundred floats); odd size to exercise uneven spans.
        d = 937
        v = RNG.normal(size=d).astype(np.float32)
        for s in (1, 3, 4):
            spec = fed.plan_shards(d, s)
            dir_ = tmp_path / f"s{s}"
            fed.save_sharded(dir_, 7, v, spec)
            back = fed.restore_sharded(dir_, spec)
            assert np.array_equal(back, v)  # bitwise
            assert back.dtype == np.float32

    def test_partial_shard_save_and_torn_save_detection(self, tmp_path):
        d = 100
        spec = fed.plan_shards(d, 2)
        v = RNG.normal(size=d).astype(np.float32)
        # Each shard process saves only its own span...
        fed.save_sharded(tmp_path, 3, v, spec, shards=[0])
        # ...a torn save (shard 1 missing) must not restore.
        with pytest.raises(FileNotFoundError):
            fed.restore_sharded(tmp_path, spec)
        fed.save_sharded(tmp_path, 3, v, spec, shards=[1])
        assert np.array_equal(fed.restore_sharded(tmp_path, spec), v)

    def test_spec_mismatch_detected(self, tmp_path):
        """Restoring with the wrong shard map (a deployment error) is a
        loud span mismatch, not a silently misassembled model."""
        d = 64
        v = RNG.normal(size=d).astype(np.float32)
        fed.save_sharded(tmp_path, 1, v, fed.plan_shards(d, 2))
        wrong = fed.plan_shards(d, 2)
        wrong.spans = ((0, d // 2 - 1), (d // 2 - 1, d))
        with pytest.raises(ValueError, match="span"):
            fed.restore_sharded(tmp_path, wrong)


# ---------------------------------------------------------------------------
# telemetry schema v10


class TestTelemetryV10:
    def test_fed_round_and_cohort_events_validate(self):
        exporters.validate_record(exporters.make_record(
            "event", event="fed_round", step=3, shards=4, cohort=1000,
            f_budget=12, realized_byz=2, budget_exceeded=False,
            round_s=1.25,
            per_shard={"0": {"latency_s": 0.2, "wire_bytes": 1024}},
        ))
        exporters.validate_record(exporters.make_record(
            "event", event="cohort", step=3,
            client_ids=[5, 9, 11], selected=[1.0, 0.0, 1.0], f_budget=1,
        ))

    def test_malformed_v10_records_rejected(self):
        with pytest.raises(ValueError):
            exporters.validate_record(exporters.make_record(
                "event", event="fed_round", step=3, shards=0, cohort=10,
            ))
        with pytest.raises(ValueError):
            exporters.validate_record(exporters.make_record(
                "event", event="cohort", client_ids=[1, 2],
                selected=[1.0],  # length mismatch
            ))
        with pytest.raises(ValueError):
            exporters.validate_record(exporters.make_record(
                "fed_bench", check="", n=10, d=10, shards=1, gar="x",
            ))
        with pytest.raises(ValueError):
            exporters.validate_record(exporters.make_record(
                "fed_bench", check="scaling", n=10, d=10, shards=1,
                gar="hier-krum", s1_bitwise_equal="yes",
            ))

    def test_fed_bench_rows_validate(self):
        exporters.validate_record(exporters.make_record(
            "fed_bench", check="scaling", n=10 ** 6,
            population=2 * 10 ** 6, d=10 ** 4, shards=4, gar="hier-krum",
            f=10447, rounds=2, round_s=8.1, round_s_sum=33.0,
            speedup=2.96, per_shard_s=[8.1, 8.0, 8.0, 7.9],
            per_shard_rss=[10 ** 9] * 4, peak_rss_bytes=10 ** 9,
        ))
        exporters.validate_record(exporters.make_record(
            "fed_bench", check="fleet", n=64, d=10 ** 4, shards=2,
            gar="hier-krum", target_rate=10.0, pre_rate=6.0,
            recovered_rate=11.0, achieved_rate=11.0, spawns=3,
            retires=0, active_initial=2, active_final=5, round_s=0.09,
        ))

    def test_summary_federated_digest_validates(self):
        exporters.validate_record(exporters.make_record(
            "summary", steps=0, events=4,
            federated={"rounds": 2, "shards": 4, "budget_exceeded": 0,
                       "top_clients": {"7": 0.9}},
        ))
        with pytest.raises(ValueError):
            exporters.validate_record(exporters.make_record(
                "summary", steps=0, events=4,
                federated={"rounds": -1, "budget_exceeded": 0},
            ))


# ---------------------------------------------------------------------------
# hierarchy additions the engine leans on


class TestStreamingAdditions:
    def test_bulk_push_many_bitwise_equals_per_row(self):
        n, f, d = 200, 9, 40
        g, _ = honest_rows(n, d)
        bulk = hierarchy.StreamingAggregator(n, f)
        bulk.push_many(g)
        one = hierarchy.StreamingAggregator(n, f)
        for row in g:
            one.push(row)
        assert np.array_equal(bulk.finalize(), one.finalize())
        batch = np.asarray(hierarchy.aggregate(g, f))
        assert np.array_equal(bulk.finalize(), batch)

    def test_reset_reuses_buffers_bitwise(self):
        n, f, d = 150, 5, 32
        g1, _ = honest_rows(n, d)
        g2, _ = honest_rows(n, d)
        red = hierarchy.StreamingAggregator(n, f)
        red.push_many(g1)
        red.finalize()
        red.reset()
        red.push_many(g2)
        out = red.finalize()
        fresh = hierarchy.StreamingAggregator(n, f)
        fresh.push_many(g2)
        assert np.array_equal(out, fresh.finalize())

    def test_push_many_guards(self):
        red = hierarchy.StreamingAggregator(8, 0, bucket_gar="average")
        red.push_many(np.zeros((8, 4), np.float32))
        with pytest.raises(ValueError, match="past the"):
            red.push_many(np.zeros((1, 4), np.float32))
        red2 = hierarchy.StreamingAggregator(64, 1)
        red2.push_many(np.zeros((4, 6), np.float32))
        with pytest.raises(ValueError, match="expected"):
            red2.push_many(np.zeros((4, 5), np.float32))


# ---------------------------------------------------------------------------
# telemetry schema v12: per-phase attribution


class TestTelemetryV12:
    def test_fed_bench_phases_validate(self):
        exporters.validate_record(exporters.make_record(
            "fed_bench", check="scaling", n=10 ** 6, d=10 ** 4, shards=4,
            gar="hier-krum", round_s=1.0,
            phases={
                "ingest": {"count": 8, "p50_s": 0.01, "p95_s": 0.02},
                "h2d": {"count": 8, "p50_s": 0.001, "p95_s": 0.002},
                "fold": {"count": 8, "p50_s": 0.005, "p95_s": 0.009},
            },
        ))

    @pytest.mark.parametrize("phases", [
        "ingest",                               # not an object
        {"ingest": [0.1, 0.2]},                 # stats not an object
        {"ingest": {"p50_s": "fast"}},          # non-numeric stat
    ])
    def test_malformed_fed_bench_phases_rejected(self, phases):
        with pytest.raises(ValueError, match="phases"):
            exporters.validate_record(exporters.make_record(
                "fed_bench", check="scaling", n=10, d=10, shards=1,
                gar="hier-krum", phases=phases,
            ))


# ---------------------------------------------------------------------------
# control plane: checkpointed failover / resume (DESIGN.md §22)


import json  # noqa: E402
import os  # noqa: E402

from garfield_tpu import controlplane as cp  # noqa: E402


class TestFailoverDeterminism:
    """The handoff contract, pinned at the trajectory level: a shard
    killed mid-round and promoted from its span checkpoint re-runs the
    interrupted round and lands on the SAME model bytes as a run that
    never died."""

    N, D, S = 16, 96, 2

    def _engine(self, tmp_path, sub):
        sampler = fed.CohortSampler(self.N, self.N, seed=11,
                                    byz_frac=0.05)
        model0 = np.random.default_rng(5).normal(
            size=self.D).astype(np.float32)
        return fed.FedRoundEngine(
            model0, self.S, sampler, lr=0.05, epoch=1,
            checkpoint_dir=str(tmp_path / sub),
        )

    def _rows(self, r):
        return np.random.default_rng([21, r]).normal(
            size=(self.N, self.D)).astype(np.float32)

    def test_kill_and_rerun_is_bitwise(self, tmp_path):
        ref = self._engine(tmp_path, "ref")
        for r in range(4):
            ref.begin_round()
            ref.ingest_rows(self._rows(r))
            ref.finish_round()

        eng = self._engine(tmp_path, "victim")
        for r in range(4):
            active, f = eng.begin_round()
            rows = self._rows(r)
            if r == 2:
                # The shard dies with half the cohort folded in. The
                # standby restores the round-1 span checkpoint and pins
                # itself to re-run round 2 — mid-round fold state is
                # deliberately NOT checkpointed (arrival order is
                # bucket assignment; a resumed half-fold would not be
                # the bytes a clean round produces).
                eng.ingest_rows(rows[: self.N // 2])
                srv, rerun = cp.promote_standby(eng, 1)
                assert rerun == 2 and eng.epoch == 2
                active, f = eng.begin_round()  # re-arm ALL shards
            eng.ingest_rows(rows)
            eng.finish_round()

        assert np.array_equal(eng.model, ref.model)  # bitwise
        # The failover bumped the epoch; the clean run never did.
        assert eng.epoch == 2 and ref.epoch == 1

    def test_resume_restores_bitwise_round_and_epoch(self, tmp_path):
        eng = self._engine(tmp_path, "a")
        eng.resize(1)  # one epoch bump (1 -> 2) recorded in control
        for r in range(3):
            eng.begin_round()
            eng.ingest_rows(self._rows(r))
            eng.finish_round()
        want = eng.model.copy()

        fresh = self._engine(tmp_path, "b")
        fresh.resize(1)
        with pytest.raises(FileNotFoundError, match="complete"):
            fresh.resume()  # its own dir is empty
        fresh._ckpt_dir = eng._ckpt_dir
        step = fresh.resume()
        assert step == 2 and fresh.round == 3
        assert np.array_equal(fresh.model, want)
        assert fresh.epoch == eng.epoch == 2
        # The resumed engine serves round 3 and stays on trajectory.
        fresh.begin_round()
        eng.begin_round()
        fresh.ingest_rows(self._rows(3))
        eng.ingest_rows(self._rows(3))
        fresh.finish_round()
        eng.finish_round()
        assert np.array_equal(fresh.model, eng.model)

    def test_restored_shard_refuses_unknown_round(self, tmp_path):
        """Satellite: after restore, the engine can only serve the
        round after its checkpoint — any other round is a LOUD refusal,
        not a silent fold against a stale span."""
        eng = self._engine(tmp_path, "a")
        for r in range(2):
            eng.begin_round()
            eng.ingest_rows(self._rows(r))
            eng.finish_round()
        eng2 = self._engine(tmp_path, "a")
        eng2.resume()
        eng2.round = 5  # a driver resuming at the wrong round
        with pytest.raises(RuntimeError, match="refusing loudly"):
            eng2.begin_round()
        with pytest.raises(RuntimeError, match="no span checkpoint"):
            eng2.shards[0].begin_round(0, self.N, 1)
        eng2.round = 2  # the one round the restored spans are valid for
        eng2.begin_round()

    def test_torn_checkpoint_never_restores_mixed_rounds(self, tmp_path):
        eng = self._engine(tmp_path, "a")
        for r in range(3):
            eng.begin_round()
            eng.ingest_rows(self._rows(r))
            eng.finish_round()
        # Tear step 2: the control record vanished (crash between the
        # span save and the control save).
        os.remove(os.path.join(eng._ckpt_dir, "control", "ctl_2.json"))
        eng2 = self._engine(tmp_path, "a")
        assert eng2.resume() == 1  # falls back to the newest COMPLETE
        with pytest.raises(FileNotFoundError, match="complete"):
            eng2.resume(step=2)
        # A control record disagreeing with its step key is torn too.
        path = os.path.join(eng._ckpt_dir, "control", "ctl_1.json")
        with open(path) as fp:
            rec = json.load(fp)
        rec["round"] = 7
        with open(path, "w") as fp:
            json.dump(rec, fp)
        with pytest.raises(ValueError, match="torn"):
            self._engine(tmp_path, "a").resume(step=1)


# ---------------------------------------------------------------------------
# bulk wire ingest (ShardServer.push_frames — ISSUE 20)


class TestShardBatchIngest:
    """push_frames is semantics-preserving bulk ingest: batch ==
    per-frame bitwise, arrival order never depends on the path taken
    (any multi-row or unreadable frame demotes the WHOLE call to the
    per-frame loop — bucket assignment IS arrival order), rejects are
    indexed ban evidence, and the call emits one v15 ``ingest_batch``
    event when a hub is installed."""

    def _servers(self, d=32, shards=2, shard=1, n=8, **kw):
        spec = fed.plan_shards(d, shards)
        sv = fed.ShardServer(shard, spec, bucket_gar="average", **kw)
        sv.begin_round(0, n, 0)
        return spec, sv

    def test_batch_bitwise_equals_per_frame(self):
        d, n = 32, 8
        rows, _ = honest_rows(n, d)
        spec, sv_b = self._servers(d=d, n=n)
        _, sv_s = self._servers(d=d, n=n)
        frames = [wire.encode(spec.slice_rows(r, 1), plane=1)
                  for r in rows]
        res = sv_b.push_frames(frames)
        assert res == list(range(n))
        for fr in frames:
            sv_s.push_frame(fr)
        assert np.array_equal(sv_b.finish_round(), sv_s.finish_round())
        assert sv_b.wire_bytes_in == sv_s.wire_bytes_in \
            == sum(len(f) for f in frames)

    def test_multi_row_frame_demotes_whole_call_preserving_order(self):
        d, n = 32, 6
        rows, _ = honest_rows(n, d)
        spec, sv_m = self._servers(d=d, n=n)
        _, sv_s = self._servers(d=d, n=n)
        # frame 2 carries TWO rows: the batch prescreen must fall back
        # for ALL frames, in list order, or bucket assignment would
        # depend on which path ran.
        frames = [
            wire.encode(spec.slice_rows(rows[0], 1), plane=1),
            wire.encode(spec.slice_rows(rows[1], 1), plane=1),
            wire.encode(spec.slice_rows(rows[2:4], 1).ravel(), plane=1),
            wire.encode(spec.slice_rows(rows[4], 1), plane=1),
            wire.encode(spec.slice_rows(rows[5], 1), plane=1),
        ]
        res = sv_m.push_frames(frames)
        assert res == [0, 1, 2, 4, 5]  # frame 2 ingests rows 2 AND 3
        assert sv_m.arrived() == n
        for fr in frames:
            sv_s.push_frame(fr)
        assert np.array_equal(sv_m.finish_round(), sv_s.finish_round())

    def test_rejects_are_indexed_ban_evidence(self):
        d, n = 32, 5
        rows, _ = honest_rows(n + 1, d)
        spec, sv = self._servers(d=d, n=n)
        frames = [wire.encode(spec.slice_rows(r, 1), plane=1)
                  for r in rows[:n]]
        bad = bytearray(frames[1])
        bad[-1] ^= 0xFF  # CRC break
        frames[1] = bytes(bad)
        # cross-shard stamp: header-level reject, still indexed
        frames[3] = wire.encode(spec.slice_rows(rows[n], 0), plane=0)
        res = sv.push_frames(frames)
        assert isinstance(res[1], wire.WireError)
        assert isinstance(res[3], wire.WireError)
        assert [r for i, r in enumerate(res) if i not in (1, 3)] \
            == [0, 1, 2]
        assert sv.arrived() == 3

    def test_ingest_batch_event_emitted_and_validates(self):
        d, n = 32, 4
        rows, _ = honest_rows(n, d)
        spec, sv = self._servers(d=d, n=n)
        frames = [wire.encode(spec.slice_rows(r, 1), plane=1)
                  for r in rows]
        bad = bytearray(frames[2])
        bad[-1] ^= 0xFF
        frames[2] = bytes(bad)
        h = tele_hub.MetricsHub()
        prev = tele_hub.install(h)
        try:
            sv.push_frames(frames)
        finally:
            tele_hub.uninstall()
            if prev is not None:
                tele_hub.install(prev)
        evs = [r for r in h.records()
               if r["kind"] == "event" and r.get("event") == "ingest_batch"]
        assert len(evs) == 1
        ev = evs[0]
        exporters.validate_record(ev)
        assert ev["shard"] == 1 and ev["frames"] == n
        assert ev["rejected"] == 1 and ev["batched"] is True
        assert ev["bytes"] == sum(
            len(f) for i, f in enumerate(frames) if i != 2)
        assert ev["step"] == 0
        stats = h.ingest_batch_stats()
        assert stats["calls"] == 1 and stats["rejected"] == 1
        assert stats["batched_s"] > 0.0 and stats["fallback_s"] == 0.0

    def test_wire_batch_transform_is_push_frames(self):
        d, n = 32, 3
        rows, _ = honest_rows(n, d)
        spec, sv = self._servers(d=d, n=n)
        items = [(5 + i, wire.encode(spec.slice_rows(r, 1), plane=1))
                 for i, r in enumerate(rows)]
        assert sv.wire_batch_transform(items) == [0, 1, 2]
        assert sv.arrived() == n

    def test_epoch_pin_applies_in_batch(self):
        d, n = 32, 4
        rows, _ = honest_rows(n, d)
        spec, sv = self._servers(d=d, n=n, epoch=3)
        frames = [wire.encode(spec.slice_rows(r, 1), plane=1, epoch=3)
                  for r in rows]
        frames[1] = wire.encode(
            spec.slice_rows(rows[1], 1), plane=1, epoch=2)  # stale
        res = sv.push_frames(frames)
        assert isinstance(res[1], wire.WireError)
        assert "epoch" in str(res[1])
        assert [r for i, r in enumerate(res) if i != 1] == [0, 1, 2]
