"""Control plane (garfield_tpu/controlplane/, DESIGN.md §22).

Fast tier-1 coverage: the membership-view codec's loud-reject surface
(truncation at every depth, host-length lies, CRC/epoch tamper,
partition invariants), the directory's strict epoch monotonicity (the
replay ban), heartbeat failure detection (in-probe retries, once-only
death, revive, the real-TCP probe), the failover handoff's API contract
(checkpoint substrate required, suspicion carried forward max-merge,
the ErrorFeedback zero-rebuild pin), the shard autoscaler's
rescind-on-refusal accounting, the env knobs, the schema-v13
membership/soak_bench validators, and a ≤30 s soak smoke (rolling
restart + partitions + churn at toy scale). The full-scale soak (the
harness's default shape) is slow-marked. The engine-level
failover bitwise-determinism pin lives in tests/test_federated.py
beside the other trajectory anchors.
"""

import json
import socket

import numpy as np
import pytest

from garfield_tpu import controlplane as cp
from garfield_tpu import federated as fed
from garfield_tpu.apps.benchmarks import soak_bench
from garfield_tpu.controlplane import membership as ms
from garfield_tpu.telemetry import exporters, hub as tele_hub
from garfield_tpu.utils import wire

RNG = np.random.default_rng(20260807)


def _view(epoch=3, d=100, shards=4, host="127.0.0.1", port0=9000):
    spec = fed.plan_shards(d, shards)
    return cp.MembershipView(epoch, d, [
        cp.Seat(s, host, port0 + s, lo, hi)
        for s, (lo, hi) in enumerate(spec.spans)
    ])


# ---------------------------------------------------------------------------
# membership views


class TestSeat:
    def test_validation(self):
        cp.Seat(0, "host.example", 80, 0, 10)  # valid
        with pytest.raises(cp.ViewError, match="port"):
            cp.Seat(0, "h", 70000, 0, 10)
        with pytest.raises(cp.ViewError, match="empty or negative"):
            cp.Seat(0, "h", 80, 10, 10)
        with pytest.raises(cp.ViewError, match="length field"):
            cp.Seat(0, "x" * 300, 80, 0, 10)
        with pytest.raises(ValueError):
            cp.Seat(99, "h", 80, 0, 10)  # past the wire nibble


class TestMembershipView:
    def test_partition_invariants(self):
        v = _view()
        assert v.num_shards == 4 and v.epoch == 3
        spec = fed.plan_shards(100, 4)
        # gap
        seats = [cp.Seat(s, "h", 1, lo, hi)
                 for s, (lo, hi) in enumerate(spec.spans)]
        bad = seats[:1] + [cp.Seat(1, "h", 1, 30, 50)] + seats[2:]
        with pytest.raises(cp.ViewError, match="contiguously"):
            cp.MembershipView(1, 100, bad)
        # wrong keying
        with pytest.raises(cp.ViewError, match="keyed"):
            cp.MembershipView(1, 100, seats[::-1])
        # coverage short of d
        with pytest.raises(cp.ViewError, match="claims"):
            cp.MembershipView(1, 101, seats)
        # epoch must fit the wire header's u32 stamp
        with pytest.raises(ValueError):
            cp.MembershipView(wire.MAX_EPOCH + 1, 100, seats)
        with pytest.raises(cp.ViewError, match="1..16"):
            cp.MembershipView(1, 100, [])

    def test_spec_canonical_partition(self):
        v = _view(d=101, shards=4)
        spec = v.spec()
        assert spec.d == 101 and spec.num_shards == 4
        # A non-balanced tiling is a valid VIEW but not an engine spec.
        odd = cp.MembershipView(1, 100, [
            cp.Seat(0, "h", 1, 0, 90), cp.Seat(1, "h", 1, 90, 100)
        ])
        with pytest.raises(cp.ViewError, match="balanced"):
            odd.spec()

    def test_roundtrip_and_equality(self):
        v = _view(epoch=7, d=257, shards=5, host="ps-3.cluster.local")
        buf = v.encode()
        out = cp.MembershipView.decode(buf)
        assert out == v and out.seats[2] == v.seats[2]
        assert cp.MembershipView.decode(bytearray(buf)) == v

    def test_decode_rejects_every_malformation(self):
        buf = _view().encode()
        with pytest.raises(cp.ViewError, match="header"):
            cp.MembershipView.decode(buf[:10])
        with pytest.raises(cp.ViewError, match="magic"):
            cp.MembershipView.decode(b"XX" + buf[2:])
        with pytest.raises(cp.ViewError, match="version"):
            cp.MembershipView.decode(buf[:2] + b"\x09" + buf[3:])
        with pytest.raises(cp.ViewError, match="CRC"):
            cp.MembershipView.decode(buf[:-1] + bytes([buf[-1] ^ 1]))
        with pytest.raises(cp.ViewError, match="CRC|truncated"):
            cp.MembershipView.decode(buf[:-3])  # truncated seat table
        with pytest.raises(cp.ViewError, match="CRC|trailing"):
            cp.MembershipView.decode(buf + b"\x00")

    def test_epoch_restamp_is_crc_mismatch(self):
        # The CRC is seeded with the epoch bytes (the wire v2
        # construction): a relay rewriting the header epoch without
        # re-authoring the record fails the CRC, attributably.
        buf = bytearray(_view(epoch=3).encode())
        off = 4  # magic(2) + ver(1) + num_seats(1); epoch is !I next
        buf[off:off + 4] = (9).to_bytes(4, "big")
        with pytest.raises(cp.ViewError, match="CRC"):
            cp.MembershipView.decode(bytes(buf))

    def test_host_length_lie(self):
        v = cp.MembershipView(1, 10, [cp.Seat(0, "abcdef", 1, 0, 10)])
        buf = bytearray(v.encode())
        # The seat's host_len byte sits right before the host bytes.
        idx = bytes(buf).rindex(b"abcdef") - 1
        assert buf[idx] == 6
        buf[idx] = 200  # claims 200 host bytes; only 6 follow
        with pytest.raises(cp.ViewError, match="CRC|host"):
            cp.MembershipView.decode(bytes(buf))

    def test_for_engine(self):
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.zeros(40, np.float32), 4, smp,
                                 epoch=5)
        v = cp.MembershipView.for_engine(eng, ports=[1, 2, 3, 4])
        assert v.epoch == 5 and v.d == 40 and v.num_shards == 4
        assert [s.port for s in v.seats] == [1, 2, 3, 4]
        assert tuple(v.spec().spans) == tuple(eng.spec.spans)
        with pytest.raises(cp.ViewError, match="ports"):
            cp.MembershipView.for_engine(eng, ports=[1])


class TestMembershipDirectory:
    def test_strictly_newer_epochs_only(self):
        d = cp.MembershipDirectory(_view(epoch=3))
        assert d.epoch == 3 and d.installs == 1
        d.install(_view(epoch=4))
        assert d.epoch == 4
        # Replay of the superseded view AND a duplicate of the current
        # one are both the stale-view ban, counted as evidence.
        for stale in (3, 4):
            with pytest.raises(cp.StaleViewError, match="attributable"):
                d.install(_view(epoch=stale))
        assert d.rejects == 2 and "epoch 4" in d.last_reject
        assert d.epoch == 4  # unchanged by the rejects

    def test_install_frame_and_malformed_not_counted_stale(self):
        d = cp.MembershipDirectory()
        assert d.epoch is None
        d.install_frame(_view(epoch=2).encode())
        assert d.epoch == 2
        with pytest.raises(cp.ViewError):
            d.install_frame(b"garbage-bytes")
        assert d.rejects == 0  # malformed != stale: no admissible epoch
        with pytest.raises(TypeError):
            d.install("not a view")


# ---------------------------------------------------------------------------
# heartbeat failure detection


class TestHeartbeatMonitor:
    def test_transient_loss_survives_in_probe_retries(self):
        # Two consecutive probe failures, then success: with retries=3
        # the target never dies — one dropped SYN is not a failover.
        fails = {"left": 2}

        def probe(key):
            if fails["left"] > 0:
                fails["left"] -= 1
                return False
            return True

        mon = cp.HeartbeatMonitor({"a": ("a",)}, probe=probe,
                                  interval_s=0.001, retries=3,
                                  backoff_s=0)
        assert mon.poll() == []
        assert mon.down == set() and mon.probes == 3

    def test_death_fires_once_and_revive_rearms(self):
        alive = {"a": True, "b": True}
        deaths = []
        mon = cp.HeartbeatMonitor(
            {k: (k,) for k in alive}, probe=lambda k: alive[k],
            interval_s=0.001, retries=2, backoff_s=0,
            on_down=deaths.append,
        )
        assert mon.run_once() == []
        alive["b"] = False
        assert mon.poll() == ["b"] and deaths == ["b"]
        assert mon.poll() == []  # a dead target is not re-declared
        mon.revive("b", target=("b",))
        alive["b"] = True
        assert mon.poll() == [] and mon.down == set()

    def test_raising_probe_is_a_failed_probe(self):
        def probe(key):
            raise OSError("probe transport died")

        mon = cp.HeartbeatMonitor({"a": ("a",)}, probe=probe,
                                  interval_s=0.001, retries=1,
                                  backoff_s=0)
        assert mon.poll() == ["a"]

    def test_retries_validated(self):
        with pytest.raises(ValueError, match="retries"):
            cp.HeartbeatMonitor({}, retries=0, interval_s=0.001)

    def test_tcp_probe_real_socket(self):
        srv = socket.socket()
        try:
            srv.bind(("127.0.0.1", 0))
            srv.listen(1)
            host, port = srv.getsockname()
            assert cp.tcp_probe(host, port, timeout_s=1.0)
        finally:
            srv.close()
        # The port is closed now: connection refused, not a hang.
        assert not cp.tcp_probe(host, port, timeout_s=0.5)


class TestEnvKnobs:
    def test_heartbeat_interval(self, monkeypatch):
        monkeypatch.delenv("GARFIELD_HEARTBEAT_MS", raising=False)
        assert cp.heartbeat_interval_s() == pytest.approx(0.1)
        monkeypatch.setenv("GARFIELD_HEARTBEAT_MS", "250")
        assert cp.heartbeat_interval_s() == pytest.approx(0.25)
        monkeypatch.setenv("GARFIELD_HEARTBEAT_MS", "nope")
        with pytest.raises(ValueError, match="GARFIELD_HEARTBEAT_MS"):
            cp.heartbeat_interval_s()
        monkeypatch.setenv("GARFIELD_HEARTBEAT_MS", "0")
        with pytest.raises(ValueError):
            cp.heartbeat_interval_s()

    def test_standby_shards(self, monkeypatch):
        monkeypatch.delenv("GARFIELD_STANDBY_SHARDS", raising=False)
        assert cp.standby_shards() == 1
        monkeypatch.setenv("GARFIELD_STANDBY_SHARDS", "3")
        assert cp.standby_shards() == 3
        monkeypatch.setenv("GARFIELD_STANDBY_SHARDS", "-1")
        with pytest.raises(ValueError):
            cp.standby_shards()

    def test_soak_env_defaults(self, monkeypatch):
        monkeypatch.setenv("GARFIELD_SOAK_ROUNDS", "9")
        monkeypatch.setenv("GARFIELD_SOAK_COHORT", "24")
        monkeypatch.setenv("GARFIELD_SOAK_D", "128")
        monkeypatch.setenv("GARFIELD_SOAK_SHARDS", "2")
        assert soak_bench._env_int("GARFIELD_SOAK_ROUNDS", 60) == 9
        assert soak_bench._env_int("GARFIELD_SOAK_COHORT", 64) == 24
        assert soak_bench._env_int("GARFIELD_SOAK_D", 2048) == 128
        assert soak_bench._env_int("GARFIELD_SOAK_SHARDS", 4) == 2


# ---------------------------------------------------------------------------
# failover handoff


class TestFailover:
    def test_requires_checkpoint_substrate(self):
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.zeros(32, np.float32), 2, smp,
                                 epoch=1)
        with pytest.raises(RuntimeError, match="checkpoint_dir"):
            cp.promote_standby(eng, 0)

    def test_no_complete_checkpoint_is_loud(self, tmp_path):
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.zeros(32, np.float32), 2, smp,
                                 epoch=1, checkpoint_dir=str(tmp_path))
        with pytest.raises(FileNotFoundError, match="complete"):
            cp.promote_standby(eng, 0)

    def test_handoff_restores_span_suspicion_and_bumps_epoch(
            self, tmp_path):
        hub = tele_hub.MetricsHub()
        prev = tele_hub.install(hub)
        try:
            hub.absorb_client_suspicion({7: (3.0, 2.0)})
            smp = fed.CohortSampler(16, 16, seed=4, byz_frac=0.05)
            eng = fed.FedRoundEngine(
                RNG.normal(size=64).astype(np.float32), 2, smp,
                epoch=1, checkpoint_dir=str(tmp_path),
            )
            eng.begin_round()
            eng.ingest_rows(RNG.normal(size=(16, 64)).astype(np.float32))
            eng.finish_round()  # writes the round-0 checkpoint
            saved_span = eng.model[eng.spec.spans[1][0]:
                                   eng.spec.spans[1][1]].copy()
            # Dirty shard 1's span in memory (the half-updated state a
            # mid-round death leaves behind), then wipe the hub's
            # suspicion the way a standby's fresh process would.
            eng.model[eng.spec.spans[1][0]:eng.spec.spans[1][1]] = -1.0
            tele_hub.install(tele_hub.MetricsHub())
            srv, rerun = cp.promote_standby(eng, 1)
            assert rerun == 1 and eng.epoch == 2 and srv.epoch == 2
            assert np.array_equal(
                eng.model[eng.spec.spans[1][0]:eng.spec.spans[1][1]],
                saved_span,
            )
            # The checkpointed suspicion rode the control record into
            # the standby's hub — the crash cannot launder history.
            snap = tele_hub.current().client_suspicion_snapshot()
            assert snap.get(7, (0.0, 0.0))[1] >= 2.0
            # The standby serves exactly the interrupted round.
            with pytest.raises(RuntimeError, match="refusing loudly"):
                srv.begin_round(5, 16, eng.shards[0]._red.f)
        finally:
            tele_hub.install(prev)

    def test_error_feedback_zero_rebuild_pin(self):
        # The recorded PR 14 decision, pinned: a restart/handoff does
        # NOT restore wire ErrorFeedback residuals — a fresh instance
        # starts at zero and the handoff module says so as data.
        assert cp.EF_RESIDUAL_RESTORED is False
        ef = wire.ErrorFeedback()
        v = RNG.normal(size=64).astype(np.float32)
        ef.update("grad", v, np.zeros_like(v))
        assert ef.residual_norm("grad") > 0.0
        # A rebuilt (post-restart / post-handoff) accumulator is zero.
        assert wire.ErrorFeedback().residual_norm("grad") == 0.0
        assert wire.ErrorFeedback().total_norm() == 0.0


# ---------------------------------------------------------------------------
# shard autoscaling


class TestShardAutoscaler:
    def test_refused_split_rescinds(self):
        # d=8 at S=8: a split to 9 is impossible (more shards than
        # parameters) — the engine refuses, the controller's accounting
        # must show NOTHING: no action count, no consumed cooldown.
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.ones(8, np.float32), 8, smp, epoch=1)
        sc = cp.ShardAutoscaler(eng, target_rate=100.0, window=2,
                                cooldown=0)
        deltas = [sc.observe(1.0) for _ in range(4)]
        assert all(d == 0 for d in deltas)
        assert sc.refusals >= 1 and sc.controller.actions == 0
        assert eng.spec.num_shards == 8 and eng.epoch == 1

    def test_split_and_merge_bump_epoch(self):
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.ones(64, np.float32), 2, smp,
                                 epoch=1)
        sc = cp.ShardAutoscaler(eng, target_rate=100.0, window=2,
                                cooldown=0, max_shards=4)
        while eng.spec.num_shards < 4:
            sc.observe(1.0)  # sustained pressure: split toward the cap
        assert sc.splits == 2 and eng.epoch == 3
        sc2 = cp.ShardAutoscaler(eng, target_rate=1.0, window=2,
                                 cooldown=0)
        deltas = [sc2.observe(0.001) for _ in range(4)]
        assert -1 in deltas and eng.spec.num_shards < 4

    def test_unhealthy_round_vetoes_merge(self):
        smp = fed.CohortSampler(64, 8, seed=0)
        eng = fed.FedRoundEngine(np.ones(64, np.float32), 4, smp,
                                 epoch=1)
        sc = cp.ShardAutoscaler(eng, target_rate=1.0, window=3,
                                cooldown=0)
        # Fast rounds (merge territory) but one carried a failover:
        # shrinking into a wobble is forbidden for a full window.
        for i in range(3):
            assert sc.observe(0.001, healthy=(i != 1)) == 0
        assert eng.spec.num_shards == 4


# ---------------------------------------------------------------------------
# schema v13


class TestSchemaV13:
    def test_membership_event_validates(self):
        rec = exporters.make_record(
            "event", event="membership", epoch=4, action="failover",
            shard=1, num_shards=4, step=12,
        )
        exporters.validate_record(rec)
        rec_pre = exporters.make_record(
            "event", event="membership", epoch=None, action="split",
            shard=None, num_shards=2, step=0,
        )
        exporters.validate_record(rec_pre)
        for bad in (
            dict(rec, action=""),
            dict(rec, epoch=-1),
            dict(rec, num_shards=0),
            dict(rec, shard=-2),
        ):
            with pytest.raises(ValueError, match="membership"):
                exporters.validate_record(bad)

    def test_soak_bench_kind_validates(self):
        rec = exporters.make_record(
            "soak_bench", check="rolling_restart", rounds=60, d=2048,
            shards=4, cohort=64, population=256, p50_s=0.01,
            p95_s=0.02, p99_s=0.03, mean_s=0.012, wall_s=1.5,
            failovers=6, partitions=0, stale_rejects=0, epoch_final=7,
            kill_cost_rounds=0.4, bitwise_equal=True,
        )
        exporters.validate_record(rec)
        for bad in (
            dict(rec, check=""),
            dict(rec, rounds=0),
            dict(rec, failovers=-1),
            dict(rec, p99_s="slow"),
            dict(rec, bitwise_equal=1),
        ):
            with pytest.raises(ValueError, match="soak_bench"):
                exporters.validate_record(bad)
        assert exporters.SCHEMA_VERSION >= 13


# ---------------------------------------------------------------------------
# the soak harness


def _soak_args(tmp_path, rounds):
    return [
        "--rounds", str(rounds), "--cohort", "16", "--d", "256",
        "--shards", "2", "--kill_every", "2", "--part_every", "2",
        "--churn_max_shards", "3",
        "--json", str(tmp_path / "SOAK.json"),
    ]


class TestSoakBench:
    def test_smoke_all_scenarios(self, tmp_path):
        """≤30 s: every scenario at toy scale, with kills and
        partitions actually exercised, the artifact twin written and
        schema-v13 valid."""
        rows = soak_bench.main(_soak_args(tmp_path, 4))
        by = {r["check"]: r for r in rows}
        assert set(by) == {"steady", "rolling_restart", "partition",
                           "churn"}
        rr = by["rolling_restart"]
        # The handoff contract on counts: rounds 2 and 4 each lose a
        # shard mid-round, each is re-run once (run_round asserts the
        # promoted standby resumes AT the killed round) and recorded
        # once, every failover bumps the epoch, and the model ends
        # bitwise where the undisturbed twin's does. The harness also
        # MEASURES the kill's cost in rounds of latency; at 4 toy rounds
        # beside five other test workers that ratio is noise, so it is
        # reported here and bounded only at full scale (slow).
        assert rr["failovers"] == 2
        assert rr["bitwise_equal"] is True
        assert rr["epoch_final"] == 1 + rr["failovers"]
        assert isinstance(rr["kill_cost_rounds"], float)
        pt = by["partition"]
        assert pt["partitions"] == 2
        assert pt["stale_rejects"] == 3 * pt["partitions"]
        assert by["steady"]["failovers"] == by["steady"]["partitions"] == 0
        for row in rows:
            assert row["rounds"] == 4
            assert 0 < row["p50_s"] <= row["p95_s"] <= row["p99_s"]
        assert exporters.validate_jsonl(str(tmp_path / "SOAK.jsonl")) == 4
        with open(tmp_path / "SOAK.json") as fp:
            assert len(json.load(fp)) == 4

    @pytest.mark.slow
    def test_full_scale_soak(self, tmp_path):
        """The harness's default shape: default knobs, 4 x 60
        sustained rounds under rolling restarts, partitions and
        churn."""
        rows = soak_bench.main([
            "--json", str(tmp_path / "SOAKBENCH.json"),
        ])
        assert sum(r["rounds"] for r in rows) >= 200
        rr = {r["check"]: r for r in rows}["rolling_restart"]
        assert rr["bitwise_equal"] is True
        assert rr["kill_cost_rounds"] <= 1.0
