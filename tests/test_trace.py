"""Distributed round tracing tests (ISSUE 8): spans, schema v5, report.

Pins the tracing plane's contracts:
  1. span lifecycle — enabled spans record wall start + monotonic
     duration + tags through the hub hook; nesting works; exceptions
     record AND propagate; disabled tracing is the shared no-op (zero
     records, reusable object);
  2. schema v5 — the ``span`` kind and the summary's ``spans``/
     ``phases`` digest validate (and malformed ones fail loudly);
  3. the report merger is DETERMINISTIC on the committed multi-role
     fixture (tests/fixtures/trace_run — a real 1 PS + 4 worker
     --async --trace run with a 300 ms straggler on worker 3) and its
     per-round critical path sums to the measured round time within
     the quoted alignment error;
  4. tracing-on vs tracing-off trajectories are BITWISE equal (spans
     are host-only observers — the taps' purity contract, host
     edition).
"""

import json
import pathlib
import time

import jax
import numpy as np
import pytest

from garfield_tpu.parallel import aggregathor
from garfield_tpu.telemetry import (
    JsonlExporter,
    MetricsHub,
    SCHEMA_VERSION,
    install,
    make_record,
    prometheus_text,
    report,
    trace,
    uninstall,
    validate_jsonl,
    validate_record,
)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURE = REPO_ROOT / "tests" / "fixtures" / "trace_run"


@pytest.fixture
def hub():
    h = MetricsHub(num_ranks=4)
    prev = install(h)
    trace.enable(who="test")
    yield h
    trace.disable()
    uninstall()
    if prev is not None:
        install(prev)


def _spans(h):
    return [r for r in h.records() if r["kind"] == "span"]


class TestSpanLifecycle:
    def test_basic_span_records(self, hub):
        with trace.span("quorum", step=3) as sp:
            time.sleep(0.001)
            sp.set(arrived=7)
        recs = _spans(hub)
        assert len(recs) == 1
        rec = recs[0]
        assert rec["phase"] == "quorum"
        assert rec["step"] == 3
        assert rec["arrived"] == 7
        assert rec["who"] == "test"
        assert rec["dur_s"] >= 0.001
        assert abs(rec["t_wall"] - time.time()) < 5.0
        validate_record(rec)

    def test_nesting(self, hub):
        with trace.span("outer", step=0):
            with trace.span("inner", step=0):
                time.sleep(0.001)
        recs = {r["phase"]: r for r in _spans(hub)}
        assert set(recs) == {"outer", "inner"}
        # The inner span is emitted first (exits first) and nests
        # inside the outer one on both clocks.
        assert recs["inner"]["dur_s"] <= recs["outer"]["dur_s"]
        assert recs["inner"]["t_wall"] >= recs["outer"]["t_wall"] - 1e-6
        in_end = recs["inner"]["t_wall"] + recs["inner"]["dur_s"]
        out_end = recs["outer"]["t_wall"] + recs["outer"]["dur_s"]
        assert in_end <= out_end + 1e-3

    def test_exception_recorded_and_propagates(self, hub):
        with pytest.raises(RuntimeError):
            with trace.span("broadcast", step=1):
                raise RuntimeError("boom")
        (rec,) = _spans(hub)
        assert rec["phase"] == "broadcast"
        assert rec["error"] == "RuntimeError"
        validate_record(rec)

    def test_disabled_is_shared_noop(self):
        trace.disable()
        s1, s2 = trace.span("a", step=0), trace.span("b")
        assert s1 is s2  # the reusable null span: zero allocation growth
        with s1 as sp:
            sp.set(x=1)  # no-op, no error
        assert not trace.enabled()

    def test_no_hub_is_safe(self):
        # Enabled tracing without an installed hub must not raise.
        uninstall()
        trace.enable(who="nohub")
        try:
            with trace.span("publish", step=0):
                pass
        finally:
            trace.disable()

    def test_phase_stats_and_last_round(self, hub):
        for step in (0, 1):
            with trace.span("gar_apply", step=step):
                time.sleep(0.001)
        stats = hub.phase_stats()
        assert stats["gar_apply"]["count"] == 2
        assert stats["gar_apply"]["p50_s"] >= 0.001
        # Last COMPLETED round = second-newest step seen.
        step, phases = hub.last_round_phases()
        assert step == 0
        assert "gar_apply" in phases

    def test_prometheus_phase_histogram(self, hub):
        with trace.span("collect", step=0):
            time.sleep(0.001)
        text = prometheus_text(hub)
        assert 'garfield_phase_seconds_bucket{phase="collect",le="+Inf"} 1' \
            in text
        assert 'garfield_phase_seconds_count{phase="collect"} 1' in text

    def test_sink_streams_spans(self, hub, tmp_path):
        exp = JsonlExporter(tmp_path / "s.jsonl")
        hub._sink = exp
        with trace.span("eval", step=2):
            pass
        exp.close()
        lines = [json.loads(l) for l in open(tmp_path / "s.jsonl")]
        assert lines and lines[0]["kind"] == "span"
        assert lines[0]["phase"] == "eval"


class TestSchemaV5:
    def test_version_bumped(self):
        # v5 introduced spans; v6 (elastic asynchrony) is additive on
        # top — span records are unchanged.
        assert SCHEMA_VERSION >= 5

    def test_span_valid(self):
        validate_record(make_record(
            "span", phase="quorum", t_wall=1e9, dur_s=0.01, step=3,
            who="cluster-ps", tid=0, arrived=3,
        ))
        # step/who optional
        validate_record(make_record("span", phase="x", t_wall=0.0,
                                    dur_s=0.0))

    @pytest.mark.parametrize("bad", [
        {"phase": "", "t_wall": 0.0, "dur_s": 0.1},
        {"phase": "q", "dur_s": 0.1},                       # no t_wall
        {"phase": "q", "t_wall": 0.0, "dur_s": -1.0},       # negative dur
        {"phase": "q", "t_wall": 0.0, "dur_s": 0.1, "step": -1},
        {"phase": "q", "t_wall": 0.0, "dur_s": 0.1, "step": 1.5},
        {"phase": "q", "t_wall": 0.0, "dur_s": 0.1, "who": 7},
    ])
    def test_span_invalid(self, bad):
        with pytest.raises(ValueError):
            validate_record(make_record("span", **bad))

    def test_summary_phases(self):
        validate_record(make_record(
            "summary", steps=1, events=0, spans=4,
            phases={"quorum": {"count": 2, "p50_s": 0.1}},
        ))
        with pytest.raises(ValueError):
            validate_record(make_record(
                "summary", steps=1, events=0, phases={"quorum": "fast"},
            ))
        with pytest.raises(ValueError):
            validate_record(make_record(
                "summary", steps=1, events=0, spans=-2,
            ))



class TestReport:
    """The merger on the committed fixture: a real traced SSMW --async
    run (1 PS + 4 workers, worker 3 straggling 300 ms, max_staleness 4,
    10 rounds). The fixture is static, so every assertion here is a
    determinism pin."""

    def test_fixture_present(self):
        assert (FIXTURE / "cluster-ps.telemetry.jsonl").exists()
        streams = sorted(FIXTURE.glob("*.telemetry.jsonl"))
        assert len(streams) == 5
        for path in streams:  # a schema change that orphans it fails here
            assert validate_jsonl(path) > 0

    def test_build_deterministic(self):
        a1 = report.build(str(FIXTURE))
        a2 = report.build(str(FIXTURE))
        md1, md2 = report.render_markdown(a1), report.render_markdown(a2)
        assert md1 == md2
        t1 = json.dumps(report.chrome_trace(a1), sort_keys=True)
        t2 = json.dumps(report.chrome_trace(a2), sort_keys=True)
        assert t1 == t2

    def test_chrome_trace_valid(self):
        tr = report.chrome_trace(report.build(str(FIXTURE)))
        assert tr["traceEvents"]
        names = set()
        pids = set()
        for ev in tr["traceEvents"]:
            assert ev["ph"] in ("X", "M")
            if ev["ph"] == "X":
                assert ev["ts"] >= 0 and ev["dur"] >= 0
                names.add(ev["name"])
            else:
                pids.add(ev["args"]["name"])
        # One process lane per role; the waiter-thread decode spans are
        # present (the collect/compute overlap, visible at last).
        assert len(pids) == 5
        assert {"broadcast", "quorum", "gar_apply", "decode",
                "publish"} <= names

    def test_critical_path_sums_to_round_time(self):
        analysis = report.build(str(FIXTURE))
        crit = analysis["critical_path"]
        assert len(crit) == 10  # num_iter rounds, no sentinel phantom
        err = max(analysis["alignment_error_s"], 1e-3)
        for row in crit:
            # Attribution never exceeds the measured round (no double
            # counting: nested spans are dropped)...
            assert row["attributed_s"] <= row["measured_s"] + err
        # ...and covers it: the per-run residual is untraced host glue,
        # bounded well below the measured total on the fixture.
        total_meas = sum(r["measured_s"] for r in crit)
        total_attr = sum(r["attributed_s"] for r in crit)
        assert total_attr >= 0.9 * total_meas

    def test_straggler_ranking_finds_victim(self):
        analysis = report.build(str(FIXTURE))
        rows = analysis["stragglers"]
        assert rows and rows[0]["role"] == "cluster-worker-3"
        # The injected 300 ms sleep dominates the honest workers' ms-
        # scale lateness by an order of magnitude.
        assert rows[0]["median_lateness_s"] > 10 * max(
            r["median_lateness_s"] for r in rows[1:]
        )

    def test_staleness_reuse_reported(self):
        st = report.build(str(FIXTURE))["staleness"]
        assert st is not None and st["rounds"] == 10
        assert st["reuse_rate"] > 0.5  # the straggler forces heavy reuse

    def test_offsets_causally_bracketed(self):
        offsets = report.build(str(FIXTURE))["offsets"]
        assert offsets["cluster-ps"]["offset_s"] == 0.0
        for name, o in offsets.items():
            if name == "cluster-ps" or o["lb_s"] is None \
                    or o["ub_s"] is None:
                continue
            assert o["lb_s"] <= o["offset_s"] <= o["ub_s"] + 1e-9

    def test_main_writes_artifacts(self, tmp_path, capsys):
        report.main([
            str(FIXTURE),
            "--trace-out", str(tmp_path / "trace.json"),
            "--md-out", str(tmp_path / "report.md"),
        ])
        tr = json.loads((tmp_path / "trace.json").read_text())
        assert tr["traceEvents"]
        md = (tmp_path / "report.md").read_text()
        assert "Per-round critical path" in md
        assert "Straggler ranking" in md


class TestTrajectoryPin:
    def test_tracing_on_off_bitwise(self):
        """Spans are host-only: running the SAME trainer loop with a
        hub installed + tracing enabled (spans wrapped around each
        dispatch, the app loop's instrumentation shape) must leave the
        TrainState bitwise identical to the untraced run."""
        from garfield_tpu import models as models_lib
        from garfield_tpu.utils import selectors

        module = models_lib.select_model("pimanet", "pima")
        loss = selectors.select_loss("bce")
        opt = selectors.select_optimizer("sgd", lr=0.05, momentum=0.9)
        rng = np.random.default_rng(0)
        # (slots, bsz, features): one per-worker shard stack per step.
        x = jax.numpy.asarray(
            rng.normal(size=(8, 16, 8)).astype(np.float32))
        y = jax.numpy.asarray(
            (np.asarray(x).sum(-1, keepdims=True) > 0).astype(np.float32))
        states = []
        for traced in (True, False):
            init_fn, step_fn, _ = aggregathor.make_trainer(
                module, loss, opt, "krum", num_workers=8, f=2,
                attack="lie",
            )
            state = init_fn(jax.random.PRNGKey(0), x[0])
            if traced:
                h = MetricsHub(num_ranks=8)
                install(h)
                trace.enable(who="pin")
            try:
                for i in range(5):
                    if traced:
                        with trace.span("dispatch", step=i):
                            state, _ = step_fn(state, x, y)
                    else:
                        state, _ = step_fn(state, x, y)
            finally:
                if traced:
                    trace.disable()
                    uninstall()
            if traced:
                assert h.counters()["spans"] == 5
            states.append(state)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            states[0], states[1],
        )


class TestHierIngestAlignment:
    """ISSUE 20 satellite: per-wave ingest accounting. The hierarchy
    reports ONE pre-timed ``hier_ingest`` span per dispatched wave
    (``trace.emit``), so per-level span counts obey
    count(hier_ingest) == count(hier_wave) == count(hier_h2d) EXACTLY —
    round 19's capture timed an outer per-push span instead and
    undercounted ingest attribution (11721 ingest vs 12102 fold/h2d
    spans). Pinned over every ingest entry point: per-row push,
    push_many (copy and zero-copy stable), per-frame push_frame, and
    bulk push_frames."""

    def _ingest_paths(self, n, d, frames, g):
        from garfield_tpu.aggregators import hierarchy

        def mk():
            return hierarchy.StreamingAggregator(
                n, 3, bucket_gar="median", bucket_size=8, wave_buckets=2,
                d=d)

        def per_row(red):
            for row in g:
                red.push(row)

        def many_copy(red):
            red.push_many(g.copy())

        def many_stable(red):
            red.push_many(g, stable=True)

        def per_frame(red):
            for fr in frames:
                red.push_frame(fr)

        def bulk_frames(red):
            assert red.push_frames(frames) == list(range(n))

        return mk, (per_row, many_copy, many_stable, per_frame,
                    bulk_frames)

    def test_counts_align_per_level_on_every_path(self, hub):
        from garfield_tpu.utils import wire as wire_mod

        n, d = 64, 16
        rng = np.random.default_rng(11)
        g = rng.normal(size=(n, d)).astype(np.float32)
        frames = [wire_mod.encode(row) for row in g]
        mk, paths = self._ingest_paths(n, d, frames, g)
        seen = 0
        for ingest in paths:
            red = mk()
            ingest(red)
            red.finalize()
            counts = {}
            for rec in _spans(hub)[seen:]:
                if rec["phase"] in ("hier_ingest", "hier_wave",
                                    "hier_h2d"):
                    lv = rec["level"]
                    counts.setdefault(lv, {}).setdefault(
                        rec["phase"], 0)
                    counts[lv][rec["phase"]] += 1
                validate_record(rec)
            seen = len(_spans(hub))
            assert counts, ingest.__name__
            for lv, by_phase in counts.items():
                assert (
                    by_phase.get("hier_ingest", 0)
                    == by_phase.get("hier_wave", 0)
                    == by_phase.get("hier_h2d", 0)
                ), (ingest.__name__, lv, by_phase)
                assert by_phase.get("hier_wave", 0) > 0

    def test_ingest_spans_are_pretimed_and_tagged(self, hub):
        from garfield_tpu.aggregators import hierarchy

        n, d = 32, 8
        rng = np.random.default_rng(5)
        g = rng.normal(size=(n, d)).astype(np.float32)
        red = hierarchy.StreamingAggregator(
            n, 1, bucket_gar="median", bucket_size=8, wave_buckets=2)
        red.push_many(g)
        red.finalize()
        ing = [r for r in _spans(hub) if r["phase"] == "hier_ingest"]
        waves = [r for r in _spans(hub) if r["phase"] == "hier_wave"]
        assert len(ing) == len(waves) > 0
        for rec in ing:
            assert rec["dur_s"] >= 0.0
            assert rec["who"] == "test"
            assert "buckets" in rec and "size" in rec and "level" in rec
            validate_record(rec)
