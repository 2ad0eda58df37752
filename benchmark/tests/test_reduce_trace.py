"""The trace reducer on a small trace recorded on the chip (TPU v5 lite, the
toy median cell of `toy.py`, a 30 ms slice; PR 24): planes and lines as the
profiler names them, the step program, the busy union, the idle gaps and
their attribution to the harness's spans, and the Mosaic kernel's events."""

import gzip
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import reduce_trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data" / "toy.xplane.pb.gz"


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "toy.xplane.pb"
    out.write_bytes(gzip.decompress(DATA.read_bytes()))
    return out


@pytest.fixture(scope="module")
def raw(path):
    return reduce_trace.load(path)


def test_the_device_plane_and_the_harness_spans_are_found(raw):
    assert list(raw["devices"]) == ["/device:TPU:0"]
    device = raw["devices"]["/device:TPU:0"]
    assert device["ops"] and device["modules"]
    assert {name for name, _, _ in raw["host"]} == {
        "bench.dispatch", "bench.wait"}
    assert reduce_trace.step_module(device["modules"]).startswith(
        "jit_step_fn")


def test_busy_is_the_union_of_operations_inside_the_window(raw, path):
    device = raw["devices"]["/device:TPU:0"]
    reduced = reduce_trace.reduce(path)
    one = reduced["fullest"]
    steps = [m for m in device["modules"] if m[0] == one["step_module"]]
    # The first run began before the trace, the last is cut where it stops:
    # the window holds the runs between them, whole.
    lo, hi = steps[1][1], steps[-2][2]
    assert math.isclose(one["window_s"], (hi - lo) / 1e9)
    assert one["steps"] == len(steps) - 2
    # The union never exceeds the window, nor the plain sum of durations.
    inside = [(s, e) for _, s, e in device["ops"] if e > lo and s < hi]
    plain = sum(min(e, hi) - max(s, lo) for s, e in inside) / 1e9
    assert 0 < one["busy_s"] <= one["window_s"]
    assert one["busy_s"] <= plain + 1e-12
    assert reduced["busy_s"] == one["busy_s"]  # one device: mean == fullest
    # Idle share is what the gaps between step programs leave, or more.
    gaps = sum(g for _, g in one["gaps"])
    assert one["window_s"] - one["busy_s"] >= gaps - 1e-9


def test_gaps_are_attributed_to_what_the_host_was_doing(path):
    reduced = reduce_trace.reduce(path)
    gaps = reduced["fullest"]["gaps"]
    assert len(gaps) == reduced["fullest"]["steps"] - 1
    assert {name for name, _ in gaps} <= {
        "bench.dispatch", "bench.wait", "untracked"}
    assert all(seconds >= 0 for _, seconds in gaps)
    assert reduced["gap_p95_s"] <= max(s for _, s in gaps)
    rows = reduced["breakdown"]
    assert len(rows["device_ops"]) <= 10 and len(rows["idle_gaps"]) <= 10
    assert rows["device_ops"][0][0].startswith("all ")


def test_mosaic_events_are_summed_per_step(raw, path):
    device = raw["devices"]["/device:TPU:0"]
    reduced = reduce_trace.reduce(path)["fullest"]
    per_step = reduce_trace.kernel_seconds_per_step(
        reduced, reduce_trace.is_mosaic)
    kernels = [o for o in device["ops"] if reduce_trace.is_mosaic(o[0])]
    assert kernels and per_step > 0
    assert per_step * reduced["steps"] <= sum(
        e - s for _, s, e in kernels) / 1e9 + 1e-12
    assert reduce_trace.short_name(kernels[0][0]).split(" ")[1] == "mosaic"
    assert reduce_trace.op_kind("dot_general.1") == "dot_general"  # XLA:CPU
    assert reduce_trace.kernel_seconds_per_step(
        reduced, lambda name: False) is None


def test_union_seconds_on_overlapping_and_clipped_spans():
    spans = [(0, 10), (5, 20), (30, 40), (35, 36), (90, 200)]
    assert reduce_trace.union_seconds(spans, 0, 100) == (20 + 10 + 10) / 1e9
    assert reduce_trace.union_seconds(spans, 8, 32) == (12 + 2) / 1e9
    assert reduce_trace.union_seconds([], 0, 100) == 0


def test_a_run_cut_by_the_end_of_the_trace_is_no_step():
    """Synthetic: five whole runs of 10 ms, then a stub of 1 ms where the
    trace stopped. Counted as a step the stub would make a step 8.5 ms."""
    ms = 1_000_000
    runs = [("jit_step_fn(1)", 0, 4 * ms)]  # began before the trace
    runs += [("jit_step_fn(1)", (4 + 10 * i) * ms, (14 + 10 * i) * ms - 5000)
             for i in range(5)]
    runs += [("jit_step_fn(1)", 54 * ms, 55 * ms)]
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", s, e)
           for _, s, e in runs]
    reduced = reduce_trace.reduce_device({"modules": runs, "ops": ops}, [])
    assert reduced["steps"] == 5
    assert math.isclose(reduced["window_s"] / reduced["steps"], 0.01,
                        rel_tol=1e-3)
    with pytest.raises(ValueError):
        reduce_trace.reduce_device(
            {"modules": runs[:3], "ops": ops[:3]}, [])
