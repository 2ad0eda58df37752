"""The yardstick's arithmetic, from shapes and synthetic timestamps alone.
Run by hand on the CPU: ``python -m pytest benchmark/tests -q``."""

import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import correct, flops, kernel_cost, peaks, window  # noqa: E402
from references import resnet  # noqa: E402

R18 = {"family": "resnet", "block": "basic", "stage_sizes": [2, 2, 2, 2],
       "stem_width": 64, "num_classes": 10, "image": [32, 32, 3]}
R50 = {"family": "resnet", "block": "bottleneck", "stage_sizes": [3, 4, 6, 3],
       "stem_width": 64, "num_classes": 100, "image": [32, 32, 3]}


def test_resnet18_forward_macs_match_a_hand_count():
    # Stem 32*32*27*64; stage s has 4 3x3 convs at width 64*2^s on
    # (32/2^s)^2 outputs, the first of stages 1..3 reading half the
    # channels, plus a 1x1 projection there; then the 512x10 head.
    hand = 32 * 32 * 27 * 64
    for s in range(4):
        w, hw = 64 * 2 ** s, (32 // 2 ** s) ** 2
        hand += 4 * hw * 9 * w * w
        if s:
            hand -= hw * 9 * (w // 2) * w  # first conv reads w/2 channels
            hand += hw * (w // 2) * w      # the projection shortcut
    hand += 512 * 10
    assert resnet.forward_macs(R18) == hand
    assert math.isclose(hand, 0.5554e9, rel_tol=1e-3)


def test_parameter_counts_are_the_published_ones():
    count = lambda m: sum(math.prod(s) for s in resnet.param_shapes(m).values())
    assert count(R18) == 11_173_962
    assert count(R50) == 23_705_252


def test_train_flops_are_six_per_multiply_add():
    config = {"model": R18, "num_workers": 8, "batch_per_worker": 256}
    assert flops.train_flops_per_image(config) == 6 * resnet.forward_macs(R18)
    assert flops.images_per_step(config) == 2048


def test_coordinate_rule_bytes_for_16_rows_of_resnet50_in_bf16():
    d = 23_705_252
    assert kernel_cost.coordinate_rule_bytes(16, d, "bfloat16") == 17 * d * 2
    least = kernel_cost.coordinate_rule_least_seconds(
        16, d, "bfloat16", peaks.peak("TPU v5 lite", "hbm_bytes_per_s"))
    assert math.isclose(least, 17 * d * 2 / 819e9)
    assert 0.9e-3 < least < 1.1e-3


def test_an_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "bf16_flops")


@pytest.mark.parametrize("span_steps", [1, 4])
def test_a_planted_stall_moves_the_p95_and_not_the_median(span_steps):
    steady = [0.1 * (i + 1) for i in range(200)]
    base = window.summarize(0.0, steady, 2048, span_steps)
    assert math.isclose(base["step_p95_ms"], 100.0, rel_tol=1e-6)
    assert math.isclose(base["images_per_s"], 20480.0, rel_tol=1e-6)
    # Fifteen of 200 steps each stall for 50 ms more: later completions shift.
    stalled, shift = [], 0.0
    for i, t in enumerate(steady):
        if i % 13 == 5:
            shift += 0.05
        stalled.append(t + shift)
    hit = window.summarize(0.0, stalled, 2048, span_steps)
    assert hit["step_p95_ms"] >= 100.0 + 50.0 / span_steps - 1e-6
    assert math.isclose(hit["step_p50_ms"], 100.0, rel_tol=1e-6)
    assert hit["images_per_s"] < base["images_per_s"]
    # Every step counts: single-step spans sum to the window, and sliding
    # spans of k steps are k - 1 fewer, each covering k steps.
    assert math.isclose(sum(window.spans(0.0, stalled)), stalled[-1])
    assert len(window.spans(0.0, stalled, span_steps)) == 201 - span_steps


def test_the_window_blocks_two_behind_and_counts_every_step():
    clock = iter(x * 0.01 for x in range(10_000))
    waited = []
    state, t_open, done, losses = window.run(
        lambda s, x: (s + 1, s), 0, [(0,), (1,)], 0.5, in_flight=2,
        wait=waited.append, clock=lambda: next(clock))
    assert state == len(done) == len(losses)
    assert waited == list(range(state))  # every step waited for, in order
    assert done == sorted(done) and done[0] > t_open


def test_worst_leaf_is_a_gap_of_norms_over_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6}
    gap, leaf = correct.worst_leaf(correct.leaf_gaps(prog, ref))
    assert leaf == "a" and math.isclose(gap, 0.1, rel_tol=1e-9)
    # The all-but-zero leaf is held against the median leaf, not itself.
    gap, leaf = correct.worst_leaf(
        correct.leaf_gaps({"a": 1.0, "b": 2.0, "c": 0.5}, ref))
    assert leaf == "c" and math.isclose(gap, 0.5, rel_tol=1e-5)
    nan = correct.leaf_gaps({"a": math.nan, "b": 2.0, "c": 0.5}, ref)
    assert correct.worst_leaf(nan)[1] == "a"
    whole = correct.whole_gap({"a": 3.0, "b": 4.0}, {"a": 6.0, "b": 8.0})
    assert math.isclose(whole, 0.5)


def test_judge_fails_on_a_number_over_its_limit_missing_or_not_finite():
    limits = {"loss1": 0.01, "grad1": 0.1}
    assert correct.judge({"loss1": 0.001, "grad1": 0.05}, limits)[0]
    assert not correct.judge({"loss1": 0.02, "grad1": 0.05}, limits)[0]
    assert not correct.judge({"loss1": 0.001}, limits)[0]
    assert not correct.judge({"loss1": math.nan, "grad1": 0.05}, limits)[0]
    assert not correct.judge(
        {"loss1": 0.001, "grad1": 0.05, "nonfinite": 2}, limits)[0]


def test_a_norm_that_is_no_number_fails_whatever_the_limits_say():
    ref = {"loss": [1.0], "grad1": {"a": 1.0, "b": 2.0, "c": 3.0, "d": 4.0},
           "dparam": {"a": 1.0, "b": 2.0, "c": 3.0, "d": 4.0}}
    prog = {"loss": [1.0], "grad1": dict(ref["grad1"], d=math.nan),
            "dparam": dict(ref["dparam"])}
    values, _ = correct.readings(prog, ref)
    # One NaN leaf of four: a sorted median would step over it.
    assert values["nonfinite"] == 1
    assert math.isnan(values["grad1_median"])
    assert math.isnan(values["grad1_whole"]) and math.isnan(values["grad1"])
    ok, check = correct.judge(values, {"dparam3": 0.1})
    assert not ok and check["nonfinite"] == {"value": 1, "limit": 0}
    sound, _ = correct.readings(ref, ref)
    assert correct.judge(sound, {"dparam3": 0.1})[0]


def test_the_span_of_step_p95_is_one_constant_and_no_traffic_file_sets_it():
    import json
    assert window.SPAN_STEPS == 4 and window.IN_FLIGHT == 2
    bench = pathlib.Path(__file__).resolve().parents[1]
    for path in (bench / "traffic").glob("*.json"):
        assert sorted(json.loads(path.read_text())) == ["attack", "rule"]
    # A window shorter than the span is read as one span.
    short = window.summarize(0.0, [0.1, 0.2], 8)
    assert math.isclose(short["step_p95_ms"], 100.0)
    assert math.isclose(short["interval_max_ms"], 100.0)


def test_dead_leaves_are_left_out_of_the_change_by_the_gradient_rule():
    ref = {"loss": [1.0], "grad1": {"a": 1.0, "b": 1.0, "dead": 1e-5},
           "dparam": {"a": 1.0, "b": 1.0, "dead": 1e-3}}
    prog = {"loss": [1.0], "grad1": dict(ref["grad1"]),
            "dparam": {"a": 1.0, "b": 1.0, "dead": 0.9}}
    values, _ = correct.readings(prog, ref)
    assert values["dparam3"] == 0.0 and values["loss1"] == 0.0


def _chip_readings():
    import json
    here = pathlib.Path(__file__).resolve().parent
    rows = [json.loads(line) for line in
            (here / "data" / "chip_readings.jsonl").read_text().splitlines()]
    limits = {
        path.stem: json.loads(path.read_text())
        for path in (here.parent / "limits").glob("*.json")}
    return [(r["workload"], r["kind"], r["seed"], r["values"],
             limits[r["workload"]]) for r in rows]


@pytest.mark.parametrize(
    "cell,kind,seed,values,limits", _chip_readings(),
    ids=lambda v: str(v) if isinstance(v, (str, int)) else "")
def test_the_committed_limits_part_the_chips_readings(
        cell, kind, seed, values, limits):
    """Readings taken on the chip at each cell's own size (PR 24;
    `readings.py`), through `judge` with the limits as committed: every
    sound run of the program comes out correct, every run of the fp8
    control and of the half-batch fault does not. (The first dozen seeds
    of a cell were read before the median-leaf and whole-vector numbers
    existed: a row is held to the numbers it has.)"""
    held = {n: limit for n, limit in limits.items() if n in values}
    assert len(held) >= 2
    ok, check = correct.judge(values, held)
    assert ok == (kind == "program"), check
