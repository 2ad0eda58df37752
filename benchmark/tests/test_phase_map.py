"""`harness.phase_map` and the per-phase readers: the labelling on a
hand-written HLO text with hand-written operation times, the readers where
no map can be had, and the traced toy cell end to end on the CPU."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import phase_map  # noqa: E402
from layer_metrics import (  # noqa: E402
    attack_phase_ms, grads_phase_ms, phase_unattributed_share,
    rule_phase_ms, rule_phase_roofline, update_phase_ms)

# What the TPU's compiler prints, cut down by hand: a plain instruction, a
# fusion of one phase, a fusion of two, a fusion of none, a Pallas kernel
# whose payload follows its metadata, an instruction with no metadata, a
# while loop whose body's operations are events of their own and a
# conditional whose branches' are not.
HLO = '''HloModule jit_step_fn, is_scheduled=true

FileNames
1 "aggregathor.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_grads (p: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %mul.1 = bf16[8,64]{1,0} multiply(%p, %p), metadata={op_name="jit(step_fn)/shard_map/phase.grads/jvp(ResNet)/mul" stack_frame_id=1}
  ROOT %add.1 = bf16[8,64]{1,0} add(%mul.1, %p), metadata={op_name="jit(step_fn)/shard_map/phase.grads/transpose(jvp(ResNet))/add"}
}

%fused_two (p.1: bf16[8,64]) -> f32[8,64] {
  %p.1 = bf16[8,64]{1,0} parameter(0)
  %cast.1 = bf16[8,64]{1,0} convert(%p.1), metadata={op_name="jit(step_fn)/shard_map/phase.grads/convert_element_type"}
  ROOT %up.1 = f32[8,64]{1,0} convert(%cast.1), metadata={op_name="jit(step_fn)/shard_map/phase.rule/convert_element_type;jit(step_fn)/shard_map/phase.rule/pad"}
}

%fused_plain (p.2: f32[64]) -> f32[64] {
  %p.2 = f32[64]{0} parameter(0)
  ROOT %div.1 = f32[64]{0} divide(%p.2, %p.2), metadata={op_name="jit(step_fn)/shard_map/div"}
}

%body (s: (s32[], f32[64])) -> (s32[], f32[64]) {
  %s = (s32[], f32[64]{0}) parameter(0)
  %g.1 = f32[64]{0} get-tuple-element(%s), index=1
  %inner.1 = f32[64]{0} fusion(%g.1), kind=kLoop, calls=%fused_plain, metadata={op_name="jit(step_fn)/shard_map/phase.rule/while/body/div"}
  ROOT %t.1 = (s32[], f32[64]{0}) tuple(%g.1, %inner.1)
}

%cond (s.1: (s32[], f32[64])) -> pred[] {
  %s.1 = (s32[], f32[64]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

%branch_a (q: f32[64]) -> f32[64] {
  %q = f32[64]{0} parameter(0)
  ROOT %neg.1 = f32[64]{0} negate(%q), metadata={op_name="jit(step_fn)/shard_map/phase.update/cond/branch_0_fun/neg"}
}

%branch_b (q.1: f32[64]) -> f32[64] {
  ROOT %q.1 = f32[64]{0} parameter(0)
}

ENTRY %main.9 (a: bf16[8,64], b: f32[64]) -> f32[64] {
  %a = bf16[8,64]{1,0} parameter(0)
  %b = f32[64]{0} parameter(1)
  %fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_grads, metadata={op_name="jit(step_fn)/shard_map/phase.grads/add"}, backend_config={"flag_configs":[]}
  %fusion.2 = f32[8,64]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_two, metadata={op_name="jit(step_fn)/shard_map/phase.rule/convert_element_type"}
  %fusion.3 = f32[64]{0} fusion(%b), kind=kLoop, calls=%fused_plain
  %coordinate_median.4 = f32[1,64]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/shard_map/phase.rule/branch_0_fun/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzvUg calls=%fused_grads metadata={op_name=\\"phase.attack\\"}"}}
  %copy.5 = f32[64]{0} copy(%b)
  %slice.6 = f32[64]{0} slice(%b), slice={[0:64]}, metadata={op_name="jit(step_fn)/shard_map/phase.attack/slice"}
  %while.7 = (s32[], f32[64]{0}) while(%b), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/shard_map/phase.rule/while"}
  %conditional.8 = f32[64]{0} conditional(%a, %b, %b), branch_computations={%branch_a, %branch_b}
  ROOT %sub.9 = f32[64]{0} subtract(%slice.6, %copy.5), metadata={op_name="jit(step_fn)/shard_map/phase.update/sub"}
}
'''

# A trace's event names: the whole HLO line on the chip.
LINES = {
    "fusion.1": "%fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%a)",
    "fusion.2": "%fusion.2 = f32[8,64]{1,0} fusion(%fusion.1), kind=kLoop",
    "fusion.3": "%fusion.3 = f32[64]{0} fusion(%b), kind=kLoop",
    "coordinate_median.4": "%coordinate_median.4 = f32[1,64]{1,0} "
                           'custom-call(%fusion.2), custom_call_target='
                           '"tpu_custom_call"',
    "copy.5": "%copy.5 = f32[64]{0} copy(%b)",
    "slice.6": "%slice.6 = f32[64]{0} slice(%b), slice={[0:64]}",
    "while.7": "%while.7 = (s32[], f32[64]{0}) while(%b)",
    "inner.1": "%inner.1 = f32[64]{0} fusion(%g.1), kind=kLoop",
    "conditional.8": "%conditional.8 = f32[64]{0} conditional(%a, %b, %b)",
    "sub.9": "%sub.9 = f32[64]{0} subtract(%slice.6, %copy.5)",
    "unknown.1": "%unknown.1 = f32[] constant(0)",
}
SECONDS = {
    "fusion.1": 0.040, "fusion.2": 0.004, "fusion.3": 0.001,
    "coordinate_median.4": 0.006, "copy.5": 0.002, "slice.6": 0.0005,
    "while.7": 0.003, "inner.1": 0.0025, "conditional.8": 0.0007,
    "sub.9": 0.0003, "unknown.1": 0.0001,
}
STEPS = 2


@pytest.fixture
def made():
    return phase_map.label_text(HLO)


@pytest.fixture
def trace():
    op_seconds = {LINES[name]: s for name, s in SECONDS.items()}
    busy = sum(SECONDS.values()) - SECONDS["while.7"]  # no two overlap else
    return {"fullest": {"op_seconds": op_seconds, "steps": STEPS,
                        "busy_s": busy, "window_s": busy * 1.001}}


FACTS = {
    "config": {"num_workers": 16, "num_params": 1000, "gar_dtype": "bfloat16"},
    "traffic": {"rule": "median", "attack": "lie"},
    "device": {"platform": "tpu", "kind": "TPU v5 lite"},
}


@pytest.fixture
def mapped(made, monkeypatch):
    monkeypatch.setattr(
        phase_map, "_compiled_text", lambda facts, fresh=False: HLO)
    monkeypatch.setattr(phase_map, "_memo", {})


@pytest.mark.parametrize("name,label", [
    ("fusion.1", "grads"),              # a fusion of one phase
    ("fusion.2", "mixed:grads+rule"),   # of two, one a merged op_name's
    ("fusion.3", "none"),               # of none
    ("coordinate_median.4", "rule"),    # metadata before the payload
    ("copy.5", "none"),                 # no metadata
    ("slice.6", "attack"),              # a plain instruction
    ("while.7", "rule"),                # a container, labelled by its body
    ("inner.1", "rule"),                # the body's own fusion: its op_name
    ("conditional.8", "update"),
    ("sub.9", "update"),
    ("mul.1", "grads"),                 # jvp( and transpose( wrapping
])
def test_each_instruction_gets_its_phase(made, name, label):
    assert made["labels"][name] == label


def test_the_text_is_read_as_computations_and_named_phases(made):
    instructions, computations = phase_map.parse(HLO)
    assert list(computations)[-1] == "main.9"
    assert computations["body"] == ["s", "g.1", "inner.1", "t.1"]
    assert instructions["while.7"][0] == "while"
    assert sorted(instructions["while.7"][2]) == ["body", "cond"]
    assert instructions["conditional.8"][2] == ["branch_a", "branch_b"]
    # What a kernel's payload says is no part of the program's text.
    assert instructions["coordinate_median.4"] == (
        "custom-call", {"rule"}, [])
    assert made["phases"] == {"grads", "rule", "attack", "update"}
    assert set(made["bodies"]) == {"while.7", "conditional.8"}


def test_event_names_are_normalised_to_the_bare_instruction_name():
    assert phase_map.name_of(LINES["fusion.1"]) == "fusion.1"
    assert phase_map.name_of("fusion.1") == "fusion.1"  # XLA:CPU
    assert phase_map.name_of("%while.7") == "while.7"


def test_a_container_whose_body_runs_as_events_is_left_out(made, trace):
    by_label, left_out = phase_map.split(trace["fullest"]["op_seconds"], made)
    assert left_out == SECONDS["while.7"]
    # The conditional's branches are no events: it keeps its time.
    assert by_label["update"] == pytest.approx(
        SECONDS["conditional.8"] + SECONDS["sub.9"])
    assert by_label["rule"] == pytest.approx(
        SECONDS["coordinate_median.4"] + SECONDS["inner.1"])
    assert by_label["none"] == pytest.approx(
        SECONDS["fusion.3"] + SECONDS["copy.5"] + SECONDS["unknown.1"])
    # Phases, mixed and none add up to the device's busy time, never more.
    assert sum(by_label.values()) == pytest.approx(trace["fullest"]["busy_s"])
    assert sum(by_label.values()) <= trace["fullest"]["busy_s"] + 1e-12


def test_the_readers_read_the_split_per_step(mapped, trace):
    def ms(seconds):
        return pytest.approx(1e3 * seconds / STEPS)

    assert grads_phase_ms.read(trace, FACTS) == ms(0.040)
    assert attack_phase_ms.read(trace, FACTS) == ms(0.0005)
    assert rule_phase_ms.read(trace, FACTS) == ms(0.006 + 0.0025)
    assert update_phase_ms.read(trace, FACTS) == ms(0.0007 + 0.0003)
    busy = trace["fullest"]["busy_s"]
    assert phase_unattributed_share.read(trace, FACTS) == pytest.approx(
        100 * (0.004 + 0.001 + 0.002 + 0.0001) / busy)
    # The roofline's time holds the mixed operation too, so time left out
    # cannot push it over 100.
    holding = (0.006 + 0.0025 + 0.004) / STEPS
    assert phase_map.holding_seconds(trace, FACTS, "rule") == pytest.approx(
        holding)
    least = 17 * 1000 * 2 / 819e9
    assert rule_phase_roofline.read(trace, FACTS) == pytest.approx(
        100 * least / holding)


def test_the_whole_split_goes_to_standard_error_once(mapped, trace, capsys):
    """For the record of a traced run: each label's time by kind of
    operation and its largest operation, the kernel by its own name."""
    import json

    grads_phase_ms.read(trace, FACTS)
    rule_phase_ms.read(trace, FACTS)
    (line,) = [l for l in capsys.readouterr().err.splitlines()
               if l.startswith("phase map: ms per step ")]
    rows = json.loads(line[len("phase map: ms per step "):].split("; ")[0])
    assert list(rows)[0] == "grads" and "mixed:grads+rule" in rows
    assert rows["rule"]["largest"].startswith("%coordinate_median.4 mosaic")
    assert rows["rule"]["kinds"] == {"mosaic": 3.0, "fusion": 1.25}
    assert rows["none"]["kinds"]["copy"] == 1.0


def test_a_named_phase_no_operation_carries_reads_zero(mapped, trace):
    del trace["fullest"]["op_seconds"][LINES["slice.6"]]
    assert attack_phase_ms.read(trace, FACTS) == 0.0


def test_a_phase_the_text_does_not_name_reads_none(mapped, trace):
    assert phase_map.phase_ms(trace, FACTS, "exchange") is None


def test_a_stale_map_raises_the_alarm(mapped, trace):
    device = trace["fullest"]
    device["op_seconds"] = {
        key.replace(".", ".9"): s for key, s in device["op_seconds"].items()}
    assert phase_unattributed_share.read(trace, FACTS) > 99


SCOPELESS = HLO.replace("phase.", "stage.")


@pytest.mark.parametrize("text", [
    None,               # the step cannot be had
    SCOPELESS,          # a program without the scopes: the PR's parent
])
def test_without_a_map_every_reader_returns_none(
        text, trace, monkeypatch, capsys):
    asked = []

    def compiled_text(facts, fresh=False):
        asked.append(fresh)
        if text is None:
            raise RuntimeError("no executable")
        return text

    monkeypatch.setattr(phase_map, "_compiled_text", compiled_text)
    monkeypatch.setattr(phase_map, "_program_names_phases", lambda: False)
    monkeypatch.setattr(phase_map, "_memo", {})
    for reader in (grads_phase_ms, attack_phase_ms, rule_phase_ms,
                   update_phase_ms, phase_unattributed_share,
                   rule_phase_roofline):
        assert reader.read(trace, FACTS) is None
    # Said once, on standard error; asked for once, and never compiled
    # again for a program that has no phases to name.
    assert capsys.readouterr().err.count("phase map: none") == 1
    assert asked == [False]


def test_a_step_from_a_cache_another_source_shares_is_compiled_again(
        trace, monkeypatch, capsys):
    """JAX's compile-cache key leaves metadata out, so the loaded step may
    be the scope-less parent's: the program names phases, its text none."""
    monkeypatch.setattr(
        phase_map, "_compiled_text",
        lambda facts, fresh=False: HLO if fresh else SCOPELESS)
    monkeypatch.setattr(phase_map, "_program_names_phases", lambda: True)
    monkeypatch.setattr(phase_map, "_memo", {})
    assert grads_phase_ms.read(trace, FACTS) > 0
    assert "compiling again" in capsys.readouterr().err


def test_the_traced_toy_cell_prints_the_phase_metrics(tmp_path):
    """`run.py` at a toy size on the CPU, traced: XLA:CPU's events are bare
    instruction names, which the map meets without their ``%``."""
    import test_run_cpu
    import toy

    cell = toy.make_cell(tmp_path, "krum", "lie", 1)
    result, err = test_run_cpu._drive(tmp_path, cell, trace=1, seconds=30)
    assert "phase map: none" not in err
    metrics = result["metrics"]
    for name in ("grads_phase_ms", "rule_phase_ms", "update_phase_ms",
                 "phase_unattributed_share"):
        assert metrics[name]["value"] >= 0
    assert metrics["grads_phase_ms"]["value"] > metrics[
        "rule_phase_ms"]["value"] > 0
    assert metrics["phase_unattributed_share"]["value"] < 50
    # A cell BENCHMARK.json does not list for them reads neither.
    assert "attack_phase_ms" not in metrics
    assert "rule_phase_roofline" not in metrics
    steps_ms = 1e3 * result["device"]["busy_s"] / result["info"]["steps"]
    assert steps_ms > 0
