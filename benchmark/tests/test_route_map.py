"""`harness.route_map` and its four readers: the routing steps labelled on a
hand-written HLO text beside the ``model.*`` labels they leave as they were,
the readers on hand-written operation times, without the vocabulary and on a
loaded text that lost it, the counters' shares, and the map of a toy token
cell's own step on the CPU."""

import json
import pathlib
import re
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import model_map, phase_map, route_map, spec  # noqa: E402
from layer_metrics import (  # noqa: E402
    moe_permute_ms, moe_route_fill, moe_sort_ms, moe_tile_fill)

P = "jit(step_fn)/phase.grads/jvp(Lfm2Moe)/layer_1/moe"
T = "jit(step_fn)/phase.grads/transpose(jvp(Lfm2Moe))/layer_1/moe"
# An expert layer's routing as the TPU's compiler prints it, cut down by
# hand: the router, the two sorts, a fusion of the slot lookup with the
# sizes, the forward and backward row gathers, a gather fused with the
# experts' first cast, the gather back fused with the weighted sum, an
# experts' instruction, and the rule.
HLO = f'''HloModule jit_step_fn, is_scheduled=true

%fused_sizes (p: s32[64]) -> s32[2] {{
  %p = s32[64]{{0}} parameter(0)
  %slot.1 = s32[64]{{0}} gather(%p, %p), metadata={{op_name="{P}/model.moe_dispatch/route.slots/gather"}}
  ROOT %size.1 = s32[2]{{0}} reduce(%slot.1), metadata={{op_name="{P}/model.moe_dispatch/route.sizes/reduce_sum"}}
}}

%fused_rows (q: f32[64,64]) -> bf16[64,64] {{
  %q = f32[64,64]{{1,0}} parameter(0)
  %row.1 = f32[64,64]{{1,0}} gather(%q, %q), metadata={{op_name="{P}/model.moe_dispatch/route.gather_rows/gather"}}
  ROOT %cast.1 = bf16[64,64]{{1,0}} convert(%row.1), metadata={{op_name="{P}/model.moe_experts/convert_element_type"}}
}}

%fused_back (r: f32[64,64]) -> f32[32,64] {{
  %r = f32[64,64]{{1,0}} parameter(0)
  %back.1 = f32[64,64]{{1,0}} gather(%r, %r), metadata={{op_name="{P}/model.moe_combine/route.return_rows/gather"}}
  ROOT %sum.1 = f32[32,64]{{1,0}} reduce(%back.1), metadata={{op_name="{P}/model.moe_combine/route.weigh/reduce_sum"}}
}}

ENTRY %main.9 (a: f32[64,64], b: s32[64]) -> f32[64] {{
  %a = f32[64,64]{{1,0}} parameter(0)
  %b = s32[64]{{0}} parameter(1)
  %dot.1 = f32[32,8]{{1,0}} dot(%a, %a), metadata={{op_name="{P}/model.moe_router/dot_general"}}
  %sort.2 = s32[64]{{0}} sort(%b), metadata={{op_name="{P}/model.moe_dispatch/route.order/jit(argsort)/sort"}}
  %sort.3 = s32[64]{{0}} sort(%sort.2), metadata={{op_name="{P}/model.moe_dispatch/route.inverse/jit(argsort)/sort"}}
  %fusion.4 = s32[2]{{0}} fusion(%b), kind=kLoop, calls=%fused_sizes
  %gather.5 = f32[64,64]{{1,0}} gather(%a, %b), metadata={{op_name="{P}/model.moe_dispatch/route.gather_rows/gather"}}
  %gather.6 = f32[64,64]{{1,0}} gather(%a, %b), metadata={{op_name="{T}/model.moe_dispatch/route.gather_rows/gather"}}
  %fusion.7 = bf16[64,64]{{1,0}} fusion(%a), kind=kLoop, calls=%fused_rows
  %fusion.8 = f32[32,64]{{1,0}} fusion(%a), kind=kLoop, calls=%fused_back
  %mul.10 = f32[64,64]{{1,0}} multiply(%a, %a), metadata={{op_name="{P}/model.moe_experts/mul"}}
  ROOT %median.11 = f32[64]{{0}} reduce(%a), metadata={{op_name="jit(step_fn)/phase.rule/reduce"}}
}}
'''
SECONDS = {
    "dot.1": 0.001, "sort.2": 0.004, "sort.3": 0.003, "fusion.4": 0.0005,
    "gather.5": 0.006, "gather.6": 0.005, "fusion.7": 0.002,
    "fusion.8": 0.0025, "mul.10": 0.001,
    "median.11": 0.007,
}
STEPS = 2
COUNTERS = {"moe_pairs_held": 30.0, "moe_rows_routed": 120.0,
            "moe_rows_visited": 40.0, "moe_max_expert_load": 9.0}
FACTS = {"config": {"name": "hand-written"}, "traffic": {},
         "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
CELLS = ["lfm2n4.median-lie", "mellum2n4.median-lie",
         "lagunaxs2n5.median-lie"]
NAMES = ["moe_sort_ms", "moe_permute_ms", "moe_route_fill", "moe_tile_fill"]
READERS = (moe_sort_ms, moe_permute_ms, moe_route_fill, moe_tile_fill)


def _without_steps(text):
    return re.sub(r"/route\.\w+", "", text)


@pytest.fixture
def route_trace():
    op_seconds = {f"%{name} = f32[] op()": s for name, s in SECONDS.items()}
    busy = sum(SECONDS.values())
    return {"fullest": {"op_seconds": op_seconds, "steps": STEPS,
                        "busy_s": busy, "window_s": busy}}


@pytest.fixture
def routed(monkeypatch):
    """The route map of ``HLO`` with ``COUNTERS``, as `labels` memoizes it;
    and the model map of the same text."""
    monkeypatch.setattr(route_map, "_memo", {})
    monkeypatch.setattr(route_map, "_program_names_steps", lambda: True)
    monkeypatch.setattr(
        route_map, "_loaded", lambda facts: (HLO, dict(COUNTERS)))
    monkeypatch.setattr(
        phase_map, "_compiled_text", lambda facts, fresh=False: HLO)
    monkeypatch.setattr(model_map, "_memo", {})


@pytest.mark.parametrize("name,label", [
    ("dot.1", "model_moe_router"),              # no step: its model scope
    ("sort.2", "order"),
    ("sort.3", "inverse"),
    ("fusion.4", "mixed:sizes+slots"),          # two steps in one fusion
    ("gather.5", "gather_rows"),
    ("gather.6", "gather_rows"),                # the backward keeps its step
    ("fusion.7", "mixed:gather_rows+model_moe_experts"),
    ("fusion.8", "mixed:return_rows+weigh"),
    ("mul.10", "model_moe_experts"),
    ("median.11", "none"),                      # outside the model
])
def test_each_instruction_gets_its_routing_step(name, label):
    made = route_map.label_route_text(HLO)
    assert made["labels"][name] == label
    assert made["steps"] == {"order", "inverse", "slots", "sizes",
                             "gather_rows", "return_rows", "weigh"}


def test_the_model_labels_are_those_of_the_text_without_routing_steps():
    """Nested inside ``model.moe_dispatch`` and ``model.moe_combine``, the
    steps leave every ``model.*`` label as it was."""
    with_steps = model_map.label_model_text(HLO)
    assert with_steps == model_map.label_model_text(_without_steps(HLO))
    assert with_steps["labels"]["sort.2"] == "moe_dispatch"
    assert with_steps["labels"]["fusion.8"] == "moe_combine"


def test_the_readers_read_the_route_split_per_step(routed, route_trace, capsys):
    assert moe_sort_ms.read(route_trace, FACTS) == pytest.approx(
        1e3 * (0.004 + 0.003) / STEPS)
    # The gather fused with the experts' cast is mixed, so left out.
    assert moe_permute_ms.read(route_trace, FACTS) == pytest.approx(
        1e3 * (0.006 + 0.005) / STEPS)
    moe_sort_ms.read(route_trace, FACTS)
    err = capsys.readouterr().err
    assert err.count("route map: ms per step") == 1
    rows = json.loads(err.split("route map: ms per step ")[1].split("; ")[0])
    assert rows["mixed:gather_rows+model_moe_experts"] == 1.0
    assert rows["mixed:return_rows+weigh"] == 1.25
    assert "model_moe_experts" not in rows and "none" not in rows


def test_the_routing_rows_add_up_to_the_model_maps_routing(
        routed, route_trace, capsys):
    """Router, dispatch and combine read the same in both maps: the steps
    only divide them."""
    route_map.step_seconds(route_trace, FACTS)
    err = capsys.readouterr().err
    whole = float(err.split("wholly in routing ")[1].split()[0])
    scopes = model_map.scopes_ms(route_trace, FACTS, route_map.ROUTING_SCOPES)
    assert whole == pytest.approx(scopes, abs=1e-4)
    assert whole == pytest.approx(1e3 * (
        0.001 + 0.004 + 0.003 + 0.0005 + 0.006 + 0.005 + 0.0025) / STEPS)


def test_without_the_vocabulary_every_reader_returns_none_unbuilt(
        monkeypatch, route_trace, capsys):
    """The parent of the PR that added the steps, under its benchmark files:
    nothing is built and nothing raises."""
    monkeypatch.setattr(route_map, "_memo", {})
    monkeypatch.setattr(route_map, "_program_names_steps", lambda: False)

    def built(facts):
        raise AssertionError("a System was built")

    monkeypatch.setattr(route_map, "_loaded", built)
    for reader in READERS:
        assert reader.read(route_trace, FACTS) is None
    assert "route map: none" in capsys.readouterr().err


def test_a_loaded_step_whose_text_lost_the_steps_is_compiled_again(
        routed, monkeypatch, route_trace, capsys):
    """JAX's compile-cache key leaves metadata out, so the loaded step may
    be the parent's compile: the program names the steps, its text none."""
    monkeypatch.setattr(route_map, "_loaded", lambda facts: (
        _without_steps(HLO), dict(COUNTERS)))
    monkeypatch.setattr(
        phase_map, "_compiled_text",
        lambda facts, fresh=False: HLO if fresh else _without_steps(HLO))
    assert moe_sort_ms.read(route_trace, FACTS) > 0
    assert moe_route_fill.read(route_trace, FACTS) == 25.0
    assert "compiling again" in capsys.readouterr().err


def test_a_text_that_never_names_a_step_reads_none(
        routed, monkeypatch, route_trace):
    monkeypatch.setattr(route_map, "_loaded", lambda facts: (
        _without_steps(HLO), dict(COUNTERS)))
    monkeypatch.setattr(phase_map, "_compiled_text",
                        lambda facts, fresh=False: _without_steps(HLO))
    for reader in READERS:
        assert reader.read(route_trace, FACTS) is None


@pytest.mark.parametrize("counters,route_fill,tile_fill", [
    (COUNTERS, 25.0, 75.0),
    ({**COUNTERS, "moe_rows_visited": 0.0}, 25.0, None),  # the fallback
    ({"moe_pairs_held": 30.0, "moe_pairs_total": 120.0,   # the parent's
      "moe_rows_visited": 40.0}, None, 75.0),
    ({}, None, None),                                     # no expert layer
])
def test_the_counter_readers_read_the_shares(
        routed, monkeypatch, route_trace, counters, route_fill, tile_fill):
    monkeypatch.setattr(
        route_map, "_loaded", lambda facts: (HLO, dict(counters)))
    assert moe_route_fill.read(route_trace, FACTS) == route_fill
    assert moe_tile_fill.read(route_trace, FACTS) == tile_fill


def test_the_four_metrics_list_the_three_token_cells():
    bench = spec.load()
    entries = bench["per_layer"][-4:]
    assert [m["name"] for m in entries] == NAMES
    for m in entries:
        assert m["workloads"] == CELLS
        assert (m["layer"], m["moves"]) == ("model blocks", "images_per_s")
    assert [(m["unit"], m["better"], m["source"]) for m in entries] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("%", "higher", "program_counter"), ("%", "higher", "program_counter")]
    for cell in bench["workloads"]:
        listed = {m["name"] for m in spec.Cell(bench, cell["name"]).metrics(
            "per_layer")} & set(NAMES)
        assert listed == (set(NAMES) if cell["name"] in CELLS else set())


def test_a_toy_token_cells_own_step_is_mapped_and_counted(monkeypatch):
    """The map of a toy Mellum cell's step, built on the CPU as on the chip
    (8 workers, one to each of the suite's 8 devices): every routing step in
    its text, and its counters summed over 2 expert layers and 8 workers:
    2 x 8 sequences x 32 tokens x 2 choices rows routed."""
    from test_mellum2_cell import TOY_CONFIG
    monkeypatch.setattr(route_map, "_memo", {})
    facts = {"config": {**TOY_CONFIG, "num_workers": 8, "f": 2,
                        "batch_per_worker": 1},
             "traffic": {"rule": "median", "attack": "lie"}}
    made = route_map.labels(facts)
    from garfield_tpu.models import lfm2
    assert made["steps"] == set(lfm2.ROUTE_STEPS)
    counters = made["counters"]
    assert counters["moe_rows_routed"] == 2 * 8 * 32 * 2
    assert 0 < counters["moe_pairs_held"] < counters["moe_rows_routed"]
    assert counters["moe_rows_visited"] == 0  # the ragged_dot fallback
    assert moe_route_fill.read({}, facts) == pytest.approx(
        100 * counters["moe_pairs_held"] / counters["moe_rows_routed"])
    assert moe_tile_fill.read({}, facts) is None
