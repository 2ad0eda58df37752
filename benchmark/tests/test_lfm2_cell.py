"""The token family's cell: ``lfm2n4.median-lie`` at a toy size through
``run.run_cell`` on the CPU (a temporary copy of the benchmark that gains a
configuration, limits and entries; the family's reference, loss and kind of
input are the committed files), the committed configuration against the
published one, and the committed limits against the committed chip readings.
"""

import json
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import toy  # noqa: E402
from test_run_cpu import _drive  # noqa: E402

CELL = "lfm2n4.median-lie"
CONFIG = toy.REPO / "benchmark/configs/lfm2-8b-a1b-ep4-n4.json"
READINGS = pathlib.Path(__file__).parent / "data" / f"chip_readings.{CELL}.jsonl"
# config.json of LiquidAI/LFM2-8B-A1B: every number and flag of it.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
PUBLISHED_LAYER_TYPES = (["conv", "conv", "full_attention"]
                         + ["conv", "conv", "conv", "full_attention"] * 4
                         + ["conv", "conv", "full_attention", "conv", "conv"])
REDUCED = ["layer_types", "num_dense_layers", "num_experts",
           "num_hidden_layers", "vocab_size"]

TOY_MODEL = {
    "family": "lfm2_moe", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "conv_L_cache": 3,
    "norm_eps": 1e-05, "rope_theta": 1000000,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "num_experts_published": 8, "experts_held": [0, 1],
    "num_experts_per_tok": 2, "routed_scaling_factor": 1,
    "vocab_size": 16384, "seq_len": 32,
}
TOY_CONFIG = {
    "name": "toy-lfm2", "source": "test only: the program's lfm2_moe_tiny",
    "topology": "aggregathor",
    "program": {"model": "lfm2_moe_tiny", "dataset": "synthtokens"},
    "model": TOY_MODEL, "init": {"residual_out_scale": 1.0},
    "num_params": 1150832,
    "num_workers": 4, "f": 1, "batch_per_worker": 2,
    "model_dtype": "float32", "gar_dtype": "float32", "loss": "next-token",
    "optimizer": {"name": "sgd", "lr": 0.05, "momentum": 0.9,
                  "weight_decay": 0.0005},
    "reduced": {}, "assumed": {},
}
# Program and reference agree to 1e-5 here (float32 both); one of a worker's
# two sequences left out reads a tenth and more.
TOY_LIMITS = {"loss1": 1e-4, "loss2": 1e-3, "loss3": 1e-3, "grad1": 5e-3,
              "grad1_whole": 5e-3, "dparam3": 5e-3, "dparam3_whole": 5e-3}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the cell ``toylfm2.median-lie`` added
    by a configuration, limits and two entries."""
    root, bench = toy._copy(tmp_path_factory.mktemp("toy_lfm2"))
    (root / "benchmark/configs/toy-lfm2.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "benchmark/limits/toylfm2.median-lie.json").write_text(
        json.dumps(TOY_LIMITS))
    bench["configs"].append({
        "name": "toy-lfm2", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy-lfm2.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "toylfm2.median-lie", "config": "toy-lfm2",
        "traffic": "median-lie", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def test_the_toy_sized_cell_comes_out_correct(checkout):
    result, err = _drive(checkout, "toylfm2.median-lie")
    assert result["correct"] is True and result["failed"] == 0
    assert all(row["value"] <= row["limit"]
               for row in result["check"].values())
    assert result["attempted"] >= 3


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_toy_sized_cell_comes_out_not_correct(checkout, fault):
    result, _ = _drive(checkout, "toylfm2.median-lie", fault=fault)
    assert result["correct"] is False


def test_the_toy_configuration_counts_its_parameters():
    import references
    shapes = references.family("lfm2_moe").param_shapes(TOY_MODEL)
    assert sum(math.prod(s) for s in shapes.values()) == TOY_CONFIG[
        "num_params"]


def test_every_width_is_the_published_one_and_reduced_names_the_cut():
    config = json.loads(CONFIG.read_text())
    assert sorted(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    # The first dense layer (leading dense layers count once) and the first
    # whole period of expert layers: published layers 1 and 3-6.
    assert config["layer_types"] == (
        PUBLISHED_LAYER_TYPES[:1] + PUBLISHED_LAYER_TYPES[2:6])
    assert len(PUBLISHED_LAYER_TYPES) == PUBLISHED["num_hidden_layers"]
    assert config["num_dense_layers"] == 1 and config["num_experts"] == 8
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    for key, text in config["reduced"].items():
        assert str(PUBLISHED.get(key, 24)).replace("65536", "65,536") in text
    # The group the family reads says the same as the published keys.
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "norm_eps", "rope_theta", "num_experts_per_tok",
                "routed_scaling_factor", "layer_types", "num_dense_layers",
                "vocab_size"):
        assert model[key] == config[key], key
    assert model["num_experts_published"] == PUBLISHED["num_experts"]
    assert model["experts_held"] == list(range(config["num_experts"]))
    assert model["head_dim"] * model["num_attention_heads"] == model[
        "hidden_size"]
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert "4 chips" in config["deployment"]


def test_num_params_is_the_sum_of_the_shapes():
    import references
    config = json.loads(CONFIG.read_text())
    family = references.family(config["model"]["family"])
    shapes = family.param_shapes(config["model"])
    assert config["num_params"] == sum(
        math.prod(s) for s in shapes.values()) == 507820288
    sizes = sorted(math.prod(s) for s in shapes.values())
    # The largest leaves: the embedding, then twelve expert stacks.
    assert sizes[-1] == 16384 * 2048 and sizes[-13:-1] == [8 * 2048 * 1792] * 12
    # 203.7M multiply-adds a token, of which the held experts are 4 x 11.0M.
    per_token = family.forward_macs(config["model"]) / config["model"][
        "seq_len"]
    assert round(per_token / 1e6, 1) == 203.7


def test_the_committed_limits_part_the_committed_readings():
    """Every row is parted. The limits were set from the rows read with the
    committed token law (12 program seeds, 2 of the control and of the half
    batch: what the review round's one chip call had room for); the first
    round's rows, read with generators the review refused, are marked
    ``sets_limits: false`` and have to come out the same way."""
    from harness import correct
    limits = json.loads(
        (toy.REPO / f"benchmark/limits/{CELL}.json").read_text())
    rows = [json.loads(line) for line in READINGS.read_text().splitlines()]
    by_kind, setting = {}, {}
    for row in rows:
        ok, _ = correct.judge(row["values"], limits)
        by_kind.setdefault(row["kind"], []).append(ok)
        if row.get("sets_limits", True):
            setting[row["kind"]] = setting.get(row["kind"], 0) + 1
            assert row["inputs"] in ("Zipf-Mandelbrot draws (committed)", "any")
    assert setting["program"] >= 12 and len(by_kind["program"]) >= 34
    assert all(by_kind["program"])
    for kind in ("control_fp8", "fault_half_batch"):
        assert setting[kind] >= 2 and len(by_kind[kind]) >= 8, kind
        assert not any(by_kind[kind]), kind
    assert by_kind["fault_unchanged_state"] == [False]
