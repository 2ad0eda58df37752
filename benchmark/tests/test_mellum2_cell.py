"""The Mellum family's cell: ``mellum2n4.median-lie`` at a toy size through
``run.run_cell`` on the CPU (a temporary copy of the benchmark that gains a
configuration, limits and entries; the family's reference, loss and kind of
input are the committed files), the committed configuration against the
published one, the committed limits against the committed chip readings, and `attention_cost`'s arithmetic. The runs through
``run.run_cell`` are marked slow (minutes); the rest is collected by tier-1
(tests/test_benchmark_harness.py).
"""

import json
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import toy  # noqa: E402

CELL = "mellum2n4.median-lie"
CONFIG = toy.REPO / "benchmark/configs/mellum2-12b-a2.5b-ep4-n4.json"
DATA = pathlib.Path(__file__).parent / "data"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# config.json of JetBrains/Mellum2-12B-A2.5B-Instruct as the catalog row has
# it (/opt/skills/guides/model-configs/architectures.jsonl): every key.
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
REDUCED = ["layer_types", "mlp_layer_types", "num_experts",
           "num_hidden_layers", "vocab_size"]

TOY_MODEL = {
    "family": "mellum", "hidden_size": 64, "moe_intermediate_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "norm_eps": 1e-06, "sliding_window": 4,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 4.0,
            "original_max_position_embeddings": 8, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0}},
    "layer_types": ["sliding_attention", "full_attention"],
    "num_dense_layers": 0, "num_experts_published": 8,
    "experts_held": [0, 1], "num_experts_per_tok": 2,
    "vocab_size": 16384, "seq_len": 32,
}
TOY_CONFIG = {
    "name": "toy-mellum2", "source": "test only: the program's mellum2_tiny",
    "topology": "aggregathor",
    "program": {"model": "mellum2_tiny", "dataset": "synthtokens"},
    "model": TOY_MODEL, "init": {"residual_out_scale": 1.0},
    "num_params": 2160000,
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
    """A copy of the benchmark with the cell ``toymellum2.median-lie`` added
    by a configuration, limits and two entries."""
    root, bench = toy._copy(tmp_path_factory.mktemp("toy_mellum2"))
    (root / "benchmark/configs/toy-mellum2.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "benchmark/limits/toymellum2.median-lie.json").write_text(
        json.dumps(TOY_LIMITS))
    bench["configs"].append({
        "name": "toy-mellum2", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy-mellum2.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "toymellum2.median-lie", "config": "toy-mellum2",
        "traffic": "median-lie", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch"])
def test_the_toy_sized_cell_is_correct_and_a_broken_one_is_not(
        checkout, fault):
    from test_run_cpu import _drive

    result, _ = _drive(checkout, "toymellum2.median-lie", fault=fault)
    assert result["correct"] is (fault == "none")
    if fault == "none":
        assert result["failed"] == 0 and result["attempted"] >= 3
        assert all(row["value"] <= row["limit"]
                   for row in result["check"].values())


def test_the_toy_configuration_counts_its_parameters():
    import references
    shapes = references.family("mellum").param_shapes(TOY_MODEL)
    assert sum(math.prod(s) for s in shapes.values()) == TOY_CONFIG[
        "num_params"]


def test_every_published_key_is_kept_and_reduced_names_the_cut():
    config = json.loads(CONFIG.read_text())
    assert sorted(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    # The first whole period: published layers 0-3.
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 4
    assert config["layer_types"] == PUBLISHED["layer_types"][:4] == PERIOD
    assert config["mlp_layer_types"] == PUBLISHED["mlp_layer_types"][:4]
    assert config["num_experts"] * 4 == PUBLISHED["num_experts"]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # The group the family reads says the same as the published keys.
    model = config["model"]
    for key in ("hidden_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "layer_types", "sliding_window",
                "rope_parameters", "vocab_size"):
        assert model[key] == config[key], key
    assert model["norm_eps"] == config["rms_norm_eps"]
    assert model["num_dense_layers"] == 0
    assert model["num_experts_published"] == PUBLISHED["num_experts"]
    assert model["experts_held"] == list(range(config["num_experts"]))
    assert model["seq_len"] == 4096 > model["sliding_window"]
    assert (config["num_workers"], config["f"], config[
        "batch_per_worker"]) == (4, 1, 1)
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert "4 chips" in config["deployment"]
    for key in ("qk_norm", "router", "rotary", "window", "mtp_head"):
        assert key in config["assumed"], key


def test_num_params_is_the_sum_of_the_shapes():
    import references
    config = json.loads(CONFIG.read_text())
    family = references.family(config["model"]["family"])
    shapes = family.param_shapes(config["model"])
    assert config["num_params"] == sum(
        math.prod(s) for s in shapes.values()) == 595154176
    per_layer = sum(math.prod(s) for p, s in shapes.items()
                    if p.startswith("layer_0/"))
    assert per_layer == 120476416
    sizes = sorted(math.prod(s) for s in shapes.values())
    # The largest leaves: embedding and head, then twelve expert stacks.
    assert sizes[-2:] == [24576 * 2304] * 2
    assert sizes[-14:-2] == [16 * 2304 * 896] * 12
    # 230.5M multiply-adds a token, the window counted where it hides keys:
    # the head 56.6M, a layer's projections 21.2M, its held experts 12.4M
    # (2 pairs a token), its scores 7.3M (sliding) or 16.8M (full).
    per_token = family.forward_macs(config["model"]) / 4096
    assert round(per_token / 1e6, 1) == 230.5


def test_attention_cost_counts_the_visible_pairs_and_the_blocks():
    from harness import attention_cost
    config = json.loads(CONFIG.read_text())
    assert attention_cost.pairs_per_head(config) == {
        "sliding_attention": 3670528, "full_attention": 8388608}
    assert sum(min(i + 1, 1024) for i in range(4096)) == 3670528
    # Blocks of 512 positions a side that hold a visible pair, of 64: the
    # diagonal and what lies under it, less what the window hides whole.
    def blocks(window):
        return sum(1 for i in range(8) for j in range(i + 1)
                   if (i - j - 1) * 512 + 1 < window)
    assert (blocks(1024), blocks(4096)) == (21, 36)
    # 2 FLOP x 3 passes x 2 contractions x 128 x 32 heads x the pairs of
    # three sliding layers and one full, x 4 sequences a step.
    flops = attention_cost.core_flops_per_step(config)
    assert flops == 2 * 3 * 2 * 128 * 32 * 4 * (3 * 3670528 + 8388608)
    assert round(flops / 1e12, 2) == 3.81


def _rows(name):
    return [json.loads(line)
            for line in (DATA / name).read_text().splitlines()]


@pytest.mark.parametrize("cell,readings,programs,controls,faults", [
    (CELL, f"chip_readings.{CELL}.jsonl", 6, 2, 0),
    ("r18n8.median-lie", "chip_readings.r18n8.median-lie.jsonl", 6, 1, 1),
])
def test_the_committed_limits_part_the_committed_readings(
        cell, readings, programs, controls, faults):
    """Every sound run of the program comes out correct; every run of the
    fp8 control fails at least four of the six numbers; the half batch
    (r18n8 alone: one sequence a worker has no half) fails too. Each cell's
    rows are a file of their own: ``chip_readings.jsonl`` is a file the
    benchmark had, which a PR that adds cells does not edit."""
    from harness import correct
    limits = json.loads(
        (toy.REPO / f"benchmark/limits/{cell}.json").read_text())
    assert len(limits) == 6
    by_kind = {}
    for row in _rows(readings):
        if row["workload"] == cell:
            ok, check = correct.judge(row["values"], limits)
            failed = [n for n, r in check.items()
                      if not r["value"] <= r["limit"]]
            by_kind.setdefault(row["kind"], []).append((ok, failed))
    assert len(by_kind["program"]) >= programs
    assert all(ok for ok, _ in by_kind["program"])
    assert len(by_kind["control_fp8"]) >= controls
    assert all(len(failed) >= 4 for _, failed in by_kind["control_fp8"])
    assert len(by_kind.get("fault_half_batch", [])) >= faults
    assert not any(ok for ok, _ in by_kind.get("fault_half_batch", []))


def test_the_second_cell_is_entries_and_a_limits_file():
    """``r18n8.median-lie`` adds no configuration, traffic mix or reader:
    its configuration and traffic are files two older cells use, and every
    per-layer metric it reports is one that lists no cells."""
    from harness import spec
    bench = spec.load()
    cell = spec.Cell(bench, "r18n8.median-lie")
    assert cell.entry == {**cell.entry, "config": "resnet18-cifar10-n8",
                          "traffic": "median-lie", "chips": 1}
    others = [w for w in bench["workloads"] if w["name"] != cell.name]
    assert cell.entry["config"] in {w["config"] for w in others}
    assert cell.entry["traffic"] in {w["traffic"] for w in others}
    assert cell.config["num_workers"] == 8 and cell.config["f"] == 2
    assert cell.traffic == {"rule": "median", "attack": "lie"}
    assert all("workloads" not in m for m in cell.metrics("per_layer"))
    assert sorted(cell.limits) == sorted(
        spec.Cell(bench, "r50n16.median-lie").limits)
