"""The Laguna family's cell: ``lagunaxs2n5.median-lie`` at a toy size through
``run.run_cell`` on the CPU (a temporary copy of the benchmark that gains a
configuration, limits and entries; the family's reference, loss and kind of
input are the committed files), the committed configuration against the
published one, the arithmetic of d, ``forward_macs`` and
`gated_attention_cost`, the committed limits against the committed chip
readings. The runs through ``run.run_cell`` are marked slow (minutes); the
rest is collected by tier-1 (tests/test_benchmark_harness.py).
"""

import json
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import toy  # noqa: E402

CELL = "lagunaxs2n5.median-lie"
CONFIG = toy.REPO / "benchmark/configs/laguna-xs2-33b-a3b-n5.json"
DATA = pathlib.Path(__file__).parent / "data"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# config.json of poolside/Laguna-XS.2 as the catalog row has it
# (/opt/skills/guides/model-configs/architectures.jsonl): every key.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
REDUCED = ["layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
           "num_experts", "num_hidden_layers", "vocab_size"]
ASSUMED = ["qk_norm", "gate", "rotary", "window", "router", "shared_expert",
           "mtp_head", "optimizer", "f", "batch", "data", "init"]

TOY_MODEL = {
    "family": "laguna", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "shared_expert_intermediate_size": 40,
    "num_attention_heads_per_layer": [4, 6, 4], "num_key_value_heads": 2,
    "head_dim": 16, "norm_eps": 1e-06, "sliding_window": 4,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 4.0,
            "original_max_position_embeddings": 8, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "num_dense_layers": 1, "num_experts_published": 8,
    "experts_held": [0, 1], "num_experts_per_tok": 2,
    "moe_routed_scaling_factor": 2.5, "vocab_size": 16384, "seq_len": 32,
}
TOY_CONFIG = {
    "name": "toy-laguna", "source": "test only: the program's laguna_tiny",
    "topology": "aggregathor",
    "program": {"model": "laguna_tiny", "dataset": "synthtokens"},
    "model": TOY_MODEL, "init": {"residual_out_scale": 1.0},
    "num_params": 2211232,
    "num_workers": 5, "f": 2, "batch_per_worker": 2,
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
def laguna_checkout(tmp_path_factory):
    """A copy of the benchmark with the cell ``toylaguna.median-lie`` added
    by a configuration, limits and two entries."""
    root, bench = toy._copy(tmp_path_factory.mktemp("toy_laguna"))
    (root / "benchmark/configs/toy-laguna.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "benchmark/limits/toylaguna.median-lie.json").write_text(
        json.dumps(TOY_LIMITS))
    bench["configs"].append({
        "name": "toy-laguna", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy-laguna.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "toylaguna.median-lie", "config": "toy-laguna",
        "traffic": "median-lie", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch"])
def test_the_toy_sized_laguna_cell_is_correct_and_a_broken_one_is_not(
        laguna_checkout, fault):
    """n = 5, f = 2 through the whole harness: the fake row is finite in
    program and reference alike, and ``nonfinite`` is 0."""
    from test_run_cpu import _drive

    result, _ = _drive(laguna_checkout, "toylaguna.median-lie", fault=fault)
    assert result["correct"] is (fault == "none")
    if fault == "none":
        assert result["failed"] == 0 and result["attempted"] >= 3
        assert result["check"]["nonfinite"]["value"] == 0
        assert all(row["value"] <= row["limit"]
                   for row in result["check"].values())


def test_the_toy_laguna_configuration_counts_its_parameters():
    import references
    shapes = references.family("laguna").param_shapes(TOY_MODEL)
    assert sum(math.prod(s) for s in shapes.values()) == TOY_CONFIG[
        "num_params"]


def test_every_published_laguna_key_is_kept_and_reduced_names_the_cut():
    config = json.loads(CONFIG.read_text())
    assert sorted(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    # The leading dense layer and the first whole period: published 0-4.
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert config[key] == PUBLISHED[key][:5], key
    assert config["layer_types"][1:] == PERIOD[1:] + PERIOD[:1]
    assert config["num_experts"] * 16 == PUBLISHED["num_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # The group the family reads says the same as the published keys, in
    # the other token configurations' spellings (`harness/moe_cost.py`).
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "head_dim",
                "num_attention_heads_per_layer", "num_key_value_heads",
                "num_experts_per_tok", "layer_types", "sliding_window",
                "rope_parameters", "moe_routed_scaling_factor",
                "vocab_size"):
        assert model[key] == config[key], key
    assert model["norm_eps"] == config["rms_norm_eps"]
    assert model["num_dense_layers"] == config["mlp_layer_types"].count(
        "dense") == 1
    assert model["num_experts_published"] == PUBLISHED["num_experts"]
    assert model["experts_held"] == list(range(config["num_experts"]))
    assert model["seq_len"] == 4096 > model["sliding_window"]
    assert (config["num_workers"], config["f"], config[
        "batch_per_worker"]) == (5, 2, 1)
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert entry["source"].startswith(
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert "16 chips" in config["deployment"]
    assert "8 chips of a host" in config["deployment"]
    assert sorted(config["assumed"]) == sorted(ASSUMED)
    from harness import moe_cost
    # 5 workers x 4 expert layers x 4,096 x 8 x 16 / 256 pairs a step.
    assert moe_cost.expert_pairs_per_step(config) == 5 * 4 * 2048


def test_laguna_num_params_and_forward_macs_are_the_sums_reckoned():
    import references
    config = json.loads(CONFIG.read_text())
    family = references.family(config["model"]["family"])
    shapes = family.param_shapes(config["model"])
    assert config["num_params"] == sum(
        math.prod(s) for s in shapes.values()) == 490298624

    def layer(i):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p.startswith(f"layer_{i}/"))

    assert [layer(i) for i in range(5)] == [
        79794432, 91885824, 91885824, 91885824, 83464448]
    assert 490298624 == 79794432 + 3 * 91885824 + 83464448 + (
        2 * 25690112 + 2048)
    # The gate is (hidden, heads of the layer): 48 and 64 wide.
    assert [shapes[f"layer_{i}/attn/g_proj/kernel"] for i in range(5)] == [
        (2048, h) for h in (48, 64, 64, 64, 48)]
    sizes = sorted(math.prod(s) for s in shapes.values())
    # The largest leaves: embedding and head, then 21 of 16.8M values (the
    # dense layer's three, twelve expert stacks, q and o of three layers of
    # 64 heads): what fold and median read 5 rows of.
    assert sizes[-2:] == [12544 * 2048] * 2
    assert sizes[-23:-2] == [16 * 2048 * 512] * 21 and sizes[-24] < 16 << 20
    # 1.407e12 multiply-adds a sequence; 42.2 TFLOP a step of 5 sequences,
    # of it the held experts 0.77.
    macs = family.forward_macs(config["model"])
    assert macs == 1406882807808
    assert round(6 * macs * 5 / 1e12, 1) == 42.2
    held = 4 * int(family.expected_pairs(config["model"], 4096)) * (
        3 * 2048 * 512)
    assert round(6 * held * 5 / 1e12, 2) == 0.77


def test_gated_attention_cost_counts_each_layers_own_heads():
    from harness import attention_cost, gated_attention_cost
    config = json.loads(CONFIG.read_text())
    assert attention_cost.pairs_per_head(config) == {
        "sliding_attention": 1966336, "full_attention": 8388608}
    assert sum(min(i + 1, 512) for i in range(4096)) == 1966336
    # Blocks of 512 positions a side that hold a visible pair, of 64: at a
    # window equal to the block the diagonal's and the ones under them.
    def blocks(window):
        return sum(1 for i in range(8) for j in range(i + 1)
                   if (i - j - 1) * 512 + 1 < window)
    assert (blocks(512), blocks(4096)) == (15, 36)
    # 2 FLOP x 3 passes x 2 contractions x 128 x (48 heads x the pairs of
    # two full layers + 64 heads x those of three sliding) x 5 sequences.
    assert gated_attention_cost.head_pairs_per_sequence(config) == (
        2 * 48 * 8388608 + 3 * 64 * 1966336)
    flops = gated_attention_cost.core_flops_per_step(config)
    assert flops == 2 * 3 * 2 * 128 * 5 * (
        2 * 48 * 8388608 + 3 * 64 * 1966336)
    assert round(flops / 1e12, 2) == 9.08
    # One head count for every layer is `attention_cost`'s count.
    other = json.loads((toy.REPO / (
        "benchmark/configs/mellum2-12b-a2.5b-ep4-n4.json")).read_text())
    other["model"]["num_attention_heads_per_layer"] = [32] * 4
    assert gated_attention_cost.core_flops_per_step(
        other) == attention_cost.core_flops_per_step(other)


def test_the_four_laguna_metrics_list_the_cell_and_read_nothing_elsewhere():
    """Each new metric lists the one cell; on a program without the scopes
    (the parent, under this PR's benchmark files) the readers return None
    and do not raise."""
    from harness import model_map, spec
    bench = spec.load()
    names = ["gated_attention_ms", "attention_gate_ms", "shared_expert_ms",
             "gated_attention_core_roofline"]
    cell = spec.Cell(bench, CELL)
    assert [m["name"] for m in cell.metrics("per_layer")][-4:] == names
    assert cell.traffic == {"rule": "median", "attack": "lie"}
    assert sorted(cell.limits) == sorted(
        spec.Cell(bench, "mellum2n4.median-lie").limits)
    for name in names:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "images_per_s"
        assert entry["source"] == "device_trace"
    facts = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
             "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    key = json.dumps([facts["config"], facts["traffic"]], sort_keys=True)
    model_map._memo[key] = None  # no map can be had
    try:
        for name in names:
            assert spec.layer_reader(name)({}, facts) is None
    finally:
        del model_map._memo[key]


@pytest.mark.parametrize("kind, name", [
    ("configs", "laguna-xs2-33b-a3b-n5"), ("workloads", CELL)])
def test_the_laguna_entries_keep_each_line_within_200_characters(kind, name):
    """The driver refuses `BENCHMARK.json` before any run over a `why` or a
    `source` longer than 200 printable characters on one line."""
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(e for e in bench[kind] if e["name"] == name)
    for key in ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200, (key, len(entry[key]))
            assert entry[key].isprintable()


def _rows(name):
    return [json.loads(line)
            for line in (DATA / name).read_text().splitlines()]


def test_the_committed_laguna_limits_part_the_committed_readings():
    """Every sound run of the program comes out correct; every run of the
    fp8 control fails at least four of the six numbers (one sequence a
    worker has no half batch)."""
    from harness import correct
    limits = json.loads(
        (toy.REPO / f"benchmark/limits/{CELL}.json").read_text())
    assert len(limits) == 6
    by_kind = {}
    for row in _rows(f"chip_readings.{CELL}.jsonl"):
        assert row["workload"] == CELL
        ok, check = correct.judge(row["values"], limits)
        failed = [n for n, r in check.items()
                  if not r["value"] <= r["limit"]]
        by_kind.setdefault(row["kind"], []).append((ok, failed))
        assert row["values"]["nonfinite"] == 0
    assert len(by_kind["program"]) >= 6
    assert all(ok for ok, _ in by_kind["program"])
    assert len(by_kind["control_fp8"]) >= 2
    assert all(len(failed) >= 4 for _, failed in by_kind["control_fp8"])
