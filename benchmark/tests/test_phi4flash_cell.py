"""The SambaY family's cell: ``phi4flashn4.median-lie`` at a toy size through
``run.run_cell`` on the CPU (a temporary copy of the benchmark that gains a
configuration, limits and entries; the family's reference, loss and kind of
input are the committed files), the committed configuration against the
published one, the arithmetic of d, ``forward_macs``, `ssm_cost` and
`diff_attention_cost`, the six metrics, the committed limits against the
committed chip readings. The runs through ``run.run_cell`` are marked slow
(minutes); the rest is collected by tier-1 (tests/test_benchmark_harness.py).
"""

import json
import math
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import toy  # noqa: E402

CELL = "phi4flashn4.median-lie"
CONFIG = toy.REPO / "benchmark/configs/phi4-mini-flash-l15-19-n4.json"
DATA = pathlib.Path(__file__).parent / "data"
STAGE = ["sliding_attention", "mamba", "full_attention", "gmu",
         "cross_attention"]
# config.json of microsoft/Phi-4-mini-flash-reasoning as published: every
# key.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
REDUCED = ["layer_types", "num_hidden_layers", "vocab_size"]
ASSUMED = ["mamba", "layout", "diff_attention", "cross_layers", "memory",
           "positions", "window", "optimizer", "f", "batch", "data", "init"]
# The numbers the cell's limits hold: ``grad1`` is left out (its worst leaf
# is a λ vector, whose gradient bf16 rounding moves by up to half its norm
# while fp8 may move it less: no limit lies between the two; PERF.md
# section 2).
LIMITED = ["grad1_median", "grad1_whole", "dparam3", "dparam3_median",
           "dparam3_whole"]
METRICS = ["ssm_scan_ms", "ssm_scan_roofline", "ssm_mixer_ms", "gmu_ms",
           "diff_attention_ms", "diff_attention_core_roofline"]

TOY_MODEL = {
    "family": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 8,
    "sliding_window": 4,
    "layer_types": ["mamba", "sliding_attention", "mamba",
                    "sliding_attention", "mamba", "full_attention", "gmu",
                    "cross_attention"],
    "layer_indices": list(range(8)), "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "norm_eps": 1e-05,
    "vocab_size": 16384, "seq_len": 32,
}
TOY_CONFIG = {
    "name": "toy-phi4flash",
    "source": "test only: the program's phi4flash_tiny",
    "topology": "aggregathor",
    "program": {"model": "phi4flash_tiny", "dataset": "synthtokens"},
    "model": TOY_MODEL, "init": {"residual_out_scale": 0.5},
    "num_params": 1343936,
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
def phi4flash_checkout(tmp_path_factory):
    """A copy of the benchmark with the cell ``toyphi4flash.median-lie``
    added by a configuration, limits and two entries."""
    root, bench = toy._copy(tmp_path_factory.mktemp("toy_phi4flash"))
    (root / "benchmark/configs/toy-phi4flash.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "benchmark/limits/toyphi4flash.median-lie.json").write_text(
        json.dumps(TOY_LIMITS))
    bench["configs"].append({
        "name": "toy-phi4flash", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy-phi4flash.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "toyphi4flash.median-lie", "config": "toy-phi4flash",
        "traffic": "median-lie", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch"])
def test_the_toy_sized_phi4flash_cell_is_correct_and_a_broken_one_is_not(
        phi4flash_checkout, fault):
    """n = 4, f = 1 through the whole harness: scan, the three kinds of
    differential core and the gated memory unit against the reference."""
    from test_run_cpu import _drive

    result, _ = _drive(phi4flash_checkout, "toyphi4flash.median-lie",
                       fault=fault)
    assert result["correct"] is (fault == "none")
    if fault == "none":
        assert result["failed"] == 0 and result["attempted"] >= 3
        assert result["check"]["nonfinite"]["value"] == 0


def test_the_toy_phi4flash_configuration_counts_its_parameters():
    import references
    shapes = references.family("phi4flash").param_shapes(TOY_MODEL)
    assert sum(math.prod(s) for s in shapes.values()) == TOY_CONFIG[
        "num_params"]


def test_every_published_phi4flash_key_is_kept_and_reduced_names_the_cut():
    config = json.loads(CONFIG.read_text())
    assert sorted(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 5
    assert config["layer_types"] == STAGE
    assert config["layer_indices"] == [15, 16, 17, 18, 19]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    model = config["model"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "sliding_window", "layer_types",
                "layer_indices", "vocab_size"):
        assert model[key] == config[key], key
    assert model["norm_eps"] == config["layer_norm_eps"]
    # The widths the config has no key for: Mamba's defaults, head 64.
    assert model["head_dim"] * PUBLISHED["num_attention_heads"] == 2560
    assert (model["mamba_d_state"], model["mamba_d_conv"],
            model["mamba_expand"]) == (16, 4, 2)
    assert model["mamba_dt_rank"] == math.ceil(2560 / 16) == 160
    assert model["seq_len"] == 4096 > model["sliding_window"]
    assert (config["num_workers"], config["f"], config[
        "batch_per_worker"]) == (4, 1, 1)
    assert config["init"]["residual_out_scale"] == 1 / math.sqrt(2 * 32)
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == REDUCED
    assert entry["source"].startswith(
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert "arXiv:2507.06607" in entry["source"]
    assert "pipeline stages" in config["deployment"]
    assert "8 chips" in config["deployment"]
    assert sorted(config["assumed"]) == sorted(ASSUMED)


def test_phi4flash_num_params_and_forward_macs_are_the_sums_reckoned():
    """d = 577,178,752, layer by layer as PERF.md section 4 reckons it; 56.7
    TFLOP of matmuls a step of 4 sequences and 2.30 of attention cores."""
    import references
    config = json.loads(CONFIG.read_text())
    family = references.family(config["model"]["family"])
    shapes = family.param_shapes(config["model"])
    assert config["num_params"] == sum(
        math.prod(s) for s in shapes.values()) == 577178752

    def layer(i):
        return sum(math.prod(s) for p, s in shapes.items()
                   if p.startswith(f"layer_{i}/"))

    assert [layer(i) for i in range(5)] == [
        98314624, 119895040, 98314624, 104867840, 91761024]
    assert shapes["embed/embedding"] == (25008, 2560)
    assert 577178752 == sum(layer(i) for i in range(5)) + 64020480 + 5120
    macs = family.forward_macs(config["model"])
    cores = 18743552 * 5120
    assert round(6 * (macs - cores) * 4 / 1e12, 1) == 56.7
    assert round(6 * cores * 4 / 1e12, 2) == 2.30


def test_the_two_cost_functions_count_the_equations():
    from harness import diff_attention_cost, ssm_cost
    config = json.loads(CONFIG.read_text())
    # x~, Δ, y forward; x~, Δ, dy in and dx~, dΔ out backward: 8 arrays of
    # 4,096 x 5,120 bf16; B, C (and their gradients) 6 of 4,096 x 16; A, D
    # float32, read twice and their gradients written.
    per = ssm_cost.bytes_per_layer_and_sequence(config)
    assert per == 2 * (8 * 4096 * 5120 + 6 * 4096 * 16) + 4 * 3 * (
        5120 * 16 + 5120) == 337375232
    assert ssm_cost.bytes_per_step(config) == 4 * per
    assert round(ssm_cost.bytes_per_step(config) / 1e9, 2) == 1.35
    # One sliding, one full, one cross layer of visible pairs a head.
    assert diff_attention_cost.pairs_per_sequence(config) == (
        1966336 + 2 * 8388608)
    assert diff_attention_cost.flops_per_pair(config) == 2 * (
        40 * 64 + 20 * 128) == 10240
    flops = diff_attention_cost.core_flops_per_step(config)
    assert flops == 3 * 10240 * (1966336 + 2 * 8388608) * 4
    assert round(flops / 1e12, 2) == 2.30


def test_the_six_phi4flash_metrics_list_the_cell_and_read_nothing_elsewhere():
    """Each new metric lists the one cell; on a program without the scopes
    (the parent, under this PR's benchmark files) the readers return None
    and do not raise; the rooflines read None for a configuration without
    their layers."""
    from harness import model_map, spec
    bench = spec.load()
    cell = spec.Cell(bench, CELL)
    assert [m["name"] for m in cell.metrics("per_layer")][-6:] == METRICS
    assert cell.traffic == {"rule": "median", "attack": "lie"}
    assert sorted(cell.limits) == sorted(LIMITED)
    for name in METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "model blocks", "images_per_s", "device_trace")
    facts = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
             "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    key = json.dumps([facts["config"], facts["traffic"]], sort_keys=True)
    model_map._memo[key] = None  # no map can be had
    try:
        for name in METRICS:
            assert spec.layer_reader(name)({}, facts) is None
    finally:
        del model_map._memo[key]
    other = spec.Cell(bench, "mellum2n4.median-lie")
    for name in ("ssm_scan_roofline", "diff_attention_core_roofline"):
        assert spec.layer_reader(name)({}, {**facts, "config": (
            other.config)}) is None


@pytest.mark.parametrize("kind, name", [
    ("configs", "phi4-mini-flash-l15-19-n4"), ("workloads", CELL)])
def test_the_phi4flash_entries_keep_each_line_within_200_characters(
        kind, name):
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    entry = next(e for e in bench[kind] if e["name"] == name)
    for key in ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200, (key, len(entry[key]))
            assert entry[key].isprintable()


def _rows(name):
    return [json.loads(line)
            for line in (DATA / name).read_text().splitlines()]


def test_the_committed_phi4flash_limits_part_the_committed_readings():
    """Every sound run of the program comes out correct; every run of the
    fp8 control fails at least three of the five numbers (one sequence a
    worker has no half batch); a state returned unchanged reads 1 and fails
    the change's three. Each limit lies between the two readings with room
    on both sides."""
    from harness import correct
    limits = json.loads(
        (toy.REPO / f"benchmark/limits/{CELL}.json").read_text())
    assert sorted(limits) == sorted(LIMITED)
    by_kind = {}
    for row in _rows(f"chip_readings.{CELL}.jsonl"):
        assert row["workload"] == CELL
        ok, check = correct.judge(row["values"], limits)
        failed = [n for n, r in check.items()
                  if not r["value"] <= r["limit"]]
        by_kind.setdefault(row["kind"], []).append((ok, failed, row))
        assert row["values"]["nonfinite"] == 0
    assert len(by_kind["program"]) >= 6
    assert all(ok for ok, _, _ in by_kind["program"])
    assert len(by_kind["control_fp8"]) >= 2
    assert all(len(failed) >= 3 for _, failed, _ in by_kind["control_fp8"])
    for name, limit in limits.items():
        sound = max(r["values"][name] for _, _, r in by_kind["program"])
        assert 1.5 * sound < limit, name
    unchanged = {name: 1.0 for name in LIMITED}
    assert not correct.judge(unchanged, limits)[0]
