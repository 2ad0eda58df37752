"""A temporary copy of the benchmark with toy cells ADDED by files and
entries alone: no file of the copy is edited except ``BENCHMARK.json``, which
gains entries. What the copy proves: a configuration, a traffic mix, a cell
and a per-layer metric arrive as data (`make_cell`), and so do a kind of
input and a reference family that states how its leaves are made — a token
model needs no edit (`make_token_cell`, the files of ``toy_token/``)."""

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TOY_CONFIG = {
    "name": "toy-resnet18",
    "source": "test only: ResNet-18 at 16x16 inputs",
    "topology": "aggregathor",
    "program": {"model": "resnet18", "dataset": "cifar10"},
    "model": {"family": "resnet", "block": "basic",
              "stage_sizes": [2, 2, 2, 2], "stem_width": 64,
              "num_classes": 10, "image": [16, 16, 3]},
    "num_params": 11173962,
    "num_workers": 8, "f": 2, "batch_per_worker": 8,
    "model_dtype": "float32", "gar_dtype": "float32",
    "loss": "cross-entropy",
    "optimizer": {"name": "sgd", "lr": 0.05, "momentum": 0.9,
                  "weight_decay": 0.0005},
    "reduced": {}, "assumed": {},
}
TOY_LIMITS = {"loss1": 1e-3, "loss2": 1e-2, "loss3": 1e-2, "grad1": 2e-2,
              "dparam3": 2e-2}
# The program's ``gpt_tiny`` on ``copytask`` shapes: 16 tokens below 32, one
# label below 10 per sequence.
TOY_TOKEN_CONFIG = {
    "name": "toy-gpt",
    "source": "test only: the program's gpt_tiny at its own sizes",
    "topology": "aggregathor",
    "program": {"model": "gpt_tiny", "dataset": "copytask"},
    "model": {"family": "toy_gpt", "vocab": 32, "seq_len": 16, "dim": 48,
              "depth": 2, "heads": 3, "mlp_dim": 96, "num_labels": 10},
    "num_params": 40810,
    "num_workers": 8, "f": 2, "batch_per_worker": 8,
    "model_dtype": "float32", "gar_dtype": "float32",
    "loss": "cross-entropy",
    "optimizer": {"name": "sgd", "lr": 0.05, "momentum": 0.9,
                  "weight_decay": 0.0005},
    "reduced": {}, "assumed": {},
}
# Program and reference agree to 1e-6 here (float32 both); half of each
# worker's rows left out reads 0.1 and more.
TOY_TOKEN_LIMITS = {"loss1": 1e-4, "loss2": 1e-3, "loss3": 1e-3,
                    "grad1": 5e-3, "dparam3": 5e-3}
TOY_TOKEN_FILES = {"toy_gpt.py": "references", "toy_tokens.py": "inputs"}
TOY_METRIC = '''"""Steps in the traced window (a toy)."""


def read(trace, facts):
    return float(trace["fullest"]["steps"]) or None
'''


def _copy(root):
    """The copy at ``root``, made from the repo on the first call, and its
    ``BENCHMARK.json`` as read."""
    root = pathlib.Path(root)
    if not (root / "BENCHMARK.json").exists():
        shutil.copytree(REPO / "benchmark", root / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root, json.loads((root / "BENCHMARK.json").read_text())


def make_token_cell(root, rule="krum", attack="lie"):
    """Add the cell ``toytok.<rule>-<attack>`` to the copy at ``root``: the
    configuration, its reference family, its kind of input, a traffic mix
    and limits, every one a new file; returns the cell's name."""
    root, bench = _copy(root)
    name, traffic = f"toytok.{rule}-{attack}", f"toytok-{rule}-{attack}"
    for file, where in TOY_TOKEN_FILES.items():
        target = root / "benchmark" / where / file
        assert not target.exists(), f"{target} would be overwritten"
        shutil.copy(pathlib.Path(__file__).parent / "toy_token" / file, target)
    (root / "benchmark/configs/toy-gpt.json").write_text(
        json.dumps(TOY_TOKEN_CONFIG))
    (root / f"benchmark/traffic/{traffic}.json").write_text(json.dumps(
        {"rule": rule, "attack": attack}))
    (root / f"benchmark/limits/{name}.json").write_text(
        json.dumps(TOY_TOKEN_LIMITS))
    bench["configs"].append({
        "name": "toy-gpt", "source": TOY_TOKEN_CONFIG["source"],
        "file": "benchmark/configs/toy-gpt.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": name, "config": "toy-gpt", "traffic": traffic, "chips": 1,
        "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return name


def make_cell(root, rule="krum", attack="lie", chips=1, limits=None):
    """Add the cell ``toy.<rule>-<attack>`` to the copy at ``root`` (made
    from the repo on the first call); returns the cell's name."""
    root, bench = _copy(root)
    name, traffic = f"toy.{rule}-{attack}", f"toy-{rule}-{attack}"
    (root / "benchmark/configs/toy-resnet18.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / f"benchmark/traffic/{traffic}.json").write_text(json.dumps(
        {"rule": rule, "attack": attack}))
    (root / f"benchmark/limits/{name}.json").write_text(
        json.dumps({**TOY_LIMITS, **(limits or {})}))
    (root / "benchmark/layer_metrics/toy_steps.py").write_text(TOY_METRIC)
    if not any(c["name"] == "toy-resnet18" for c in bench["configs"]):
        bench["configs"].append({
            "name": "toy-resnet18", "source": TOY_CONFIG["source"],
            "file": "benchmark/configs/toy-resnet18.json", "reduced": [],
            "why": "test"})
        bench["per_layer"].append({
            "name": "toy_steps", "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "app loop",
            "moves": "images_per_s", "workloads": []})
    bench["workloads"].append({
        "name": name, "config": "toy-resnet18", "traffic": traffic,
        "chips": chips, "why": "test"})
    bench["per_layer"][-1]["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return name
