"""``run.py`` end to end at a toy size on the CPU, in a temporary copy of the
benchmark that gains toy cells by files and entries alone (`toy.py`): ResNet
cells of the family the benchmark has, and a token cell whose kind of input
and reference family arrive with it.

Each run is a process of its own (the device count is fixed at start-up).
The chip check is switched off here and nowhere else: ``run.run_cell(...,
check_device=False)``. Slow by CPU compiles: about a minute per run cold.
"""

import filecmp
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import toy  # noqa: E402

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]

DRIVER = textwrap.dedent("""
    import sys
    sys.path.insert(0, "benchmark")
    import jax.numpy as jnp
    import run

    def unchanged(sut):
        # A step that returns its state unchanged (XLA:CPU does not donate).
        step = sut.step
        sut.step = lambda state, x, y: (state, step(state, x, y)[1])

    def half_batch(sut):
        # Half of each worker's rows left out, the mean over the rest: the
        # first half twice gives exactly those statistics and that mean.
        step = sut.step
        def halved(state, x, y):
            h = x.shape[1] // 2
            return step(state, jnp.concatenate([x[:, :h], x[:, :h]], 1),
                        jnp.concatenate([y[:, :h], y[:, :h]], 1))
        sut.step = halved

    def altered(sut):
        # The answer altered where it is produced: the loss of every step.
        step = sut.step
        def lossy(state, x, y):
            state, loss = step(state, x, y)
            return state, loss * 1.05
        sut.step = lossy

    hooks = {"none": None, "unchanged": unchanged, "half_batch": half_batch,
             "altered": altered}
    cell, trace, fault, seconds = sys.argv[1:5]
    run.run_cell(
        run.parse(["--workload", cell, "--seed", "3000000019",
                   "--seconds", seconds, "--trace", trace]),
        check_device=False, system_hook=hooks[fault])
""")


def _env(devices):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES=str(devices),
        PYTHONPATH=str(toy.REPO),
        JAX_COMPILATION_CACHE_DIR=os.environ.get(
            "JAX_COMPILATION_CACHE_DIR",
            str(pathlib.Path.home() / ".cache/garfield_tpu/jax_cache")))
    env.pop("XLA_FLAGS", None)
    return env


def _drive(root, cell, *, trace=0, fault="none", devices=1, seconds=1):
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, cell, str(trace), fault, str(seconds)],
        cwd=root, env=_env(devices), capture_output=True, text=True,
        timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("toy_checkout")
    # Eight rows of a batch of 8: which row is the median of a coordinate
    # turns on the last bit, and three steps amplify it (0.05 read in f32).
    loose = {"dparam3": 0.2}
    cells = {
        (rule, attack, chips): toy.make_cell(root, rule, attack, chips, lim)
        for rule, attack, chips, lim in [
            ("krum", "lie", 1, None), ("median", "lie", 4, loose),
            ("average", "none", 1, None)]
    }
    cells["token"] = toy.make_token_cell(root)
    return root, cells


def _changed(ours, theirs):
    """Files of the tree ``ours`` that ``theirs`` lacks or holds changed."""
    found = filecmp.dircmp(ours, theirs, ignore=["__pycache__"])
    out = [*found.left_only, *found.diff_files, *found.funny_files]
    for name in found.common_dirs:
        out += [f"{name}/{p}" for p in _changed(ours / name, theirs / name)]
    return out


def test_the_copy_differs_from_the_tree_by_added_files_and_entries(checkout):
    root, _ = checkout
    assert _changed(toy.REPO / "benchmark", root / "benchmark") == []
    ours = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    theirs = json.loads((root / "BENCHMARK.json").read_text())
    assert sorted(ours) == sorted(theirs)
    for key, value in ours.items():
        if isinstance(value, list) and value and isinstance(value[0], dict):
            assert theirs[key][:len(value)] == value
        else:
            assert theirs[key] == value


def test_a_token_cell_arrives_as_files_and_comes_out_correct(checkout):
    """The program's ``gpt_tiny`` through ``run_cell``: int32 ids in, leaves
    (`embedding`, `pos_embedding`) the harness's defaults have no rule for,
    a reference family of its own; no file of the copy was edited."""
    root, cells = checkout
    result, err = _drive(root, cells["token"])
    assert result["correct"] is True and result["failed"] == 0
    assert all(row["value"] <= row["limit"]
               for row in result["check"].values())
    assert "the stack stays on the device" in err


def test_the_committed_benchmark_json_has_exactly_the_contracts_keys():
    bench = json.loads((toy.REPO / "BENCHMARK.json").read_text())
    assert sorted(bench) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    for cell in bench["workloads"]:
        assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
        assert len(cell["why"]) <= 200
        for kind in ("traffic", "limits"):
            stem = cell["traffic"] if kind == "traffic" else cell["name"]
            assert (toy.REPO / "benchmark" / kind / f"{stem}.json").exists()
    for metric in bench["per_layer"]:
        name = metric["name"].replace(".", "_").replace("-", "_")
        assert (toy.REPO / "benchmark/layer_metrics" / f"{name}.py").exists()


def test_one_device_run_prints_the_result_line_last(checkout):
    root, cells = checkout
    result, err = _drive(root, cells["krum", "lie", 1])
    assert list(result)[:5] == RESULT_KEYS and list(result)[-1] == "check"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert sorted(result["metrics"]) == [
        "images_per_s", "setup_s", "step_p95_ms"]
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    # Each number compared is printed beside its limit, last on stderr.
    tail = err.strip().splitlines()[-len(result["check"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


def test_the_step_cache_keeps_and_hands_back_a_compiled_program(tmp_path):
    """`system.StepCache` in one process on a small jitted function: what it
    saves it loads, under a key made of the sources, the configuration and
    the traffic mix; a torn entry is said and not loaded. (On the chip the
    run after a cell's first reports ``step_from: step cache``; XLA:CPU runs
    never use it, see `StepCache`.)"""
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, "benchmark")
        import jax, jax.numpy as jnp
        from harness import system
        assert system.StepCache.of(sys.argv[1], {}, {}) is None  # XLA:CPU
        step = jax.jit(lambda s, x, y: (s + x.sum(), {"loss": y.sum() + s}))
        state, batch = jnp.float32(1), (jnp.ones(3), jnp.ones(2))
        cache = system.StepCache(sys.argv[1], {"a": 1}, {"rule": "krum"})
        assert cache.load(state, batch) is None
        cache.save(step.lower(state, *batch).compile())
        assert cache.path.exists()
        again = system.StepCache(sys.argv[1], {"a": 1}, {"rule": "krum"})
        assert again.path == cache.path
        new, metrics = again.load(state, batch)(state, *batch)
        assert float(new) == 4.0 and float(metrics["loss"]) == 3.0
        other = system.StepCache(sys.argv[1], {"a": 2}, {"rule": "krum"})
        assert other.path != cache.path
        cache.path.write_bytes(b"torn")
        assert again.load(state, batch) is None
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "cache")],
        cwd=toy.REPO, env=_env(1), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "not loaded" in proc.stderr


def test_four_device_cell_is_data_and_reads_every_device(checkout):
    root, cells = checkout
    result, _ = _drive(root, cells["median", "lie", 4], trace=1, devices=4,
                       seconds=45)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    # The toy cell's own per-layer metric came from the file the copy added;
    # the kernel's roofline finds no Mosaic event on the CPU and is left out.
    assert "toy_steps" in result["metrics"]
    assert "median_kernel_roofline" not in result["metrics"]
    assert "step_mfu" not in result["metrics"]  # no peak off the chip
    assert {"device_idle_share", "host_gap_p95_ms"} <= set(result["metrics"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(result["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_a_broken_timed_path_comes_out_not_correct(checkout, fault):
    root, cells = checkout
    result, _ = _drive(root, cells["krum", "lie", 1], fault=fault)
    assert result["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_token_cell_comes_out_not_correct(checkout, fault):
    root, cells = checkout
    result, _ = _drive(root, cells["token"], fault=fault)
    assert result["correct"] is False


def test_the_fp8_control_fails_the_toy_limits(checkout):
    """The control of the correctness check at a size a test can hold: the
    reference with fp8 operands, put in the program's place."""
    root, _ = checkout
    code = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, "benchmark")
        from harness import correct, reference, spec
        cell = spec.Cell(spec.load(), "toy.average-none")
        for seed in (11, 12, 13):
            ref = reference.run(cell.config, cell.traffic, seed)
            ctl = reference.run(cell.config, cell.traffic, seed, quant="fp8")
            ok, check = correct.judge(
                correct.readings(ctl, ref)[0], cell.limits)
            print(json.dumps({"ok": ok, "check": check}))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=_env(1),
        capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert len(rows) == 3 and not any(r["ok"] for r in rows)


def test_without_a_tpu_the_command_refuses_and_prints_no_result(checkout):
    root, cells = checkout
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         cells["krum", "lie", 1], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=_env(1), capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_alone_with_the_benchmark_the_command_refuses(tmp_path):
    toy.make_cell(tmp_path, "krum", "lie", 1)
    env = _env(1)
    env.pop("PYTHONPATH")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "toy.krum-lie",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
