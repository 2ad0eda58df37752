"""The repo-root entry points and the start-up helper they share.

``chip_smoke.py`` refuses to run off the chip and its legs work at toy size;
``__graft_entry__.dryrun_multichip`` raises rather than run somewhere else;
``profiling.enable_compile_cache`` can be placed from outside and otherwise
stays in the checkout; ``profiling.peak_bf16`` knows no default; and every
``GARFIELD_*`` variable the program reads is in the README's table.
"""

import ast
import importlib.util
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import pytest

from garfield_tpu.utils import profiling

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_entry", REPO_ROOT / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class TestCompileCache:
    def test_env_var_set_leaves_config_alone(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        before = jax.config.jax_compilation_cache_dir
        assert profiling.enable_compile_cache() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_dir_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        before = jax.config.jax_compilation_cache_dir
        try:
            got = profiling.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert got == str(REPO_ROOT / ".jax_cache")
        # The path is part of the cache key: nothing in it may change from
        # one process or installation to the next.
        import tempfile

        assert not got.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in got
        assert not re.search(r"\d+\.\d+", got), got  # no version component


def test_chip_smoke_refuses_off_chip():
    """``python chip_smoke.py`` without a TPU: non-zero, one line on stderr,
    no result on stdout, no leg started (so nothing compiled)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_chip_smoke_last_line_is_exactly_ok_and_device(capsys):
    """Whoever checks a chip run parses the last stdout line and accepts no
    key beyond ``ok`` and ``device`` {platform, kind, count}; the per-leg
    facts and ``claim: null`` ride on the summary line before it."""
    smoke = _load("chip_smoke")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    legs = {"kernels": {"ok": True, "compile_s": 1.0}}
    assert smoke.report(device, {"cache_dir": "/x"}, legs) is True
    summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert list(json.loads(last)) == ["ok", "device"]
    tag = "[chip_smoke] summary "
    assert summary.startswith(tag)
    full = json.loads(summary[len(tag):])
    assert full["legs"] == legs and full["device"] == device
    assert list(full)[-1] == "claim" and full["claim"] is None
    legs["kernels"]["ok"] = False
    assert smoke.report(device, {}, legs) is False
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def test_dryrun_multichip_raises_on_too_few_devices():
    entry = _load("__graft_entry__")
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"dryrun_multichip\\({n}\\)"):
        entry.dryrun_multichip(n)


def test_peak_bf16_unknown_accelerator_is_an_error():
    class Fake:
        platform = "tpu"
        device_kind = "TPU v0 imaginary"

    with pytest.raises(RuntimeError, match="TPU v0 imaginary"):
        profiling.peak_bf16(Fake)
    assert profiling.peak_bf16(jax.devices()[0]) is None  # cpu: no device metric


def _env_names_read():
    """Every string constant that is exactly a ``GARFIELD_*`` name in the
    program's sources: the argument of an ``os.environ`` read (or of a
    helper that makes one), never a sentence that mentions a name."""
    sources = [REPO_ROOT / "chip_smoke.py", REPO_ROOT / "__graft_entry__.py"]
    sources += sorted((REPO_ROOT / "garfield_tpu").rglob("*.py"))
    names = set()
    for path in sources:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and re.fullmatch(r"GARFIELD_[A-Z0-9_]+", node.value):
                names.add(node.value)
    return names


def test_every_env_knob_is_documented():
    """The README's "Environment variables" table and the variables the
    program reads are the same set: a new knob is a row with its default and
    its reader, a removed one takes its row along."""
    readme = (REPO_ROOT / "README.md").read_text()
    section = readme.split("## Environment variables", 1)[1].split("\n## ", 1)[0]
    rows = [line for line in section.splitlines() if line.startswith("| `")]
    documented = [re.match(r"\| `([A-Z0-9_]+)`", row).group(1) for row in rows]
    garfield = [name for name in documented if name.startswith("GARFIELD_")]
    assert len(garfield) == len(set(garfield)), "a name has two rows"
    assert set(garfield) == _env_names_read()
    for row in rows:  # name | default | read by | what it controls
        assert row.count(" | ") >= 3, row


@pytest.mark.slow
def test_chip_smoke_legs_at_toy_size():
    """Every leg of ``chip_smoke.run``, on the CPU: a small convnet, a short
    stack, kernels in interpret mode. Same code path as the chip run minus
    the device check and the Mosaic-text assertions."""
    smoke = _load("chip_smoke")
    toy = smoke.Size(
        kernel_d=1031, model="convnet", dataset="mnist", loss="nll",
        input_shape=(28, 28, 1), batch=4, num_iter=4, acc_freq=2,
        median_steps=2, interpret=True,
    )
    legs = smoke.run(toy)
    assert list(legs) == ["kernels", "trainer_krum", "trainer_median"]
    assert all(leg["ok"] for leg in legs.values())
    assert legs["kernels"]["cases"] == 12
    assert legs["trainer_krum"]["steps"] == 4
    assert legs["trainer_krum"]["eval_reports"] == 2
    assert legs["trainer_median"]["steps"] == 2
    device = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    assert smoke.report(device, {}, legs)  # the closing lines must serialize
