"""The benchmark's own fast tests, collected by tier-1.

`benchmark/tests/` holds the yardstick to the bit — the FLOP and byte
counts, the trace reducer, the phase join, the seeded weights — and the
driver's command collects `tests/` alone. This file loads those test files
by path and re-exports their test functions and fixtures, so they run under
`pytest tests/` as cases of this file; nothing under `benchmark/` knows.
Left by hand (minutes each): `test_reference.py`, `test_run_cpu.py`,
`test_lfm2_cell.py`; of `test_mellum2_cell.py` and `test_laguna_cell.py` the
runs through ``run.run_cell``, which they mark slow.
"""

import importlib.util
import pathlib

from _pytest.fixtures import FixtureFunctionDefinition

BENCH_TESTS = pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "tests"
FILES = ("test_arithmetic", "test_reduce_trace", "test_seeded_arrays",
         "test_phase_map", "test_mellum2_cell", "test_laguna_cell")


for _name in FILES:
    _spec = importlib.util.spec_from_file_location(
        f"benchmark_tests.{_name}", BENCH_TESTS / f"{_name}.py")
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    for _key, _value in vars(_module).items():
        if _key.startswith("test_") or isinstance(
                _value, FixtureFunctionDefinition):
            assert _key not in globals(), f"{_key}: two files define it"
            globals()[_key] = _value
