"""Test configuration: force a virtual 8-device CPU platform.

This is the fake-backend the reference lacked (SURVEY §4): every distributed
construct is testable single-process by running the SPMD program over 8
host-local CPU devices. The platform, the device count and the compile
cache are all set through the environment BEFORE jax is imported, so jax
reads them itself and every subprocess a test spawns inherits them.
"""

import os

# GARFIELD_TPU_TESTS=1 opts OUT of the CPU forcing so the real-TPU test
# files (tests/test_ops_tpu.py — on-device Mosaic-lowering equality) run
# against the chip; everything else skips itself off-CPU or on-TPU as
# appropriate.
_USE_TPU = os.environ.get("GARFIELD_TPU_TESTS", "").lower() not in (
    "", "0", "false",
)

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_NUM_CPU_DEVICES"] = "8"

# Persistent compilation cache: CPU compiles of the large SPMD programs
# dominate suite time; caching them across runs keeps the suite inside its
# budget. JAX_COMPILATION_CACHE_DIR is honoured when the caller set it;
# the default is one fixed directory OUTSIDE the checkout — the chip tool
# copies the checkout as it stands for every call, and the CPU suite's
# cache is hundreds of MB the chip never reads. Setting the variable
# (rather than jax.config) is what keeps ``profiling.enable_compile_cache``
# — called by every app run the tests drive — from re-pointing the cache
# at <checkout>/.jax_cache.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.expanduser("~/.cache/garfield_tpu/jax_cache"),
)

import jax

# The suite's programs are many and small: cache every compile that takes
# longer than a cache read, not only those above jax's 1 s default.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# End-to-end trainer files last. Alphabetical collection puts
# test_apps.py (ten full CLI training runs, ~1 min each on a 1-core
# container) FIRST, so a tier-1 wall-clock budget hit starves the entire
# unit matrix behind it. Run units first and the end-to-end runs last: a
# timeout then costs the slowest, most redundant coverage (the app flows
# are also exercised piecewise by the unit files), not the matrix.
# test_hierarchy_stream.py is end-to-end too (slow-marked multi-wave
# TCP-exchange ingest into the hierarchical reducer), as are the
# multi-PROCESS deployment suites (subprocess fleets over PeerExchange):
# test_multihost_integration.py, test_cluster.py, test_async_cluster.py.
# All collect before the app runs when slow tests are enabled.
_RUN_LAST = {
    "test_multihost_integration.py": 1,
    "test_hierarchy_stream.py": 2,
    "test_cluster.py": 3,
    "test_async_cluster.py": 4,
    "test_defense_cluster.py": 5,
    "test_dataplane_cluster.py": 6,
    "test_fed_cluster.py": 7,
    "test_apps.py": 8,
}

# Tier-1 wall-clock budget of the verify command (ROADMAP.md): the
# watchdog below warns when a run gets close, so a creeping suite is
# visible BEFORE the external timeout starts starving the e2e tail.
_TIER1_BUDGET_S = 870


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda it: _RUN_LAST.get(it.fspath.basename, 0))
    # Tier-1 budget discipline: any TIER-1 test (not slow-marked) that
    # drives a full CLI training run (the app_*.main pattern) must live
    # in a file REGISTERED in _RUN_LAST, so a wall-clock budget hit
    # starves the slowest, most redundant end-to-end coverage — never
    # the unit matrix collected behind it. A new e2e-style test added
    # outside the registered files fails here at collection instead of
    # silently eating the tier-1 budget first. (Slow-marked app runs are
    # exempt: they never enter the tier-1 shard.)
    import inspect
    import re

    pattern = re.compile(r"\bapp_\w+\.main\(")
    src_cache = {}
    file_src_cache = {}
    popen = re.compile(r"\bsubprocess\.Popen\b")
    garfield = re.compile(r"garfield_tpu\.(apps|utils\.multihost)|"
                          r"multihost_child")
    for it in items:
        fn = getattr(it, "function", None)
        if fn is None:
            continue
        # Multi-process e2e discipline: a FILE that spawns garfield
        # subprocess fleets (subprocess.Popen + app/multihost plumbing)
        # must be registered in _RUN_LAST — those files hold the most
        # expensive, most redundant coverage and must collect last even
        # in full-suite runs; a new one fails here at collection.
        path = str(it.fspath)
        if path not in file_src_cache:
            try:
                with open(path) as fp:
                    src = fp.read()
            except OSError:
                src = ""
            file_src_cache[path] = bool(
                popen.search(src) and garfield.search(src)
            )
        assert not file_src_cache[path] or (
            it.fspath.basename in _RUN_LAST
        ), (
            f"{it.fspath.basename} spawns garfield subprocess fleets "
            "(multi-process e2e) but is not registered in "
            "conftest._RUN_LAST — register it so the unit matrix keeps "
            "collection priority"
        )
        if (it.get_closest_marker("slow") is not None
                or it.fspath.basename in _RUN_LAST):
            continue
        if fn not in src_cache:
            try:
                src_cache[fn] = bool(pattern.search(inspect.getsource(fn)))
            except (OSError, TypeError):
                src_cache[fn] = False
        assert not src_cache[fn], (
            f"{it.nodeid} drives a full app CLI run (app_*.main) from a "
            "tier-1 test outside conftest._RUN_LAST — move it to a "
            "registered end-to-end file (or slow-mark it) so the unit "
            "matrix keeps collection priority (tier-1 budget discipline)"
        )


def pytest_sessionstart(session):
    import time

    session._garfield_t0 = time.time()


def pytest_sessionfinish(session, exitstatus):
    # Tier-1 budget watchdog: the fast shard (-m 'not slow') must stay
    # under the verify command's 870 s timeout on the 1-core box. Warn
    # at 90% so growth is caught in review, not as a truncated CI run.
    import sys
    import time

    markexpr = getattr(session.config.option, "markexpr", "") or ""
    if "not slow" not in markexpr:
        return
    wall = time.time() - getattr(session, "_garfield_t0", time.time())
    if wall > 0.9 * _TIER1_BUDGET_S:
        print(
            f"\n[tier-1 budget watchdog] fast shard took {wall:.0f}s — "
            f"{'OVER' if wall > _TIER1_BUDGET_S else 'within 10% of'} "
            f"the {_TIER1_BUDGET_S}s budget; trim or slow-mark the "
            "newest fast tests (conftest._TIER1_BUDGET_S)",
            file=sys.stderr,
        )
