"""Step timing, XLA profiler traces, and bandwidth accounting.

Counterpart of the reference's opt-in instrumentation (SURVEY §5):
  - per-step wall time: ``timeit(train_step, number=1)`` prints
    (Aggregathor/trainer.py:244-247) -> ``StepTimer``;
  - profiler: ``torch.autograd.profiler.profile(enabled=bench)``
    (Aggregathor/trainer.py:234-239) -> ``jax.profiler.trace`` (XLA/TPU
    timeline viewable in TensorBoard/Perfetto);
  - bandwidth: psutil NIC byte deltas (garfieldpp/tools.py:152-163, printed
    trainer.py:240-241). A TPU mesh has no NIC counters to poll; collective
    traffic is fully determined by the program, so we *derive* per-step bytes
    from the collective shapes instead (``collective_bytes``).
"""

import contextlib
import time

import jax
import numpy as np

__all__ = [
    "StepTimer",
    "StepsTrace",
    "peak_bf16",
    "collective_bytes",
    "convert_to_gbit",
    "enable_compile_cache",
]


def enable_compile_cache():
    """Turn on JAX's persistent compile cache; return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it into its own
    config, so nothing is touched — whoever launched the process decides
    where compiled programs live (a chip job's output directory, a CI
    volume). Unset: ``<checkout>/.jax_cache``, derived from this package's
    location. The path is part of the cache key, so it is a fixed place —
    never a temp name, a pid, a time or a version string — and every entry
    point (``apps/common.train``, ``chip_smoke.py``,
    ``__graft_entry__.py``) shares it. A failure
    to configure the cache raises: a run that silently recompiles
    ResNet-18 every time is a bug, not a degraded mode.
    """
    import os
    import pathlib

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    cache_dir = str(
        pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_bf16(device):
    """Peak bf16 FLOP/s of ``device``; None on the CPU platform (a CPU run
    checks the program, it has no device metric). An accelerator missing
    from ``_PEAK_BF16`` is an error, not a default."""
    if device.platform == "cpu":
        return None
    if device.device_kind not in _PEAK_BF16:
        raise RuntimeError(
            f"device kind {device.device_kind!r} has no entry in "
            "profiling._PEAK_BF16; add its published peak before running "
            "on it"
        )
    return _PEAK_BF16[device.device_kind]


class StepTimer:
    """Wall-clock timer that blocks on device results for honest numbers.

    ``with timer.step(): ...`` records one step; ``summary()`` reports
    count/mean/min/max seconds, like the per-step prints at
    Aggregathor/trainer.py:244-247 but aggregated.
    """

    def __init__(self):
        self.times = []

    @contextlib.contextmanager
    def step(self, block_on=None):
        t0 = time.perf_counter()
        yield
        if block_on is not None:
            jax.block_until_ready(block_on)
        self.times.append(time.perf_counter() - t0)

    def record_chunk(self, total_s, k):
        """Fold one k-step chunked dispatch (``--chunk_steps``): the steps
        shared one dispatch + one sync, so the only honest per-step number
        is the mean ``total_s / k`` — recorded k times to keep ``last()``,
        ``summary()`` and the percentiles per-STEP shaped."""
        self.times.extend([total_s / k] * k)

    def last(self):
        return self.times[-1] if self.times else float("nan")

    def summary(self):
        if not self.times:
            return {"count": 0}
        a = np.asarray(self.times)
        return {
            "count": int(a.size),
            "mean_s": float(a.mean()),
            "min_s": float(a.min()),
            "max_s": float(a.max()),
            "total_s": float(a.sum()),
            # Tail percentiles: the mean hides the dispatch-tail spread
            # chunking exists to kill (the 130/s best-window vs 108/s
            # typical gap, PERF.md r8) — p50/p95/p99 make the fewer-fatter-
            # dispatches win visible in committed artifacts.
            "p50_s": float(np.percentile(a, 50)),
            "p95_s": float(np.percentile(a, 95)),
            "p99_s": float(np.percentile(a, 99)),
        }


class StepsTrace:
    """``--profile_dir``: one ``jax.profiler`` trace of ``steps`` whole
    training steps, from step ``first`` on. Dispatch is asynchronous, so
    the trace starts once what was dispatched before has run, and stops
    once the last traced step has: ``before(i, pending)`` ahead of step
    i's dispatch, ``after(end, pending)`` behind each dispatch (``end`` the
    next step's index; None at the end of the run). ``pending`` is what to
    wait for. Nothing happens when ``log_dir`` is None."""

    STEPS = 8

    def __init__(self, log_dir, first, steps=STEPS):
        self.log_dir, self.first, self.last = log_dir, first, first + steps
        self.tracing = False

    def before(self, i, pending):
        if self.log_dir is not None and i == self.first:
            jax.block_until_ready(pending)
            jax.profiler.start_trace(str(self.log_dir))
            self.tracing = True

    def after(self, end, pending):
        if self.tracing and (end is None or end >= self.last):
            jax.block_until_ready(pending)
            jax.profiler.stop_trace()
            self.tracing = False


def collective_bytes(topology, *, num_workers, d, num_ps=1, rounds=1,
                     bytes_per_el=4, axis_size=None):
    """Per-step collective traffic (bytes) implied by the topology's program.

    Replaces NIC-counter polling (garfieldpp/tools.py:152-163): the SPMD
    program's communication volume is static. Counts the all_gather payloads
    per device (ring all-gather moves (k-1)/k of the gathered buffer over
    ICI, k = axis size):

      - aggregathor: one (n_w, d) gradient all_gather           (server.py:112-159)
      - byzsgd:      + one (n_ps, d) model all_gather           (server.py:161-184)
      - learn:       gradient gather x (1 + rounds) + model gather
                                                                (LEARN/trainer.py:208-257)
    """
    k = axis_size if axis_size else num_workers
    frac = (k - 1) / k if k > 1 else 0.0
    grad_gather = num_workers * d * bytes_per_el * frac
    model_gather = num_ps * d * bytes_per_el * frac
    if topology in ("centralized",):
        return 0
    if topology in ("aggregathor", "garfield_cc"):
        return int(grad_gather)
    if topology == "byzsgd":
        return int(grad_gather + model_gather)
    if topology == "learn":
        return int(grad_gather * (1 + rounds) + num_workers * d * bytes_per_el * frac)
    raise ValueError(f"unknown topology {topology!r}")


def convert_to_gbit(num_bytes):
    """Bytes -> Gbit (garfieldpp/tools.py:161-163)."""
    return num_bytes * 8 / (1024 ** 3)
