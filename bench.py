"""North-star benchmark: Byzantine-resilient SGD steps/sec/chip.

Config (BASELINE.md measurement plan, mirroring Aggregathor/run_exp.sh:5-14):
ResNet-18 / CIFAR-10, 8 logical workers folded onto the available chip(s),
batch 25/worker, Multi-Krum with f=2 under the "little is enough" lie attack
(byzWorker.py:108-125) — i.e. the full hot path: per-worker fwd+bwd,
all_gather, on-device attack injection, O(n^2 d) Krum scoring, SGD update,
all inside one jit'd SPMD program.

Runs on whatever backend the process was started with and never changes
it: a CPU run is asked for with ``JAX_PLATFORMS=cpu`` and nothing else.
Any failure — a bad rule, a worker count that does not fold onto the
devices, an accelerator whose ``device_kind`` is missing from
``_PEAK_BF16``, a compile error — is a traceback and a non-zero exit.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"chunk_steps", "platform", "device_kind", "n_devices"}. The last three are
``jax.devices()[0].platform``, ``.device_kind`` and ``len(jax.devices())``:
a line from a CPU run says so itself. ``vs_baseline`` divides by
``BASELINE.json.published.steps_per_sec_per_chip`` — the reference repo
publishes no numbers (SURVEY §6), so that slot holds this repo's first
driver-recorded measurement (round 1: 50.9139). ``mfu`` is model-FLOPs
utilization: XLA-reported flops of the compiled step / measured step time /
the chip's peak bf16 FLOP/s; null on the CPU platform, which has no entry
in the peak table and no device metric to report.

Env knobs: GARFIELD_BENCH_STEPS (timed steps, default 20),
GARFIELD_BENCH_WORKERS, GARFIELD_BENCH_F, GARFIELD_BENCH_BATCH,
GARFIELD_BENCH_GAR / GARFIELD_BENCH_ATTACK (rule/attack for off-default
table rows, e.g. average + none for the fault-free row; the official
metric name is emitted only for the default krum + lie config),
GARFIELD_BENCH_TRIALS (independent timed trials, default 4; the reported
value is the BEST trial),
GARFIELD_BENCH_F32_GAR (set to disable the default bf16 aggregation
pipeline on TPU and run the GAR phase at full width),
GARFIELD_BENCH_CHUNK (K steps scanned on device per dispatch via
core.make_chunked_step; per-step time = chunk_time / K; the JSON line
carries chunk_steps so rows stay attributable).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

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
            "bench._PEAK_BF16; add its published peak before benchmarking "
            "on it"
        )
    return _PEAK_BF16[device.device_kind]


def _step_flops(compiled, axis_size, chunk=1):
    """Global FLOPs of one train step, from XLA's cost model.

    ``cost_analysis`` reports the partitioned per-device module, so the XLA
    number is scaled by ``axis_size`` to a global count — and divided by
    ``chunk`` when the compiled module is a K-step chunked program (the
    per-step quantity is what MFU needs)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost["flops"])
    if flops <= 0:
        raise RuntimeError(f"XLA cost analysis reported flops={flops}")
    return flops * axis_size / chunk


def _measure(step_fn, init_fn, x, y, steps, chunk=1):
    """Compile, warm up, and time one configuration. Returns
    (dt_per_step, compiled).

    ``chunk > 1`` (GARFIELD_BENCH_CHUNK) times the CHUNKED program
    (core.make_chunked_step): each dispatch scans ``chunk`` steps on
    device, the sync happens once per chain, and the per-step time is
    chunk_time / chunk. The paired-reps estimator composes naturally — a
    chunk IS a dependency chain, so the k-dispatch chain it times is a
    k*chunk-step chain and the constant sync cost still cancels in the
    difference (PERF.md "How a step is timed")."""
    from garfield_tpu.parallel import core as core_lib
    from garfield_tpu.utils import profiling

    state = init_fn(jax.random.PRNGKey(1234), x[0])

    # AOT-compile once: the same executable serves warmup, timing, and the
    # cost-analysis read — no second compile after timing finishes.
    if chunk > 1:
        # One-slot batch axis: the bench reuses a single synthetic batch,
        # so the on-device index b = (i0 + k) % 1 always selects it.
        xs, ys = x[:, None], y[:, None]
        chunked = core_lib.make_chunked_step(step_fn, chunk, 1)
        compiled = chunked.lower(state, xs, ys, jnp.int32(0)).compile()
        call = lambda st: compiled(st, xs, ys, jnp.int32(0))
    else:
        compiled = step_fn.lower(state, x, y).compile()
        call = lambda st: compiled(st, x, y)

    for _ in range(3):  # warmup: stabilize clocks
        state, metrics = call(state)
    jax.block_until_ready(metrics["loss"])

    state_box = [state]

    def timed(k):
        state = state_box[0]
        t0 = time.perf_counter()
        for _ in range(k):
            state, metrics = call(state)
        jax.block_until_ready(metrics["loss"])
        state_box[0] = state
        return time.perf_counter() - t0

    # Paired-reps timing: the constant per-chain cost cancels in the
    # difference (utils/profiling.paired_reps).
    dt = profiling.paired_reps(timed, steps)
    if dt is None:  # below noise floor at this rep count: lengthen the chain
        dt = profiling.paired_reps(timed, steps * 4)
    if dt is None:
        # Last resort: single-run wall time / steps. Includes the constant
        # sync cost, so it UNDER-reports throughput — conservative, never
        # the ~1/floor fantasy number the old clamp could produce.
        dt = timed(steps) / steps
    return dt / chunk, compiled


def _emit_jsonl(fields):
    """Append the schema-versioned JSONL twin of the stdout line
    (garfield_tpu.telemetry.exporters), validated by the tier-1 schema
    check so a malformed capture fails loudly. Path: GARFIELD_BENCH_JSONL
    (default ./bench_telemetry.jsonl; empty string disables)."""
    from garfield_tpu.telemetry import exporters

    path = os.environ.get("GARFIELD_BENCH_JSONL", "bench_telemetry.jsonl")
    if path:
        exporters.append_record(
            path, exporters.make_record("bench", t=time.time(), **fields)
        )


def main():
    """Run the benchmark on the backend this process was started with and
    print ONE JSON line (also appended as a schema-versioned JSONL record,
    ``_emit_jsonl``). Any failure propagates: a traceback and a non-zero
    exit, never a line that looks like a result."""
    from garfield_tpu import models
    from garfield_tpu.parallel import aggregathor
    from garfield_tpu.utils import profiling, selectors

    profiling.enable_compile_cache()

    num_workers = int(os.environ.get("GARFIELD_BENCH_WORKERS", 8))
    f = int(os.environ.get("GARFIELD_BENCH_F", 2))
    gar_name = os.environ.get("GARFIELD_BENCH_GAR", "krum")
    attack_name = os.environ.get("GARFIELD_BENCH_ATTACK", "lie")
    if attack_name in ("", "none"):
        attack_name = None
    batch = int(os.environ.get("GARFIELD_BENCH_BATCH", 25))
    steps = max(1, int(os.environ.get("GARFIELD_BENCH_STEPS", 20)))
    # On-device step chunking (core.make_chunked_step): K steps per
    # dispatch, per-step time = chunk_time / K. 1 = the per-step program.
    chunk = max(1, int(os.environ.get("GARFIELD_BENCH_CHUNK", 1)))

    device = jax.devices()[0]
    platform = device.platform
    n_dev = len(jax.devices())
    peak = peak_bf16(device)  # unknown accelerator: fail before compiling
    # bf16 compute routes conv/matmul onto the MXU; params stay f32.
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    module = models.select_model("resnet18", "cifar10", dtype=dtype)
    loss_fn = selectors.select_loss("cross-entropy")
    # Reference AggregaThor defaults: SGD lr 0.2, momentum 0.9, wd 5e-4
    # (Aggregathor/run_exp.sh:39-40).
    opt = selectors.select_optimizer(
        "sgd", lr=0.2, momentum=0.9, weight_decay=5e-4
    )

    # Default mesh: every device on the "workers" axis. A worker count that
    # does not fold onto them raises in make_trainer (mesh.fold) — the
    # bench never quietly uses fewer devices than the host has.
    init_fn, step_fn, _ = aggregathor.make_trainer(
        module, loss_fn, opt, gar_name,
        num_workers=num_workers, f=f, attack=attack_name,
        # bf16 aggregation pipeline on TPU (half the HBM/ICI bytes through
        # attack+gather+GAR; Gram still accumulates f32): +~2% on one chip
        # (PERF.md r3), the honest TPU-first default. GARFIELD_BENCH_F32_GAR
        # restores the full-width pipeline.
        gar_dtype=(
            jnp.bfloat16
            if platform == "tpu"
            and not os.environ.get("GARFIELD_BENCH_F32_GAR")
            else None
        ),
    )

    rng = np.random.default_rng(1234)
    x = jnp.asarray(
        rng.standard_normal((num_workers, batch, 32, 32, 3)), jnp.float32
    )
    y = jnp.asarray(rng.integers(0, 10, (num_workers, batch)), jnp.int32)

    trials = max(1, int(os.environ.get("GARFIELD_BENCH_TRIALS", 4)))
    dt = compiled = None
    for trial in range(trials):
        trial_dt, compiled = _measure(
            step_fn, init_fn, x, y, steps, chunk=chunk
        )
        print(
            f"bench trial {trial + 1}/{trials}: "
            f"{1.0 / trial_dt / n_dev:.2f} steps/s/chip",
            file=sys.stderr,
        )
        dt = trial_dt if dt is None else min(dt, trial_dt)

    steps_per_sec_per_chip = 1.0 / dt / n_dev
    mfu = None
    if peak is not None:
        flops = _step_flops(compiled, n_dev, chunk=chunk)
        mfu = flops / dt / (peak * n_dev)
    with open(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BASELINE.json")
    ) as fp:
        baseline = json.load(fp)["published"]["steps_per_sec_per_chip"]
    vs = steps_per_sec_per_chip / baseline
    # One format string for every config: the official north-star name
    # ("...w8_f2_krum_lie") falls out of the defaults. vs_baseline is only
    # meaningful against the published krum/lie batch-25 record, so any
    # off-default knob (rule, attack, cohort, batch, f32 pipeline) reports
    # it as None instead of an apples-to-oranges ratio.
    metric = (
        f"byzsgd_steps_per_sec_per_chip_resnet18_cifar10_"
        f"w{num_workers}_f{f}_{gar_name}_{attack_name or 'none'}"
    )
    official = (
        (gar_name, attack_name, num_workers, f, batch)
        == ("krum", "lie", 8, 2, 25)
        and not os.environ.get("GARFIELD_BENCH_F32_GAR")
        and platform == "tpu"  # a CPU run is f32 — not the record's config
    )
    if not official:
        vs = None
    result = {
        "metric": metric,
        "value": round(steps_per_sec_per_chip, 4),
        "unit": "steps/s/chip",
        "vs_baseline": round(vs, 4) if vs is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # Attribution: how many steps each dispatch scanned on device
        # (1 = the classic per-step program).
        "chunk_steps": chunk,
        # The device as JAX reports it — every line says where it ran.
        "platform": platform,
        "device_kind": device.device_kind,
        "n_devices": n_dev,
    }
    print(json.dumps(result))
    _emit_jsonl(result)


if __name__ == "__main__":
    main()
