"""The quickest proof that the main path still starts on the chip.

    python chip_smoke.py        # from the repo root, no arguments, one process

Drives the system once through the entry points a user would call, at the
full width of C0 (ResNet-18 / CIFAR-10, d = 11,173,962, 8 workers x batch
25, f = 2 under the "little is enough" attack, bf16 model and bf16
aggregation pipeline, SGD lr 0.2 / momentum 0.9 / wd 5e-4, the seeded
synthetic surrogate for data, random weights from a seed):

  kernels         the three Pallas coordinate kernels at d = 11,173,962,
                  8 rows and the 7-row phase-2 shape of bulyan n=11 f=2,
                  f32 and bf16, through the public functions, equal to the
                  in-tree ``*_reference`` on device, Mosaic custom call in
                  the compiled text;
  trainer_krum    ``garfield_tpu.apps.aggregathor.main`` — the CLI: 12
                  steps of Multi-Krum with accuracy evaluations and WITHOUT
                  ``--bench``, so async dispatch, TrainState donation and
                  the eval side thread all run;
  trainer_median  ``parallel.aggregathor.make_trainer`` (the entry the
                  benchmark's harness uses) with median + lie, AOT compiled, a few
                  steps ended by ``block_until_ready``; the compiled step
                  must hold the Mosaic custom call — the rule inside the
                  step is the kernel, not its XLA fallback.

Both trainers are built on the default mesh, which must span every device
JAX reports; on more than one device the batch must be addressable on all
of them and the compiled step must contain an all-gather.

The first act is to fail (non-zero, one line, nothing compiled) unless
``jax.devices()[0].platform == "tpu"``; the script never sets
``jax_platforms``. A failed leg is a traceback and a non-zero exit — there
is no retry and no fallback. The last stdout line is the result and nothing
else: ``{"ok": true, "device": {"platform", "kind", "count"}}``, the device
as JAX reports it — whoever checks the run parses that line and accepts no
other key. The line before it, ``[chip_smoke] summary {...}``, carries the
per-leg facts, versions, cache directory and ``claim: null``; every
wall-clock in it is labelled information, not a metric. Tests drive ``run``
and ``report`` with a toy ``Size`` on the CPU (tests/test_entry_points.py);
the default invocation has no way around the device check.
"""

import contextlib
import dataclasses
import io
import json
import math
import sys

MOSAIC_CALL = "tpu_custom_call"
NUM_WORKERS, F = 8, 2
LR, MOMENTUM, WEIGHT_DECAY = 0.2, 0.9, 5e-4


@dataclasses.dataclass(frozen=True)
class Size:
    """What one smoke run covers. ``FULL`` is what ``python chip_smoke.py``
    runs; a test builds a toy one."""

    kernel_d: int  # columns of the kernel leg's (rows, d) stacks
    model: str
    dataset: str
    loss: str
    input_shape: tuple
    batch: int  # per worker
    num_iter: int  # trainer_krum steps
    acc_freq: int  # trainer_krum evaluates after steps 0, acc_freq, ...
    median_steps: int  # trainer_median steps
    # Off-chip only: Pallas kernels in interpret mode, and no Mosaic text
    # expected anywhere (XLA:CPU has none to show).
    interpret: bool = False


FULL = Size(
    kernel_d=11_173_962, model="resnet18", dataset="cifar10",
    loss="cross-entropy", input_shape=(32, 32, 3), batch=25,
    num_iter=12, acc_freq=6, median_steps=4,
)


def require_tpu():
    """The first act: the device JAX found, or exit non-zero with one line."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU; JAX found platform "
            f"{device.platform!r} ({device.device_kind}). Nothing was run."
        )
    return device


class CompileClock:
    """Seconds jax spent in compile-or-load-from-cache, and persistent-cache
    hits, while the ``with`` block ran (``jax.monitoring`` events) — the
    same reading for the CLI leg, whose compiles happen inside ``train``,
    and the AOT legs. Information for the cold/warm comparison, not a
    metric."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def facts(self):
        return {
            "compile_s": round(self.seconds, 2),
            "cache_hits": self.cache_hits,
        }


class _Tee(io.TextIOBase):
    """Write-through to ``stream`` that keeps a copy (the CLI leg counts
    the accuracy reports the eval thread printed)."""

    def __init__(self, stream):
        self.stream = stream
        self.copy = io.StringIO()

    def write(self, text):
        self.copy.write(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


def check(ok, message):
    """Raise unless ``ok`` — a plain ``assert`` would vanish under ``-O``."""
    if not ok:
        raise AssertionError(message)


def _assert_spans_all_devices(devices, what):
    import jax

    check(set(devices) == set(jax.devices()), (
        f"{what} spans {len(set(devices))} of {len(jax.devices())} devices"
    ))
    check(NUM_WORKERS % len(jax.devices()) == 0, (
        f"{NUM_WORKERS} workers do not fold onto {len(jax.devices())} devices"
    ))


def leg_kernels(size):
    """Pallas kernels == in-tree references, on device, at ``kernel_d``."""
    import jax
    import jax.numpy as jnp

    from garfield_tpu.ops import coordinate

    interp = size.interpret

    def cases(rows):
        beta = rows - 2 * F  # bulyan's phase-2 width for a rows-row stack
        return [
            ("coordinate_median",
             lambda g: coordinate.coordinate_median(g, interpret=interp),
             coordinate.coordinate_median_reference),
            (f"trimmed_mean(f={F})",
             lambda g: coordinate.trimmed_mean(g, F, interpret=interp),
             lambda g: coordinate.trimmed_mean_reference(g, F)),
            (f"averaged_median_mean(beta={beta})",
             lambda g: coordinate.averaged_median_mean(
                 g, beta, interpret=interp),
             lambda g: coordinate.averaged_median_mean_reference(g, beta)),
        ]

    done = 0
    with CompileClock() as clock:
        for rows in (NUM_WORKERS, 7):  # 7 = bulyan n=11 f=2 after phase 1
            for dtype in (jnp.float32, jnp.bfloat16):
                g = jax.random.normal(
                    jax.random.PRNGKey(rows), (rows, size.kernel_d), dtype
                )
                # Selection is exact; the means differ from the reference
                # by summation order (f32) or one rounding (bf16).
                tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
                for name, fn, ref in cases(rows):
                    compiled = jax.jit(fn).lower(g).compile()
                    if not interp:
                        check(MOSAIC_CALL in compiled.as_text(), (
                            f"{name}: no Mosaic custom call in the compiled "
                            "program — the XLA fallback ran, not the kernel"
                        ))
                    got = compiled(g).astype(jnp.float32)
                    want = jax.jit(ref)(g).astype(jnp.float32)
                    check(got.shape == (size.kernel_d,), got.shape)
                    diff = jnp.abs(got - want)
                    if name == "coordinate_median":
                        ok = jnp.all(diff == 0)
                    else:
                        ok = jnp.all(diff <= tol + tol * jnp.abs(want))
                    max_diff = float(jnp.max(diff))
                    check(bool(ok), (
                        f"{name} rows={rows} {jnp.dtype(dtype).name}: kernel "
                        f"!= reference, max |diff| {max_diff:.3e}"
                    ))
                    done += 1
                    print(f"[kernels] {name} rows={rows} "
                          f"{jnp.dtype(dtype).name} d={size.kernel_d}: ok, "
                          f"max |diff| {max_diff:.1e}", flush=True)
    return {"ok": True, **clock.facts(), "cases": done}


def leg_trainer_krum(size):
    """The CLI, as a user runs it: Multi-Krum under lie, evaluations on the
    side thread, no ``--bench``."""
    from garfield_tpu.apps import aggregathor

    # No --train_size: data.load_dataset ignores it for the image datasets,
    # so the run stages the full 50k-sample surrogate whatever it says.
    argv = [
        "--dataset", size.dataset, "--model", size.model,
        "--loss", size.loss, "--batch", str(size.batch),
        "--num_workers", str(NUM_WORKERS), "--fw", str(F),
        "--gar", "krum", "--attack", "lie",
        "--dtype", "bfloat16", "--gar_dtype", "bfloat16",
        "--optimizer", "sgd",
        "--opt_args", json.dumps({
            "lr": str(LR), "momentum": str(MOMENTUM),
            "weight_decay": str(WEIGHT_DECAY),
        }),
        "--num_iter", str(size.num_iter), "--acc_freq", str(size.acc_freq),
    ]
    tee = _Tee(sys.stdout)
    with CompileClock() as clock, contextlib.redirect_stdout(tee):
        # train() joins its eval threads and re-raises what they raised.
        state, summary = aggregathor.main(argv)
    check(int(state.step) == size.num_iter, int(state.step))
    loss, acc = summary["final_loss"], summary["final_accuracy"]
    check(math.isfinite(loss), loss)
    check(0.0 <= acc <= 1.0, acc)
    reports = [
        float(line.split("Accuracy:")[1].split()[0])
        for line in tee.copy.getvalue().splitlines() if "Accuracy:" in line
    ]
    want_reports = len(range(0, size.num_iter, size.acc_freq))
    check(len(reports) == want_reports, (reports, want_reports))
    check(all(0.0 <= a <= 1.0 for a in reports), reports)
    _assert_spans_all_devices(
        state.step.sharding.device_set, "trainer_krum's TrainState"
    )
    return {
        "ok": True, **clock.facts(), "steps": size.num_iter, "loss": loss,
        "accuracy": acc, "eval_reports": len(reports),
        "train_wall_s_info": round(summary["train_wall_s"], 2),
    }


def leg_trainer_median(size):
    """``make_trainer`` as the benchmark's harness calls it: median under
    lie, AOT."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from garfield_tpu import models
    from garfield_tpu.parallel import aggregathor
    from garfield_tpu.utils import selectors

    module = models.select_model(
        size.model, size.dataset, dtype=jnp.bfloat16
    )
    init_fn, step_fn, _ = aggregathor.make_trainer(
        module,
        selectors.select_loss(size.loss),
        selectors.select_optimizer(
            "sgd", lr=LR, momentum=MOMENTUM, weight_decay=WEIGHT_DECAY
        ),
        "median",
        num_workers=NUM_WORKERS, f=F, attack="lie", gar_dtype=jnp.bfloat16,
    )
    _assert_spans_all_devices(
        step_fn.mesh.devices.flat, "make_trainer's default mesh"
    )
    rng = np.random.default_rng(1234)
    x_np = rng.standard_normal(
        (NUM_WORKERS, size.batch) + tuple(size.input_shape)
    ).astype(np.float32)
    y_np = rng.integers(
        0, models.num_classes_dict[size.dataset], (NUM_WORKERS, size.batch)
    ).astype(np.int32)
    x = jax.device_put(x_np, step_fn.batch_sharding)
    y = jax.device_put(y_np, step_fn.batch_sharding)
    _assert_spans_all_devices(
        [s.device for s in x.addressable_shards], "the sharded batch"
    )
    state = init_fn(jax.random.PRNGKey(1234), x_np[0])
    with CompileClock() as clock:
        compiled = step_fn.lower(state, x, y).compile()
    text = compiled.as_text()
    if not size.interpret:
        check(MOSAIC_CALL in text, (
            "no Mosaic custom call in the compiled step — the median inside "
            "the step is the XLA fallback, not the Pallas kernel"
        ))
    if len(jax.devices()) > 1:
        check("all-gather" in text, "no all-gather in the compiled step")
    for _ in range(size.median_steps):
        state, metrics = compiled(state, x, y)
    jax.block_until_ready(metrics["loss"])
    loss = float(metrics["loss"])
    check(math.isfinite(loss), loss)
    check(int(state.step) == size.median_steps, int(state.step))
    return {
        "ok": True, **clock.facts(), "steps": size.median_steps, "loss": loss,
        "mosaic_in_step": MOSAIC_CALL in text,
        "all_gather_in_step": "all-gather" in text,
    }


def run(size):
    """All three legs, in order; returns ``{leg: facts}``. Raises on the
    first failure."""
    legs = {}
    for name, leg in (
        ("kernels", leg_kernels),
        ("trainer_krum", leg_trainer_krum),
        ("trainer_median", leg_trainer_median),
    ):
        print(f"[chip_smoke] leg {name} ...", flush=True)
        legs[name] = leg(size)
        print(f"[chip_smoke] leg {name}: {json.dumps(legs[name])}",
              flush=True)
    return legs


def report(device_facts, details, legs):
    """The two closing stdout lines: the summary, then the result line —
    exactly ``ok`` and ``device``, last."""
    ok = all(leg["ok"] for leg in legs.values())
    summary = {
        "ok": ok,
        "device": device_facts,
        **details,
        "legs": legs,
        "note": "compile_s, cache_hits and *_info fields are information, "
                "not metrics",
        "claim": None,
    }
    print(f"[chip_smoke] summary {json.dumps(summary)}", flush=True)
    print(json.dumps({"ok": ok, "device": device_facts}), flush=True)
    return ok


def main():
    device = require_tpu()

    import importlib.metadata

    import jax
    import jaxlib

    from garfield_tpu.utils import profiling

    profiling.peak_bf16(device)  # a device kind without a published peak: error
    device_facts = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    details = {
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "cache_dir": profiling.enable_compile_cache(),
    }
    print(f"[chip_smoke] {json.dumps({'device': device_facts, **details})}",
          flush=True)
    legs = run(FULL)
    if not report(device_facts, details, legs):
        sys.exit(1)


if __name__ == "__main__":
    main()
