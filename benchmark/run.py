"""One run of one cell: ``python3 benchmark/run.py --workload <name> --seed
<n> --seconds <s> --trace <0|1>``, from the root of a checkout.

Set-up (backend, state and batches from the seed, the compiled step, three
warm-up steps that the correctness check also reads), then the measured
window, then the plain reference and the comparison. The last line of
standard output is the result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402

from harness import reference  # noqa: E402

TRACE_SECONDS = 3.0
TRACE_MIN_STEPS = 6


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def device_facts(chips, check_device):
    """The device as JAX reports it; refuses anything but the cell's chips."""
    devices = jax.devices()
    platform = devices[0].platform
    if check_device and (platform != "tpu" or len(devices) != chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} {platform} device(s)")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak():
    """Peak bytes on the fullest device: the allocator's peak in use plus
    its peak reserved — on the TPU a running program's temporaries are
    reserved, not "in use" — as ``memory_stats()`` reports both (0 where the
    backend keeps no such statistic, as XLA:CPU)."""
    peaks = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


class SliceTracer:
    """Traces a steady slice in the middle of a window. `window.run` calls it
    before every dispatch: once half of the window has passed it starts the
    profiler, and stops it once ``TRACE_SECONDS`` and ``TRACE_MIN_STEPS``
    steps have gone by, or when the window closes."""

    def __init__(self, directory, start_after):
        self.directory = directory
        self.start_after = start_after
        self.state = "waiting"
        self._steps_before = 0
        self._started_at = None

    def __call__(self, elapsed, done):
        if self.state == "waiting" and elapsed >= self.start_after:
            shutil.rmtree(self.directory, ignore_errors=True)
            self._steps_before = len(done)
            self._started_at = elapsed
            self.state = "tracing"
            jax.profiler.start_trace(str(self.directory))
        elif self.state == "tracing" and (
                elapsed - self._started_at >= TRACE_SECONDS
                and len(done) - self._steps_before >= TRACE_MIN_STEPS):
            self.stop()

    def stop(self):
        if self.state == "waiting":
            raise RuntimeError("the window closed before the trace began")
        if self.state == "tracing":
            jax.profiler.stop_trace()
        self.state = "stopped"


def first_steps(sut):
    """The warm-up steps, through the window's own call and feed; returns the
    state they leave and what the check compares with the reference once the
    window has closed: each step's loss, the first aggregated gradient's
    norm per leaf, the parameters' change per leaf. The harness's copy of
    the start goes once they are read."""
    state, losses = sut.state, []
    for i in range(reference.STEPS):
        state, loss = sut.step(state, *sut.batches[i % len(sut.batches)])
        losses.append(loss)
        if i == 0:
            grad1 = sut.first_gradient_norms(state)
    dparam = sut.change_norms(state)
    losses, grad1, dparam = jax.device_get((losses, grad1, dparam))
    sut.forget_start()
    return state, {
        "loss": [float(l) for l in losses],
        "grad1": {p: float(v) for p, v in grad1.items()},
        "dparam": {p: float(v) for p, v in dparam.items()},
    }


def run_cell(args, *, check_device=True, system_hook=None, out=sys.stdout):
    """Everything after argument parsing; returns the result it printed.
    ``check_device=False`` and ``system_hook`` (which may break the timed
    path underneath) are for the harness's own tests."""
    from harness import correct, flops, reduce_trace, spec, system, window

    bench = spec.load()
    cell = spec.Cell(bench, args.workload)
    device = device_facts(cell.chips, check_device)
    cache_dir = system.enable_compile_cache()
    config, traffic = cell.config, cell.traffic

    t_system = time.perf_counter()
    sut = system.System(config, traffic, args.seed, cache_dir)
    if system_hook is not None:
        system_hook(sut)
    t_steps = time.perf_counter()
    state, program = first_steps(sut)
    phases = {"start_s": t_system - T_START, **sut.phases,
              "first_steps_s": time.perf_counter() - t_steps,
              "step_from": sut.step_from}

    tracer = SliceTracer(spec.ROOT / ".bench_trace" / cell.name,
                         args.seconds / 2) if args.trace else None
    setup_s = time.perf_counter() - T_START
    state, t_open, done, losses = window.run(
        sut.step, state, sut.batches, args.seconds,
        wait=jax.block_until_ready,
        annotate=jax.profiler.TraceAnnotation if tracer else None,
        before_dispatch=tracer)
    if tracer:
        tracer.stop()
    images = flops.images_per_step(config)
    numbers = window.summarize(t_open, done, images)
    numbers["setup_s"] = setup_s
    values = jax.device_get(losses)
    failed = sum(1 for v in values if not math.isfinite(float(v)))
    device["memory_peak_bytes"] = memory_peak()
    sut.free(state)

    t_ref = time.perf_counter()
    ref = reference.run(config, traffic, args.seed)
    reference_s = time.perf_counter() - t_ref
    read, where = correct.readings(program, ref)
    ok, check = correct.judge(read, cell.limits)

    result = {"correct": ok, "attempted": len(done), "failed": failed}
    if args.trace:
        facts = {
            "config": config, "traffic": traffic, "chips": cell.chips,
            "device": device, "images_per_step": images,
        }
        trace = reduce_trace.reduce(reduce_trace.find(tracer.directory))
        shutil.rmtree(tracer.directory, ignore_errors=True)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        metrics = {}
        for metric in cell.metrics("per_layer"):
            value = spec.layer_reader(metric["name"])(trace, facts)
            if value is not None:
                metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        result["breakdown"] = trace["breakdown"]
    else:
        metrics = {
            m["name"]: {"value": numbers[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }
    result["metrics"] = metrics
    result["device"] = device
    result["info"] = {
        "steps": numbers["steps"], "window_s": numbers["window_s"],
        "images_per_s": numbers["images_per_s"],
        "step_p95_ms": numbers["step_p95_ms"],
        "step_p50_ms": numbers["step_p50_ms"],
        "interval_p95_ms": numbers["interval_p95_ms"],
        "interval_max_ms": numbers["interval_max_ms"],
        "loss_first": program["loss"], "loss_last": float(values[-1]),
        "setup": phases, "reference_s": reference_s,
        "total_s": time.perf_counter() - T_START,
    }
    result["check"] = check
    correct.report(check, where)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    run_cell(parse(argv))


if __name__ == "__main__":
    main()
