"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read. ``jax.profiler.ProfileData`` only.

What a TPU trace holds (read by hand, PERF.md section 5): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Modules`` has one event per run of a
compiled program and whose line ``XLA Ops`` has one event per operation; the
host's threads are lines of ``/host:CPU``, where the harness's
``TraceAnnotation`` spans (``bench.dispatch``, ``bench.wait``) appear by
name. XLA:CPU has no device plane: its operations are events with an
``hlo_op`` stat on the host's threads, which this module then reads as one
device so that a rehearsal off the chip runs the same code.

The traced window runs from the start of the second run of the step program
in the trace to the end of its last but one: the first run began before the
trace did, and the last is cut where the trace stops (the harness stops it
just after a step completes, so the cut run is a stub of a millisecond or
two, which counted as a step would make every per-step number 1/n too
small). The step program is the module that took most device time.
"""

import collections
import pathlib
import re

from . import window as window_lib

HOST_SPANS = ("bench.dispatch", "bench.wait")
DEVICE_PREFIX = "/device:TPU:"
TOP = 10
# A Pallas (Mosaic) kernel is a custom call to this target; the event's name
# is the operation's whole HLO line.
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?.*?\s([\w\-]+)\(")


def op_kind(hlo):
    """The operation's kind (``fusion``, ``copy``, ...), ``mosaic`` for a
    Pallas kernel; the first word of a name that is no HLO line (XLA:CPU)."""
    if MOSAIC_TARGET in hlo:
        return "mosaic"
    match = _HLO.match(hlo)
    return match.group(3) if match else hlo.split(".")[0][:40]


def short_name(hlo):
    """``%fusion.12 fusion bf16[8,64]`` from an operation's whole HLO line:
    name, kind, first result shape."""
    match = _HLO.match(hlo)
    if not match:
        return hlo[:80]
    name, shape, _ = match.groups()
    return " ".join(part for part in (name, op_kind(hlo), shape) if part)


def is_mosaic(hlo):
    return MOSAIC_TARGET in hlo


def find(trace_dir):
    """The one ``*.xplane.pb`` under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}")
    return found[0]


def load(path):
    """``{"devices": {name: {"ops": [...], "modules": [...]}}, "host":
    [...]}``, every event ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    on_chip = any(p.name.startswith(DEVICE_PREFIX) for p in planes)
    devices, host = {}, []
    cpu_ops, cpu_modules = [], collections.defaultdict(list)
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                "ops": _events(lines.get("XLA Ops")),
                "modules": _events(lines.get("XLA Modules")),
            }
            continue
        for line in plane.lines:
            for event in line.events:
                span = (event.name, event.start_ns,
                        event.start_ns + event.duration_ns)
                if event.name in HOST_SPANS:
                    host.append(span)
                elif not on_chip and event.duration_ns > 0:
                    # XLA:CPU: operations are host events with an hlo_op stat.
                    stats = dict(event.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append(span)
                        cpu_modules[
                            (stats.get("hlo_module"), line.name)].append(span)
    if cpu_ops:
        devices["/host:CPU"] = {
            "ops": sorted(cpu_ops, key=lambda e: e[1]),
            "modules": _cpu_modules(cpu_modules),
        }
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _events(line):
    if line is None:
        return []
    return sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns)
         for e in line.events), key=lambda e: e[1])


def _cpu_modules(by_module_thread):
    """XLA:CPU names no module runs: one run is the ops of one module on one
    thread, from the first to the last."""
    runs = []
    for (module, _), ops in by_module_thread.items():
        runs.append((str(module), min(o[1] for o in ops),
                     max(o[2] for o in ops)))
    return sorted(runs, key=lambda e: e[1])


def union_seconds(spans, lo, hi):
    """Seconds covered by the union of ``(start_ns, end_ns)`` clipped to
    [lo, hi]."""
    total, edge = 0, lo
    for start, end in sorted(spans):
        start, end = max(start, edge), min(end, hi)
        if end > start:
            total += end - start
            edge = end
    return total / 1e9


def step_module(modules):
    """Name of the module that took most device time: the step program."""
    time_of = collections.Counter()
    for name, start, end in modules:
        time_of[name] += end - start
    return time_of.most_common(1)[0][0] if time_of else None


def _covering(host, lo, hi):
    """Name of the harness span covering most of [lo, hi], or ``untracked``."""
    best, best_ns = "untracked", 0
    for name, start, end in host:
        ns = min(end, hi) - max(start, lo)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce_device(dev, host):
    """One device's window, busy time, steps, gaps and kernel time."""
    name = step_module(dev["modules"])
    steps = [m for m in dev["modules"] if m[0] == name]
    if len(steps) < 4:
        raise ValueError(
            f"the trace holds {len(steps)} runs of the step program; "
            "four are the least to reduce")
    counted = steps[1:-1]
    lo, hi = counted[0][1], counted[-1][2]
    inside = [o for o in dev["ops"] if o[2] > lo and o[1] < hi]
    busy = union_seconds([(o[1], o[2]) for o in inside], lo, hi)
    gaps = [
        (_covering(host, a[2], b[1]), (b[1] - a[2]) / 1e9)
        for a, b in zip(counted, counted[1:])
    ]
    op_time = collections.Counter()
    for op, start, end in inside:
        op_time[op] += (min(end, hi) - max(start, lo)) / 1e9
    return {
        "step_module": name, "steps": len(counted),
        "window_s": (hi - lo) / 1e9, "busy_s": busy, "gaps": gaps,
        "op_seconds": dict(op_time),
    }


def reduce(path):
    """The reduced trace the per-layer readers take."""
    raw = load(path)
    if not raw["devices"]:
        raise ValueError(f"{path}: no device plane and no XLA:CPU operation")
    per_device = {
        name: reduce_device(dev, raw["host"])
        for name, dev in raw["devices"].items()
    }
    fullest = max(per_device.values(), key=lambda d: d["busy_s"])
    count = len(per_device)
    longest = sorted(fullest["gaps"], key=lambda g: -g[1])[:TOP]
    return {
        "devices": per_device,
        "fullest": fullest,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / count,
        "window_s": sum(d["window_s"] for d in per_device.values()) / count,
        "gap_p95_s": window_lib.percentile(
            [g[1] for g in fullest["gaps"]], 95),
        "breakdown": {
            "device_ops": top_operations(fullest["op_seconds"]),
            "idle_gaps": [[n, s] for n, s in longest],
        },
    }


def top_operations(op_seconds, kinds=4):
    """At most `TOP` rows of ``[name, seconds]``: the ``kinds`` kinds of
    operation that took most time, each summed over all its operations
    (``all fusion``), then the single operations that took most."""
    by_kind = collections.Counter()
    for hlo, seconds in op_seconds.items():
        by_kind["all " + op_kind(hlo)] += seconds
    rows = by_kind.most_common(kinds)
    single = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    rows += [(short_name(n), s) for n, s in single[:TOP - len(rows)]]
    return [[name, seconds] for name, seconds in rows]


def kernel_seconds_per_step(device, match):
    """Summed device seconds per step of the operations ``match(name)``
    accepts; None where the trace has none."""
    total = sum(s for n, s in device["op_seconds"].items() if match(n))
    return total / device["steps"] if total > 0 else None
