"""The comparison that decides `correct`: program against plain reference.

Numbers read, of which a cell compares those its ``limits/<cell>.json``
gives a limit: ``loss1..3`` — each of the first three steps' loss, relative
gap; ``grad1`` — the norm of the first aggregated gradient as the optimizer
got it, worst leaf; ``dparam3`` — the norm of the parameters' change after
the three steps, worst counted leaf; and, steadier from seed to seed, the
median leaf's gap (``grad1_median``, ``dparam3_median``) and the gap of all
leaves as one vector (``grad1_whole``, ``dparam3_whole``). A leaf's reading
is the gap between the program's norm and the reference's (not the norm of
their difference) over the reference's norm of that leaf or of the median
leaf, whichever is larger. Leaves whose reference gradient is under a
thousandth of the median leaf's are left out of ``dparam3`` (they move by
round-off alone). ``nonfinite`` counts the program's losses and norms that
are no finite number; its limit is 0 in every cell, and any of them makes
every number it enters NaN, which fails whatever the limit.
"""

import math
import statistics
import sys

DEAD_LEAF = 1e-3


def leaf_gaps(program, reference, counted=None):
    """``{path: gap}``: per leaf, the gap between the program's norm and the
    reference's over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    if set(program) != set(reference):
        raise ValueError("program and reference have different leaves")
    floor = statistics.median(reference.values())
    return {
        path: abs(program[path] - ref) / max(ref, floor)
        for path, ref in reference.items()
        if counted is None or path in counted
    }


def worst_leaf(gaps):
    """The largest of ``leaf_gaps`` and the leaf that has it (a NaN gap is
    the worst)."""
    where = max(gaps, key=lambda p: math.inf if gaps[p] != gaps[p] else gaps[p])
    return gaps[where], where


def whole_gap(program, reference, counted=None):
    """The gap of norms over all counted leaves taken as one vector."""
    paths = [p for p in reference if counted is None or p in counted]
    norm = lambda d: math.sqrt(sum(d[p] ** 2 for p in paths))
    return abs(norm(program) - norm(reference)) / norm(reference)


def _median(values):
    """The median, NaN where any value is (a sort would hide it)."""
    values = list(values)
    if any(v != v for v in values):
        return math.nan
    return statistics.median(values)


def nonfinite(program):
    """How many of the program's losses and norms are no finite number."""
    numbers = [*program["loss"], *program["grad1"].values(),
               *program["dparam"].values()]
    return sum(1 for v in numbers if not math.isfinite(v))


def readings(program, reference):
    """``{name: value}`` of every number that may be compared, and the
    leaves at fault.

    ``program`` and ``reference``: ``{"loss": [l1, l2, l3], "grad1": {path:
    norm}, "dparam": {path: norm}}``. For the gradient and the change: the
    worst leaf (``grad1``, ``dparam3``), the median leaf (``*_median``) and
    all leaves as one vector (``*_whole``)."""
    out, where = {"nonfinite": nonfinite(program)}, {}
    for i, (p, r) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out[f"loss{i}"] = abs(p - r) / abs(r)
    floor = DEAD_LEAF * statistics.median(reference["grad1"].values())
    live = {p for p, g in reference["grad1"].items() if g >= floor}
    for name, key, counted in (("grad1", "grad1", None),
                               ("dparam3", "dparam", live)):
        gaps = leaf_gaps(program[key], reference[key], counted)
        out[name], where[name] = worst_leaf(gaps)
        out[f"{name}_median"] = _median(gaps.values())
        out[f"{name}_whole"] = whole_gap(
            program[key], reference[key], counted)
    return out, where


def judge(values, limits):
    """``(correct, {name: {"value", "limit"}})``: every number that has a
    limit must be finite and within it; a limit with no number fails.
    ``nonfinite``, which `readings` always gives, is held to 0 whether the
    cell's file names it or not."""
    check, ok = {}, True
    if "nonfinite" in values:
        limits = {"nonfinite": 0, **limits}
    for name, limit in limits.items():
        value = values.get(name, math.nan)
        check[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, check


def report(check, where, stream=sys.stderr):
    for name, row in check.items():
        leaf = f" ({where[name]})" if where.get(name) else ""
        print(f"check {name}: {row['value']:.6g} limit {row['limit']:.6g}"
              f"{leaf}", file=stream)
