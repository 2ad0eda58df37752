"""Share of the device's busy time that the per-phase times do not explain,
in %: the operations that hold instructions of several phases (``mixed``) and
those of no phase (``none``), over the busy seconds of the traced window. It
is also the alarm for a stale map: it reads near 100 when the instruction
names of the trace stop matching the program's text. None where there is no
map (`harness.phase_map`)."""

from harness import phase_map


def read(trace, facts):
    by_label = phase_map.step_seconds(trace, facts)
    device = trace["fullest"]
    if by_label is None or device["busy_s"] <= 0:
        return None
    unexplained = sum(
        seconds for label, seconds in by_label.items()
        if label == "none" or label.startswith("mixed:"))
    return 100.0 * unexplained * device["steps"] / device["busy_s"]
