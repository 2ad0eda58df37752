"""Device time per step of the operations wholly in ``phase.rule``, in ms:
everything from the gathered tree to the aggregated tree, the coordinate
kernels with their upcast, pad and slice included (`harness.phase_map`). None
where the program names no such phase."""

from harness import phase_map


def read(trace, facts):
    return phase_map.phase_ms(trace, facts, "rule")
