"""Device time per step of the operations wholly in ``phase.grads``, in ms:
the per-slot forward and backward passes and the cast to the aggregation
pipeline's width (`harness.phase_map`). None where the program names no such
phase."""

from harness import phase_map


def read(trace, facts):
    return phase_map.phase_ms(trace, facts, "grads")
