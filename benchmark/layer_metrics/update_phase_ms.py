"""Device time per step of the operations wholly in ``phase.update``, in ms:
the optimizer's update, its application and the new state
(`harness.phase_map`). None where the program names no such phase."""

from harness import phase_map


def read(trace, facts):
    return phase_map.phase_ms(trace, facts, "update")
