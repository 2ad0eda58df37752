"""Device time per step of the operations wholly in ``phase.attack``, in ms:
row poisoning and the making of a folded attack's fake row; 0.0 where the
program's text names the phase and no traced operation carries it
(`harness.phase_map`). None where the program names no such phase."""

from harness import phase_map


def read(trace, facts):
    return phase_map.phase_ms(trace, facts, "attack")
