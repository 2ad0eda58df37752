"""Device time per step of the operations wholly in ``model.gmu`` in ms: the
gated memory units, forward, recomputed and backward (`harness.model_map`).
None where the program names no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("gmu",))
