"""Device time per step of the operations wholly in ``model.attention_gate``,
in ms: the per-head output gate of every attention layer — its projection
(hidden x heads), the sigmoid and the multiply with the core's output —,
forward, recomputed and backward (`harness.model_map`). None where the
program names no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("attention_gate",))
