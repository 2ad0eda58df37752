"""Device time per step of the operations wholly in ``model.full_attention``,
in ms: the attention cores of the full (causal) layers (the transposes to the
kernels' layout, the kernels, ``sum(do . o)``), forward, recomputed and
backward (`harness.model_map`). None where the program names no such
scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("full_attention",))
