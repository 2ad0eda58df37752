"""Device time per step of the operations wholly in ``model.attention_proj``,
``model.window_attention``, ``model.full_attention`` or
``model.cross_attention`` in ms: the whole of every differential attention
layer — projections, the cores with λ, the combine and the per-head norm —,
forward, recomputed and backward, operations that mix two of the four
included (`harness.model_map`). None where the program names no such
scope."""

from harness import model_map

SCOPES = ("attention_proj", "window_attention", "full_attention",
          "cross_attention")


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, SCOPES)
