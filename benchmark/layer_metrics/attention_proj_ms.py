"""Device time per step of the operations wholly in ``model.attention_proj``,
in ms: the q, k, v and o projections, the per-head q/k RMSNorm and the rotary
embedding of every attention layer, forward, recomputed and backward
(`harness.model_map`). None where the program names no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("attention_proj",))
