"""Device time per step of the operations wholly in ``model.attention_proj``,
``model.attention_gate``, ``model.window_attention`` or
``model.full_attention``, in ms: the whole of every gated attention layer —
projections, q/k RMSNorm and rotary embedding, the cores, the per-head gate
—, forward, recomputed and backward, operations that mix two of the four
included (`harness.model_map`). None where the program names no such
scope."""

from harness import model_map

SCOPES = ("attention_proj", "attention_gate", "window_attention",
          "full_attention")


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, SCOPES)
