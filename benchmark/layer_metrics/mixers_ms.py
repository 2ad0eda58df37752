"""Device time per step of the operations wholly in ``model.conv_mixer`` and
``model.attention``, in ms: the gated short convolutions and the grouped-query
attention (`harness.model_map`). None where the program names no such
scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("conv_mixer", "attention"))
