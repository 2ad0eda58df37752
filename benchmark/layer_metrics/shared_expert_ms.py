"""Device time per step of the operations wholly in ``model.shared_expert``,
in ms: the SwiGLU every token passes beside the routed experts, in every
expert layer, forward, recomputed and backward (`harness.model_map`). None
where the program names no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("shared_expert",))
