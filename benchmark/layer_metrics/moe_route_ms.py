"""Device time per step of the operations wholly in ``model.moe_router``,
``model.moe_dispatch`` and ``model.moe_combine``, in ms: scores, selection
and weights, the sort of the (token, expert) pairs and the two permutations
around the expert matmuls (`harness.model_map`). None where the program names
no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(
        trace, facts, ("moe_router", "moe_dispatch", "moe_combine"))
