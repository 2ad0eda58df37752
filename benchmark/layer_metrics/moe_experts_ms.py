"""Device time per step of the operations wholly in ``model.moe_experts``, in
ms: the expert layers' grouped matmuls and their gates, forward, recomputed
and backward (`harness.model_map`). None where the program names no such
scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("moe_experts",))
