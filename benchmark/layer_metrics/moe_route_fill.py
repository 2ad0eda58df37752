"""The share of what routing sorts and moves that an expert here uses, in %:
Σ ``moe_pairs_held`` ÷ Σ ``moe_rows_routed`` × 100 over the expert layers
(`harness.route_map`: the program's counters, seed 0, after the warm-up's
steps). None where the program names no routing step or keeps no such
counter."""

from harness import route_map


def read(trace, facts):
    counters = route_map.counters(facts)
    if not counters or not counters.get("moe_rows_routed"):
        return None
    return 100.0 * counters["moe_pairs_held"] / counters["moe_rows_routed"]
