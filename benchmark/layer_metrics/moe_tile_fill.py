"""The share of the grouped-matmul kernels' row tiles that hold a pair, in
%: Σ ``moe_pairs_held`` ÷ Σ ``moe_rows_visited`` × 100 over the expert layers
(`harness.route_map`: the program's counters, seed 0, after the warm-up's
steps). None where the program names no routing step, keeps no such counter,
or visits no row tile (``moe_rows_visited`` 0: the ``ragged_dot``
fallback)."""

from harness import route_map


def read(trace, facts):
    counters = route_map.counters(facts)
    if not counters or not counters.get("moe_rows_visited"):
        return None
    return 100.0 * counters["moe_pairs_held"] / counters["moe_rows_visited"]
