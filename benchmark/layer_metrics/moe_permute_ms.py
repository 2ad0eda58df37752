"""Device time per step of the operations wholly in ``route.gather_rows`` or
``route.return_rows``, in ms: the expert layers' row permutations into the
sorted order and back, forward and backward, with their masks and the
broadcast of a token's row to its choices (`harness.route_map`). None where
the program names no routing step."""

from harness import route_map


def read(trace, facts):
    return route_map.steps_ms(trace, facts, ("gather_rows", "return_rows"))
