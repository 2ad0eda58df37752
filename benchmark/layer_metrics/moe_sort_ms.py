"""Device time per step of the operations wholly in ``route.order`` or
``route.inverse``, in ms: the expert layers' two sorts of the (token, expert)
pairs by held slot, the order and its inverse (`harness.route_map`). None
where the program names no routing step."""

from harness import route_map


def read(trace, facts):
    return route_map.steps_ms(trace, facts, ("order", "inverse"))
