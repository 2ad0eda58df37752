"""Coordinate-wise lower median (torch's ``stack(g).median(dim=0)``): of an
even number of rows, the smaller of the two middle values."""

import jax.numpy as jnp

# A leaf's result needs that leaf's rows alone (`harness/reference.py`).
LEAFWISE = True


def aggregate(stack, f):
    out = {}
    for path, g in stack.items():
        out[path] = jnp.sort(g, axis=0)[(g.shape[0] - 1) // 2]
    return out
