"""Plain average of all rows."""

import jax.numpy as jnp

# A leaf's result needs that leaf's rows alone (`harness/reference.py`).
LEAFWISE = True


def aggregate(stack, f):
    return {path: jnp.mean(g, axis=0) for path, g in stack.items()}
