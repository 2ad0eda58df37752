"""Plain average of all rows."""

import jax.numpy as jnp


def aggregate(stack, f):
    return {path: jnp.mean(g, axis=0) for path, g in stack.items()}
