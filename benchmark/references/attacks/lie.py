""""A little is enough" (Baruch et al. 2019) as Garfield's byzWorker.py runs
it: the f Byzantine workers (the highest ranks) all submit
``mu + z * sigma``, the coordinate-wise mean and Bessel-corrected standard
deviation of the colluding cohort's own honest gradients, z = 1.035."""

import jax.numpy as jnp

# A leaf's result needs that leaf's rows alone (`harness/reference.py`).
LEAFWISE = True

Z = 1.035


def byzantine(n, f):
    """The last f ranks are Byzantine."""
    return [i >= n - f for i in range(n)]


def apply(stack, byz):
    """``stack``: {path: (n, size) f32}. Rows written out, no remap."""
    rows = [i for i, b in enumerate(byz) if b]
    out = {}
    for path, g in stack.items():
        cohort = g[jnp.asarray(rows)]
        mu = jnp.mean(cohort, axis=0)
        sigma = jnp.sqrt(
            jnp.sum(jnp.square(cohort - mu), axis=0) / (len(rows) - 1))
        fake = mu + Z * sigma
        out[path] = jnp.stack(
            [fake if b else g[i] for i, b in enumerate(byz)])
    return out
