"""Multi-Krum (Blanchard et al. 2017) as Garfield's aggregators/krum.py runs
it: the score of row i is the sum of its n - f - 1 smallest Euclidean
distances to the other rows; the m = n - f - 2 rows of lowest score (ties to
the lower index) are averaged. Distances by differences, no Gram trick."""

import jax.numpy as jnp


def selection(stack, f):
    """Indices of the m selected rows, best first, and every row's score."""
    n = next(iter(stack.values())).shape[0]
    sq = jnp.zeros((n, n), jnp.float32)
    for g in stack.values():
        sq = sq + jnp.stack(
            [jnp.sum(jnp.square(g - g[i]), axis=1) for i in range(n)])
    dist = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, jnp.sqrt(sq))
    scores = jnp.sum(jnp.sort(dist, axis=1)[:, : n - f - 1], axis=1)
    return jnp.argsort(scores, stable=True)[: n - f - 2], scores


def aggregate(stack, f):
    sel, _ = selection(stack, f)
    return {path: jnp.mean(g[sel], axis=0) for path, g in stack.items()}
