"""Brute GAR: minimum-diameter subset selection (optimal, exponential).

Counterpart of pytorch_impl/libs/aggregators/brute.py (:32-68): enumerate all
C(n, n-f) subsets of size n-f, pick the one with the smallest diameter (max
pairwise Euclidean distance; any subset containing a non-finite pair is
dropped), and average it. Requires n >= 2f+1 (:104).

TPU design: the combination table is enumerated once at trace time (n, f are
static) into an index tensor, the distance matrix is one Gram matmul, and the
per-subset diameter is a batched gather + max — fully vectorized, no Python
loop at run time (the reference's native version enumerates on a CPU
threadpool, py_brute/brute.cpp + combinations.hpp).
"""

import functools
import itertools

import jax.numpy as jnp
import numpy as np

from . import register
from ._common import as_stack, num_gradients, pairwise_distances

# Enumeration guard: C(n, n-f) combinations are materialized as one index
# tensor; keep the same practical bound the reference applies to its brute
# sweeps.
MAX_COMBINATIONS = 2_000_000


@functools.lru_cache(maxsize=64)
def _combination_table(n, f):
    combos = np.array(
        list(itertools.combinations(range(n), n - f)), dtype=np.int32
    )
    return combos  # (C, n-f)


def _min_diameter_subset(dist, n, f):
    """(n-f,) indices of the minimum-diameter subset — the single source
    of the selection math (flat, tree, Gram-form, and influence paths all
    route here, so their trajectory equality cannot silently drift)."""
    combos = _combination_table(n, f)
    # (C, k, k) pairwise distances inside each candidate subset.
    sub = dist[combos[:, :, None], combos[:, None, :]]
    diam = jnp.max(sub, axis=(1, 2))  # inf iff subset holds a non-finite pair
    return jnp.asarray(combos)[jnp.argmin(diam)]


def _selection_weights_from_dist(dist, n, f):
    """1/(n-f) one-hot weights over the minimum-diameter subset."""
    sel = _min_diameter_subset(dist, n, f)
    return jnp.zeros((n,), jnp.float32).at[sel].set(1.0 / (n - f))


def selection_indices(gradients, f):
    """Index set (n-f,) of the minimum-diameter subset."""
    g = as_stack(gradients)
    return _min_diameter_subset(
        pairwise_distances(g, exclude_self=False), g.shape[0], f
    )


def aggregate(gradients, f, **kwargs):
    """Average of the minimum-diameter subset of size n-f.

    Masked matvec instead of ``mean(g[sel])`` — the same zero-guarded
    one-hot form as krum's (PERF.md: fuses, and 0 * inf stays 0)."""
    g = as_stack(gradients)
    n = g.shape[0]
    w = _selection_weights_from_dist(
        pairwise_distances(g, exclude_self=False), n, f
    ).astype(g.dtype)
    gz = jnp.where((w != 0)[:, None], g, 0)
    return w @ gz


def tree_aggregate(grads_tree, f, **kwargs):
    """Tree-mode brute: the min-diameter selection needs only pairwise
    distances, i.e. the summed per-leaf Gram (krum's trick — the
    reference's own selection is pure pairwise-distance, brute.py:32-68);
    the average is one per-leaf weighted row sum."""
    import jax

    from ._common import distances_from_gram, tree_gram, tree_weighted_sum

    leaves = jax.tree.leaves(grads_tree)
    n = leaves[0].shape[0]
    dist = distances_from_gram(tree_gram(grads_tree), exclude_self=False)
    return tree_weighted_sum(
        grads_tree, _selection_weights_from_dist(dist, n, f)
    )


def gram_select(gram, f, **kwargs):
    """Gram-form selection weights (parallel.fold): the folded-attack path
    remaps THIS matrix instead of writing poisoned rows."""
    from ._common import distances_from_gram

    n = gram.shape[0]
    return _selection_weights_from_dist(
        distances_from_gram(gram, exclude_self=False), n, f
    )


def check(gradients, f, **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 2 * f + 1:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 1) // 2}"
        )
    import math

    if math.comb(n, n - f) > MAX_COMBINATIONS:
        return (
            f"brute enumeration C({n}, {n - f}) = {math.comb(n, n - f)} exceeds "
            f"the practical bound {MAX_COMBINATIONS}"
        )
    return None


def upper_bound(n, f, d):
    """Variance/norm bound (n-f)/(2f) (brute.py:107-116)."""
    return (n - f) / (2 * f)


def influence(honests, attacks, f, **kwargs):
    """Ratio of Byzantine gradients in the selected subset (brute.py:119-139)."""
    stack = jnp.concatenate([as_stack(honests), as_stack(attacks)], axis=0)
    sel = np.asarray(selection_indices(stack, f))
    return float(np.sum(sel >= len(honests))) / (stack.shape[0] - f)


register("brute", aggregate, check, upper_bound=upper_bound,
         influence=influence, tree_aggregate=tree_aggregate,
         gram_select=gram_select)
