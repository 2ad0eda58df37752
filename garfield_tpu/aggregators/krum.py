"""(Multi-)Krum GAR.

Counterpart of pytorch_impl/libs/aggregators/krum.py: score of node i = sum
of its n-f-1 smallest Euclidean distances to the other nodes (:31-63), and
Multi-Krum averages the m best-scored gradients with default m = n-f-2
(:65-80). Selection requires n >= 2f+3 (:98-113).

TPU design: the O(n^2) distance matrix is one Gram matmul on the MXU
(replacing the reference's CUDA per-pair reduction kernels, py_krum/krum.cu);
score + selection are a row-sort and a stable argsort — all fused by XLA
inside the surrounding jit'd train step.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import register
from ._common import (
    as_stack,
    distances_from_gram,
    num_gradients,
    pairwise_distances,
    tree_gram,
    tree_weighted_sum,
)
from ..ops import coordinate as _coord


def _sortnet_select(use_sortnet=None):
    """Whether the fast selection path is on: explicit override wins, else
    the ``GARFIELD_SORTNET_SELECT`` knob (default ON). Read at TRACE time —
    callers that compare both paths must pass the
    override explicitly so each impl gets its own jit closure instead of
    poisoning a shared cache with an env read."""
    if use_sortnet is not None:
        return bool(use_sortnet)
    return os.environ.get("GARFIELD_SORTNET_SELECT", "1").lower() not in (
        "", "0", "false",
    )


def _scores_from_dist(dist, n, f, use_sortnet=None):
    """Krum score of row i = sum of its n-f-1 smallest distances to the
    other rows (krum.py:55-63). The single source of the score formula —
    the flat path, the tree path, and selection_indices all go through it,
    so the trajectory-equality the tests assert cannot silently break.

    Fast path (GARFIELD_SORTNET_SELECT, default on): the full row sort is
    never materialized. n <= MAX_SORT_N runs the odd-even network's
    k-smallest-sum (``sortnet_row_sums`` — one batched network under the
    hierarchy's vmapped wave instead of per-bucket XLA variadic sorts);
    larger n reduces via negated ``lax.top_k`` (negation is exact; dist
    has no NaN — diag and non-finite entries are +inf). EVERY path sums
    its k ascending values as an explicit add chain: a chain's order is
    fixed (XLA never reassociates float adds) where an axis ``jnp.sum``
    may regroup per fusion context, so the on/off paths see identical
    operands in identical order — same scores bitwise, the trajectory pin
    tests/test_gars.py asserts.
    """
    k = n - f - 1

    def _chain(cols):
        acc = cols[0]
        for i in range(1, k):
            acc = acc + cols[i]
        return acc

    if _sortnet_select(use_sortnet):
        if n <= _coord.MAX_SORT_N:
            return _coord.sortnet_row_sums(dist, k, axis=1)
        neg, _ = jax.lax.top_k(-dist, k)  # k smallest, ascending after -
        return _chain([-neg[:, i] for i in range(k)])
    sorted_d = jnp.sort(dist, axis=1)
    return _chain([sorted_d[:, i] for i in range(k)])


def _selection_weights_from_dist(dist, n, f, m, use_sortnet=None):
    """One-hot/m weight vector over the m best-scored rows (stable ties) —
    the masked matvec form of ``mean(g[sel])`` (see ``aggregate``). On the
    fast path at n <= MAX_SORT_N the m best indices come from the
    index-carrying network (``sortnet_top_m``), which reproduces the
    stable-argsort prefix bitwise (strict-< network: ties keep ascending
    index order); above the bound the stable argsort stays — ``top_k``'s
    tie order is not contractually stable, and flat n > 32 selection is
    off the critical path (the hierarchy folds buckets of <= 32)."""
    scores = _scores_from_dist(dist, n, f, use_sortnet)
    if _sortnet_select(use_sortnet) and n <= _coord.MAX_SORT_N:
        sel = _coord.sortnet_top_m(scores, m, axis=0)
    else:
        sel = jnp.argsort(scores)[:m]
    return jnp.zeros((n,), jnp.float32).at[sel].set(1.0 / m)


def selection_indices(gradients, f, m=None, use_sortnet=None):
    """Indices of the m best-scored gradients, best first (stable ties)."""
    g = as_stack(gradients)
    n = g.shape[0]
    if m is None:
        m = n - f - 2
    dist = pairwise_distances(g)  # (n, n), diag/non-finite -> +inf
    scores = _scores_from_dist(dist, n, f, use_sortnet)
    if _sortnet_select(use_sortnet) and n <= _coord.MAX_SORT_N:
        return _coord.sortnet_top_m(scores, m, axis=0)
    return jnp.argsort(scores)[:m]


def aggregate(gradients, f, m=None, use_sortnet=None, **kwargs):
    """Multi-Krum: average of the m best-scored gradients.

    The average is computed as a one-hot weight matvec ``w @ g`` rather than
    ``mean(g[sel])``: the dynamic gather materializes an (m, d) copy before
    reducing, while the masked matvec lets XLA fuse the zero-guard into the
    dot's operand read — measured ~1.5x faster at n=8/16, d=11.2M on a real
    chip (PERF.md).
    """
    g = as_stack(gradients)
    n = g.shape[0]
    if m is None:
        m = n - f - 2
    w = _selection_weights_from_dist(
        pairwise_distances(g), n, f, m, use_sortnet
    ).astype(g.dtype)
    # Zero-weight rows must not poison the matvec with NaN/Inf coordinates
    # (0 * inf = nan); selected rows pass through untouched, preserving the
    # reference's mean(g[sel]) semantics exactly.
    gz = jnp.where((w != 0)[:, None], g, 0)
    return w @ gz


def tree_aggregate(grads_tree, f, m=None, use_sortnet=None, **kwargs):
    """Tree-mode Multi-Krum: no (n, d) flat stack.

    The pairwise distances need only the Gram matrix, which is the sum of
    per-leaf Grams (``_common.tree_gram``); the selection average is a
    per-leaf weighted row sum. Saves the flatten + unflatten round trip —
    ~5 ms/step at ResNet-18 scale on one chip (PERF.md).
    """
    leaves = jax.tree.leaves(grads_tree)
    n = leaves[0].shape[0]
    if m is None:
        m = n - f - 2
    dist = distances_from_gram(tree_gram(grads_tree))
    w = _selection_weights_from_dist(dist, n, f, m, use_sortnet)
    return tree_weighted_sum(grads_tree, w)


def gram_select(gram, f, m=None, use_sortnet=None, **kwargs):
    """Selection weights from a (possibly attack-remapped) Gram matrix —
    the Gram-form interface behind the folded attack path (parallel.fold):
    ``aggregate(stack) == gram_select(stack @ stack.T) @ stack``. Under the
    hierarchy's vmapped wave fold this is where the batched selection
    network lands: one network over the whole (W, s, s) wave instead of W
    per-bucket XLA sorts."""
    n = gram.shape[0]
    if m is None:
        m = n - f - 2
    return _selection_weights_from_dist(
        distances_from_gram(gram), n, f, m, use_sortnet
    )


def check(gradients, f, m=None, **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 2 * f + 3:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 3) // 2}"
        )
    if m is not None and (not isinstance(m, int) or m < 1 or m > n - f - 2):
        return (
            f"invalid number of selected gradients, got m = {m!r}, "
            f"expected 1 <= m <= {n - f - 2}"
        )
    return None


def upper_bound(n, f, d):
    """Variance/norm bound for (Multi-)Krum (krum.py:115-124)."""
    return 1 / math.sqrt(
        2 * (n - f + f * (n + f * (n - f - 2) - 2) / (n - 2 * f - 2))
    )


def influence(honests, attacks, f, m=None, **kwargs):
    """Ratio of Byzantine gradients among the m selected (krum.py:126-150)."""
    stack = jnp.concatenate([as_stack(honests), as_stack(attacks)], axis=0)
    n = stack.shape[0]
    if m is None:
        m = n - f - 2
    sel = np.asarray(selection_indices(stack, f, m))
    return float(np.sum(sel >= len(honests))) / m


register("krum", aggregate, check, upper_bound=upper_bound,
         influence=influence, tree_aggregate=tree_aggregate,
         gram_select=gram_select)
