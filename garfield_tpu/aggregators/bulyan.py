"""Bulyan (over Multi-Krum) GAR.

Counterpart of pytorch_impl/libs/aggregators/bulyan.py (:31-84): requires
n >= 4f+3 (:114). Two phases:

1. Selection: n-2f-2 rounds. In round i, each still-active node is scored by
   the sum of its m_i smallest distances to the other active nodes, with
   m_i = min(m, (n-f-2) - i) and m defaulting to n-f-2 (bulyan.py:49-56);
   the round emits the Multi-Krum average of the m_i best-scored active
   gradients (bulyan.py:68) and prunes the single best-scored node.
2. Coordinate-wise averaged median over the n-2f-2 emitted vectors: per
   coordinate, average the beta = (n-2f-2) - 2f values closest to the
   (lower) median (bulyan.py:77-84).

NOTE: the reference's incremental score update after pruning is buggy (it
reads an undefined ``distance[gid]`` and misindexes ``scores[gid]``,
bulyan.py:74-76 — only reached on score ties). This implementation
recomputes scores from the active set each round, which is the intended
semantics and side-steps the bug; equivalence with the reference holds
whenever the reference path is well-defined.

TPU design: one Gram-matmul distance matrix reused across rounds; the
sequential selection is a ``lax.fori_loop`` whose body is masked sort +
prefix-sum + dynamic index — no host sync, compiles to a single XLA while
loop (the reference needed its largest CUDA kernel here, py_bulyan/bulyan.cu).
"""

import math

import jax
import jax.numpy as jnp

from . import register
from ._common import (
    as_stack,
    concat_stack,
    distances_from_gram,
    num_gradients,
    pairwise_distances,
    unflatten_vec,
)
from ..ops import coordinate as _coord


def _selection_weight_matrix(dist, n, f, m, dtype, use_sortnet=None):
    """Phase-1 selection as a (rounds, n) weight matrix.

    The selection loop only needs the (n, n) distance matrix: each round
    scores the active nodes, records the Multi-Krum selection *weights*
    (1/m_i on the m_i best, 0 elsewhere), and prunes the best node. The
    selected averages are then weight matmuls after the loop — the loop
    never touches the d-sized data, so the whole phase costs a single MXU
    pass over the stack (flat) or one matmul per leaf (tree).

    Sortnet path (``use_sortnet=True``, n <= MAX_SORT_N): the round body's
    row sort and stable argsort both run on the odd-even network —
    bitwise-equal (same NaN-last total order, strict-< stable ties; the
    masked matrix carries only finite values and +inf, never NaN). Unlike
    krum, this is OPT-IN rather than env-default: the fori_loop re-sorts
    the masked n x n matrix every round, so the network's O(n^2) exchange
    rounds compound (slower than the XLA sort at every bucket size:
    XLA:CPU, round 19; not measured on the chip). GARFIELD_SORTNET_SELECT
    therefore does not reach this loop; pass ``use_sortnet=True`` to A/B
    it.
    """
    m_max = n - f - 2
    rounds = n - 2 * f - 2
    sortnet = use_sortnet is True and n <= _coord.MAX_SORT_N

    def round_body(i, carry):
        active, weights = carry
        m_i = jnp.minimum(m, m_max - i)
        pair_ok = active[:, None] & active[None, :]
        masked = jnp.where(pair_ok, dist, jnp.inf)
        sorted_rows = (
            _coord.sortnet_sort(masked, axis=1) if sortnet
            else jnp.sort(masked, axis=1)
        )
        csum = jnp.cumsum(sorted_rows, axis=1)
        scores = jax.lax.dynamic_index_in_dim(csum, m_i - 1, axis=1, keepdims=False)
        scores = jnp.where(active, scores, jnp.inf)
        # stable: ties break on lowest index
        order = (
            _coord.sortnet_argsort(scores, axis=0) if sortnet
            else jnp.argsort(scores)
        )
        w = jnp.zeros((n,), dtype).at[order].set(
            (jnp.arange(n) < m_i).astype(dtype) / m_i
        )
        weights = weights.at[i].set(w)
        active = active.at[order[0]].set(False)
        return active, weights

    active0 = jnp.ones((n,), dtype=bool)
    weights0 = jnp.zeros((rounds, n), dtype=dtype)
    _, weights = jax.lax.fori_loop(0, rounds, round_body, (active0, weights0))
    return weights


def aggregate(gradients, f, m=None, use_sortnet=None, **kwargs):
    """Bulyan over Multi-Krum."""
    g = as_stack(gradients)
    n, d = g.shape
    if m is None:
        m = n - f - 2
    rounds = n - 2 * f - 2
    dist = pairwise_distances(g)  # (n, n), diag/non-finite -> +inf
    weights = _selection_weight_matrix(dist, n, f, m, g.dtype, use_sortnet)
    # Rows never selected in any round must not poison the matmul with
    # NaN/Inf coordinates (0 * inf = nan); rows that are selected pass
    # through untouched (reference mean semantics).
    used = jnp.any(weights != 0, axis=0)
    selected = weights @ jnp.where(used[:, None], g, 0)  # (rounds, d)

    # Coordinate-wise averaged median (bulyan.py:77-84); fused Pallas kernel
    # on TPU (garfield_tpu/ops/coordinate.py); off the Pallas path the
    # gather-free threshold formulation (averaged_median_mean_xla), so
    # n > MAX_SORT_N degrades gracefully instead of hitting the
    # catastrophic sort+argsort+gather.
    from .. import ops

    beta = rounds - 2 * f
    return ops.averaged_median_mean(selected, beta)


def _select_and_phase2(stack, weights, treedef, shapes, beta):
    """Shared tail of the tree/folded paths: ONE selection matmul over the
    concatenated stack, ONE fused phase-2 kernel, slice back per leaf.

    Per-leaf (rounds, n) @ (n, size) matmuls were measured to eat the whole
    tree-path win at ResNet-18 scale (62 launches, each padded to the MXU
    tile) and per-leaf phase-2 kernels likewise; the single-concat form is
    the bucket-all layout that measured fastest (PERF.md round 4).
    """
    from .. import ops

    used = jnp.any(weights != 0, axis=0)
    selected = jnp.matmul(
        weights.astype(stack.dtype), jnp.where(used[:, None], stack, 0)
    )  # (rounds, d)
    return unflatten_vec(
        ops.averaged_median_mean(selected, beta), treedef, shapes
    )


def tree_aggregate(grads_tree, f, m=None, use_sortnet=None, **kwargs):
    """Tree-mode Bulyan: concat-first.

    Unlike Krum (whose Gram + weighted-sum both decompose per leaf and fuse
    into the backward), Bulyan's selection MATMUL and fused phase-2 kernel
    want one flat stack anyway — and per-leaf Grams measured SLOWER than a
    single flat Gram here (PERF.md round 4). So the tree twin's job is only
    to build that stack cheaply: ONE axis-1 concat of the reshaped stacked
    leaves (measured faster than the flat path's vmapped ravel_pytree) and
    a sliced unflatten of the result.
    """
    leaves, treedef = jax.tree.flatten(grads_tree)
    n = leaves[0].shape[0]
    if m is None:
        m = n - f - 2
    rounds = n - 2 * f - 2
    beta = rounds - 2 * f
    stack, shapes = concat_stack(leaves)
    dist = pairwise_distances(stack)
    weights = _selection_weight_matrix(dist, n, f, m, jnp.float32, use_sortnet)
    return _select_and_phase2(stack, weights, treedef, shapes, beta)


def fold_aggregate(gram_p, apply_rows, f, m=None, use_sortnet=None, **kwargs):
    """Folded-attack Bulyan (parallel.fold): phase 1 runs on the poisoned
    Gram (a static remap of the raw extended Gram — the rows are never
    rewritten); ``apply_rows`` materializes the per-round selected averages
    as one remapped weight matmul over the concatenated extended stack, and
    phase 2 is one fused kernel over the resulting (rounds, d)."""
    from .. import ops

    n = gram_p.shape[0]
    if m is None:
        m = n - f - 2
    rounds = n - 2 * f - 2
    beta = rounds - 2 * f
    dist = distances_from_gram(gram_p)
    weights = _selection_weight_matrix(dist, n, f, m, jnp.float32, use_sortnet)
    selected, unflatten = apply_rows(weights)  # (rounds, d)
    return unflatten(ops.averaged_median_mean(selected, beta))


def check(gradients, f, m=None, **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 4 * f + 3:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 3) // 4}"
        )
    if m is not None and (not isinstance(m, int) or m < 1 or m > n - f - 2):
        return (
            f"invalid number of selected gradients, got m = {m!r}, "
            f"expected 1 <= m <= {n - f - 2}"
        )
    return None


def upper_bound(n, f, d):
    """Same bound as (Multi-)Krum (bulyan.py:117-126)."""
    return 1 / math.sqrt(
        2 * (n - f + f * (n + f * (n - f - 2) - 2) / (n - 2 * f - 2))
    )


register("bulyan", aggregate, check, upper_bound=upper_bound,
         tree_aggregate=tree_aggregate, fold_aggregate=fold_aggregate)
