"""Aksel GAR: average of the gradients closest to the coordinate-wise median.

Counterpart of pytorch_impl/libs/aggregators/aksel.py (:24-64): compute the
coordinate-wise median, rank gradients by squared Euclidean distance to it,
and average the c closest, where c = (n+1)//2 in mode "mid" or c = n-f in
mode "n-f". Requires n >= 2f+1.
"""

import jax.numpy as jnp
import numpy as np

from . import register
from ._common import as_stack, coordinate_median, num_gradients


def _selection(g, f, mode):
    n = g.shape[0]
    med = coordinate_median(g)
    # f32 accumulation: under a bf16 pipeline an input-dtype sum over ~1e7
    # terms absorbs late addends and quantizes the ranking — the flat,
    # tree, and folded paths must make the SAME selections (the
    # pairwise_distances/tree_gram parity rule, _common.py).
    dist = jnp.sum(
        jnp.square((g - med[None, :]).astype(jnp.float32)), axis=1
    )
    return jnp.argsort(dist)[: _count(n, f, mode)], _count(n, f, mode)


def _weights(dist, n, c):
    """1/c one-hot weights over the c rows closest to the median — the
    single source of the selection, shared by every path."""
    sel = jnp.argsort(dist)[:c]
    return jnp.zeros((n,), jnp.float32).at[sel].set(1.0 / c)


def _count(n, f, mode):
    if mode == "mid":
        return (n + 1) // 2
    if mode == "n-f":
        return n - f
    raise NotImplementedError(f"unknown aksel mode {mode!r}")


def aggregate(gradients, f, mode="mid", **kwargs):
    """Average of the c gradients closest to the coordinate median."""
    g = as_stack(gradients)
    sel, _ = _selection(g, f, mode)
    return jnp.mean(g[sel], axis=0)


def tree_aggregate(stacked_tree, f, mode="mid", **kwargs):
    """Tree-mode aksel: per-leaf medians (Pallas kernels on TPU), the
    distances-to-median tree-reduce as sums of per-leaf squared norms, and
    the average is one per-leaf weighted row sum — no (n, d) flat stack."""
    import jax

    from ._common import tree_coordinatewise, tree_weighted_sum

    leaves = jax.tree.leaves(stacked_tree)
    n = leaves[0].shape[0]
    med = tree_coordinatewise(
        coordinate_median, stacked_tree, name="aksel"
    )
    dist = sum(
        jnp.sum(
            jnp.square(
                (l - m[None]).astype(jnp.float32).reshape(n, -1)
            ),
            axis=1,
        )
        for l, m in zip(leaves, jax.tree.leaves(med))
    )
    return tree_weighted_sum(
        stacked_tree, _weights(dist, n, _count(n, f, mode))
    )


def fold_flat_aggregate(ext_stack, row_map, row_scale, f=0, key=None,
                        mode="mid", **kwargs):
    """Folded-attack form (parallel/fold.py): median of the poisoned rows
    via the remapped-row Pallas kernel, distances via per-row scalars of
    the raw extended stack (direct cancellation-free ||row - med|| for
    unit-scale rows; the additive expansion for scaled rows), selection
    average as one scattered-weight matvec — the poisoned stack never
    materializes."""
    import numpy as np_

    from .. import ops

    rows = ext_stack.shape[0]
    rmap = np_.asarray(row_map)
    scales = np_.asarray(row_scale, np_.float32)
    n = rmap.size
    med = ops.coordinate_median(ext_stack, row_map=rmap, row_scale=scales)
    med32 = med.astype(jnp.float32)
    finite = jnp.isfinite(ext_stack)
    x_safe = jnp.where(finite, ext_stack, 0)
    # Subtract in the STACK dtype and upcast only for the square (ADVICE
    # r5 #3): the flat/tree paths compute (g - med) in the input dtype
    # before the f32 cast, so a f32 subtraction here would round the sort
    # keys differently under a bf16 pipeline and rank near-tied rows
    # differently — the same quantize-before-square rule as
    # ops._avgmed_kernel's ``quant_dtype``. Unit-scale rows (every row of
    # the lie/empire/crash folds) now match the where-path bitwise; the
    # additive expansion for exotic scales below stays f32 (its where-path
    # counterpart materializes scaled rows, which no dtype choice here can
    # reproduce exactly — it is selection-equivalent away from exact ties).
    dev = (x_safe - med.astype(ext_stack.dtype)[None, :]).astype(jnp.float32)
    nsq_direct = jnp.sum(dev * dev, axis=1)
    unit_mask = scales == 1.0
    if bool(unit_mask.all()):
        dist = nsq_direct[rmap]
    elif bool((scales[~unit_mask] == 0.0).all()):
        # Only zero scales besides units (the crash fold): the expansion
        # degenerates to ||med||^2 — skip the sq/dot stack passes.
        msq = jnp.sum(med32 * med32)
        dist = jnp.where(jnp.asarray(unit_mask), nsq_direct[rmap], msq)
    else:
        sq = jnp.sum(jnp.square(x_safe.astype(jnp.float32)), axis=1)
        dot = jnp.sum(x_safe.astype(jnp.float32) * med32[None, :], axis=1)
        msq = jnp.sum(med32 * med32)
        s = jnp.asarray(scales)
        dist = jnp.where(
            jnp.asarray(unit_mask),
            nsq_direct[rmap],
            jnp.maximum(s * s * sq[rmap] - 2.0 * s * dot[rmap] + msq, 0.0),
        )
    # The spec ranks by squared distance where non-finite rows sort by
    # their (non-finite) distance; mirror pairwise semantics: non-finite
    # logical rows rank last (+inf), and zero-scaled rows are exact zero
    # vectors whatever the raw row holds.
    row_bad = jnp.any(~finite, axis=1)[rmap] & jnp.asarray(scales != 0)
    dist = jnp.where(row_bad, jnp.inf, dist)
    w_log = _weights(dist, n, _count(n, f, mode))
    w_phys = (
        jnp.zeros((rows,), jnp.float32)
        .at[rmap]
        .add(w_log * jnp.asarray(scales))
    )
    # x_safe is already non-finite-sanitized, so no extra row mask is
    # needed (a per-row `used` built with .at[rmap].set would be
    # nondeterministic for the duplicate physical indices lie/empire
    # plans produce).
    return jnp.matmul(
        w_phys.astype(ext_stack.dtype), x_safe,
        preferred_element_type=jnp.float32,
    ).astype(ext_stack.dtype)


def check(gradients, f, mode="mid", **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 2 * f + 1:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 1) // 2}"
        )
    if mode not in ("mid", "n-f"):
        return f"invalid operation mode {mode!r}"
    return None


def influence(honests, attacks, f, mode="mid", **kwargs):
    """Ratio of Byzantine gradients among the c selected (aksel.py:76-98)."""
    stack = jnp.concatenate([as_stack(honests), as_stack(attacks)], axis=0)
    sel, c = _selection(stack, f, mode)
    sel = np.asarray(sel)
    return float(np.sum(sel >= len(honests))) / c


register("aksel", aggregate, check, influence=influence,
         tree_aggregate=tree_aggregate,
         fold_flat_aggregate=fold_flat_aggregate)
