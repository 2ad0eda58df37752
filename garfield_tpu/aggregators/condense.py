"""Condense GAR: randomized coordinate mixing of median and first gradient.

Counterpart of pytorch_impl/libs/aggregators/condense.py (:36-42): sample a
Bernoulli(p) mask per coordinate; output = mask * median + (1-mask) * g[0].
Requires n >= 2f+2 (:56).

Randomness: jax is functionally pure, so the rule takes an explicit PRNG
``key`` — the topologies all derive one from their replicated per-step rng
and pass it in (the torch-global-RNG coupling of the reference has no
counterpart here). When ``key`` is omitted (host-side convenience, e.g.
calling ``gars["condense"](stack, f=1)`` at a REPL), a fixed key(0) is used:
deterministic and independent of call order — pass distinct keys to vary
the mask.
"""

import math

import jax
import jax.numpy as jnp

from . import register
from ._common import as_stack, coordinate_median, num_gradients


def aggregate(gradients, f, p=0.9, key=None, **kwargs):
    """Bernoulli(p)-masked mix of coordinate median and gradient 0."""
    g = as_stack(gradients)
    if key is None:
        key = jax.random.key(0)
    mask = jax.random.bernoulli(key, p, shape=(g.shape[1],)).astype(g.dtype)
    return coordinate_median(g) * mask + g[0] * (1.0 - mask)


def _leaf_spans(leaves):
    spans, off = [], 0
    for l in leaves:
        size = 1
        for s in l.shape[1:]:
            size *= s
        spans.append((off, off + size))
        off += size
    return spans, off


def _masked_mix(leaves, medians, rows0, p, key):
    """``mask * median + (1 - mask) * row 0`` per leaf: the Bernoulli mask
    is drawn once over the full flat dimension (the same (d,) draw the
    flat path makes) and SLICED per leaf in ravel order, so the same key
    gives the same trajectory on either path."""
    spans, d = _leaf_spans(leaves)
    if key is None:
        key = jax.random.key(0)
    mask = jax.random.bernoulli(key, p, shape=(d,))
    out = []
    for l, m, r0, (a, b) in zip(leaves, medians, rows0, spans):
        mk = mask[a:b].reshape(l.shape[1:]).astype(l.dtype)
        out.append(m.astype(l.dtype) * mk + r0 * (1.0 - mk))
    return out


def tree_aggregate(stacked_tree, f, p=0.9, key=None, **kwargs):
    """Tree-mode condense, EXACTLY equal to the flat path (see
    ``_masked_mix``); the median runs per leaf (Pallas kernels on TPU)."""
    from ._common import tree_coordinatewise

    leaves, treedef = jax.tree.flatten(stacked_tree)
    med = jax.tree.leaves(
        tree_coordinatewise(coordinate_median, stacked_tree, name="condense")
    )
    return jax.tree.unflatten(
        treedef, _masked_mix(leaves, med, [l[0] for l in leaves], p, key)
    )


def tree_aggregate_ext(stacked_tree, extra_tree, row_map, row_scale, f=0,
                       key=None, p=0.9, **kwargs):
    """Folded-attack twin (parallel/fold.py): per-leaf REMAPPED medians
    (the Pallas kernels apply row_map/row_scale in-register, the fake row
    a second operand) and the poisoned row 0 reconstructed from the remap
    — one static row index and scale — so the poisoned stack never
    materializes."""
    import numpy as np

    from .. import ops
    from ._common import tree_coordinatewise

    rmap = np.asarray(row_map)
    scales = np.asarray(row_scale, np.float32)
    leaves, treedef = jax.tree.flatten(stacked_tree)
    med = jax.tree.leaves(tree_coordinatewise(
        lambda g, e=None: ops.coordinate_median(
            g, extra=e, row_map=rmap, row_scale=scales
        ),
        stacked_tree, extra_tree, name="condense",
    ))
    i0, s0 = int(rmap[0]), float(scales[0])
    n = leaves[0].shape[0]
    if s0 == 0.0:  # crash: exact zeros, not 0*inf
        rows0 = [jnp.zeros_like(l[0]) for l in leaves]
    elif i0 == n:
        rows0 = [e.astype(l.dtype) for l, e in zip(
            leaves, treedef.flatten_up_to(extra_tree))]
    else:
        rows0 = [l[i0] for l in leaves]
    if s0 not in (0.0, 1.0):
        rows0 = [r * s0 for r in rows0]
    return jax.tree.unflatten(
        treedef, _masked_mix(leaves, med, rows0, p, key)
    )


def check(gradients, f, p=0.9, key=None, **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 2 * f + 2:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 2) // 2}"
        )
    if p <= 0 or p > 1:
        return f"expected positive selection probability, got {p}"
    return None


def upper_bound(n, f, d):
    """Same bound as the median, 1/sqrt(n-f) (condense.py:60-69)."""
    return 1 / math.sqrt(n - f)


register("condense", aggregate, check, upper_bound=upper_bound,
         tree_aggregate=tree_aggregate,
         tree_aggregate_ext=tree_aggregate_ext)
