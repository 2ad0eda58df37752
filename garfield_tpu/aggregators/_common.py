"""Shared jit-friendly primitives for the GAR library.

These helpers encode the semantics that every reference rule builds on
(pytorch_impl/libs/aggregators/*.py):
  - pairwise Euclidean (non-squared) distances with non-finite values mapped
    to +inf (krum.py:44-48, bulyan.py, brute.py:33-36);
  - the *lower* coordinate-wise median — torch's ``median(dim=0)`` returns the
    lower of the two middle elements for even n, and sorts NaN last, which is
    what makes the reference's median "NaN-resilient" (median.py:39).

"Sum of the k smallest" selections (krum.py:55-63) appear rule-side as sorted
prefix sums; stable ``jnp.argsort`` reproduces the reference's stable
``list.sort`` tie-breaking.

All functions are pure and shape-polymorphic only in the static sense: n, d,
f must be Python ints at trace time (XLA static shapes).
"""

import jax
import jax.numpy as jnp


def as_stack(gradients):
    """Normalize input to a (n, d) stacked array.

    Accepts the reference-style list of 1-D vectors (krum.py aggregate takes
    ``gradients`` as a list) or an already-stacked 2-D array — the natural
    form after ``jax.lax.all_gather`` on the workers mesh axis.
    """
    if isinstance(gradients, (list, tuple)):
        return jnp.stack([jnp.asarray(g).reshape(-1) for g in gradients])
    g = jnp.asarray(gradients)
    if g.ndim != 2:
        raise ValueError(f"expected (n, d) gradient stack, got shape {g.shape}")
    return g


def num_gradients(gradients):
    """Static number of gradients n (leading dim / list length)."""
    if isinstance(gradients, (list, tuple)):
        return len(gradients)
    return int(gradients.shape[0])


def distances_from_gram(gram, *, exclude_self=True):
    """(n, n) Euclidean distances from a Gram matrix <g_i, g_j>.

    ||x-y||^2 = ||x||^2 + ||y||^2 - 2<x,y>; the squared norms are the Gram
    diagonal. Non-finite distances (a Byzantine gradient containing NaN/Inf
    poisons its whole row) become +inf, mirroring the reference's isfinite
    guard (krum.py:46-48). The diagonal is +inf when exclude_self (so
    "k smallest" never counts the self-distance), else 0.
    """
    # Per-pair SYMMETRIC distances, like the reference's (it computes each
    # unordered pair once and reads it for both directions): XLA's matmul
    # may accumulate gram[i, j] and gram[j, i] in different orders, and
    # the resulting 1-ulp asymmetry breaks STRUCTURAL score ties the
    # wrong way — e.g. Bulyan/Krum at m=1, where the two endpoints of the
    # globally-closest pair tie exactly and the stable lowest-index
    # tie-break must decide (caught by the paper-transcribed brute-force
    # oracle in tests/test_reference_parity.py).
    gram = 0.5 * (gram + gram.T)
    sq = jnp.diagonal(gram)
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    dist = jnp.where(jnp.isfinite(dist), dist, jnp.inf)
    n = gram.shape[0]
    diag = jnp.inf if exclude_self else 0.0
    return jnp.where(jnp.eye(n, dtype=bool), diag, dist)


def pairwise_distances(g, *, exclude_self=True):
    """(n, n) Euclidean distance matrix via the Gram trick.

    The inner product rides the MXU instead of materializing (n, n, d)
    differences (see ``distances_from_gram``). The Gram is ACCUMULATED in
    at-least-float32 like ``tree_gram`` — under bf16 gradients the flat and
    tree paths must make the SAME selections — via
    ``preferred_element_type``, so the (n, d) operands stay in their input
    dtype (no f32 copy of the stack; bf16 in / f32 out is the MXU's native
    mode).
    """
    acc = jnp.promote_types(g.dtype, jnp.float32)
    return distances_from_gram(
        jnp.matmul(g, g.T, preferred_element_type=acc),
        exclude_self=exclude_self,
    )


def tree_gram(grads_tree):
    """(n, n) Gram matrix of a stacked gradient tree, summed over leaves.

    <g_i, g_j> over the flat concatenation equals the sum of per-leaf inner
    products, so the Gram of the virtual (n, d) stack is computed without
    ever materializing it — each leaf contributes one (n, size) MXU matmul.
    Accumulated in at-least-float32 regardless of leaf dtype (matching
    ``pairwise_distances`` so flat and tree selections agree under bf16),
    with the leaf operands kept in their input dtype.
    """
    leaves = jax.tree.leaves(grads_tree)
    n = leaves[0].shape[0]
    acc_dtype = jnp.promote_types(leaves[0].dtype, jnp.float32)
    total = jnp.zeros((n, n), acc_dtype)
    for leaf in leaves:
        x = leaf.reshape(n, -1)
        total = total + jnp.matmul(
            x, x.T, preferred_element_type=acc_dtype
        )
    return total


def tree_weighted_sum(grads_tree, w):
    """Per-leaf weighted sum of rows: the tree analog of ``w @ stack``.

    Zero-weight rows are masked out before the contraction so a NaN/Inf in
    an unselected (Byzantine) row cannot poison the result (0 * inf = nan)
    — same guard as the flat selection-average (krum.py docstring).
    """
    keep = (w != 0)

    def one(leaf):
        wl = w.astype(leaf.dtype)
        mask = keep.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.tensordot(wl, jnp.where(mask, leaf, 0), axes=(0, 0))

    return jax.tree.map(one, grads_tree)


def tree_coordinatewise(fn, stacked_tree, extra_tree=None, *, name):
    """Apply a coordinate-wise ``(n,) + shape -> shape`` reducer per LEAF of
    a stacked gradient tree — the shared plumbing of the tree-mode twins
    (median, tmean, condense, cclip's center init): coordinate-wise rules
    decompose per leaf, so the (n, d) flat stack never materializes
    (PERF.md: 21.3 -> 16.2 ms/step for the median aggregathor step on the
    chip). Each leaf goes to ``fn`` in its own shape — ``ops.coordinate``
    picks the view that costs no copy — and, where ``extra_tree`` holds a
    folded attack's fake row, as ``fn(leaf, extra_leaf)``. ``name`` says
    which rule in the once-per-trace ``[coordinate]`` line."""
    from ..ops import coordinate

    leaves, treedef = jax.tree.flatten(stacked_tree)
    coordinate.log_views(name, leaves, extra_tree is not None)
    if extra_tree is None:
        return jax.tree.unflatten(treedef, [fn(l) for l in leaves])
    extras = treedef.flatten_up_to(extra_tree)
    return jax.tree.unflatten(
        treedef, [fn(l, e) for l, e in zip(leaves, extras)]
    )


def concat_stack(leaves):
    """(stack, shapes): ONE axis-1 concat of the reshaped stacked leaves.

    The concat-first layout for rules that want a flat (n, d) stack anyway
    (Bulyan's selection matmul + fused phase-2): measured cheaper than the
    flat path's vmapped ravel_pytree (PERF.md r4). ``shapes`` feeds
    ``unflatten_vec`` — single-sourced here so the tree and folded paths
    cannot drift."""
    n = leaves[0].shape[0]
    stack = jnp.concatenate([l.reshape(n, -1) for l in leaves], axis=1)
    return stack, [l.shape[1:] for l in leaves]


def unflatten_vec(vec, treedef, shapes):
    """Slice a flat (d,) vector back into a pytree with the given leaf
    ``shapes`` (leaf-order spans, the inverse of an axis-1 concat of
    reshaped leaves). Shared by tree-mode Bulyan and the folded path."""
    off, parts = 0, []
    for shape in shapes:
        sz = 1
        for s in shape:
            sz *= s
        parts.append(vec[off:off + sz].reshape(shape))
        off += sz
    return jax.tree.unflatten(treedef, parts)


def coordinate_median(g):
    """Lower coordinate-wise median of a (n, d) stack -> (d,).

    torch's ``stack(g).median(dim=0)[0]`` semantics (median.py:39): for even n
    the smaller middle element (index (n-1)//2 of the sorted column), and NaN
    values sort last so up to ceil(n/2)-1 NaN entries per coordinate do not
    contaminate the result. Dispatches to the Pallas TPU kernel
    (garfield_tpu.ops) on TPU; jnp sort elsewhere.
    """
    from .. import ops

    return ops.coordinate_median(g)


