"""Folded attack+GAR fast path: poison the Gram, never the rows.

The round-3 profiling conclusion (PERF.md "Known frontier") was that ANY
gradient attack costs ~4.5 ms/step on the north-star krum+lie config because
the whole-tree ``where`` rewrite forces the stacked gradient tree to
materialize and breaks the Gram/weighted-sum-into-backward fusion the
fault-free step enjoys. This module removes that structural tax for the
deterministic attacks by exploiting their row-level algebra
(``attacks.plan_gradient_attack_fold``):

  poisoned row i == row_scale[i] * extended_stack[row_map[i]]

where ``extended_stack`` is the raw stack plus at most one shared fake row
(lie's mu + z*sigma / empire's -eps*mu, byzWorker.py:108-143 — every
colluding Byzantine publishes the SAME vector). Consequently

  poisoned_gram = (scale outer scale) * raw_gram[row_map][:, row_map]

is a static remap of the raw ``(n+1, n+1)`` Gram — computed with ONE extra
row in the per-leaf Gram matmuls that fuse into the backward epilogue
exactly like the fault-free step — and the GAR's selection average is one
weighted row sum over the extended stack. Nothing attack-shaped ever touches
the (n, d)-sized data path.

Measured on the v5e chip (same-process paired-reps, ResNet-18/CIFAR-10, 8
workers, krum f=2 under lie, bf16 pipeline): 14.4-14.7 -> 12.4-12.6 ms/step
(1.16x), within 0.6 ms of the fault-free step — where four round-2/3
attempts that still wrote poisoned rows (elementwise where, row scatter,
contiguous DUS, flat-path algebraic folding) all measured within noise of
each other (PERF.md).

Applies when the topology's tree path is eligible, the attack is
deterministic (lie/empire/reverse/crash), and the rule exposes a
fold-capable interface: ``gram_select`` (krum, average: the only form
that gets the EXTENDED tree, fake row concatenated under every leaf —
its Gram and weighted sum want it), ``fold_aggregate`` (Bulyan),
``tree_aggregate_ext`` (the coordinate-wise median/tmean/condense: the
raw stacked tree and the fake row's tree APART — their Pallas kernels
read the stack where the gradient pass left it, take the fake row as a
second operand and apply the row remap/scale in-register,
ops/coordinate.py; the concatenated, upcast (n+1)-row copy this path
made until PR 29 cost 81 of lfm2n4's 598 ms a step, PERF.md section 6),
or ``fold_flat_aggregate`` (cclip —
the remap applies to per-row scalars of its iterations, r5). Randomized
attacks (random/drop) keep the ``where`` tree path. Zero-scale rows
(the crash attack) are sanitized everywhere a 0*inf could otherwise
produce NaN: the remapped Gram's zero-scale rows/cols are forced to
exact zeros (matching the where-path's literal zero row, whose inner
products are exactly 0 even when the raw gradient is non-finite), the
weighted sums already mask zero-weight rows (``tree_weighted_sum`` /
``apply_rows``'s ``used`` guard), and the coordinate-wise kernels
special-case zero scales in-register — so folded selection equals
where-path selection even with non-finite raw gradients (ADVICE r4;
asserted in tests/test_fold.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..aggregators._common import tree_gram, tree_weighted_sum
from ..attacks import plan_gradient_attack_fold, plan_model_attack_fold
from . import core

__all__ = [
    "plan_for",
    "plan_for_model",
    "folded_tree_aggregate",
    "folded_tree_aggregate_multi",
]


def plan_for(gar, attack, byz_mask, attack_params):
    """Single-sourced fold eligibility gate for the topology builders
    (aggregathor, byzsgd AND learn): a plan exists iff the rule has a
    fold-capable form (``gram_select``, ``fold_aggregate``, or the
    coordinate-wise ``tree_aggregate_ext``) and the attack folds
    (deterministic, with actual Byzantine slots, and GARFIELD_NO_FOLD
    unset). ``byz_mask`` may be any array-like; it must be concrete (the
    plan is static)."""
    if (gar.gram_select is None and gar.fold_aggregate is None
            and gar.tree_aggregate_ext is None
            and gar.fold_flat_aggregate is None):
        return None
    return plan_gradient_attack_fold(
        attack, np.asarray(byz_mask, dtype=bool), **attack_params
    )


def plan_for_model(gar, attack, byz_mask, attack_params):
    """Fold gate for MODEL-plane exchanges (LEARN gossip, ByzSGD gather).

    The deterministic model attacks (byzServer.py:93-98 reverse, the crash
    fault) are pure per-row scalings — no cohort statistics, no shared fake
    row — so their plan is an identity row map with scales and the same
    Gram-remap machinery applies. Randomized model attacks (random, drop)
    have no folded form and keep the where-path."""
    if (gar.gram_select is None and gar.fold_aggregate is None
            and gar.tree_aggregate_ext is None
            and gar.fold_flat_aggregate is None):
        return None
    return plan_model_attack_fold(
        attack, np.asarray(byz_mask, dtype=bool), **attack_params
    )


def _sanitize_gram(gram_p, row_scale):
    """Force zero-scale (crash) rows/cols of a remapped Gram to exact
    zeros. scale==0 means the poisoned row IS the zero vector, whose
    inner products are exactly 0 — but 0 * inf = NaN if the raw row the
    remap points at is non-finite, which the where-path cannot produce
    (its literal zero row dots finitely). Static no-op when no scale is
    zero, so lie/empire/reverse pay nothing."""
    zero = np.asarray(row_scale) == 0
    if not zero.any():
        return gram_p
    zmask = jnp.asarray(zero)
    return jnp.where(zmask[:, None] | zmask[None, :], 0.0, gram_p)


def _extended(build_extra, stacked_tree):
    """The stacked tree plus the plan's fake row, if it has one."""
    if build_extra is None:
        return stacked_tree
    with core.phase("attack"):
        extra = build_extra(stacked_tree)
        return jax.tree.map(
            lambda l, e: jnp.concatenate([l, e[None]], axis=0),
            stacked_tree, extra,
        )


def folded_tree_aggregate(gar, plan, stacked_tree, *, f, key=None,
                          gar_params=None, subset_sel=None,
                          row_weights=None, return_weights=False):
    """Aggregate a stacked gradient TREE under a folded attack plan.

    Args:
      gar: a registered GAR exposing ``gram_select`` or ``fold_aggregate``.
      plan: ``attacks.GradientAttackFold`` (static row_map/row_scale +
        optional shared fake-row builder).
      stacked_tree: raw per-worker gradients, leading n axis per leaf.
      f: declared tolerance (static).
      key: PRNG key forwarded to the rule (condense's mask; the Gram-form
        rules draw no randomness).
      gar_params: rule hyper-parameters (e.g. krum's ``m``).
      subset_sel: optional (q,) dynamic row indices — the wait-n-f subset
        (server.py:134-155) COMPOSED with the fold: supported for
        ``gram_select`` rules only, where subsetting is a (q, q) gather of
        the remapped Gram plus a weight scatter — no per-leaf row gathers,
        so the async emulation keeps the fast path (VERDICT r4 #5).
      row_weights: optional (n,) per-row scalars (may be traced) COMPOSED
        with the fold — the bounded-staleness discount
        (``utils.rounds.staleness_weights``, DESIGN.md §14). A weighted
        poisoned row is ``(w_i * row_scale_i) * ext[row_map[i]]``, i.e.
        exactly the fold's own row-scale algebra, so the weights multiply
        into the remapped Gram (outer product) and the selection weights
        without the rows ever materializing — ``plan_for`` still applies.
        Supported for ``gram_select`` rules only (the other fold forms
        consume row VALUES; topologies route weighted aggregation there
        through the flat path). Weights must be strictly positive (the
        hard cutoff excludes rows BEFORE the fold; a traced zero weight
        would defeat the static crash-row sanitization).

      return_weights: also return the rule's (n,) selection weights (the
        ``gram_select`` output, scattered to the n logical ranks on the
        subset path) — the feedback signal the adaptive-adversary and
        closed-loop-defense carries consume (DESIGN.md §16) without a
        second selection pass. Supported for ``gram_select`` rules only.

    Returns the aggregated gradient tree (no leading axis) — identical in
    exact arithmetic to ``gar.tree_aggregate(where-poisoned tree)``; with
    ``return_weights``, the tuple ``(tree, weights)``.

    Two layouts, each the measured winner for its rule family (PERF.md r4):

      - ``gram_select`` rules (krum, average) consume the stack only via
        Gram + one weighted row sum, both of which decompose per leaf — the
        extended stack stays a TREE and the per-leaf Grams fuse into the
        backward epilogue;
      - ``fold_aggregate`` rules (Bulyan) need a flat stack for the
        selection matmul and the fused phase-2 kernel anyway, and per-leaf
        Grams measured SLOWER here — so the stack is concatenated ONCE and
        the extension is assembled in BLOCK form (raw Gram, cross-dots c,
        |a|^2) without ever materializing an (n+1, d) array.
    """
    if subset_sel is not None and gar.gram_select is None:
        raise ValueError(
            "subset_sel composes with gram_select rules only (the "
            "coordinate-wise / iterative folds need row values, where a "
            "dynamic subset would force per-leaf gathers — topologies "
            "route those to the flat path instead)"
        )
    if row_weights is not None and gar.gram_select is None:
        raise ValueError(
            "row_weights (the staleness discount) composes with "
            "gram_select rules only — other fold forms consume row "
            "values; topologies route weighted aggregation there through "
            "the flat path"
        )
    if return_weights and gar.gram_select is None:
        raise ValueError(
            "return_weights needs a gram_select rule: only its selection "
            "is one (n,) weight vector (the other fold forms compose "
            "multi-row reductions) — the adaptive/defense carries route "
            "other rules through the where-path's tap recomputation"
        )
    params = dict(gar_params or {})
    # Carried center (stateful rules, cclip): arrives as a params-shaped
    # TREE from TrainState.gar_state; only the flat-iteration branch
    # consumes it (as the concatenated vector).
    center_tree = params.pop("center", None)
    # The attack's share comes first: the shared fake row — appended to the
    # tree for the Gram-form rules, whose Gram and weighted sum want the
    # extended tree, and handed on apart for every other form. All that
    # follows is the rule's.
    ext = extra = None
    if gar.gram_select is not None:
        ext = _extended(plan.build_extra, stacked_tree)
    elif plan.build_extra is not None:
        with core.phase("attack"):
            extra = plan.build_extra(stacked_tree)
    return _folded_rule(
        gar, plan, stacked_tree, ext, extra, center_tree, params,
        f=f, key=key, subset_sel=subset_sel, row_weights=row_weights,
        return_weights=return_weights,
    )


@core.phase("rule")
def _folded_rule(gar, plan, stacked_tree, ext, extra, center_tree, params,
                 *, f, key, subset_sel, row_weights, return_weights):
    """``folded_tree_aggregate`` after the attack's share: ``ext`` is the
    extended tree (Gram-form rules), ``extra`` the fake row's tree alone
    (every other form; None where the plan has no fake row)."""
    leaves, treedef = jax.tree.flatten(stacked_tree)
    n = leaves[0].shape[0]

    def sanitize_gram(gram_p):
        """See ``_sanitize_gram`` — closure over this plan's scales."""
        return _sanitize_gram(gram_p, plan.row_scale)

    if gar.tree_aggregate_ext is not None and gar.gram_select is None:
        # Coordinate-wise rules (median, tmean, condense): per-leaf kernels
        # that read the stack where the gradient pass left it and the fake
        # row beside it, the remap applied in-register — no poisoned stack,
        # no extended stack, no cohort-moment passes outside the fake-row
        # build.
        return gar.tree_aggregate_ext(
            stacked_tree, extra, plan.row_map, plan.row_scale,
            f=f, key=key, **params
        )
    if ext is not None:
        rmap = plan.row_map
        scale = jnp.asarray(plan.row_scale)
        if row_weights is not None:
            # Staleness composition (DESIGN.md §14): per-row weights are
            # row scales, so they fold into the SAME algebra the attack
            # plan uses — the Gram remap below and the weighted sum both
            # see the composed scale and nothing row-shaped materializes.
            scale = scale * jnp.asarray(row_weights, scale.dtype)
        scale_outer = scale[:, None] * scale[None, :]
        gram = tree_gram(ext)  # (n+k, n+k), fuses into the backward like f=0
        gram_p = sanitize_gram(gram[rmap][:, rmap] * scale_outer)
        if subset_sel is not None:
            w_sub = gar.gram_select(
                gram_p[subset_sel][:, subset_sel], f=f, key=key, **params
            )
            w = jnp.zeros((n,), jnp.float32).at[subset_sel].set(w_sub)
        else:
            w = gar.gram_select(gram_p, f=f, key=key, **params)
        sel_w = w.astype(jnp.float32)  # raw selection, pre row-scale
        w = sel_w * scale
        w_ext = jnp.zeros((n + plan.num_extra,), jnp.float32).at[rmap].add(w)
        out = tree_weighted_sum(ext, w_ext)
        return (out, sel_w) if return_weights else out

    if gar.fold_flat_aggregate is not None:
        # Iterative row-value rules (cclip): the rule needs actual row
        # values every iteration, so the EXTENDED stack is materialized
        # once (concat-first, like Bulyan's layout) and the remap/scale is
        # applied to row-level scalars inside the rule — still no poisoned
        # stack, no per-iteration attack passes.
        from ..aggregators._common import concat_stack, unflatten_vec

        stack, shapes = concat_stack(leaves)
        if extra is not None:
            a_flat = jnp.concatenate(
                [l.reshape(-1) for l in jax.tree.leaves(extra)]
            )
            stack = jnp.concatenate(
                [stack, a_flat[None].astype(stack.dtype)], axis=0
            )
        center = None
        if center_tree is not None:
            center = jnp.concatenate(
                [l.reshape(-1) for l in jax.tree.leaves(center_tree)]
            )
        vec = gar.fold_flat_aggregate(
            stack, plan.row_map, plan.row_scale, f=f, key=key,
            center=center, **params,
        )
        return unflatten_vec(vec, treedef, shapes)

    # fold_aggregate rules: flat-block layout.
    from ..aggregators._common import concat_stack, unflatten_vec

    rmap = plan.row_map
    scale = jnp.asarray(plan.row_scale)
    scale_outer = scale[:, None] * scale[None, :]
    stack, shapes = concat_stack(leaves)
    acc = jnp.promote_types(stack.dtype, jnp.float32)
    gram = jnp.matmul(stack, stack.T, preferred_element_type=acc)
    a_flat = None
    if extra is not None:
        a_flat = jnp.concatenate(
            [l.reshape(-1) for l in jax.tree.leaves(extra)]
        )
        c = jnp.matmul(stack, a_flat, preferred_element_type=acc)  # <g_i, a>
        aa = jnp.dot(a_flat, a_flat, preferred_element_type=acc)
        gram = jnp.concatenate([
            jnp.concatenate([gram, c[:, None]], axis=1),
            jnp.concatenate([c[None, :], aa[None, None]], axis=1),
        ], axis=0)  # (n+1, n+1), no (n+1, d) array ever built
    gram_p = sanitize_gram(gram[rmap][:, rmap] * scale_outer)

    def apply_rows(W):
        """(r, n) selection weights -> (W @ poisoned_stack, unflatten)."""
        r = W.shape[0]
        W_s = W.astype(jnp.float32) * scale[None, :]
        W_ext = jnp.zeros((r, n + plan.num_extra), jnp.float32).at[
            :, rmap
        ].add(W_s)
        used = jnp.any(W_ext != 0, axis=0)
        selected = jnp.matmul(
            W_ext[:, :n].astype(stack.dtype),
            jnp.where(used[:n, None], stack, 0),
        )
        if a_flat is not None:
            a_safe = jnp.where(used[n], a_flat, 0)  # NaN fake x 0 weight
            selected = selected + jnp.outer(
                W_ext[:, n].astype(stack.dtype), a_safe
            )
        return selected, lambda vec: unflatten_vec(vec, treedef, shapes)

    return gar.fold_aggregate(gram_p, apply_rows, f=f, key=key, **params)


def folded_tree_aggregate_multi(gar, plan, stacked_tree, *, f, keys=None,
                                gar_params=None, subset_sels=None,
                                row_weights=None):
    """Per-OBSERVER folded aggregation: m wait-n-f views of ONE exchange.

    The decentralized topologies (LEARN phases 2/3/5, ByzSGD's model
    plane) have every local observer slot aggregate its OWN seeded
    q-subset of the same gathered stack. For ``gram_select`` rules that
    is m sub-Gram selections of a SINGLE extension + Gram build — the
    expensive (n, d)-shaped work (fake-row moments, per-leaf Gram
    matmuls) is paid once, and each observer adds only a (q, q) gather
    of the tiny Gram plus one weight row. The weighted sums batch into
    one (m, rows) matmul per leaf.

    Args:
      plan: ``GradientAttackFold`` for a deterministic attack, or None for
        the identity fold (no attack, or a randomized attack already
        applied to the tree via the where-path).
      keys: optional (m,) stacked PRNG keys, one per observer (the
        Gram-form rules draw no randomness, but the key rides through for
        signature parity with the flat path).
      subset_sels: (m, q) per-observer row indices, or None for full
        participation (every observer sees all n rows — m identical
        selections, still one Gram).
      row_weights: optional (n,) per-row scalars (may be traced) COMPOSED
        with the fold exactly as in ``folded_tree_aggregate`` — the
        bounded-staleness discount (``utils.rounds.staleness_weights``,
        DESIGN.md §15): a row's staleness is a property of its PUBLISHER,
        so one weight vector is shared by every observer, multiplying
        into the remapped Gram and the per-observer weight rows through
        the fold's own row-scale algebra.

    Returns the aggregated tree with a leading m axis. Rows non-finite in
    the raw stack are handled exactly as ``apply_rows``: a row selected
    by NO observer is masked out of the contraction; the Gram-form rules'
    +inf-distance guard keeps non-finite rows out of every selection, so
    this matches the per-observer where-path.
    """
    if gar.gram_select is None:
        raise ValueError(
            "folded_tree_aggregate_multi needs a gram_select rule (the "
            "per-observer sub-Gram composition; other fold forms need row "
            "values per observer — topologies route those to the flat path)"
        )
    leaves, treedef = jax.tree.flatten(stacked_tree)
    n = leaves[0].shape[0]
    params = dict(gar_params or {})
    params.pop("center", None)  # gram_select rules are stateless
    if plan is None:
        rmap = np.arange(n)
        scale_np = np.ones(n, np.float32)
        build_extra, num_extra = None, 0
    else:
        rmap, scale_np = plan.row_map, plan.row_scale
        build_extra, num_extra = plan.build_extra, plan.num_extra
    ext = _extended(build_extra, stacked_tree)
    with core.phase("rule"):
        scale = jnp.asarray(scale_np)
        if row_weights is not None:
            # Staleness composition (DESIGN.md §15): per-row weights are row
            # scales, so they multiply into the same algebra the attack plan
            # uses — the remapped Gram below and every observer's weight row
            # see the composed scale; nothing row-shaped materializes.
            scale = scale * jnp.asarray(row_weights, scale.dtype)
        gram = tree_gram(ext)  # (n+k, n+k), ONE build for all observers
        gram_p = _sanitize_gram(
            gram[rmap][:, rmap] * (scale[:, None] * scale[None, :]), scale_np
        )

        def select_one(sel, key):
            if sel is None:
                w = gar.gram_select(gram_p, f=f, key=key, **params)
            else:
                w_sub = gar.gram_select(
                    gram_p[sel][:, sel], f=f, key=key, **params
                )
                w = jnp.zeros((n,), jnp.float32).at[sel].set(w_sub)
            return w

        if subset_sels is None:
            if keys is None:
                W = select_one(None, None)[None]
            else:
                W = jax.vmap(lambda k: select_one(None, k))(keys)
        elif keys is None:
            W = jax.vmap(lambda s: select_one(s, None))(subset_sels)
        else:
            W = jax.vmap(select_one)(subset_sels, keys)
        m = W.shape[0]
        W = W.astype(jnp.float32) * scale[None, :]
        W_ext = jnp.zeros((m, n + num_extra), jnp.float32).at[:, rmap].add(W)
        used = jnp.any(W_ext != 0, axis=0)

        def one_leaf(leaf):
            rows = leaf.shape[0]
            flat = leaf.reshape(rows, -1)
            out = jnp.matmul(
                W_ext.astype(leaf.dtype), jnp.where(used[:, None], flat, 0)
            )
            return out.reshape((m,) + leaf.shape[1:])

        out_tree = jax.tree.map(one_leaf, ext)
        if subset_sels is None and keys is None:
            # Full participation, no per-observer keys: ONE selection —
            # return it without the leading axis (the caller broadcasts).
            return jax.tree.map(lambda l: l[0], out_tree)
        return out_tree
