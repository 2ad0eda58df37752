"""Folded attack application (parallel/fold.py + attacks fold plans).

The folded path must be value-equivalent to the reference-semantics where-path
(poison rows, then aggregate): same attacks, same rules, same stacks — only
the algebra is restructured (Gram remap instead of row rewrite).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.aggregators import gars
from garfield_tpu.attacks import (
    apply_gradient_attack_tree,
    plan_gradient_attack_fold,
)
from garfield_tpu.parallel import core
from garfield_tpu.parallel.fold import (
    folded_tree_aggregate,
    folded_tree_aggregate_multi,
)

N, F = 8, 2


def _stacked_tree(key, n=N):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (n, 5, 3)),
        "b": jax.random.normal(k2, (n, 7)),
        "s": jax.random.normal(k3, (n, 1)),
    }


class TestFoldPlans:
    @pytest.mark.parametrize("attack", ["lie", "empire", "reverse", "crash"])
    def test_deterministic_attacks_fold(self, attack):
        plan = plan_gradient_attack_fold(attack, core.default_byz_mask(N, F))
        assert plan is not None
        assert plan.row_map.shape == (N,)
        assert plan.row_scale.shape == (N,)

    @pytest.mark.parametrize("attack", ["random", "drop", None, "none"])
    def test_unfoldable_attacks_return_none(self, attack):
        assert plan_gradient_attack_fold(
            attack, core.default_byz_mask(N, F)
        ) is None

    def test_no_byzantine_rows_returns_none(self):
        assert plan_gradient_attack_fold("lie", np.zeros(N, bool)) is None

    def test_env_escape_hatch(self, monkeypatch):
        monkeypatch.setenv("GARFIELD_NO_FOLD", "1")
        assert plan_gradient_attack_fold(
            "lie", core.default_byz_mask(N, F)
        ) is None


class TestFoldedAggregate:
    # bulyan (n >= 4f+3) runs at f=1 and exercises the fold_aggregate
    # branch (weight-MATRIX apply_rows); krum/average the gram_select
    # branch; median/tmean the coordinate-wise tree_aggregate_ext branch
    # (remapped-row kernels); cclip the fold_flat_aggregate branch
    # (extended-stack iterations, r5).
    @pytest.mark.parametrize("gar_name,f", [
        ("krum", F), ("average", F), ("bulyan", 1),
        ("median", F), ("tmean", F), ("cclip", F),
        # r5 completions: brute (gram_select), aksel (fold_flat),
        # condense (remapped-row kernels + reconstructed row 0).
        ("brute", F), ("aksel", F), ("condense", F),
    ])
    @pytest.mark.parametrize("attack", ["lie", "empire", "reverse", "crash"])
    def test_matches_where_path(self, gar_name, f, attack):
        gar = gars[gar_name]
        mask = core.default_byz_mask(N, f)
        tree = _stacked_tree(jax.random.PRNGKey(3))
        plan = plan_gradient_attack_fold(attack, mask)
        key = jax.random.PRNGKey(7)  # condense's mask; inert elsewhere
        got = folded_tree_aggregate(gar, plan, tree, f=f, key=key)
        poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
        want = gar.tree_aggregate(poisoned, f=f, key=key)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )

    @pytest.mark.parametrize("gar_name", ["krum", "average", "brute"])
    @pytest.mark.parametrize("attack", ["lie", "reverse"])
    def test_subset_composes_with_fold(self, gar_name, attack):
        """Wait-n-f subsets compose with the fold for Gram-form rules: the
        sub-Gram selection must equal poisoning + row subset + rule."""
        gar = gars[gar_name]
        mask = core.default_byz_mask(N, F)
        tree = _stacked_tree(jax.random.PRNGKey(19))
        q = N - 1
        sel = core.subset_indices(jax.random.PRNGKey(23), N, q)
        plan = plan_gradient_attack_fold(attack, mask)
        got = folded_tree_aggregate(
            gar, plan, tree, f=F, subset_sel=sel
        )
        poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
        sub = jax.tree.map(lambda l: l[sel], poisoned)
        want = gar.tree_aggregate(sub, f=F)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )

    def test_subset_rejected_for_non_gram_rules(self):
        plan = plan_gradient_attack_fold(
            "lie", core.default_byz_mask(N, F)
        )
        with pytest.raises(ValueError, match="gram_select"):
            folded_tree_aggregate(
                gars["median"], plan, _stacked_tree(jax.random.PRNGKey(2)),
                f=F, subset_sel=jnp.arange(N - 1),
            )

    def test_matches_where_path_nonstandard_mask(self):
        """Byzantine rows need not be the trailing slots."""
        mask = np.zeros(N, bool)
        mask[[1, 4]] = True
        tree = _stacked_tree(jax.random.PRNGKey(5))
        plan = plan_gradient_attack_fold("lie", mask)
        got = folded_tree_aggregate(gars["krum"], plan, tree, f=F)
        poisoned = apply_gradient_attack_tree("lie", tree, jnp.asarray(mask))
        want = gars["krum"].tree_aggregate(poisoned, f=F)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )

    def test_krum_m_param_reaches_gram_select(self):
        mask = core.default_byz_mask(N, F)
        tree = _stacked_tree(jax.random.PRNGKey(9))
        plan = plan_gradient_attack_fold("reverse", mask)
        got = folded_tree_aggregate(
            gars["krum"], plan, tree, f=F, gar_params={"m": 1}
        )
        poisoned = apply_gradient_attack_tree("reverse", tree, jnp.asarray(mask))
        want = gars["krum"].tree_aggregate(poisoned, f=F, m=1)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )

    def test_lie_single_byzantine_nan_cohort(self):
        """fw=1: Bessel std of a one-row cohort is NaN (torch semantics);
        both paths must agree — krum treats the NaN fake row as infinitely
        distant and never selects it."""
        mask = core.default_byz_mask(N, 1)
        tree = _stacked_tree(jax.random.PRNGKey(11))
        plan = plan_gradient_attack_fold("lie", mask)
        got = folded_tree_aggregate(gars["krum"], plan, tree, f=1)
        poisoned = apply_gradient_attack_tree("lie", tree, jnp.asarray(mask))
        want = gars["krum"].tree_aggregate(poisoned, f=1)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )
        for leaf in jax.tree.leaves(got):
            assert np.isfinite(np.asarray(leaf)).all()

    @pytest.mark.parametrize("carried_center", [False, True])
    def test_cclip_lie_single_byzantine_nan_cohort(self, carried_center):
        """fw=1 lie: the fake row is all-NaN (Bessel std of one sample).
        cclip's fold guards at ROW level (weight 0 == vote the current
        center), which coincides with the where-path's entry-level guard
        exactly when the whole row is non-finite — this case. The carried
        (nonzero) center variant covers the PRODUCTION configuration (v_0
        = previous aggregate): the NaN row's radius must enter the tau
        median as the where-path's 0, not ||v|| (review-caught tau shift,
        r5)."""
        mask = core.default_byz_mask(N, 1)
        tree = _stacked_tree(jax.random.PRNGKey(11))
        center = (
            jax.tree.map(
                lambda l: 3.0 + jnp.mean(l, axis=0), tree
            ) if carried_center else None
        )
        plan = plan_gradient_attack_fold("lie", mask)
        got = folded_tree_aggregate(
            gars["cclip"], plan, tree, f=1,
            gar_params={"center": center} if center is not None else None,
        )
        poisoned = apply_gradient_attack_tree("lie", tree, jnp.asarray(mask))
        want = gars["cclip"].tree_aggregate(poisoned, f=1, center=center)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )
        for leaf in jax.tree.leaves(got):
            assert np.isfinite(np.asarray(leaf)).all()

    @pytest.mark.parametrize("attack", ["lie", "empire", "reverse", "crash"])
    def test_cclip_fold_with_carried_center_matches_where_path(self, attack):
        """Every deterministic attack folds identically under a carried
        nonzero center (the aggregathor stateful-center configuration)."""
        mask = core.default_byz_mask(N, F)
        tree = _stacked_tree(jax.random.PRNGKey(17))
        center = jax.tree.map(lambda l: 1.5 * jnp.mean(l, axis=0), tree)
        plan = plan_gradient_attack_fold(attack, mask)
        got = folded_tree_aggregate(
            gars["cclip"], plan, tree, f=F, gar_params={"center": center}
        )
        poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
        want = gars["cclip"].tree_aggregate(poisoned, f=F, center=center)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            got, want,
        )

    def test_gram_select_consistency(self):
        """gram_select(stack @ stack.T) @ stack == aggregate(stack)."""
        g = jax.random.normal(jax.random.PRNGKey(2), (N, 33))
        gram = g @ g.T
        w = gars["krum"].gram_select(gram, f=F)
        np.testing.assert_allclose(
            np.asarray(w @ g), np.asarray(gars["krum"].unchecked(g, f=F)),
            rtol=1e-5, atol=1e-6,
        )


class TestFoldedAggregateMulti:
    """Per-observer sub-Gram composition (fold.folded_tree_aggregate_multi):
    ONE extension+Gram build, m wait-n-f selections — must equal each
    observer's own poison-subset-aggregate where-path."""

    @pytest.mark.parametrize("gar_name", ["krum", "average", "brute"])
    @pytest.mark.parametrize("attack", ["lie", "reverse", "crash", None])
    def test_matches_per_observer_where_path(self, gar_name, attack):
        gar = gars[gar_name]
        mask = core.default_byz_mask(N, F)
        tree = _stacked_tree(jax.random.PRNGKey(29))
        q, m = N - 1, 4
        sels = jnp.stack([
            core.subset_indices(jax.random.PRNGKey(100 + i), N, q)
            for i in range(m)
        ])
        keys = jax.random.split(jax.random.PRNGKey(31), m)
        plan = (
            plan_gradient_attack_fold(attack, mask)
            if attack is not None else None
        )
        poisoned = tree
        if attack is not None and plan is None:
            pytest.skip("attack folds; nothing to test via identity plan")
        if attack is not None:
            poisoned = apply_gradient_attack_tree(
                attack, tree, jnp.asarray(mask)
            )
        got = folded_tree_aggregate_multi(
            gar, plan, tree, f=F, keys=keys, subset_sels=sels
        )
        for i in range(m):
            sub = jax.tree.map(lambda l: l[sels[i]], poisoned)
            want = gar.tree_aggregate(sub, f=F, key=keys[i])
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a[i]), np.asarray(b), rtol=1e-5, atol=1e-6
                ),
                got, want,
            )

    def test_identity_plan_randomized_attack_composes(self):
        """Randomized attacks take the tree where-path FIRST, then the
        identity fold — the dispatch the decentralized topologies use."""
        gar = gars["krum"]
        mask = core.default_byz_mask(N, F)
        tree = _stacked_tree(jax.random.PRNGKey(37))
        poisoned = apply_gradient_attack_tree(
            "random", tree, jnp.asarray(mask), key=jax.random.PRNGKey(5)
        )
        q, m = N - 1, 3
        sels = jnp.stack([
            core.subset_indices(jax.random.PRNGKey(200 + i), N, q)
            for i in range(m)
        ])
        got = folded_tree_aggregate_multi(
            gar, None, poisoned, f=F, subset_sels=sels
        )
        for i in range(m):
            sub = jax.tree.map(lambda l: l[sels[i]], poisoned)
            want = gar.tree_aggregate(sub, f=F)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a[i]), np.asarray(b), rtol=1e-5, atol=1e-6
                ),
                got, want,
            )

    def test_non_gram_rule_rejected(self):
        with pytest.raises(ValueError, match="gram_select"):
            folded_tree_aggregate_multi(
                gars["median"], None, _stacked_tree(jax.random.PRNGKey(2)),
                f=F, subset_sels=jnp.stack([jnp.arange(N - 1)] * 2),
            )


class TestBf16FoldParity:
    """bf16 fold-parity rows (ADVICE r5 #3/#5): under the narrow pipeline
    the folded selection must match the where-path. aksel now quantizes its
    deviation to the stack dtype before squaring (same sort keys bitwise),
    so its aggregates agree to weighted-sum rounding; cclip's residual
    reduction-order drift is documented in its fold docstring, and this row
    pins the agreed tolerance."""

    def _bf16_tree(self, key):
        return jax.tree.map(
            lambda l: l.astype(jnp.bfloat16), _stacked_tree(key)
        )

    @pytest.mark.parametrize("attack", ["lie", "empire", "reverse", "crash"])
    def test_aksel_bf16_selection_parity(self, attack):
        gar = gars["aksel"]
        mask = core.default_byz_mask(N, F)
        tree = self._bf16_tree(jax.random.PRNGKey(41))
        plan = plan_gradient_attack_fold(attack, mask)
        got = folded_tree_aggregate(gar, plan, tree, f=F)
        poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
        want = gar.tree_aggregate(poisoned, f=F)
        # A selection mismatch swaps O(1)-magnitude rows in a c=4 average
        # (error ~0.25); bf16 weighted-sum rounding is ~1e-2. The tolerance
        # separates the two regimes cleanly.
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-2,
            ),
            got, want,
        )

    @pytest.mark.parametrize("attack", ["lie", "reverse"])
    @pytest.mark.parametrize("carried_center", [False, True])
    def test_cclip_bf16_documented_drift_bound(self, attack, carried_center):
        gar = gars["cclip"]
        mask = core.default_byz_mask(N, F)
        tree = self._bf16_tree(jax.random.PRNGKey(43))
        center = (
            jax.tree.map(
                lambda l: jnp.mean(l.astype(jnp.float32), axis=0), tree
            ) if carried_center else None
        )
        plan = plan_gradient_attack_fold(attack, mask)
        got = folded_tree_aggregate(
            gar, plan, tree, f=F,
            gar_params={"center": center} if center is not None else None,
        )
        poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
        want = gar.tree_aggregate(poisoned, f=F, center=center)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-2,
            ),
            got, want,
        )
        for leaf in jax.tree.leaves(got):
            assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("gar_name,f", [
    ("median", 1), ("tmean", 1),      # coordinate-wise kernels
    ("krum", 1), ("average", 1),      # gram_select (sanitized Gram)
    ("bulyan", 1),                    # fold_aggregate (sanitized Gram)
    ("cclip", 1),                     # fold_flat (row-level guard)
])
def test_crash_fold_nonfinite_row_stays_zero(gar_name, f):
    """A crashed slot whose raw gradient overflowed (inf) must behave as
    the where-path's literal ZERO row through every folded form: the
    coordinate-wise kernels special-case zero scales in-register, and the
    Gram-form rules sanitize the remapped Gram's zero-scale rows/cols
    (0 * inf would otherwise be NaN and read as infinitely distant,
    changing selection — ADVICE r4)."""
    gar = gars[gar_name]
    mask = core.default_byz_mask(N, 1)
    tree = _stacked_tree(jax.random.PRNGKey(13))
    tree = jax.tree.map(
        lambda l: l.at[N - 1].set(jnp.inf), tree
    )
    plan = plan_gradient_attack_fold("crash", mask)
    got = folded_tree_aggregate(gar, plan, tree, f=f)
    poisoned = apply_gradient_attack_tree("crash", tree, jnp.asarray(mask))
    want = gar.tree_aggregate(poisoned, f=f)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        got, want,
    )
    for leaf in jax.tree.leaves(got):
        assert np.isfinite(np.asarray(leaf)).all()


# --- the coordinate rules read the stack where it lies (PR 29) -------------


@pytest.fixture
def kernel_in_interpret_mode(monkeypatch):
    """Every dispatch of ``ops.coordinate`` runs the Pallas kernel, in
    interpret mode: the folded path below is the kernel's, not the XLA
    fallback this CPU backend would take."""
    from garfield_tpu.ops import coordinate

    dispatch = coordinate._dispatch

    def forced(g, extra, reduce, spec_fn, sel, n, tile, interpret, op):
        return dispatch(g, extra, reduce, spec_fn, sel, n, tile, True, op)

    monkeypatch.setattr(coordinate, "_dispatch", forced)


def _conv_tree(key, n=N, dtype=jnp.float32):
    """Leaves of every view: a convolution kernel (tap-major), a matrix
    with a last axis of 64, a stack of matrices, a vector, a scalar."""
    ks = jax.random.split(key, 5)
    shapes = {"conv": (3, 3, 8, 128), "dense": (24, 64), "experts": (2, 16, 128),
              "bias": (7,), "scale": (1,)}
    return {
        name: jax.random.normal(k, (n,) + shape).astype(dtype)
        for k, (name, shape) in zip(ks, shapes.items())
    }


@pytest.mark.parametrize("attack", ["lie", "empire", "reverse", "crash"])
@pytest.mark.parametrize("gar_name", ["median", "tmean", "condense"])
def test_folded_kernel_matches_where_path(
        gar_name, attack, kernel_in_interpret_mode):
    """Every ``tree_aggregate_ext`` rule: the folded result through the
    kernel (stack and fake row apart) equals poisoning the rows and
    aggregating them."""
    gar = gars[gar_name]
    mask = core.default_byz_mask(N, F)
    tree = _conv_tree(jax.random.PRNGKey(23))
    plan = plan_gradient_attack_fold(attack, mask)
    key = jax.random.PRNGKey(7)
    got = folded_tree_aggregate(gar, plan, tree, f=F, key=key)
    poisoned = apply_gradient_attack_tree(attack, tree, jnp.asarray(mask))
    want = gar.tree_aggregate(poisoned, f=F, key=key)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        got, want,
    )


def test_folded_median_lie_at_f1_takes_the_second_smallest_honest_row(
        kernel_in_interpret_mode):
    """lie at f = 1 (lfm2n4's traffic): the cohort's Bessel deviation is
    0 / 0, the fake row all NaN and last in the order, so the median of
    four is the second smallest of the three honest rows."""
    n = 4
    tree = _conv_tree(jax.random.PRNGKey(5), n=n, dtype=jnp.bfloat16)
    plan = plan_gradient_attack_fold("lie", core.default_byz_mask(n, 1))
    got = folded_tree_aggregate(gars["median"], plan, tree, f=1)
    want = jax.tree.map(lambda l: jnp.sort(l[:3], axis=0)[1], tree)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
        got, want,
    )


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, except inside a
    Pallas kernel's body, with the branch index path it lies on."""
    def walk(jaxpr, path):
        for eqn in jaxpr.eqns:
            yield path, eqn
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name == "cond":
                for i, branch in enumerate(eqn.params["branches"]):
                    yield from walk(branch.jaxpr, path + ((id(eqn), i),))
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, path)
    return list(walk(jaxpr, ()))


def test_folded_median_of_a_bf16_tree_copies_no_stack(monkeypatch):
    """On the kernel's branch the folded median holds no ``concatenate``
    along the worker axis and no ``convert_element_type`` of an (n, ·) or
    (n + 1, ·) operand: the stack goes to the kernel as it lies, the fake
    row beside it. (The XLA fallback's branch of ``platform_dependent`` may
    concatenate.)"""
    from garfield_tpu.ops import coordinate

    monkeypatch.setattr(
        coordinate, "use_pallas", lambda n=None, op=None: True)
    tree = _conv_tree(jax.random.PRNGKey(1), dtype=jnp.bfloat16)
    tree.pop("bias"), tree.pop("scale")  # vectors: flat, upcast outside
    plan = plan_gradient_attack_fold("lie", core.default_byz_mask(N, F))
    jaxpr = jax.make_jaxpr(
        lambda t: folded_tree_aggregate(gars["median"], plan, t, f=F)
    )(tree).jaxpr
    eqns = _eqns(jaxpr)
    kernels = [path for path, e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3 and all(kernels)  # one a leaf, each in a branch
    on_kernel_branch = [  # at the top, or down some kernel's own branch
        e for path, e in eqns
        if any(path == kpath[:len(path)] for kpath in kernels)
    ]
    assert on_kernel_branch
    for eqn in on_kernel_branch:
        rows = [v.aval.shape[0] for v in eqn.invars
                if getattr(v.aval, "ndim", 0) >= 2]
        if eqn.primitive.name == "concatenate":
            assert eqn.params["dimension"] != 0 or not any(
                r in (N, N + 1) for r in rows), eqn
        if eqn.primitive.name == "convert_element_type":
            assert not any(r in (N, N + 1) for r in rows), eqn


def test_the_coordinate_line_is_logged_once_per_trace(capsys):
    tree = _conv_tree(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    plan = plan_gradient_attack_fold("lie", core.default_byz_mask(N, F))
    step = jax.jit(
        lambda t: folded_tree_aggregate(gars["median"], plan, t, f=F))
    step(tree), step(tree)  # the second call traces nothing
    lines = [l for l in capsys.readouterr().err.splitlines()
             if "[coordinate] median:" in l]
    assert len(lines) == 1
    assert "in place 3 leaves / 0.01M values, flat 2 / 0.00M" in lines[0]
    assert "block (8, 8, 128) bfloat16, fake row apart" in lines[0]
