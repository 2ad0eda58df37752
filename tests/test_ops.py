"""Pallas coordinate-kernel tests (interpret mode on the CPU test mesh).

The jnp reference implementations in garfield_tpu/ops/coordinate.py ARE the
spec (they reproduce the torch semantics of the reference's median.py:39 and
bulyan.py:77-84); the kernels must match them bit-for-bit, including NaN
placement and stable tie-breaking.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.ops import coordinate


def _rand(n, d, seed, nan_frac=0.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    if nan_frac:
        mask = rng.random((n, d)) < nan_frac
        # never a full-NaN column beyond what median tolerates
        mask[0] = False
        x = np.where(mask, np.nan, x)
    return x


@pytest.mark.parametrize("n", [1, 2, 3, 8, 9, 15])
@pytest.mark.parametrize("d", [1, 64, 130, 1024])
def test_median_matches_reference(n, d):
    x = _rand(n, d, seed=n * 1000 + d)
    got = coordinate.coordinate_median(x, interpret=True, tile=128)
    want = coordinate.coordinate_median_reference(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_median_nan_resilient():
    x = _rand(9, 257, seed=7, nan_frac=0.2)
    got = coordinate.coordinate_median(x, interpret=True, tile=128)
    want = coordinate.coordinate_median_reference(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_median_even_n_takes_lower():
    x = np.array([[1.0, 10.0], [2.0, 20.0], [3.0, 30.0], [4.0, 40.0]], np.float32)
    got = coordinate.coordinate_median(x, interpret=True, tile=128)
    np.testing.assert_array_equal(np.asarray(got), [2.0, 20.0])


@pytest.mark.parametrize("s,beta", [(3, 1), (5, 3), (8, 4), (9, 9), (11, 5)])
def test_averaged_median_mean_matches_reference(s, beta):
    x = _rand(s, 300, seed=s * 31 + beta)
    got = coordinate.averaged_median_mean(x, beta, interpret=True, tile=128)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), beta)
    # rtol floor 1e-5, atol 1e-7: interpret-mode accumulation order drifts
    # by a ulp or two across jax releases (observed 1e-8 abs on 0.4.37);
    # selection flips would show as whole-row ~1e-1 jumps, not last-ulp.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7
    )


def test_averaged_median_mean_stable_ties():
    # Rows 0 and 2 are equidistant from the median; stable argsort must pick
    # the lower row index. Any unstable sort averages a different pair.
    x = np.array([[0.0], [1.0], [2.0], [5.0]], np.float32)  # median = 1.0
    got = coordinate.averaged_median_mean(x, 2, interpret=True, tile=128)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), [0.5])  # rows 1 then 0


def test_averaged_median_mean_nan():
    x = _rand(7, 140, seed=3, nan_frac=0.15)
    got = coordinate.averaged_median_mean(x, 3, interpret=True, tile=128)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_beta_bounds():
    x = _rand(4, 8, seed=0)
    with pytest.raises(ValueError):
        coordinate.averaged_median_mean(x, 0, interpret=True)
    with pytest.raises(ValueError):
        coordinate.averaged_median_mean(x, 5, interpret=True)


def test_dispatch_falls_back_off_tpu():
    # On the CPU test backend use_pallas() is False: public wrappers must
    # route to the jnp reference and still be correct.
    assert not coordinate.use_pallas()
    x = _rand(6, 50, seed=11)
    np.testing.assert_array_equal(
        np.asarray(coordinate.coordinate_median(x)),
        np.asarray(coordinate.coordinate_median_reference(jnp.asarray(x))),
    )


def test_cpu_lowering_on_tpu_default_process(monkeypatch):
    """ADVICE r1 / VERDICT r2 #7 regression: a computation jitted for CPU
    devices in a process whose DEFAULT backend is TPU must take the XLA
    fallback, not fail lowering the Pallas kernel. The per-call choice is
    made by ``lax.platform_dependent`` at lowering time; simulate the
    TPU-default process by patching ``jax.default_backend`` so the
    ``use_pallas`` gate opens, then lower+run on this CPU backend."""
    monkeypatch.setattr(coordinate.jax, "default_backend", lambda: "tpu")
    assert coordinate.use_pallas()  # gate open: dispatch reaches the router
    x = _rand(6, 50, seed=13)
    got = jax.jit(coordinate.coordinate_median)(x)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(coordinate.coordinate_median_reference(jnp.asarray(x))),
    )
    got = jax.jit(lambda a: coordinate.averaged_median_mean(a, 3))(x)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(
            coordinate.averaged_median_mean_reference(jnp.asarray(x), 3)
        ),
        rtol=1e-6,
    )


def test_median_bf16():
    """bfloat16 stacks go through the same kernels (16-sublane tiling)."""
    x = _rand(9, 257, seed=21).astype(jnp.bfloat16)
    got = coordinate.coordinate_median(x, interpret=True, tile=128)
    want = coordinate.coordinate_median_reference(jnp.asarray(x))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_averaged_median_mean_bf16():
    x = _rand(7, 140, seed=22).astype(jnp.bfloat16)
    got = coordinate.averaged_median_mean(x, 3, interpret=True, tile=128)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), 3)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=1e-2,
    )


@pytest.mark.parametrize("n,f", [(3, 1), (7, 2), (9, 0), (11, 5)])
def test_trimmed_mean_matches_reference(n, f):
    x = _rand(n, 300, seed=n * 17 + f, nan_frac=0.05 if f else 0.0)
    got = coordinate.trimmed_mean(x, f, interpret=True, tile=128)
    want = coordinate.trimmed_mean_reference(jnp.asarray(x), f)
    # Same interpret-mode ulp allowance as the avgmed reference rows.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7
    )


def test_trimmed_mean_bounds():
    x = _rand(4, 8, seed=1)
    with pytest.raises(ValueError):
        coordinate.trimmed_mean(x, 2, interpret=True)  # n - 2f = 0


@pytest.mark.parametrize("s,beta", [(8, 4), (33, 13), (64, 31), (128, 17)])
def test_averaged_median_mean_xla_matches_reference(s, beta):
    """The gather-free production fallback == the argsort+gather spec,
    including at n > MAX_SORT_N where it is the only non-Pallas path."""
    x = _rand(s, 300, seed=s * 7 + beta, nan_frac=0.05)
    got = coordinate.averaged_median_mean_xla(jnp.asarray(x), beta)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), beta)
    # atol: the masked sum and the gathered mean accumulate in different
    # orders; near-zero coordinates differ by O(1e-8) in f32.
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_averaged_median_mean_xla_stable_ties():
    x = np.array([[0.0], [1.0], [2.0], [5.0]], np.float32)  # median = 1.0
    got = coordinate.averaged_median_mean_xla(jnp.asarray(x), 2)
    np.testing.assert_array_equal(np.asarray(got), [0.5])  # rows 1 then 0
    # Duplicated deviations across MANY rows: quota admits exactly the
    # lowest-index ties.
    x2 = np.array([[1.0], [1.0], [1.0], [1.0], [9.0]], np.float32)
    got2 = coordinate.averaged_median_mean_xla(jnp.asarray(x2), 3)
    want2 = coordinate.averaged_median_mean_reference(jnp.asarray(x2), 3)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


def test_averaged_median_mean_xla_nan_flood():
    """> s - beta NaN rows per coordinate: spec result is NaN; the
    threshold formulation must restore it, not silently emit 0."""
    x = np.full((5, 3), np.nan, np.float32)
    x[0] = 1.0  # one finite row, beta=3 must pull 2 NaN rows
    got = coordinate.averaged_median_mean_xla(jnp.asarray(x), 3)
    want = coordinate.averaged_median_mean_reference(jnp.asarray(x), 3)
    assert np.isnan(np.asarray(want)).all()
    assert np.isnan(np.asarray(got)).all()


def test_large_n_fallback_warns_only_on_tpu_backend(monkeypatch):
    """n > MAX_SORT_N: silent on CPU (Pallas was never an option), loud on
    a TPU backend (the 75x fused path is being given up)."""
    x = _rand(coordinate.MAX_SORT_N + 1, 16, seed=2)
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")  # CPU backend: must NOT warn
        coordinate.coordinate_median(x)
    monkeypatch.setattr(
        coordinate.jax, "default_backend", lambda: "tpu"
    )
    coordinate._warned_large_n.discard("coordinate_median")
    with pytest.warns(UserWarning, match="MAX_SORT_N"):
        assert coordinate.use_pallas(
            coordinate.MAX_SORT_N + 1, op="coordinate_median"
        ) is False
    # ... and only once per op per process.
    with _w.catch_warnings():
        _w.simplefilter("error")
        coordinate.use_pallas(
            coordinate.MAX_SORT_N + 1, op="coordinate_median"
        )


def test_large_n_warning_recommends_hierarchy(monkeypatch):
    """Satellite pin (ISSUE 6): the n > MAX_SORT_N warning must point the
    user at the hierarchical bucketed rules (the recommended fix), and the
    XLA fallback it announces must be GRACEFUL — same result as the jnp
    reference at a federated-ish n."""
    monkeypatch.setattr(coordinate.jax, "default_backend", lambda: "tpu")
    coordinate._warned_large_n.discard("trimmed_mean")
    with pytest.warns(UserWarning) as rec:
        assert coordinate.use_pallas(64, op="trimmed_mean") is False
    text = str(rec[0].message)
    assert "MAX_SORT_N=32" in text
    assert "hier-krum" in text and "hierarchy" in text
    # Graceful XLA-path result at n > MAX_SORT_N (the non-Pallas path is
    # the spec itself).
    monkeypatch.setattr(coordinate.jax, "default_backend", lambda: "cpu")
    x = _rand(64, 200, seed=3)
    np.testing.assert_array_equal(
        np.asarray(coordinate.coordinate_median(x)),
        np.asarray(coordinate.coordinate_median_reference(x)),
    )


class TestSortNet:
    """The jnp odd-even-network entry points (the hierarchical bucket
    fold's coordinate fast path): bitwise-equal semantics to the reference
    sorts, batch axes, NaN resilience, and the MAX_SORT_N bound."""

    def test_median_matches_reference_bitwise(self):
        x = _rand(17, 300, seed=21)
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_median(x, axis=0)),
            np.asarray(coordinate.coordinate_median_reference(x)),
        )

    def test_median_batched_matches_per_bucket(self):
        xb = np.stack([_rand(8, 64, seed=s) for s in range(5)])
        got = np.asarray(coordinate.sortnet_median(xb, axis=1))
        want = np.stack([
            np.asarray(coordinate.coordinate_median_reference(xb[i]))
            for i in range(5)
        ])
        np.testing.assert_array_equal(got, want)

    def test_median_nan_resilient(self):
        x = _rand(9, 40, seed=22)
        x[:3, :] = np.nan  # up to ceil(n/2)-1 NaNs sort last
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_median(x, axis=0)),
            np.asarray(coordinate.coordinate_median_reference(x)),
        )

    def test_tmean_matches_reference(self):
        x = _rand(16, 128, seed=23)
        np.testing.assert_allclose(
            np.asarray(coordinate.sortnet_trimmed_mean(x, 3, axis=0)),
            np.asarray(coordinate.trimmed_mean_reference(x, 3)),
            rtol=1e-6, atol=1e-6,
        )

    def test_bounded_by_max_sort_n(self):
        with pytest.raises(ValueError, match="MAX_SORT_N"):
            coordinate.sortnet_median(
                np.zeros((coordinate.MAX_SORT_N + 1, 4), np.float32), axis=0)


@pytest.mark.parametrize("op", ["median", "tmean"])
def test_remap_kernel_matches_materialized(op):
    """row_map/row_scale (the folded-attack remap, parallel/fold.py) applied
    in-register must equal materializing the remapped stack first —
    including a duplicated fake row (lie) and a scaled row (reverse)."""
    ext = _rand(9, 300, seed=11)  # 8 raw rows + 1 fake row
    row_map = np.array([0, 1, 2, 3, 4, 5, 8, 8])  # byz rows 6,7 -> fake
    row_scale = np.array([1.0, 1.0, 1.0, 1.0, 1.0, -100.0, 1.0, 1.0])
    eff = ext[row_map] * row_scale[:, None].astype(np.float32)
    if op == "median":
        got = coordinate.coordinate_median(
            ext, row_map=row_map, row_scale=row_scale,
            interpret=True, tile=128,
        )
        want = coordinate.coordinate_median_reference(jnp.asarray(eff))
    else:
        got = coordinate.trimmed_mean(
            ext, 2, row_map=row_map, row_scale=row_scale,
            interpret=True, tile=128,
        )
        want = coordinate.trimmed_mean_reference(jnp.asarray(eff), 2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_remap_validates_bounds():
    x = _rand(4, 16, seed=3)
    with pytest.raises(ValueError):
        coordinate.coordinate_median(x, row_map=[0, 1, 2, 9])
    with pytest.raises(ValueError):
        coordinate.coordinate_median(
            x, row_map=[0, 1], row_scale=[1.0, 1.0, 1.0]
        )


class TestSortNetSelection:
    """The index-carrying network entry points (PR 19's selection
    kernels): bitwise-equal to ``jnp.argsort(..., stable=True)`` —
    stable ties, NaN-last — under vmap and bf16 upcast, plus the krum
    score's chained prefix sum and the MAX_SORT_N bound. These are the
    substitutability pins that let GARFIELD_SORTNET_SELECT default on
    without moving any Gram-path trajectory."""

    def _keys(self, w, n, seed, ties=False, nans=0):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((w, n)).astype(np.float32)
        if ties:
            # Quantize hard so duplicate keys are guaranteed: stability
            # is only observable on ties.
            x = np.round(x * 2.0) / 2.0
        if nans:
            for r in range(w):
                x[r, rng.choice(n, size=nans, replace=False)] = np.nan
        return x

    @pytest.mark.parametrize("n", [1, 2, 3, 8, 17, 32])
    @pytest.mark.parametrize("ties,nans", [(False, 0), (True, 0),
                                           (False, 2), (True, 2)])
    def test_argsort_matches_stable_argsort(self, n, ties, nans):
        if nans >= n:
            pytest.skip("need at least one finite key")
        x = self._keys(6, n, seed=n * 7 + nans, ties=ties, nans=nans)
        got = np.asarray(coordinate.sortnet_argsort(x, axis=-1))
        want = np.asarray(jnp.argsort(x, axis=-1, stable=True))
        np.testing.assert_array_equal(got, want)

    def test_argmin_and_top_m_are_argsort_prefixes(self):
        x = self._keys(5, 16, seed=3, ties=True, nans=1)
        ref = np.asarray(jnp.argsort(x, axis=-1, stable=True))
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_argmin(x, axis=-1)), ref[:, 0])
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_top_m(x, 5, axis=-1)),
            ref[:, :5])

    def test_sort_matches_jnp_sort_bitwise(self):
        x = self._keys(4, 23, seed=9, ties=True, nans=3)
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_sort(x, axis=-1)),
            np.asarray(jnp.sort(x, axis=-1)))

    def test_vmap_matches_loop(self):
        xb = self._keys(7, 12, seed=5, ties=True)
        got = np.asarray(jax.vmap(
            lambda r: coordinate.sortnet_top_m(r, 4, axis=-1))(xb))
        want = np.stack([
            np.asarray(coordinate.sortnet_top_m(xb[i], 4, axis=-1))
            for i in range(7)
        ])
        np.testing.assert_array_equal(got, want)

    def test_bf16_upcast_orders_like_f32(self):
        x = jnp.asarray(self._keys(4, 20, seed=11, ties=True),
                        jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_argsort(x, axis=-1)),
            np.asarray(jnp.argsort(x.astype(jnp.float32), axis=-1,
                                   stable=True)))

    def test_row_sums_matches_chained_sorted_prefix(self):
        x = self._keys(6, 14, seed=13)
        k = 9
        rows = np.asarray(jnp.sort(x, axis=-1))
        acc = rows[:, 0]
        for i in range(1, k):
            acc = acc + rows[:, i]  # same chain shape as the kernel
        np.testing.assert_array_equal(
            np.asarray(coordinate.sortnet_row_sums(x, k, axis=-1)), acc)

    def test_bounded_by_max_sort_n_exact_message(self):
        n = coordinate.MAX_SORT_N + 1
        with pytest.raises(ValueError, match=(
                rf"sorting-network path is bounded by "
                rf"MAX_SORT_N={coordinate.MAX_SORT_N}, got n={n}; use the "
                rf"XLA sort or bucket hierarchically")):
            coordinate.sortnet_argsort(np.zeros((2, n), np.float32))
        with pytest.raises(ValueError, match="MAX_SORT_N"):
            coordinate.sortnet_row_sums(np.zeros((n, 2), np.float32).T, 3)

    def test_top_m_and_row_sums_validate_bounds(self):
        x = np.zeros((3, 8), np.float32)
        with pytest.raises(ValueError, match=r"m must be in \[1, 8\]"):
            coordinate.sortnet_top_m(x, 0)
        with pytest.raises(ValueError, match=r"k must be in \[1, 8\]"):
            coordinate.sortnet_row_sums(x, 9)


# --- the operands where they lie (PR 29): the fake row a second operand,
# --- half-precision rows upcast in VMEM, the worker axis leading -----------

_DTYPES = [jnp.bfloat16, jnp.float16, jnp.float32]


def _stack_and_fake(n, tail, dtype, seed, fake="normal"):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((n,) + tail), dtype)
    e = jnp.asarray(rng.standard_normal(tail), dtype)
    if fake == "nan":
        e = jnp.full(tail, jnp.nan, dtype)
    return g, e


def _written_out(g, e, row_map, row_scale):
    """The logical rows as f32, written out the slow way: ``scale *
    ext[row_map]`` with exact zeros under a zero scale."""
    ext = np.concatenate(
        [np.asarray(g, np.float32), np.asarray(e, np.float32)[None]]
    ).reshape(g.shape[0] + 1, -1)
    with np.errstate(invalid="ignore"):  # 0 * inf, replaced just below
        rows = ext[np.asarray(row_map)] * np.asarray(
            row_scale, np.float32)[:, None]
    rows[np.asarray(row_scale) == 0.0] = 0.0
    return jnp.sort(jnp.asarray(rows), axis=0)  # ascending, NaN last


@jax.jit
def _chain_mean(rows):
    """The kernels' mean: rows added in order, one division — jitted like
    the kernel's body, so that XLA treats the division by a constant the
    same way on both sides."""
    acc = rows[0]
    for i in range(1, rows.shape[0]):
        acc = acc + rows[i]
    return acc / rows.shape[0]


def _expect(op, sorted_rows, f):
    n = sorted_rows.shape[0]
    if op == "median":
        return sorted_rows[(n - 1) // 2]
    return _chain_mean(sorted_rows[f:n - f])


def _run(op, g, e, row_map, row_scale, f, tile=128):
    kwargs = dict(extra=e, row_map=row_map, row_scale=row_scale,
                  interpret=True, tile=tile)
    if op == "median":
        return coordinate.coordinate_median(g, **kwargs)
    return coordinate.trimmed_mean(g, f, **kwargs)


def _lie_plan(n, f):
    """Lie's remap: the f Byzantine rows (the last f) all read the fake
    row, index n — a duplicated map where f > 1."""
    return np.array(list(range(n - f)) + [n] * f), np.ones(n)


@pytest.mark.parametrize("dtype", _DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("n", [2, 4, 5, 8, 16, 17, 32])
@pytest.mark.parametrize("op", ["median", "tmean"])
def test_fake_row_apart_matches_sorted_rows(op, n, dtype):
    """The kernel over (stack, fake row) against ``jnp.sort`` over the
    written-out rows, bit for bit, in the operand's own dtype."""
    f = max(1, n // 4)
    g, e = _stack_and_fake(n, (24, 128), dtype, seed=n)
    row_map, row_scale = _lie_plan(n, f)
    trim = min(f, (n - 1) // 2)
    got = _run(op, g, e, row_map, row_scale, trim)
    want = _expect(op, _written_out(g, e, row_map, row_scale), trim)
    assert got.dtype == jnp.dtype(dtype) and got.shape == (24, 128)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want.astype(dtype), np.float32)
        .reshape(24, 128))


def _plans(n):
    ident = np.arange(n)
    byz3 = np.arange(n) >= n - 3
    return {
        # lie at f = 1: the cohort's Bessel deviation is 0/0, the row NaN
        "lie_f1_nan_fake": (np.where(ident == n - 1, n, ident), np.ones(n)),
        # crash: a zero scale over a row that holds inf and NaN
        "crash_nonfinite": (ident, np.where(byz3, 0.0, 1.0)),
        "lie_f3_duplicated": (np.where(byz3, n, ident), np.ones(n)),
        "reverse_scale": (ident, np.where(byz3, -100.0, 1.0)),
    }


@pytest.mark.parametrize("dtype", _DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("plan", list(_plans(8)))
@pytest.mark.parametrize("op", ["median", "tmean"])
def test_fake_row_apart_under_each_attack_plan(op, plan, dtype):
    n = 8
    g, e = _stack_and_fake(
        n, (3, 3, 16, 128), dtype, seed=5,
        fake="nan" if plan == "lie_f1_nan_fake" else "normal")
    if plan == "crash_nonfinite":
        g = g.at[n - 1].set(jnp.inf).at[n - 2, 0].set(jnp.nan)
    row_map, row_scale = _plans(n)[plan]
    got = _run(op, g, e, row_map, row_scale, 3)
    want = _expect(op, _written_out(g, e, row_map, row_scale), 3)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32).reshape(-1),
        np.asarray(want.astype(dtype), np.float32))
    if plan != "reverse_scale" or op == "median":
        assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("dtype", _DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("tail,tile,why", [
    ((7, 19), 128, "size no multiple of 128, taken whole in the lanes"),
    ((300,), 128, "flat, its last block ragged in the lanes"),
    ((3, 3, 8, 64), 128, "a last axis of 64, tap-major"),
    ((40, 128), 2048, "in place, its last block of rows ragged"),
    ((2, 50, 256), None, "a stack of matrices, rows collapsed, two lane blocks"),
    ((5,), None, "a vector under one tile"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_views_and_ragged_blocks(tail, tile, why, dtype):
    n = 5
    g, e = _stack_and_fake(n, tail, dtype, seed=len(tail))
    row_map, row_scale = _lie_plan(n, 1)
    got = _run("median", g, e, row_map, row_scale, 0, tile=tile)
    want = _expect("median", _written_out(g, e, row_map, row_scale), 0)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32).reshape(-1),
        np.asarray(want.astype(dtype), np.float32))


def test_view_is_a_function_of_shape_and_dtype():
    view = coordinate._view
    # worker-major for matrices, tap-major for convolution kernels, flat
    # for vectors and flat stacks; bf16 blocks in whole (16, 128) tiles
    assert view(5, 4, (16384, 2048), jnp.bfloat16) == coordinate._View(
        True, False, (1, 16384, 2048), (480, 512), (80, 128))
    assert view(5, 4, (8, 2048, 1792), jnp.bfloat16) == coordinate._View(
        True, False, (8, 2048, 1792), (960, 256), (80, 128))
    assert view(17, 16, (3, 3, 256, 256), jnp.bfloat16) == coordinate._View(
        True, True, (9, 256, 256), (256, 256), (16, 128))
    assert view(17, 16, (2048, 100), jnp.bfloat16).block == (640, 100)
    assert view(17, 16, (2048,), jnp.float32) == coordinate._View(
        False, False, (2048,), (2048,), (2048,))
    assert view(33, 32, (11_000_000,), jnp.float32).block == (8192,)
    # the double-buffered block stays under a few MB at MAX_SORT_N
    v = view(33, 32, (4096, 1024), jnp.float32)
    assert 2 * 34 * v.block[0] * v.block[1] * 4 <= 5 << 20
    with pytest.raises(ValueError):
        view(5, 4, (300,), jnp.float32, tile=100)


@pytest.mark.parametrize("dtype", _DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("s,beta", [(4, 2), (5, 3), (8, 4), (13, 7), (17, 9)])
def test_bulyan_second_phase_matches_sorted_rows(s, beta, dtype):
    """``averaged_median_mean`` through the same call, in the operand's own
    dtype: the beta rows nearest the lower median (deviations rounded to
    the dtype, ties to the lowest row), summed in that order in f32."""
    g = jnp.asarray(
        np.random.default_rng(s).standard_normal((s, 6, 128)), dtype)
    got = coordinate.averaged_median_mean(g, beta, interpret=True, tile=128)
    rows = np.asarray(g, np.float32).reshape(s, -1)
    med = np.asarray(jnp.sort(jnp.asarray(rows), axis=0))[(s - 1) // 2]
    dev = np.asarray(
        jnp.asarray(np.abs(rows - med)).astype(dtype), np.float32)
    order = np.argsort(dev, axis=0, kind="stable")[:beta]
    picked = jnp.asarray(np.take_along_axis(rows, order, axis=0))
    want = _chain_mean(picked).astype(dtype)
    assert got.dtype == jnp.dtype(dtype) and got.shape == (6, 128)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32).reshape(-1), np.asarray(want, np.float32))


def test_fake_row_shape_is_checked():
    g, e = _stack_and_fake(4, (8, 128), jnp.float32, seed=0)
    with pytest.raises(ValueError, match="fake row"):
        coordinate.coordinate_median(g, extra=e[:4], row_map=[0, 1, 2, 4])
    with pytest.raises(ValueError):  # index 5: past the fake row
        coordinate.coordinate_median(g, extra=e, row_map=[0, 1, 2, 5])
