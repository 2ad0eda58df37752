"""On-device Pallas kernel equality (opt-in: real TPU only).

The interpret-mode tests (test_ops.py) verify the kernels against the jnp
spec on CPU; this file runs the SAME equality checks through real Mosaic
lowering — bf16 16-sublane tiling with n < 16 rows, the (n, tile)
BlockSpec, NaN ordering — so a lowering divergence from the spec cannot
ship unnoticed (ADVICE r1). Skipped automatically off-TPU; the verify
drive runs it on the real chip each round:

    cd /root/repo && python -m pytest tests/test_ops_tpu.py -q
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

if jax.default_backend() != "tpu":
    pytest.skip("real-TPU kernel checks; CPU runs use interpret mode",
                allow_module_level=True)

from garfield_tpu.ops import coordinate


def _rand(n, d, seed, nan_frac=0.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(dtype)
    if nan_frac:
        mask = rng.random((n, d)) < nan_frac
        mask[0] = False
        x = np.where(mask, np.nan, x).astype(dtype)
    return x


@pytest.mark.parametrize("n,d,dtype,nan_frac", [
    (8, 4096, np.float32, 0.0),
    (9, 1031, np.float32, 0.15),   # odd n, non-tile-multiple d, NaNs
    (7, 2048, jnp.bfloat16, 0.0),  # n < 16 rows under bf16 (2,1) tiling
    (32, 1024, np.float32, 0.0),   # MAX_SORT_N boundary
])
def test_median_on_device(n, d, dtype, nan_frac):
    x = _rand(n, d, seed=n * 7 + d, nan_frac=nan_frac, dtype=dtype)
    got = np.asarray(coordinate.coordinate_median(jnp.asarray(x)), np.float32)
    want = np.asarray(
        coordinate.coordinate_median_reference(jnp.asarray(x)), np.float32
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,f", [(9, 2), (16, 5)])
def test_tmean_on_device(n, f):
    x = _rand(n, 4096, seed=n, nan_frac=0.05)
    got = np.asarray(coordinate.trimmed_mean(jnp.asarray(x), f))
    want = np.asarray(coordinate.trimmed_mean_reference(jnp.asarray(x), f))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("s,beta,dtype", [
    (8, 4, np.float32),
    (11, 5, np.float32),
    (7, 3, jnp.bfloat16),
])
def test_avgmed_on_device(s, beta, dtype):
    x = _rand(s, 4096, seed=s * 3 + beta, dtype=dtype)
    got = np.asarray(
        coordinate.averaged_median_mean(jnp.asarray(x), beta), np.float32
    )
    want = np.asarray(
        coordinate.averaged_median_mean_reference(jnp.asarray(x), beta),
        np.float32,
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_remap_kernel_on_device():
    """Folded-attack remap (row_map/row_scale) inside the Mosaic-lowered
    kernel: duplicated fake row + scaled row vs materialized remap."""
    ext = _rand(9, 2048, seed=21, dtype=jnp.bfloat16)
    row_map = np.array([0, 1, 2, 3, 4, 5, 8, 8])
    row_scale = np.array([1.0] * 5 + [-100.0, 1.0, 1.0])
    eff = (np.asarray(ext, np.float32)[row_map]
           * row_scale[:, None]).astype(np.float32)
    got = np.asarray(coordinate.coordinate_median(
        jnp.asarray(ext), row_map=row_map, row_scale=row_scale
    ), np.float32)
    want = np.asarray(coordinate.coordinate_median_reference(
        jnp.asarray(eff, jnp.float32)
    ), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("n,f,tail", [
    (4, 1, (2048, 1792)),     # worker-major, blocks of (480, 128), a ragged one
    (16, 3, (3, 3, 256, 256)),  # tap-major, chunks of one (16, 128) tile a row
    (16, 3, (2048, 100)),     # a last axis of 100 taken whole
    (5, 1, (20000,)),         # flat, its last block ragged mid-tile
    # n = 5, f = 2 (PR 34): an odd n whose fake row is a number and stands
    # twice, over a stack of 16 expert kernels read worker-major in place.
    (5, 2, (16, 2048, 512)),
])
def test_operands_in_place_on_device(n, f, tail):
    """The block shapes of PR 29 through real Mosaic lowering, in bf16: the
    stack read where it lies, upcast in VMEM, the fake row (all NaN, as
    lie at f = 1 makes it; finite at f > 1, where it stands f times) a
    second operand — against ``jnp.sort`` over the written-out rows, bit
    for bit."""
    rng = np.random.default_rng(n)
    g = jnp.asarray(rng.standard_normal((n,) + tail), jnp.bfloat16)
    e = (jnp.full(tail, jnp.nan, jnp.bfloat16) if f == 1 else
         jnp.asarray(rng.standard_normal(tail), jnp.bfloat16))
    row_map = np.array(list(range(n - f)) + [n] * f)
    row_scale = np.array([-2.0] + [1.0] * (n - 1))
    got = coordinate.coordinate_median(
        g, extra=e, row_map=row_map, row_scale=row_scale)
    ext = jnp.concatenate([g, e[None]]).astype(jnp.float32)
    eff = ext[row_map] * jnp.asarray(row_scale, jnp.float32).reshape(
        (-1,) + (1,) * len(tail))
    want = jnp.sort(eff, axis=0)[(n - 1) // 2].astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16 and got.shape == tail
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("n,heads,kv,t,head,dtype,window", [
    (2, 32, 8, 2048, 64, jnp.bfloat16, None),   # one slot of lfm2n4
    (1, 4, 4, 384, 128, jnp.float32, None),  # group 1, blocks of 128, head 128
    (1, 32, 4, 4096, 128, jnp.bfloat16, 1024),  # a sliding layer of mellum2n4
    (1, 32, 4, 4096, 128, jnp.bfloat16, None),  # its full layer: dq at 32 MiB
    (1, 8, 2, 1024, 64, jnp.bfloat16, 200),     # a window that cuts blocks
    # The two groups of one model (PR 34), two KV heads of its eight: a
    # window equal to the block at a group of 8, causal at a group of 6.
    (1, 16, 2, 4096, 128, jnp.bfloat16, 512),
    (1, 12, 2, 4096, 128, jnp.bfloat16, None),
])
def test_attention_kernels_on_device(n, heads, kv, t, head, dtype, window):
    """The blockwise attention kernels (ops/attention.py) through real
    Mosaic lowering, taken by ``causal_gqa`` itself, against the einsum
    path: the output and dq, dk, dv under ``jax.grad`` with the block
    recomputed (``jax.checkpoint``), as the model runs it. Tolerances as
    tests/test_attention.py states them; float32 operands go through the
    MXU's bf16 passes on either path, hence no tighter there."""
    from garfield_tpu.models.lfm2 import einsum_attention
    from garfield_tpu.ops import attention

    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(keys[0], (n, t, heads, head), dtype)
    k = jax.random.normal(keys[1], (n, t, kv, head), dtype)
    v = jax.random.normal(keys[2], (n, t, kv, head), dtype)
    weight = jax.random.normal(keys[3], (n, t, heads, head), jnp.float32)
    assert attention.misfit(q.shape, kv, dtype) is None

    def both(core):
        def loss(q, k, v):
            out = jax.checkpoint(core)(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, got), got_grads = both(
        lambda *a: attention.causal_gqa(*a, einsum_attention, window=window))
    (_, want), want_grads = both(
        lambda *a: einsum_attention(*a, window=window))
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(
        f32(got), f32(want), rtol=2.0 ** -7, atol=2.0 ** -7)
    for leaf, a, b in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            f32(a), f32(b), atol=2.0 ** -6 * float(np.abs(f32(b)).max()),
            err_msg=leaf)


@pytest.mark.parametrize("m,k,n,g,held", [
    # One slot's expert matmuls of lfm2n4, mellum2n4 and lagunaxs2n5, the
    # inner pair's shape and the third's.
    (16384, 2048, 1792, 8, 1 / 4), (16384, 1792, 2048, 8, 1 / 4),
    (32768, 2304, 896, 16, 1 / 4), (32768, 896, 2304, 16, 1 / 4),
    (32768, 2048, 512, 16, 1 / 16), (32768, 512, 2048, 16, 1 / 16),
])
def test_grouped_matmul_on_device(m, k, n, g, held):
    """The grouped-matmul kernels (ops/grouped.py) through real Mosaic
    lowering, taken by ``grouped_matmul`` itself with the rule's tiles,
    against ``lax.ragged_dot``: the result and both gradients under the
    layer's mask, uneven groups with an empty one and an absent tail."""
    from garfield_tpu.ops import grouped

    dtype = jnp.bfloat16
    rng = np.random.default_rng(m + k)
    share = rng.dirichlet(np.full(g, 2.0)) * held * m
    sizes = np.floor(share).astype(np.int32)
    sizes[g // 2] = 0
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    rows = jax.random.normal(keys[0], (m, k), dtype)
    weights = jax.random.normal(keys[1], (g, k, n), dtype) * k ** -0.5
    weight = jax.random.normal(keys[2], (m, n), jnp.float32)
    here = (jnp.arange(m) < sizes.sum())[:, None]
    sizes = jnp.asarray(sizes)
    assert grouped.misfit((m, k, n), dtype, dtype) is None
    assert grouped.plan(k, n, dtype)[0] == (128, k, n)  # what the cells run

    def ragged(rows, weights, sizes):
        return jax.lax.ragged_dot(
            rows, weights, sizes, preferred_element_type=dtype)

    def both(dot):
        # Every array an argument: a constant of this size in the program
        # costs minutes of compilation.
        def loss(rows, weights, sizes, here, weight):
            out = jnp.where(
                here, dot(jnp.where(here, rows, 0), weights, sizes), 0)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(
                rows, weights, sizes, here, weight)

    (_, got), got_grads = both(
        lambda r, w, s: grouped.grouped_matmul(r, w, s, ragged))
    (_, want), want_grads = both(ragged)
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(
        f32(got), f32(want), rtol=2.0 ** -7, atol=2.0 ** -7)
    for leaf, a, b in zip(("rows", "weights"), got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            f32(a), f32(b), atol=2.0 ** -6 * float(np.abs(f32(b)).max()),
            err_msg=leaf)


@pytest.mark.parametrize("tokens,k,hidden,held", [
    # One slot's expert layer of lfm2n4, mellum2n4 and lagunaxs2n5.
    (4096, 4, 2048, 1 / 4), (4096, 8, 2304, 1 / 4), (4096, 8, 2048, 1 / 16),
])
def test_held_rows_on_device(tokens, k, hidden, held):
    """The held-row kernels (ops/route.py) through real Mosaic lowering,
    taken by the expert layer's own branch functions, against the
    permutation: the dispatched rows (equal), the combined value and the
    cotangents of x, out and the weights, and zeros to the row tile."""
    from garfield_tpu.models import lfm2
    from garfield_tpu.ops import route

    dtype, m = jnp.bfloat16, tokens * k
    assert route.misfit((tokens, k, hidden), dtype) is None
    rng = np.random.default_rng(m + hidden)
    slots = np.where(rng.random(m) < held, rng.integers(0, 16, m), 16)
    order = np.argsort(slots, kind="stable").astype(np.int32)
    inverse = jnp.asarray(np.argsort(order), jnp.int32)
    order, total = jnp.asarray(order), jnp.int32((slots < 16).sum())
    keys = jax.random.split(jax.random.PRNGKey(tokens + hidden), 4)
    x = jax.random.normal(keys[0], (tokens, hidden), dtype)
    weights = jax.random.uniform(keys[1], (tokens, k)).astype(dtype)
    d_rows = jax.random.normal(keys[2], (m, hidden), jnp.float32)
    d_y = jax.random.normal(keys[3], (tokens, hidden), jnp.float32)
    here = (jnp.arange(m) < total)[:, None]

    def both(dispatch, back):
        # Every array an argument: a constant of this size in the program
        # costs minutes of compilation.
        def run(x, weights, order, inverse, total, d_rows, d_y):
            rows, pull = jax.vjp(
                lambda x: dispatch(x, order, inverse, total)[0], x)
            y, pull_back = jax.vjp(lambda o, w: back(
                o, w, order, inverse, total, dtype), rows * 2, weights)
            d_out, d_weights = pull_back(d_y.astype(dtype))
            return (rows, pull(d_rows.astype(dtype))[0], y,
                    jnp.where(here, d_out, 0), d_weights)
        return jax.jit(run)(x, weights, order, inverse, total, d_rows, d_y)

    got = both(lfm2._rows_held, lfm2._back_held)
    want = both(lfm2._rows_permuted, lfm2._back_permuted)
    f32 = lambda a: np.asarray(a, np.float32)
    t, end = int(total), -(-int(total) // 128) * 128
    np.testing.assert_array_equal(f32(got[0])[:t], f32(want[0])[:t])
    assert not f32(got[0])[t:end].any()
    for name, a, b in zip(("d_x", "y", "d_out", "d_weights"), got[1:],
                          want[1:]):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            f32(a), f32(b), atol=2.0 ** -7 * float(np.abs(f32(b)).max()),
            err_msg=name)


@pytest.mark.parametrize("n,t,channels,dtype", [
    (1, 4096, 5120, jnp.bfloat16),  # one slot of phi4flashn4's Mamba layer
    (2, 1000, 384, jnp.float32),    # padded positions, three channel tiles
])
def test_selective_scan_kernels_on_device(n, t, channels, dtype):
    """The selective scan's kernels (ops/scan.py) through real Mosaic
    lowering, taken by ``selective_scan`` itself under the recomputed
    block's policy, against ``sequential_scan``: y and the cotangents of x,
    delta, A, B, C and D. A bf16 leaf within two steps of bf16's grid at its
    largest entry, a float32 one within 1e-4 of it (the sums run in
    another order)."""
    from garfield_tpu.ops import scan

    state = 16
    assert scan.misfit((n, t, channels, state), dtype) is None
    keys = jax.random.split(jax.random.PRNGKey(t + channels), 7)
    args = (jax.random.normal(keys[0], (n, t, channels), dtype),
            jax.nn.softplus(jax.random.normal(keys[1], (n, t, channels))
                            - 2.0),
            -jnp.exp(jax.random.normal(keys[2], (channels, state))),
            jax.random.normal(keys[3], (n, t, state), dtype),
            jax.random.normal(keys[4], (n, t, state), dtype),
            jax.random.normal(keys[5], (channels,)))
    weight = jax.random.normal(keys[6], (n, t, channels), jnp.float32)
    keep = jax.checkpoint_policies.save_only_these_names(*scan.KEPT)

    def both(core):
        def loss(*a):
            y = jax.checkpoint(core, policy=keep)(*a)
            return jnp.sum(y.astype(jnp.float32) * weight), y
        return jax.jit(jax.value_and_grad(
            loss, argnums=range(6), has_aux=True))(*args)

    (_, got), got_grads = both(scan.selective_scan)
    (_, want), want_grads = both(scan.sequential_scan)
    f32 = lambda x: np.asarray(x, np.float32)

    def close(a, b, name):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        room = 2.0 ** -7 if a.dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(
            f32(a), f32(b), atol=room * float(np.abs(f32(b)).max()),
            err_msg=name)

    close(got, want, "y")
    for name, a, b in zip(("dx", "ddelta", "dA", "dB", "dC", "dD"),
                          got_grads, want_grads):
        close(a, b, name)
