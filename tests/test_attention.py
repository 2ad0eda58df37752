"""The blockwise attention kernels (ops/attention.py) in interpret mode
against the einsum path (models/lfm2.einsum_attention), which is their spec
and what runs wherever they do not.

Tolerances. float32: the two paths differ by the order of the softmax's
sums alone, a few ulp. bfloat16: both round the probabilities to 8 bits
(2**-8 relative) — the kernels before they are divided by their sum, the
einsum path after — and every result once more, so an output or a gradient
may differ by two steps of its grid, 2**-7 of its size; a gradient sums
such terms over up to t keys, so it is held to 2**-6 of the leaf's largest
entry.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.models.lfm2 import einsum_attention
from garfield_tpu.ops import attention

# (heads, kv_heads, t, block, head): groups of 1 and 4, and of 6 and 8 (the
# two groups of one model whose head count is its layer's); t of one block
# and of several (skipped, crossed and full blocks all occur at 4 x 4),
# square and oblong blocks; head sizes 64 and 16.
SHAPES = {
    "group1-one-block-head16": (2, 2, 16, 16, 16),
    "group4-one-block-head64": (8, 2, 16, 16, 64),
    "group1-several-head64": (2, 2, 32, 8, 64),
    "group4-several-head16": (8, 2, 32, 8, 16),
    "group4-several-head64": (4, 1, 32, 8, 64),
    "group4-wide-key-blocks": (4, 1, 32, (8, 16), 16),
    "group4-tall-q-blocks": (4, 1, 32, (16, 8), 16),
    "group6-several-head16": (12, 2, 32, 8, 16),
    "group8-several-head16": (8, 1, 32, 8, 16),
}
CASES = [(name, dtype) for name in SHAPES
         for dtype in (jnp.float32, jnp.bfloat16)]
IDS = [f"{name}-{jnp.dtype(dtype).name}" for name, dtype in CASES]


@pytest.fixture(autouse=True)
def every_line_is_new():
    """``[attention]`` lines are said once a process: forget them."""
    attention._said.clear()


def _operands(name, dtype, n=2, seed=0):
    heads, kv, t, block, head = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (n, t, heads, head), dtype)
    k = jax.random.normal(keys[1], (n, t, kv, head), dtype)
    v = jax.random.normal(keys[2], (n, t, kv, head), dtype)
    weight = jax.random.normal(keys[3], (n, t, heads, head), jnp.float32)
    return (q, k, v), weight, block


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("name,dtype", CASES, ids=IDS)
def test_the_kernels_output_equals_the_einsum_paths(name, dtype):
    operands, _, block = _operands(name, dtype)
    got = attention.blockwise(*operands, block=block, interpret=True)
    want = einsum_attention(*operands)
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = 5e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("name,dtype", CASES, ids=IDS)
def test_the_kernels_gradients_equal_the_einsum_paths(name, dtype):
    """dq, dk and dv under ``jax.grad``; dk and dv are sums over the query
    heads that share the KV head."""
    operands, weight, block = _operands(name, dtype)

    def grads(core):
        return jax.grad(
            lambda *a: jnp.sum(core(*a).astype(jnp.float32) * weight),
            argnums=(0, 1, 2))(*operands)

    got = grads(functools.partial(
        attention.blockwise, block=block, interpret=True))
    want = grads(einsum_attention)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    for leaf, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            _f32(a), _f32(b), atol=tol * float(np.abs(_f32(b)).max()),
            err_msg=leaf)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_first_row_sees_one_key_and_no_row_a_later_one(dtype):
    """Row 0 has one visible key, so its output is v's row 0 to the bit;
    changing k and v from position 9 on — inside the second of four blocks,
    one the diagonal crosses — leaves rows 0..8 as they were, outputs and
    dq, and moves the rows after."""
    (q, k, v), weight, block = _operands("group4-several-head16", dtype)
    core = functools.partial(attention.blockwise, block=block, interpret=True)
    out = core(q, k, v)
    group = q.shape[2] // k.shape[2]
    np.testing.assert_array_equal(
        _f32(out[:, 0]), _f32(jnp.repeat(v[:, 0], group, axis=1)))
    k2, v2 = k.at[:, 9:].add(1), v.at[:, 9:].multiply(-2)
    later = core(q, k2, v2)
    np.testing.assert_array_equal(_f32(out[:, :9]), _f32(later[:, :9]))
    moved = np.abs(_f32(out[:, 9:]) - _f32(later[:, 9:])).max(axis=-1)
    assert float(moved.min()) > 0  # every later row of every head

    def dq(k, v):
        return jax.grad(lambda q: jnp.sum(
            core(q, k, v).astype(jnp.float32) * weight))(q)

    a, b = dq(k, v), dq(k2, v2)
    np.testing.assert_array_equal(_f32(a[:, :9]), _f32(b[:, :9]))
    # With one key the softmax is the constant 1: no gradient reaches q
    # (do . v less the sum of do * o, which is v there: rounding alone).
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    assert float(jnp.abs(_f32(a[:, 0])).max()) <= tol * float(
        jnp.abs(_f32(a)).max())


def test_what_the_kernels_cannot_take_goes_the_einsum_way_and_says_why(
        capsys):
    """A t the blocks do not divide; on this backend, any shape at all."""
    (q, k, v), _, _ = _operands("group4-several-head16", jnp.float32)
    want = einsum_attention(q, k, v)
    got = attention.causal_gqa(q, k, v, einsum_attention, block=12,
                               interpret=True)
    np.testing.assert_array_equal(got, want)
    assert ("[attention] einsum: t = 32 is not a multiple of the blocks "
            "(12, 12)") in capsys.readouterr().err
    odd = tuple(a[:, :20] for a in (q, k, v))
    attention.causal_gqa(*odd, einsum_attention)
    assert ("[attention] einsum: t = 20 is not a multiple of the block "
            "128") in capsys.readouterr().err
    assert jax.default_backend() != "tpu"
    long = tuple(jnp.tile(a, (1, 4, 1, 1)) for a in (q, k, v))
    attention.causal_gqa(*long, einsum_attention)
    assert "[attention] einsum: head size 16" in capsys.readouterr().err
    attention.causal_gqa(*(jnp.tile(a, (1, 1, 1, 4)) for a in long),
                         einsum_attention)
    assert "[attention] einsum: no TPU lowering" in capsys.readouterr().err


def test_the_line_names_the_blocks_and_is_said_once(capsys):
    operands, _, block = _operands("group4-several-head16", jnp.bfloat16)
    for _ in range(3):
        attention.causal_gqa(*operands, einsum_attention, block=block,
                             interpret=True)
    lines = [line for line in capsys.readouterr().err.splitlines()
             if "[attention]" in line]
    assert lines == [
        "[attention] blockwise: (n, heads, kv_heads, t, head) = "
        "(2, 8, 2, 32, 16) bfloat16, blocks (8, 8), causal blocks skipped "
        "6 of 16, interpret mode"]
    assert attention.blocks_run(2048, 512, 512) == (10, 6, 0)
    assert attention.blocks_run(2048, 256, 512) == (20, 12, 0)
    # The sliding layers of the window cell: 21 of 64 run, causal 36.
    assert attention.blocks_run(4096, 512, 512, 1024) == (21, 28, 15)
    assert attention.blocks_run(4096, 512, 512) == (36, 28, 0)
    # A window equal to the block: the diagonal's blocks and the ones under
    # them, 15 of 64, none of them whole.
    assert attention.blocks_run(4096, 512, 512, 512) == (15, 28, 21)


# Windows over 32 positions in blocks of 8 (or 8 x 16, 16 x 8): one that
# aligns with the blocks, ones that cut them (so a crossed block hides whole
# rows, whose running sums the next block has to wipe), one key alone, one
# block and a bit, and one short of t.
WINDOWS = (1, 5, 8, 13, 16, 31)
BAND_CASES = [(name, window, dtype)
              for name in ("group4-several-head16", "group4-wide-key-blocks",
                           "group4-tall-q-blocks", "group1-several-head64")
              for window in WINDOWS for dtype in (jnp.float32, jnp.bfloat16)
              if dtype == jnp.float32 or window in (5, 16)]
# A window equal to the block at groups of 6 and 8: every block that runs is
# crossed by an edge, the diagonal's by both.
BAND_CASES += [(name, 8, dtype)
               for name in ("group6-several-head16", "group8-several-head16")
               for dtype in (jnp.float32, jnp.bfloat16)]


@pytest.mark.parametrize(
    "name,window,dtype", BAND_CASES,
    ids=[f"{n}-w{w}-{jnp.dtype(d).name}" for n, w, d in BAND_CASES])
def test_the_banded_kernels_equal_the_banded_einsum(name, window, dtype):
    """Output, dq, dk and dv with a window that aligns with, cuts and
    exceeds the blocks, under the causal tests' tolerances."""
    operands, weight, block = _operands(name, dtype)

    def both(core):
        return jax.value_and_grad(
            lambda *a: jnp.sum(core(*a).astype(jnp.float32) * weight),
            argnums=(0, 1, 2))(*operands)

    want_out = einsum_attention(*operands, window=window)
    got_out = attention.blockwise(
        *operands, window=window, block=block, interpret=True)
    tol = 5e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(
        _f32(got_out), _f32(want_out), rtol=tol, atol=tol)
    _, got = both(functools.partial(
        attention.blockwise, window=window, block=block, interpret=True))
    _, want = both(functools.partial(einsum_attention, window=window))
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    # With one key the softmax is the constant 1 and dq, dk are zero by the
    # einsum path: rounding of the others' size is all the kernels leave.
    largest = max(float(np.abs(_f32(b)).max()) for b in want)
    for leaf, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            _f32(a), _f32(b), err_msg=leaf, atol=tol * max(
                float(np.abs(_f32(b)).max()), 0.1 * largest))
    # The window does hide keys: the causal result differs.
    assert float(np.abs(_f32(want_out) - _f32(
        einsum_attention(*operands))).max()) > 1e-3


@pytest.mark.parametrize("window", [None, 32, 33, 4096])
def test_a_window_of_t_or_more_is_the_causal_program_bit_for_bit(
        window, capsys):
    """Outputs and gradients equal to the bit, the same line said, and the
    same jaxpr: no operation is added for a window that hides nothing."""
    operands, weight, block = _operands("group4-several-head16", jnp.bfloat16)

    def loss(window):
        return lambda *a: jnp.sum(attention.causal_gqa(
            *a, einsum_attention, window=window, block=block,
            interpret=True).astype(jnp.float32) * weight)

    want = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(*operands)
    causal_line = capsys.readouterr().err
    attention._said.clear()
    got = jax.value_and_grad(loss(window), argnums=(0, 1, 2))(*operands)
    assert capsys.readouterr().err == causal_line
    assert "causal blocks skipped 6 of 16" in causal_line
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(_f32(a), _f32(b))
    text = [str(jax.make_jaxpr(jax.grad(loss(w), argnums=(0, 1, 2)))(
        *operands)) for w in (None, window)]
    assert text[0] == text[1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_key_a_window_back_moves_nothing(dtype):
    """With a window of 5 over blocks of 8, row i sees keys i - 4 .. i:
    changing k and v at position 9 moves rows 9..13 and no other, outputs
    and dq; the line names the window and both kinds of skipped block."""
    (q, k, v), weight, block = _operands("group4-several-head16", dtype)
    core = functools.partial(
        attention.blockwise, window=5, block=block, interpret=True)
    k2, v2 = k.at[:, 9].add(1), v.at[:, 9].multiply(-2)
    out, later = core(q, k, v), core(q, k2, v2)
    still = np.r_[0:9, 14:32]
    np.testing.assert_array_equal(_f32(out[:, still]), _f32(later[:, still]))
    moved = np.abs(_f32(out[:, 9:14]) - _f32(later[:, 9:14])).max(axis=-1)
    assert float(moved.min()) > 0

    def dq(k, v):
        return jax.grad(lambda q: jnp.sum(
            core(q, k, v).astype(jnp.float32) * weight))(q)

    a, b = dq(k, v), dq(k2, v2)
    np.testing.assert_array_equal(_f32(a[:, still]), _f32(b[:, still]))
    assert attention.blocks_run(32, 8, 8, 5) == (7, 6, 3)


def test_the_line_names_the_window_and_the_blocks_skipped_on_each_side(
        capsys):
    operands, _, block = _operands("group4-several-head16", jnp.bfloat16)
    attention.causal_gqa(*operands, einsum_attention, window=5, block=block,
                         interpret=True)
    assert ("blocks (8, 8), window 5, blocks run 7 of 16 (skipped 6 above "
            "the diagonal, 3 below the band), interpret mode"
            ) in capsys.readouterr().err
    # The einsum way takes the window too.
    got = attention.causal_gqa(*operands, einsum_attention, window=5)
    assert "[attention] einsum: t = 32" in capsys.readouterr().err
    np.testing.assert_array_equal(
        _f32(got), _f32(einsum_attention(*operands, window=5)))


@pytest.mark.parametrize("shape,kv,dtype,why", [
    ((2, 2048, 32, 64), 8, jnp.bfloat16, None),
    ((1, 4096, 32, 128), 4, jnp.bfloat16, None),
    ((2, 2048, 32, 64), 8, jnp.float32, None),
    ((1, 384, 4, 128), 4, jnp.bfloat16, None),
    # Groups of 8 and 6 at heads of 128: dq of a KV head 32 MiB (the limit
    # itself) and 24 MiB.
    ((1, 4096, 64, 128), 8, jnp.bfloat16, None),
    ((1, 4096, 48, 128), 8, jnp.bfloat16, None),
    ((2, 2048, 32, 64), 5, jnp.bfloat16, "do not share"),
    ((2, 2000, 32, 64), 8, jnp.bfloat16, "not a multiple of the block"),
    ((2, 2048, 32, 64), 8, jnp.float16, "dtype float16"),
    ((2, 2048, 32, 80), 8, jnp.bfloat16, "head size 80"),
    ((2, 16384, 32, 64), 8, jnp.bfloat16, "does not stay in VMEM"),
])
def test_which_shapes_fit_the_chips_blocks(shape, kv, dtype, why):
    said = attention.misfit(shape, kv, dtype)
    assert (said is None) if why is None else (why in said), said
