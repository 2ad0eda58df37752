"""The grouped-matmul kernels (ops/grouped.py) in interpret mode against
``jax.lax.ragged_dot``, their spec and their fallback: the value, the two
cotangents and the gradient of a scalar, over group sizes whose boundaries
fall inside the row tiles, empty groups, an absent tail and nothing at all;
then the tile rule, the path choice and the count of rows visited.

Rows past ``sum(sizes)`` are undefined in either path (interpret mode leaves
NaN there), so everything is compared as `lfm2.ExpertLayer` reads it: under
its ``here`` mask, on the rows going in and on the result coming out.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.ops import attention, coordinate, grouped

M, K, N, G = 64, 16, 24, 4
SIZES = {
    "even": (16, 16, 16, 16),
    "uneven": (5, 20, 9, 30),
    "empty_first": (0, 20, 14, 30),
    "empty_middle": (5, 20, 0, 39),
    "empty_last": (25, 20, 19, 0),
    "absent_tail": (5, 20, 9, 10),
    "absent_tail_and_empty": (0, 7, 3, 0),
    "nothing": (0, 0, 0, 0),
}
# Two tile rules: the contraction and the columns whole, one visit a step;
# and both cut, the float32 scratch carried over the contraction's steps,
# with a longer row tile that every boundary of ``uneven`` crosses.
TILES = {"whole": (8, K, N), "cut": (16, 8, 8)}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
CASES = [(s, d, t) for s in SIZES for d in DTYPES for t in TILES]


def _ragged(rows, weights, sizes):
    return jax.lax.ragged_dot(
        rows, weights, sizes, preferred_element_type=rows.dtype)


@functools.lru_cache(maxsize=None)
def _both(case, dtype, tiles):
    """{path: (value, (d rows, d weights) of a random cotangent, the
    gradient of sum(value ** 2))} for the kernels and for ``ragged_dot``,
    under the layer's mask; as float32 NumPy arrays."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    dtype = DTYPES[dtype]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    rows = jax.random.normal(keys[0], (M, K), dtype)
    weights = jax.random.normal(keys[1], (G, K, N), dtype) / 4
    cotangent = jax.random.normal(keys[2], (M, N), dtype)
    here = (jnp.arange(M) < jnp.sum(sizes))[:, None]

    def masked(dot, rows, weights):
        return jnp.where(
            here, dot(jnp.where(here, rows, 0), weights, sizes), 0)

    @jax.jit
    def every(dot_rows, dot_weights):
        out = {}
        for path, dot in (
                ("kernels", functools.partial(
                    grouped.kernels, tiles=TILES[tiles], interpret=True)),
                ("ragged_dot", _ragged)):
            layer = functools.partial(masked, dot)
            value, pull = jax.vjp(layer, dot_rows, dot_weights)
            scalar = jax.grad(lambda r, w: jnp.sum(
                layer(r, w).astype(jnp.float32) ** 2), (0, 1))
            out[path] = (value, pull(cotangent), scalar(dot_rows, dot_weights))
        return out

    return jax.tree.map(
        lambda x: np.asarray(x, np.float32), every(rows, weights))


def _close(got, want, dtype):
    """Float32: the sums' order differs. bfloat16: one rounding at the end
    of a float32 sum on either path, so a step of its grid at the largest
    entry."""
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1.0)
    atol = {"float32": 1e-5, "bfloat16": 2.0 ** -7}[dtype] * scale
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("case,dtype,tiles", CASES)
def test_the_value_equals_ragged_dots(case, dtype, tiles):
    both = _both(case, dtype, tiles)
    _close(both["kernels"][0], both["ragged_dot"][0], dtype)
    if case == "nothing":
        assert not both["kernels"][0].any()


@pytest.mark.parametrize("case,dtype,tiles", CASES)
def test_the_rows_cotangent_equals_ragged_dots(case, dtype, tiles):
    both = _both(case, dtype, tiles)
    _close(both["kernels"][1][0], both["ragged_dot"][1][0], dtype)


@pytest.mark.parametrize("case,dtype,tiles", CASES)
def test_the_weights_cotangent_equals_ragged_dots(case, dtype, tiles):
    """Per group over its rows only; a group of no rows gives zeros."""
    both = _both(case, dtype, tiles)
    got, want = both["kernels"][1][1], both["ragged_dot"][1][1]
    _close(got, want, dtype)
    for group, size in enumerate(SIZES[case]):
        assert size or not got[group].any(), group


@pytest.mark.parametrize("case,dtype,tiles", CASES)
def test_the_gradient_of_a_scalar_equals_ragged_dots(case, dtype, tiles):
    both = _both(case, dtype, tiles)
    for got, want in zip(both["kernels"][2], both["ragged_dot"][2]):
        _close(got, want, dtype)


def visits(sizes, m, tm):
    """A NumPy count of the forward kernel's visits: a row tile once for
    each group that has a row in it; no kernel, no table."""
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes)
    return sum(
        1 for tile in range(m // tm) for start, end in zip(ends - sizes, ends)
        if max(start, tile * tm) < min(end, (tile + 1) * tm))


@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("tm", [8, 16, 32])
def test_rows_visited_equals_a_count_from_sizes_and_the_row_tile(case, tm):
    """By the kernels' path, on `grouped_matmul`'s arguments; 0 where it
    takes the fallback (the rule's row tile does not divide 64 rows)."""
    want = visits(SIZES[case], M, tm) * tm
    rows, weights = jnp.ones((M, K)), jnp.ones((G, K, N))
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    assert int(grouped.rows_visited(
        rows, weights, sizes, tiles=(tm, K, N), interpret=True)) == want
    assert want >= sum(SIZES[case])
    assert grouped.rows_visited(rows, weights, sizes) == 0


@pytest.mark.parametrize("shape,dtype,kwargs,why", [
    ((192, 64, 48), jnp.float32, {},
     "m = 192 is not a multiple of the row tile 128"),
    ((256, 64, 128), jnp.float32, {}, "k = 64 is no multiple of 128 lanes"),
    ((256, 128, 48), jnp.float32, {}, "n = 48 is no multiple of 128 lanes"),
    ((256, 128, 128), jnp.float16, {},
     "dtype float16 (the kernels take bfloat16 and float32)"),
    ((256, 128, 128), jnp.bfloat16, {"weights_dtype": jnp.float32},
     "rows bfloat16 against weights float32"),
    ((64, 16, 24), jnp.float32, {"override": (8, 16, 16), "lowered": False},
     "(m, k, n) = (64, 16, 24) is not a multiple of the tiles (8, 16, 16)"),
    ((64, 16, 24), jnp.float32, {"override": (8, 16, 24)},
     "k = 16 is no multiple of 128 lanes"),
    ((256, 256, 128), jnp.float32, {"override": (128, 64, 128)},
     "tiles (128, 64, 128) do not fill (8, 128) tiles"),
    ((64, 16, 24), jnp.float32, {"override": (8, 16, 24), "lowered": False},
     None),
    ((256, 256, 128), jnp.bfloat16, {}, None),
])
def test_misfit_says_why(shape, dtype, kwargs, why):
    assert grouped.misfit(shape, dtype, **kwargs) == why


@pytest.mark.parametrize("k,n,want", [
    # One slot of lfm2n4, mellum2n4 and lagunaxs2n5, and their transposes.
    (2048, 1792, (128, 2048, 1792)), (1792, 2048, (128, 1792, 2048)),
    (2304, 896, (128, 2304, 896)), (896, 2304, (128, 896, 2304)),
    (2048, 512, (128, 2048, 512)), (512, 2048, (128, 512, 2048)),
])
def test_the_tile_rule_reads_shapes_only(k, n, want):
    """The row tile is the constant; the contraction and the columns whole
    where the blocks fit; the gradients' tiles by the same rule over their
    own shapes; twice the width, smaller blocks."""
    made = grouped.plan(k, n, jnp.bfloat16)
    assert made[0] == want == grouped.tiles(k, n, jnp.bfloat16)
    assert made[1] == grouped.tiles(n, k, jnp.bfloat16)
    assert made[1][1] == n  # rows' contracts over n, whole
    assert made[2] == grouped.tiles(k, n, jnp.bfloat16, weights_pass=True)
    for weights_pass in (False, True):
        tm, tk, tn = grouped.tiles(4 * k, 4 * n, jnp.float32, weights_pass)
        assert tm == grouped.ROW_TILE and (tk, tn) != (4 * k, 4 * n)
        assert 4 * k % tk == 0 and 4 * n % tn == 0
        assert grouped._block_bytes(
            tm, tk, tn, 4, weights_pass) <= grouped.BLOCK_BYTES


def _lines(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if "[experts]" in line]


def test_the_path_is_chosen_by_shape_and_lowering_and_said_once(
        monkeypatch, capsys):
    attention._said.clear()
    sizes = jnp.asarray(SIZES["uneven"], jnp.int32)
    rows, weights = jnp.ones((M, K)), jnp.ones((G, K, N))
    for _ in range(2):
        out = grouped.grouped_matmul(rows, weights, sizes, _ragged)
        assert out.shape == (M, N)
    assert _lines(capsys) == [
        "[experts] ragged_dot: m = 64 is not a multiple of the row tile 128"]
    # Shapes that fit, lowered for the CPU: the fallback, and why.
    rows, weights = jnp.ones((256, 128)), jnp.ones((2, 128, 128))
    sizes = jnp.asarray((100, 90), jnp.int32)
    want = grouped.grouped_matmul(rows, weights, sizes, _ragged)
    assert grouped.rows_visited(rows, weights, sizes) == 0
    assert _lines(capsys) == ["[experts] ragged_dot: no TPU lowering"]
    # As on the chip, up to the lowering: `platform_dependent` takes the
    # fallback's branch here, and the line and the rows visited are the
    # kernels'.
    monkeypatch.setattr(coordinate, "use_pallas", lambda *a, **k: True)
    got = jax.jit(functools.partial(
        grouped.grouped_matmul, fallback=_ragged))(rows, weights, sizes)
    assert int(grouped.rows_visited(rows, weights, sizes)) == 3 * 128
    np.testing.assert_array_equal(got[:190], want[:190])
    assert _lines(capsys) == [
        "[experts] grouped: (m, k, n) = (256, 128, 128) g=2 float32, tiles "
        "(128, 128, 128), gradients (128, 128, 128) rows, (128, 128, 128) "
        "weights"]
    # Interpret mode runs the kernels anywhere.
    got = grouped.grouped_matmul(
        rows, weights, sizes, _ragged, tiles=(64, 128, 128), interpret=True)
    np.testing.assert_allclose(got[:190], want[:190], rtol=1e-6)
    assert _lines(capsys)[0].endswith(
        "tiles (64, 128, 128), gradients (64, 128, 128) rows, (64, 128, 128) "
        "weights, interpret mode")
