"""Grouped matmul over rows sorted by group (Pallas, TPU): the held experts'
matmuls of a mixture-of-experts layer.

``grouped_matmul(rows, weights, sizes, fallback)`` is ``jax.lax.ragged_dot``:
rows ``(m, k)`` lie sorted by group, group i owns the ``sizes[i]`` rows after
those of group i - 1 and meets ``weights[i]`` ``(k, n)``; ``sum(sizes)`` may
be less than m, and what the rows past it give is not defined (the caller
masks them). The kernels here are written after
``jax.experimental.pallas.ops.tpu.megablox`` (Gale et al., MegaBlocks) under
one ``jax.custom_vjp``; what they add is that the tiles follow the shapes
(`tiles`), so that a group's weights are fetched once and not once a row
tile:

  forward   grid (n tile, visit, k tile). A visit is one (row tile, group)
            pair that share a row: a tile inside one group is visited once,
            a tile that group boundaries cross once for each group in it,
            with the other groups' rows masked where it is stored. Tiles
            past ``sum(sizes)`` are no visit: the grid ends at the last one
            (its extent is a traced scalar), so they cost no step and no
            fetch. With ``tk = k`` consecutive visits of one group name the
            same weight block, and the pipeline does not fetch it again.
  rows'     the gradient of the rows is the same kernel on the cotangent
            against the weights read transposed in VMEM (``a @ b.T``): no
            transposed copy in HBM.
  weights'  grid (n tile, k tile, visit), visits innermost: ``rows_tile.T @
            cotangent_tile`` over the group's rows of each visit, summed in
            a float32 scratch that is written once a group. An empty group
            has one visit that adds nothing and writes zeros.

**Arithmetic**: ``ragged_dot``'s with ``preferred_element_type`` the
operands' dtype: operands as they come (bf16 in the token cells), float32
accumulation, rounded once. What differs is the order of a float32 sum.

**The tile rule** (`tiles`) reads shapes only: k, n and the dtype's width
give the contraction's and the columns' tiles; the row tile is ``ROW_TILE``,
which won or tied at every group length timed on the chip, 128 to 2,048
rows a group (docs/DESIGN.md section 28 has the table).

**Which path** (`grouped_matmul`): the kernels where the computation is
lowered for the TPU (``lax.platform_dependent``, as `attention.causal_gqa`)
and the shapes fit (`misfit`), else ``fallback``; ``interpret=True`` runs the
kernels anywhere. Said once for each distinct line, on the ``info`` channel:
``[experts] grouped: ...`` or ``[experts] ragged_dot: <why>``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import coordinate
from .attention import say

__all__ = ["grouped_matmul", "rows_visited", "kernels", "tiles", "misfit",
           "plan", "ROW_TILE"]

LANES = 128
# The row tile (256 tied it at 2,048 rows a group and lost below, 512 lost
# everywhere), and what the blocks of one call may take of VMEM (inputs and
# output twice, the float32 scratch once).
ROW_TILE = 128
BLOCK_BYTES = 40 << 20
VMEM_LIMIT_BYTES = 64 << 20

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _divisors(x):
    """The tiles of an axis of ``x``, largest first: x itself and, of its
    divisors, the multiples of LANES."""
    return [d for d in range(x, 0, -1)
            if x % d == 0 and (d == x or d % LANES == 0)]


def _block_bytes(tm, tk, tn, width, weights_pass):
    """VMEM the blocks of one call take: each operand and the output twice
    (the pipeline's two buffers), the float32 scratch once."""
    if weights_pass:  # (tm, tk), (tm, tn) -> (tk, tn)
        return 2 * width * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    return 2 * width * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def tiles(k, n, dtype, weights_pass=False):
    """``(tm, tk, tn)`` for rows (m, k) against groups' (k, n), from the
    shapes alone. ``tm`` is ``ROW_TILE``: a tile that a boundary crosses is
    computed once a group, so the tiles pay about one tile of padding a
    group, and a longer tile feeds the MXU no better than that costs.
    ``tk``, then ``tn``: the whole axis where the blocks fit ``BLOCK_BYTES``,
    else its largest divisor that is a multiple of LANES and fits — with the
    contraction whole, consecutive visits of a group read the same weight
    block and a group's weights are fetched once."""
    width = jnp.dtype(dtype).itemsize
    for tk in _divisors(k):
        for tn in _divisors(n):
            if _block_bytes(
                    ROW_TILE, tk, tn, width, weights_pass) <= BLOCK_BYTES:
                return ROW_TILE, tk, tn
    return ROW_TILE, _divisors(k)[-1], _divisors(n)[-1]


def plan(k, n, dtype, override=None):
    """The three kernels' tiles ``(forward, rows', weights')``, each ``(tm,
    tk, tn)`` over its own (k, n) — rows' contracts over n —, by the rule or
    from one ``override`` of the forward kernel's (rows' takes it with tk
    and tn swapped)."""
    if override is not None:
        tm, tk, tn = override
        return (tm, tk, tn), (tm, tn, tk), (tm, tk, tn)
    return (tiles(k, n, dtype), tiles(n, k, dtype),
            tiles(k, n, dtype, weights_pass=True))


def misfit(shape, dtype, weights_dtype=None, override=None, lowered=True):
    """Why the kernels cannot take rows (m, k) against (g, k, n) of
    ``shape`` = (m, k, n), or None. ``lowered``: for the chip, whose lanes a
    tile has to fill; interpret mode takes any tiles that divide."""
    m, k, n = shape
    if weights_dtype is not None and jnp.dtype(weights_dtype) != jnp.dtype(
            dtype):
        return (f"rows {jnp.dtype(dtype).name} against weights "
                f"{jnp.dtype(weights_dtype).name}")
    made = plan(k, n, dtype, override)
    if m % made[0][0]:
        return f"m = {m} is not a multiple of the row tile {made[0][0]}"
    for (tm, tk, tn), (a, b) in zip(made, ((k, n), (n, k), (k, n))):
        if a % tk or b % tn:
            return (f"(m, k, n) = ({m}, {a}, {b}) is not a multiple of the "
                    f"tiles ({tm}, {tk}, {tn})")
    if not lowered:
        return None
    if jnp.dtype(dtype).name not in ("bfloat16", "float32"):
        return (f"dtype {jnp.dtype(dtype).name} (the kernels take bfloat16 "
                "and float32)")
    for name, x in (("k", k), ("n", n)):
        if x % LANES:
            return f"{name} = {x} is no multiple of {LANES} lanes"
    for tm, tk, tn in made:
        if tm % 8 or tk % LANES or tn % LANES:
            return f"tiles ({tm}, {tk}, {tn}) do not fill (8, {LANES}) tiles"
    return None


def _running(x):
    """The running sum of a short vector, as one masked reduction (it fuses
    with what reads it, where a cumsum is an operation of its own)."""
    i = jnp.arange(x.shape[0])
    return jnp.sum(jnp.where(i[None] <= i[:, None], x[None], 0), axis=1)


def _visits(sizes, m, tm, empty):
    """The (row tile, group) pairs a kernel visits, in order: ``(offsets
    (g + 1,), group (v,), tile (v,), count)`` with v = m / tm + g - 1 the
    most there can be; entries past ``count`` are not read. ``empty``: a
    group of no rows still has one visit (weights': its zeros are
    written). Comparisons and sums over (v, g) only: no gather, no
    scatter."""
    g = sizes.shape[0]
    ends = _running(sizes)
    first = (ends - sizes) // tm
    each = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, int(empty))
    upto = _running(each)
    visit = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(visit[:, None] >= upto[None], axis=1, dtype=jnp.int32), g - 1)
    # The tile of a group's n-th visit is its first tile + n.
    shift = jnp.sum(jnp.where(
        group[:, None] == jnp.arange(g)[None], (first - upto + each)[None],
        0), axis=1)
    tile = jnp.clip(visit + shift, 0, m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group, tile.astype(jnp.int32),
            upto[-1].astype(jnp.int32))


def _own(offsets_ref, group_ref, tile_ref, v, tm):
    """``(whole, rows)`` of visit v: whether every row of its tile is its
    group's, and a function ``rows(width)`` to the (tm, width) mask of those
    that are."""
    group = group_ref[v]
    start, end = offsets_ref[group], offsets_ref[group + 1]
    top = tile_ref[v] * tm

    def rows(width):
        row = top + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
        return (row >= start) & (row < end)

    return (top >= start) & (top + tm <= end), rows


def _rows_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
                 *scratch, tm, dims, steps):
    v, kk = pl.program_id(1), pl.program_id(2)
    whole, rows = _own(offsets_ref, group_ref, tile_ref, v, tm)
    part = jax.lax.dot_general(
        x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32)

    def store(acc):
        @pl.when(whole)
        def _():
            o_ref[...] = acc.astype(o_ref.dtype)

        @pl.when(~whole)
        def _():
            o_ref[...] = jnp.where(
                rows(o_ref.shape[1]), acc, o_ref[...].astype(jnp.float32)
            ).astype(o_ref.dtype)

    if steps == 1:
        store(part)
        return
    (acc_ref,) = scratch

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = part

    @pl.when(kk > 0)
    def _():
        acc_ref[...] += part

    @pl.when(kk == steps - 1)
    def _():
        store(acc_ref[...])


def _weights_kernel(offsets_ref, group_ref, tile_ref, x_ref, d_ref, o_ref,
                    acc_ref, *, tm):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[v]
    opens = (v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group)
    closes = (v == last) | (group_ref[jnp.minimum(v + 1, last)] != group)
    whole, rows = _own(offsets_ref, group_ref, tile_ref, v, tm)
    empty = offsets_ref[group + 1] == offsets_ref[group]

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(x, d):
        acc_ref[...] += jax.lax.dot_general(
            x, d, _TN, preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        add(x_ref[...], d_ref[...])

    # Both operands masked: a row past the last group may hold anything, and
    # 0 x NaN is no 0.
    @pl.when(~whole & ~empty)
    def _():
        add(jnp.where(rows(x_ref.shape[1]), x_ref[...], 0),
            jnp.where(rows(d_ref.shape[1]), d_ref[...], 0))

    @pl.when(closes)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


# Jitted, so that a step lowers each kernel once for each distinct shape and
# not once a call: 144 to 180 calls a step otherwise, half again the step's
# lowering time.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _rows_call(x, w, sizes, block, transposed, interpret):
    """x (m, k) against w (g, k, n), or with ``transposed`` against w (g, n,
    k) read as its transpose -> (m, n) in x's dtype."""
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tm, tk, tn = block
    steps = k // tk
    *visits, count = _visits(sizes, m, tm, False)
    if transposed:
        w_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, v, kk, offsets, group, tile: (
                group[v], j, kk))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, v, kk, offsets, group, tile: (
                group[v], kk, j))
    return pl.pallas_call(
        functools.partial(
            _rows_kernel, tm=tm, dims=_NT if transposed else _NN, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count, steps),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda j, v, kk, offsets, group, tile: (
                        tile[v], kk)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, kk, offsets, group, tile: (
                    tile[v], j)),
            scratch_shapes=(
                [pltpu.VMEM((tm, tn), jnp.float32)] if steps > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="grouped_matmul_rows_t" if transposed else "grouped_matmul_rows",
    )(*visits, x, w)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _weights_call(x, d, sizes, block, interpret):
    """Per group, x (m, k) transposed against d (m, n) over the group's rows
    -> (g, k, n) in x's dtype."""
    (m, k), n, g = x.shape, d.shape[1], sizes.shape[0]
    tm, tk, tn = block
    *visits, count = _visits(sizes, m, tm, True)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, count),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda j, kk, v, offsets, group, tile: (
                        tile[v], kk)),
                pl.BlockSpec(
                    (tm, tn), lambda j, kk, v, offsets, group, tile: (
                        tile[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda j, kk, v, offsets, group, tile: (
                    group[v], kk, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), x.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="grouped_matmul_weights",
    )(*visits, x, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _core(rows, weights, sizes, blocks, interpret):
    return _rows_call(rows, weights, sizes, blocks[0], False, interpret)


def _core_fwd(rows, weights, sizes, blocks, interpret):
    # Nothing is made here for the backward pass: it reads the operands, and
    # the result is the caller's to name.
    return _rows_call(rows, weights, sizes, blocks[0], False, interpret), (
        rows, weights, sizes)


def _core_bwd(blocks, interpret, kept, d_out):
    rows, weights, sizes = kept
    return (_rows_call(d_out, weights, sizes, blocks[1], True, interpret),
            _weights_call(rows, d_out, sizes, blocks[2], interpret), None)


_core.defvjp(_core_fwd, _core_bwd)


def kernels(rows, weights, sizes, *, tiles=None, interpret=False):
    """The kernels on rows (m, k), weights (g, k, n), sizes (g,) int32; the
    result (m, n) in the rows' dtype, rows past ``sum(sizes)`` undefined.
    ``tiles``: the forward kernel's (tm, tk, tn) in place of the rule's."""
    blocks = plan(*weights.shape[1:], rows.dtype, tiles)
    return _core(rows, weights, sizes.astype(jnp.int32), blocks, interpret)


def _path(rows, weights, tiles, interpret):
    """``(why not the kernels or None, the three kernels' tiles)``."""
    (m, k), (_, _, n) = rows.shape, weights.shape
    why = misfit((m, k, n), rows.dtype, weights.dtype, tiles,
                 lowered=not interpret)
    if why is None and not interpret and not coordinate.use_pallas():
        why = "no TPU lowering"
    return why, plan(k, n, rows.dtype, tiles)


def grouped_matmul(rows, weights, sizes, fallback, *, tiles=None,
                   interpret=False):
    """``ragged_dot`` of rows (m, k) sorted by group, weights (g, k, n) and
    sizes (g,), by the kernels where they apply (module docstring), else
    ``fallback(rows, weights, sizes)``; says which once."""
    why, blocks = _path(rows, weights, tiles, interpret)
    if why is not None:
        say(f"[experts] ragged_dot: {why}")
        return fallback(rows, weights, sizes)
    (m, k), (g, _, n) = rows.shape, weights.shape
    say(f"[experts] grouped: (m, k, n) = ({m}, {k}, {n}) g={g} "
        f"{jnp.dtype(rows.dtype).name}, tiles {blocks[0]}, gradients "
        f"{blocks[1]} rows, {blocks[2]} weights"
        + (", interpret mode" if interpret else ""))
    run = functools.partial(kernels, tiles=tiles, interpret=interpret)
    if interpret:
        return run(rows, weights, sizes)
    return jax.lax.platform_dependent(
        rows, weights, sizes, tpu=run, default=fallback)


def rows_visited(rows, weights, sizes, *, tiles=None, interpret=False):
    """The rows of the row tiles that `grouped_matmul`'s forward kernel
    visits on the same arguments: the row tile times its visits, 0 where
    the fallback runs. Over ``sum(sizes)`` it is the padding the tiles
    pay."""
    why, blocks = _path(rows, weights, tiles, interpret)
    if why is not None:
        return 0
    tm = blocks[0][0]
    return _visits(sizes.astype(jnp.int32), rows.shape[0], tm, False)[3] * tm
