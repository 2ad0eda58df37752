"""An expert layer's row movement over the held pairs only (Pallas, TPU).

A token chooses k experts; the pairs are sorted by expert, those of experts
not held here last (`models/lfm2.py`). ``order`` is that sort, ``inverse``
its inverse, ``total = sum(sizes)`` the pairs held. Only rows ``i < total``
of the sorted order reach an expert; the fallback (`lfm2.ExpertLayer`'s
broadcast, ``_permute`` and mask) moves all tokens x k rows, four times a
layer and slot. The kernels here move the held rows alone:

  gather_held   ``rows[i] = x[token_of[i]]`` for ``i < total`` (``token_of
                = order // k``), zero from ``total`` to the end of its tile;
                the backward pass is `combine_held`'s kernel without weights.
  combine_held  ``y[t] = sum of w[p] * out[i]`` over the held rows ``i`` of
                token t (p the pair of row i), the rows read in sorted order
                and added into a float32 copy of y; backward: the rows'
                cotangent ``w[p] * d_y[token_of[i]]`` and the weights'
                ``<d_y[token_of[i]], out[i]>`` by `gather_held`'s kernel.

Both walk the sorted rows in tiles, and the grid ends at the last tile that
holds a pair (its extent is a traced scalar, as `ops.grouped`'s): tiles past
it cost no step and no fetch. **The tail invariant**: every row a grouped
kernel can read holds data or zero — `gather_held` writes zeros from
``total`` to the end of its last tile, a multiple of ``grouped.ROW_TILE``;
rows past that are neither written nor read.

**Why the token side stays in VMEM**: Mosaic slices a tiled HBM or VMEM
array only along whole (8, 128) tiles (16 rows in bf16), so one row cannot
be a DMA's source or destination, nor a 16-bit array's dynamic load. A
32-bit array can be read and written one row at a dynamic index in VMEM. So
the token-side array (x, or d_y) is held whole in VMEM as 32-bit words —
a 16-bit row packed two columns a word, column c beside column c + h / 2 —
and the combine adds into a float32 (tokens, hidden) scratch; the sorted
side streams through the grid in tiles. docs/DESIGN.md section 30 has the
timings and what was tried.

**Arithmetic**: products and sums in float32, rounded once to the rows'
dtype; the fallback's bf16 products and sum are XLA's to round (on the chip
the two agree to the bit at the token cells' shapes).

**Which path** (`path`, then `either`): the kernels where the step is
lowered for the TPU (``lax.platform_dependent``) and the shapes fit
(`misfit`), else the caller's fallback. Said once for each distinct line:
``[route] held rows: ...`` or ``[route] permute: <why>``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import coordinate, grouped
from .attention import say

__all__ = ["gather_held", "combine_held", "tile", "misfit", "path", "either"]

LANES = 128
# Sorted rows a grid step, largest first: the first that divides the rows
# and fits VMEM beside the resident arrays.
TILES = (512, 256, 128)
VMEM_LIMIT_BYTES = 100 << 20
# The token-side loops (pack, zero, round) go this many rows a step.
CHUNK = 256


def _resident_bytes(tokens, hidden, dtype, rows):
    """VMEM one call takes at a tile of ``rows``: the larger kernel's, the
    combine's float32 sum and its (tokens, hidden) result beside two input
    tiles and their float32 copy; the gather's input and its packed words
    are less."""
    width = jnp.dtype(dtype).itemsize
    return tokens * hidden * (4 + width) + rows * hidden * (2 * width + 4)


def tile(shape, dtype):
    """The sorted rows a grid step for (tokens, k, hidden) = ``shape``: the
    largest of ``TILES`` that divides tokens x k and fits
    ``VMEM_LIMIT_BYTES`` beside the resident arrays; None where none does.
    Reads the shapes and the dtype only."""
    tokens, k, hidden = shape
    return next((rows for rows in TILES if tokens * k % rows == 0
                 and _resident_bytes(tokens, hidden, dtype, rows)
                 <= VMEM_LIMIT_BYTES), None)


def _chunk(tokens):
    return math.gcd(tokens, CHUNK)


def misfit(shape, dtype, lowered=True):
    """Why the kernels cannot move the rows of (tokens, k, hidden) =
    ``shape``, or None. ``lowered``: for the chip, whose lanes a packed row
    fills and whose sublanes a chunk of tokens fills."""
    tokens, k, hidden = shape
    name = jnp.dtype(dtype).name
    if name not in ("bfloat16", "float32"):
        return f"dtype {name} (the kernels take bfloat16 and float32)"
    if tile(shape, dtype) is None:
        if tokens * k % TILES[-1]:
            return (f"tokens x k = {tokens * k} is not a multiple of the "
                    f"tile {TILES[-1]}")
        need = _resident_bytes(tokens, hidden, dtype, TILES[-1])
        return (f"(tokens, hidden) = ({tokens}, {hidden}) needs "
                f"{need >> 20} MiB of VMEM")
    if not lowered:
        return None
    lanes = LANES * (4 // jnp.dtype(dtype).itemsize)
    if hidden % lanes:
        return f"hidden = {hidden} is no multiple of {lanes} lanes"
    if _chunk(tokens) % 16:
        return f"tokens = {tokens} is no multiple of 16"
    return None


def _pack(x):
    """(r, h) rows as (r, w) uint32 words: float32 bit for bit, a 16-bit
    row two columns a word (c in the high half, c + h / 2 in the low)."""
    bits = jax.lax.bitcast_convert_type(
        x.astype(jnp.float32), jnp.uint32)
    if x.dtype.itemsize == 4:
        return bits
    half = x.shape[1] // 2
    return (bits[:, :half] & jnp.uint32(0xFFFF0000)) | (bits[:, half:] >> 16)


def _unpack(words, dtype):
    """`_pack`'s inverse, as float32 (a 16-bit value exactly)."""
    f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    if jnp.dtype(dtype).itemsize == 4:
        return f32(words)
    return jnp.concatenate(
        [f32(words & jnp.uint32(0xFFFF0000)), f32(words << 16)], axis=1)


def _live(total_ref, rows):
    """The rows of this step's tile below ``total``."""
    return jnp.clip(total_ref[0] - pl.program_id(0) * rows, 0, rows)


def _by_chunks(tokens, body):
    """``body(slice)`` over the token axis, ``_chunk(tokens)`` rows a time:
    one vector operation of (tokens, hidden) would be unrolled whole."""
    c = _chunk(tokens)

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * c, c), c))
        return carry

    jax.lax.fori_loop(0, tokens // c, step, 0)


def _gather_kernel(total_ref, token_ref, *refs, rows, scaled):
    """A tile of sorted rows from the token-side array: ``x[token_of[i]]``,
    or with ``scaled`` ``w[i] * d_y[token_of[i]]`` and ``<d_y[token_of[i]],
    out[i]>``; rows past ``total`` zero."""
    if scaled:
        (scale_ref, x_ref, other_ref, o_ref, dot_ref, packed, got,
         column) = refs
    else:
        x_ref, o_ref, packed, got = refs
    live = _live(total_ref, rows)

    @pl.when(pl.program_id(0) == 0)
    def _():
        def pack(at):
            packed[at, :] = _pack(x_ref[at, :])
        _by_chunks(x_ref.shape[0], pack)

    def fetch(r, carry):
        got[pl.ds(r, 1), :] = packed[pl.ds(token_ref[0, r], 1), :]
        if scaled:
            column[pl.ds(r, 1), :] = jnp.full(
                (1, LANES), scale_ref[0, r], jnp.float32)
        return carry

    jax.lax.fori_loop(0, live, fetch, 0)
    here = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < live
    value = _unpack(got[...], o_ref.dtype)
    if not scaled:
        o_ref[...] = jnp.where(here, value, 0).astype(o_ref.dtype)
        return
    o_ref[...] = jnp.where(
        here, value * column[:, :1], 0).astype(o_ref.dtype)
    dots = jnp.where(here, jnp.sum(
        value * other_ref[...].astype(jnp.float32), axis=1, keepdims=True), 0)
    # A column to a lane-dense row: (rows, 128) transposed, first row.
    dot_ref[...] = jnp.broadcast_to(dots, (rows, LANES)).T[:1]


def _sum_kernel(total_ref, token_ref, *refs, rows, weighted):
    """Adds a tile of sorted rows, each times its weight, into the float32
    sum of its token; the result is rounded once after the last tile."""
    if weighted:
        weight_ref, rows_ref, y_ref, acc, tile32 = refs
    else:
        rows_ref, y_ref, acc, tile32 = refs
    tokens = y_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        def zero(at):
            acc[at, :] = jnp.zeros((_chunk(tokens), acc.shape[1]), acc.dtype)
        _by_chunks(tokens, zero)

    tile32[...] = rows_ref[...].astype(jnp.float32)

    def add(r, carry):
        row = tile32[pl.ds(r, 1), :]
        if weighted:
            row = row * weight_ref[0, r]
        t = token_ref[0, r]
        acc[pl.ds(t, 1), :] += row
        return carry

    jax.lax.fori_loop(0, _live(total_ref, rows), add, 0)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        def store(at):
            y_ref[at, :] = acc[at, :].astype(y_ref.dtype)
        _by_chunks(tokens, store)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _steps(total, rows):
    """Tiles that hold a pair, at least one (it writes the zeros or the
    result where nothing is held)."""
    return jnp.maximum((total[0] + rows - 1) // rows, 1)


def _by_tile(v, rows):
    """(m,) per sorted row -> (m / rows, 1, rows): a tile's scalars as one
    SMEM block."""
    return v.reshape(-1, 1, rows)


def _smem(rows):
    return pl.BlockSpec((None, 1, rows), lambda j, total: (j, 0, 0),
                        memory_space=pltpu.SMEM)


def _whole(shape):
    """A token-side array held in VMEM for the whole grid, one buffer."""
    return pl.BlockSpec(shape, lambda j, total: (0, 0),
                        pipeline_mode=pl.Buffered(1))


def _tiled(rows, hidden):
    return pl.BlockSpec((rows, hidden), lambda j, total: (j, 0))


# Jitted, so that a step lowers each kernel once for each distinct shape.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _gather_call(x, token_of, total, rows, interpret, scale=None, other=None):
    """``(m, hidden)`` sorted rows from ``x`` (tokens, hidden); with
    ``scale`` (m,) and ``other`` (m, hidden) also the rows' dot products
    with ``other`` (m,) float32."""
    (tokens, hidden), m = x.shape, token_of.shape[0]
    words = hidden // 2 if x.dtype.itemsize == 2 else hidden
    scaled = scale is not None
    total = total.reshape(1).astype(jnp.int32)
    in_specs = [_smem(rows)] + [_smem(rows)] * scaled + [
        _whole((tokens, hidden))] + [_tiled(rows, hidden)] * scaled
    out_specs = [_tiled(rows, hidden)]
    out_shape = [jax.ShapeDtypeStruct((m, hidden), x.dtype)]
    scratch = [pltpu.VMEM((tokens, words), jnp.uint32),
               pltpu.VMEM((rows, words), jnp.uint32)]
    operands = [_by_tile(token_of, rows)]
    if scaled:
        out_specs.append(pl.BlockSpec(
            (None, 1, rows), lambda j, total: (j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((m // rows, 1, rows),
                                              jnp.float32))
        scratch.append(pltpu.VMEM((rows, LANES), jnp.float32))
        operands.append(_by_tile(scale.astype(jnp.float32), rows))
    operands.append(x)
    if scaled:
        operands.append(other)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, rows=rows, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_steps(total, rows),),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        # The rows' cotangent takes ``other``'s buffer: each tile is read
        # before its own is written, and nothing reads ``other`` after.
        input_output_aliases={4: 0} if scaled else {},
        compiler_params=_params(),
        interpret=interpret,
        name="held_rows_gather",
    )(total, *operands)
    return (out[0], out[1].reshape(m)) if scaled else out[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _sum_call(sorted_rows, token_of, total, tokens, rows, interpret,
              weight=None):
    """``(tokens, hidden)``: each token's sorted rows below ``total``, each
    times its ``weight`` (m,), summed."""
    m, hidden = sorted_rows.shape
    weighted = weight is not None
    total = total.reshape(1).astype(jnp.int32)
    operands = [_by_tile(token_of, rows)] + (
        [_by_tile(weight.astype(jnp.float32), rows)] if weighted else [])
    return pl.pallas_call(
        functools.partial(_sum_kernel, rows=rows, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_steps(total, rows),),
            in_specs=[_smem(rows)] * (1 + weighted) + [_tiled(rows, hidden)],
            out_specs=_whole((tokens, hidden)),
            scratch_shapes=[pltpu.VMEM((tokens, hidden), jnp.float32),
                            pltpu.VMEM((rows, hidden), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), sorted_rows.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="held_rows_sum",
    )(total, *operands, sorted_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gather(x, token_of, total, rows, interpret):
    return _gather_call(x, token_of, total, rows, interpret)


def _gather_fwd(x, token_of, total, rows, interpret):
    return _gather_call(x, token_of, total, rows, interpret), (
        token_of, total, x.shape[0])


def _gather_bwd(rows, interpret, kept, d_rows):
    token_of, total, tokens = kept
    return (_sum_call(d_rows, token_of, total, tokens, rows, interpret),
            None, None)


_gather.defvjp(_gather_fwd, _gather_bwd)


def gather_held(x, token_of, total, *, interpret=False):
    """Rows ``(tokens x k, hidden)`` in sorted order: ``x[token_of[i]]`` for
    ``i < total``, zero to the end of that tile, undefined past it.
    ``token_of`` (tokens x k,) int32 is ``order // k``."""
    rows = tile((x.shape[0], token_of.shape[0] // x.shape[0], x.shape[1]),
                x.dtype)
    return _gather(x, token_of.astype(jnp.int32), jnp.asarray(total, jnp.int32),
                   rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine(out, weights, order, inverse, total, rows, interpret):
    return _combine_fwd(out, weights, order, inverse, total, rows,
                        interpret)[0]


def _sorted_pairs(weights, order):
    """Each sorted row's token and weight."""
    return order // weights.shape[1], weights.reshape(-1)[order]


def _combine_fwd(out, weights, order, inverse, total, rows, interpret):
    token_of, weight = _sorted_pairs(weights, order)
    y = _sum_call(out, token_of, total, weights.shape[0], rows, interpret,
                  weight)
    return y, (out, weights, order, inverse, total)


def _combine_bwd(rows, interpret, kept, d_y):
    out, weights, order, inverse, total = kept
    token_of, weight = _sorted_pairs(weights, order)
    d_out, dots = _gather_call(d_y, token_of, total, rows, interpret,
                               weight, out)
    d_weights = jnp.where(inverse < total, dots[inverse], 0)
    return (d_out, d_weights.reshape(weights.shape).astype(weights.dtype),
            None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def combine_held(out, weights, order, inverse, total, *, interpret=False):
    """``(tokens, hidden)``: token t's held rows of ``out`` (tokens x k,
    hidden), sorted by ``order`` (``inverse`` its inverse), each times its
    pair's weight in ``weights`` (tokens, k), summed; rows of ``out`` past
    ``total`` are not read."""
    tokens, k = weights.shape
    rows = tile((tokens, k, out.shape[1]), out.dtype)
    return _combine(out, weights, order.astype(jnp.int32),
                    inverse.astype(jnp.int32), jnp.asarray(total, jnp.int32),
                    rows, interpret)


def path(shape, dtype):
    """Why the expert layer of (tokens, k, hidden) = ``shape`` moves its rows
    by the permutation, or None for the kernels; says which once."""
    tokens, k, hidden = shape
    why = misfit(shape, dtype)
    if why is None and not coordinate.use_pallas():
        why = "no TPU lowering"
    if why is not None:
        say(f"[route] permute: {why}")
        return why
    rows = tile(shape, dtype)
    say(f"[route] held rows: (tokens, k, hidden) = ({tokens}, {k}, "
        f"{hidden}) {jnp.dtype(dtype).name}, tiles of {rows} sorted rows, "
        f"zeros to a multiple of {grouped.ROW_TILE}, "
        f"{_resident_bytes(tokens, hidden, dtype, rows) / 2**20:.1f} MiB "
        "of VMEM")
    return None


def either(kernels, fallback, *args, why):
    """``kernels(*args)`` where `path` said None and the step is lowered for
    the TPU, else ``fallback(*args)``."""
    if why is not None:
        return fallback(*args)
    return jax.lax.platform_dependent(*args, tpu=kernels, default=fallback)
