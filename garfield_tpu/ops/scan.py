"""The selective scan of a Mamba layer (Gu & Dao, arXiv:2312.00752).

``selective_scan(x, delta, A, B, C, D)`` runs, for every sequence and
channel c, a state of ``d_state`` numbers along the positions t::

    s_t = exp(delta_t[c] A[c]) * s_{t-1} + delta_t[c] x_t[c] B_t
    y_t[c] = s_t . C_t + D[c] x_t[c]

x, delta (n, t, channels); A (channels, state); B, C (n, t, state); D
(channels). The state is float32 whatever the operands' dtype; y comes back
in x's.

**Which path** (``selective_scan``): the kernels below where the step is
lowered for the TPU (``lax.platform_dependent``, as
`ops.attention.causal_gqa`) and the shapes fit (`misfit`); the chunked loop
everywhere else — the CPU, the tiny presets, any shape that does not fit.
The choice reads shapes, dtypes and the lowering only. Said once for each
distinct line, on the ``info`` channel: ``[ssm] kernels: (n, t, channels,
state) = (1, 4096, 5120, 16), channel tile 512, chunks of 128, state
float32; kept y + chunk states 52.4 MB a sequence`` or ``[ssm] chunked:
<why>; (n, t, channels, state) = ..., chunks of 64, loop over positions,
state float32``. `sequential_scan` is the plain spec.

**The kernels** (Pallas, TPU), under one ``jax.custom_vjp``:

  forward   grid (sequence, channel tile, chunk of ``CHUNK`` positions), the
            chunk axis sequential. The state, (state, channel tile) float32,
            stays in VMEM across the chunks and in vregs across a chunk's
            positions (`tile`: 16 x 512 is 8 vregs). Writes y once, in x's
            dtype, and the state at each chunk's start (n, chunks, state,
            channels) float32.
  backward  the same grid, the chunks in reverse. It carries the state's
            cotangent g in float32 from chunk to chunk; for each chunk it
            rebuilds the chunk's states in VMEM from the kept chunk-start
            state, then walks the positions backward (a_t = exp(delta_t A),
            u_t = delta_t x_t)::

              g_t   = C_t dy_t + a_{t+1} g_{t+1}
              dC_t  = sum_c s_t[:, c] dy_t[c]       dB_t = sum_c g_t[:, c] u_t[c]
              du_t  = sum_s g_t[s] B_t[s]           dx_t = delta_t du_t + D dy_t
              ddelta_t = x_t du_t + sum_s g_t a_t A s_{t-1}
              dA = sum g_t a_t delta_t s_{t-1}      dD = sum dy_t x_t

            dA and dD sum in float32 scratch across the chunks; dB and dC
            leave the kernel as float32 partials a channel tile, summed
            outside it.

**Layout.** x_t and delta_t are channel rows (lanes) that broadcast over the
state's sublanes; B_t and C_t are state columns that broadcast over the
channel lanes. B and C reach the kernels transposed, (n, state, t), so that
a chunk is one (state, 128) block, and a position's column is picked by a
lane mask and a lane sum (exact: one term). A 16-bit row cannot be loaded
at a dynamic index, so each chunk's x and dy are read whole and widened to
float32 in VMEM. A sequence that is no multiple of ``CHUNK`` is padded with
positions whose delta is 0, which leave the state as it is.

**Kept, by name** (``KEPT``): the forward rule names y and the chunk-start
states, so that a block recomputed under a ``save_only_these_names`` policy
keeps them and does not run the forward kernel again; the backward kernel
reads the states, the memory's backward reads y.

**The chunked loop** (`_chunked`). Differentiated as written, a scan over
the positions keeps every position's state for the backward pass: t x
channels x state float32 a sequence (1.34 GB at t 4,096, 5,120 channels,
state 16). An outer ``lax.scan`` walks chunks of the sequence, carrying the
state in float32, and each chunk's body is recomputed in the backward pass
(``jax.checkpoint``): what the backward pass keeps is the state at each
chunk's start, and one chunk's positions at a time while it runs. Inside a
chunk a loop over the positions carries the state (``lax.scan``: one
position's elementwise update a step, which XLA fuses whole). The chunk's
length comes from the shapes (`chunk_length`): the longest power of two up
to ``CHUNK_MAX`` whose per-position states stay within ``CHUNK_BYTES``; a
sequence that is no multiple of it is padded with positions whose delta is
0.

docs/DESIGN.md section 31 has the timings of both and of the forms tried.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import coordinate
from .attention import say

__all__ = ["selective_scan", "sequential_scan", "kernels", "misfit", "tile",
           "chunk_length", "KEPT", "CHUNK", "CHUNK_MAX", "CHUNK_BYTES"]

# 64 positions ran the cell's chunked scan fastest of 64, 128 and 256 on a
# v5e.
CHUNK_MAX = 64
# Bytes of float32 states a chunk's positions may hold at once.
CHUNK_BYTES = 64 << 20

LANES = 128
# The kernels' positions a chunk: one lane-dense (state, 128) block of B^T
# and C^T.
CHUNK = LANES
# Channels a grid step, largest first: the first that divides the channels
# and keeps the float32 state within ``STATE_BYTES`` (8 vregs).
TILES = (512, 256, 128)
STATE_BYTES = 32 << 10
# Positions a step of the kernels' loops (`_positions`).
UNROLL = 8
VMEM_LIMIT_BYTES = 32 << 20

# What the backward pass reads of the forward kernel, under the names a
# caller's checkpoint policy keeps them by: y and the chunk-start states.
KEPT = ("ssm_y", "ssm_states")


def chunk_length(n, t, channels, state):
    """Positions a chunk: the longest power of two up to ``CHUNK_MAX`` (and
    up to t) whose n x channels x state float32 states a position fit in
    ``CHUNK_BYTES`` together; at least 1."""
    per_position = n * channels * state * 4
    length = 1
    while (2 * length <= min(t, CHUNK_MAX)
           and 2 * length * per_position <= CHUNK_BYTES):
        length *= 2
    return length


def _step(a_t, s, inputs):
    """One position: the state (n, state, channels) and y (n, channels)."""
    x_t, d_t, b_t, c_t = (v.astype(jnp.float32) for v in inputs)
    s = (jnp.exp(d_t[:, None, :] * a_t[None]) * s
         + (d_t * x_t)[:, None, :] * b_t[:, :, None])
    return s, jnp.sum(s * c_t[:, :, None], axis=1)


def sequential_scan(x, delta, A, B, C, D):
    """The spec: one ``lax.scan`` over every position, float32, nothing
    recomputed."""
    n, _, channels = x.shape
    s0 = jnp.zeros((n, A.shape[1], channels), jnp.float32)
    time_major = [jnp.swapaxes(v, 0, 1) for v in (x, delta, B, C)]
    _, y = jax.lax.scan(
        functools.partial(_step, A.T.astype(jnp.float32)), s0, time_major)
    y = jnp.swapaxes(y, 0, 1) + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype)


def _chunked(x, delta, A, B, C, D, length):
    """The scan's y in x's dtype: chunks of ``length`` positions, each
    recomputed in the backward pass."""
    n, t, channels = x.shape
    chunks = -(-t // length)
    pad = chunks * length - t
    a_t = A.T.astype(jnp.float32)

    def arrange(v):  # (n, t, m) -> (chunks, length, n, m), zeros after t
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return v.reshape(n, chunks, length, -1).transpose(1, 2, 0, 3)

    @jax.checkpoint
    def chunk(s, inputs):
        return jax.lax.scan(functools.partial(_step, a_t), s, inputs)

    s0 = jnp.zeros((n, A.shape[1], channels), jnp.float32)
    _, y = jax.lax.scan(chunk, s0, tuple(map(arrange, (x, delta, B, C))))
    y = y.transpose(2, 0, 1, 3).reshape(n, chunks * length, channels)[:, :t]
    y = y + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype)


def tile(channels, state):
    """Channels a grid step: the largest of ``TILES`` that divides
    ``channels`` and keeps a (state, tile) float32 state within
    ``STATE_BYTES``; None where none does."""
    return next((c for c in TILES if channels % c == 0
                 and state * c * 4 <= STATE_BYTES), None)


def misfit(shape, dtype, lowered=True):
    """Why the kernels cannot scan (n, t, channels, state) = ``shape`` with x
    of ``dtype``, or None. ``lowered``: for the chip, whose sublanes the
    state fills."""
    _, _, channels, state = shape
    name = jnp.dtype(dtype).name
    if name not in ("bfloat16", "float32"):
        return f"dtype {name} (the kernels take bfloat16 and float32)"
    if channels % LANES:
        return f"channels = {channels} is not a multiple of {LANES}"
    if tile(channels, state) is None:
        return (f"state = {state}: a (state, {LANES}) float32 state exceeds "
                f"{STATE_BYTES >> 10} KiB")
    if lowered and state % 8:
        return f"state = {state} is not a multiple of 8 sublanes"
    return None


def _positions(body, carry):
    """``body(i, carry)`` for i = 0 .. CHUNK - 1, ``UNROLL`` positions a
    loop step: Mosaic unrolls a ``fori_loop`` whole or not at all, and a
    step of one position runs the kernels 3.7 times slower."""
    def step(k, carry):
        for i in range(UNROLL):
            carry = body(k * UNROLL + i, carry)
        return carry

    return jax.lax.fori_loop(0, CHUNK // UNROLL, step, carry)


def _column(m, lanes, i):
    """Column ``i`` of a (state, CHUNK) value, as (state, 1)."""
    return jnp.sum(jnp.where(lanes == i, m, 0.0), axis=1, keepdims=True)


def _forward_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, skip_ref, y_ref,
                    s0_ref, s_scr, u_scr, y_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[0, 0] = s_scr[...]
    x = x_ref[0].astype(jnp.float32)
    u_scr[...] = d_ref[0] * x
    a_t = a_ref[...]
    b, c = (r[0].astype(jnp.float32) for r in (b_ref, c_ref))
    lanes = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)

    def position(i, s):
        row = pl.ds(i, 1)
        s = (jnp.exp(d_ref[0, row, :] * a_t) * s
             + _column(b, lanes, i) * u_scr[row, :])
        y_scr[row, :] = jnp.sum(s * _column(c, lanes, i), axis=0,
                                keepdims=True)
        return s

    s_scr[...] = _positions(position, s_scr[...])
    y_ref[0] = (y_scr[...] + skip_ref[...] * x).astype(y_ref.dtype)


def _backward_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, skip_ref, s0_ref,
                     dy_ref, dx_ref, dd_ref, db_ref, dc_ref, da_ref,
                     dskip_ref, h_scr, da_scr, dskip_scr, st_scr, u_scr,
                     dy_scr, du_scr, dg_scr):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)
        da_scr[...] = jnp.zeros_like(da_scr)
        dskip_scr[...] = jnp.zeros_like(dskip_scr)

    x = x_ref[0].astype(jnp.float32)
    delta = d_ref[0]
    u_scr[...] = delta * x
    dy_scr[...] = dy_ref[0].astype(jnp.float32)
    a_t = a_ref[...]
    b, c = (r[0].astype(jnp.float32) for r in (b_ref, c_ref))
    lanes = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)

    # The chunk's states again: st_scr[i + 1] is s at position i,
    # st_scr[0] the state before the chunk.
    st_scr[0] = s0_ref[0, 0]

    def rebuild(i, s):
        row = pl.ds(i, 1)
        s = (jnp.exp(d_ref[0, row, :] * a_t) * s
             + _column(b, lanes, i) * u_scr[row, :])
        st_scr[i + 1] = s
        return s

    _positions(rebuild, st_scr[0])

    def position(j, carry):
        h, da, db, dc = carry
        i = CHUNK - 1 - j
        row = pl.ds(i, 1)
        d_i, u_i, dy_i = d_ref[0, row, :], u_scr[row, :], dy_scr[row, :]
        s_i = st_scr[i + 1]
        a_i = jnp.exp(d_i * a_t)
        g = _column(c, lanes, i) * dy_i + h
        du_scr[row, :] = jnp.sum(g * _column(b, lanes, i), axis=0,
                                 keepdims=True)
        gas = g * a_i * st_scr[i]
        dg_scr[row, :] = jnp.sum(gas * a_t, axis=0, keepdims=True)
        db = jnp.where(lanes == i, jnp.sum(g * u_i, axis=1, keepdims=True),
                       db)
        dc = jnp.where(lanes == i, jnp.sum(s_i * dy_i, axis=1,
                                           keepdims=True), dc)
        return a_i * g, da + gas * d_i, db, dc

    zeros = jnp.zeros_like(b)
    h, da, db, dc = _positions(
        position, (h_scr[...], jnp.zeros_like(a_t), zeros, zeros))
    h_scr[...] = h
    da_scr[...] += da
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc
    dy, du = dy_scr[...], du_scr[...]
    dx_ref[0] = (delta * du + skip_ref[...] * dy).astype(dx_ref.dtype)
    dd_ref[0] = x * du + dg_scr[...]
    dskip_scr[...] += jnp.sum(dy * x, axis=0, keepdims=True)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        da_ref[0] = da_scr[...]
        dskip_ref[0] = dskip_scr[...]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(shape, width, reverse):
    """BlockSpecs of the kernels' operands over grid (sequence, channel
    tile, chunk), the chunks in reverse where ``reverse``."""
    n, t, channels, state = shape
    chunks = t // CHUNK
    at = (lambda k: chunks - 1 - k) if reverse else (lambda k: k)
    rows = pl.BlockSpec((1, CHUNK, width), lambda b, j, k: (b, at(k), j))
    lanes = pl.BlockSpec((1, state, CHUNK), lambda b, j, k: (b, 0, at(k)))
    starts = pl.BlockSpec(
        (1, 1, state, width), lambda b, j, k: (b, at(k), 0, j))
    per_tile = pl.BlockSpec(
        (1, 1, state, CHUNK), lambda b, j, k: (b, j, 0, at(k)))
    param = pl.BlockSpec((state, width), lambda b, j, k: (0, j))
    skip = pl.BlockSpec((1, width), lambda b, j, k: (0, j))
    summed = pl.BlockSpec((1, state, width), lambda b, j, k: (b, 0, j))
    summed_row = pl.BlockSpec((1, 1, width), lambda b, j, k: (b, 0, j))
    return rows, lanes, starts, per_tile, param, skip, summed, summed_row


def _forward(x, delta, a_t, bt, ct, skip, width, interpret):
    """Arranged operands (`kernels`) -> y in x's shape and dtype and the
    chunk-start states (n, chunks, state, channels) float32."""
    n, t, channels = x.shape
    state = a_t.shape[0]
    rows, lanes, starts, _, param, skip_spec, _, _ = _specs(
        (n, t, channels, state), width, False)
    return pl.pallas_call(
        _forward_kernel,
        grid=(n, channels // width, t // CHUNK),
        in_specs=[rows, rows, param, lanes, lanes, skip_spec],
        out_specs=[rows, starts],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((n, t // CHUNK, state, channels),
                                 jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((state, width), jnp.float32),
            pltpu.VMEM((CHUNK, width), jnp.float32),
            pltpu.VMEM((CHUNK, width), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="selective_scan_forward",
    )(x, delta, a_t, bt, ct, skip)


def _backward(x, delta, a_t, bt, ct, skip, starts, dy, width, interpret):
    n, t, channels = x.shape
    state = a_t.shape[0]
    tiles = channels // width
    rows, lanes, start, per_tile, param, skip_spec, summed, summed_row = (
        _specs((n, t, channels, state), width, True))
    f32 = jnp.float32
    dx, dd, db, dc, da, dskip = pl.pallas_call(
        _backward_kernel,
        grid=(n, tiles, t // CHUNK),
        in_specs=[rows, rows, param, lanes, lanes, skip_spec, start, rows],
        out_specs=[rows, rows, per_tile, per_tile, summed, summed_row],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype),
            jax.ShapeDtypeStruct((n, tiles, state, t), f32),
            jax.ShapeDtypeStruct((n, tiles, state, t), f32),
            jax.ShapeDtypeStruct((n, state, channels), f32),
            jax.ShapeDtypeStruct((n, 1, channels), f32)],
        scratch_shapes=[
            pltpu.VMEM((state, width), f32),
            pltpu.VMEM((state, width), f32),
            pltpu.VMEM((1, width), f32),
            pltpu.VMEM((CHUNK + 1, state, width), f32),
            *[pltpu.VMEM((CHUNK, width), f32)] * 4],
        compiler_params=_params(),
        interpret=interpret,
        name="selective_scan_backward",
    )(x, delta, a_t, bt, ct, skip, starts, dy)
    return (dx, dd, jnp.sum(da, axis=0),
            jnp.sum(db, axis=1).astype(bt.dtype),
            jnp.sum(dc, axis=1).astype(ct.dtype),
            jnp.sum(dskip, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(x, delta, a_t, bt, ct, skip, width, interpret):
    return _forward(x, delta, a_t, bt, ct, skip, width, interpret)[0]


def _core_fwd(x, delta, a_t, bt, ct, skip, width, interpret):
    # Named here, in the rule, as `ops.attention._core_fwd` names its own:
    # a policy sees the residuals where they are made.
    y, starts = (checkpoint_name(v, name) for v, name in zip(
        _forward(x, delta, a_t, bt, ct, skip, width, interpret), KEPT))
    return y, (x, delta, a_t, bt, ct, skip, starts)


def _core_bwd(width, interpret, residuals, dy):
    return _backward(*residuals, dy, width, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def kernels(x, delta, A, B, C, D, *, interpret=False):
    """y of the selective scan by the kernels (module docstring), in x's
    dtype: delta widened to float32, B and C transposed to (n, state, t),
    the positions padded to a multiple of ``CHUNK``."""
    n, t, channels = x.shape
    pad = -t % CHUNK

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    bt, ct = (padded(v).transpose(0, 2, 1) for v in (B, C))
    y = _core(padded(x), padded(delta.astype(jnp.float32)),
              A.T.astype(jnp.float32), bt, ct,
              D.astype(jnp.float32).reshape(1, channels),
              tile(channels, A.shape[1]), interpret)
    return y[:, :t] if pad else y


def selective_scan(x, delta, A, B, C, D, *, kept=None, interpret=False):
    """y of the selective scan (module docstring) in x's dtype, by the
    kernels where they apply, else by `_chunked` in chunks of
    `chunk_length` positions; says which once. Where the kernels run,
    ``kept(KEPT, bytes)`` is told what they keep for the backward pass,
    reckoned from the shapes. ``interpret=True`` runs the kernels anywhere."""
    n, t, channels = x.shape
    state = A.shape[1]
    shape = f"(n, t, channels, state) = ({n}, {t}, {channels}, {state})"
    length = chunk_length(n, t, channels, state)
    chunked = functools.partial(_chunked, length=length)
    why = misfit((n, t, channels, state), x.dtype, lowered=not interpret)
    if why is None and not interpret and not coordinate.use_pallas():
        why = "no TPU lowering"
    if why is not None:
        say(f"[ssm] chunked: {why}; {shape}, chunks of {length}, loop over "
            "positions, state float32")
        return chunked(x, delta, A, B, C, D)
    chunks = -(-t // CHUNK)
    per_sequence = chunks * channels * (
        CHUNK * jnp.dtype(x.dtype).itemsize + state * 4)
    say(f"[ssm] kernels: {shape}, channel tile {tile(channels, state)}, "
        f"chunks of {CHUNK}, state float32; kept y + chunk states "
        f"{per_sequence / 1e6:.3g} MB a sequence"
        + (", interpret mode" if interpret else ""))
    if kept is not None:
        kept(KEPT, n * per_sequence)
    run = functools.partial(kernels, interpret=interpret)
    if interpret:
        return run(x, delta, A, B, C, D)
    return jax.lax.platform_dependent(
        x, delta, A, B, C, D, tpu=run, default=chunked)
