"""Causal grouped-query attention that never writes its scores (Pallas, TPU).

``causal_gqa(q, k, v, fallback)`` is the attention core of a decoder block:
scores q . k / sqrt(head), causal mask, float32 softmax, probabilities x v.
Written as einsums (the ``fallback`` a caller hands in: `models/lfm2.py` has
the one copy) it writes the ``(t, t)`` scores and probabilities of every head
to HBM, forward, recomputed and backward. The kernels here walk the scores
block by block with an online softmax (Dao et al., FlashAttention; after
``jax.experimental.pallas.ops.tpu.flash_attention`` and ``splash_attention``)
under one ``jax.custom_vjp``:

  forward   one program per (sequence, KV head, block of q rows): the q rows
            of ALL heads of the group, stacked ``(group x bq, head)``, meet
            one K/V block at a time, so K and V are read once a group and a
            matmul streams group x bq rows. Kept: the output and, for the
            backward pass, one float32 log-sum-exp a row.
  backward  one program per (sequence, KV head, block of keys), q blocks
            innermost: it recomputes the score block transposed (keys in
            sublanes, q rows in lanes: the row statistics are lane vectors),
            and gives dv, dk — summed over the group's heads in float32
            scratch — and dq, which stays in VMEM for the whole KV head and
            is written once.

Blocks wholly above the diagonal are skipped — their grid steps compute
nothing and their block indices repeat the last needed one, so nothing is
fetched for them —, blocks the diagonal crosses are masked.

**A band, not only a triangle** (``window``): key j is visible to query i iff
0 <= i - j < window. Blocks wholly below the band are skipped as those above
the diagonal are (the block index is clamped into the band from both sides),
blocks either edge crosses are masked, forward and backward. A row that a
crossed block hides whole leaves ``exp(MASKED - MASKED) = 1`` terms in its
running sums; the next block's ``exp(MASKED - max) = 0`` wipes them, and
every row has at least its own key. ``window=None`` or ``window >= t`` is the
causal program, operation for operation.

**Arithmetic**: the einsum path's. Operands in their dtype (bf16 in the token
cell), scores accumulated in float32 and scaled by 1 / sqrt(head), softmax
statistics in float32, probabilities rounded to the operands' dtype for the
second contraction, which accumulates in float32 and is rounded once. What
differs is the order: the probabilities are rounded before they are divided
by their sum, not after.

**Which path** (``causal_gqa``): the kernels where the computation is lowered
for the TPU (``lax.platform_dependent``, as `coordinate._dispatch`) and the
shapes fit (``misfit``), else ``fallback``; ``interpret=True`` runs the
kernels anywhere. Said once for each distinct line, on the ``info`` channel:
``[attention] blockwise: ...`` or ``[attention] einsum: <why>``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import coordinate

__all__ = ["KEPT", "causal_gqa", "blockwise", "misfit", "blocks_run", "say"]

LANES = 128
# Rows of q a head and keys a block: the largest of these that divides t.
BLOCKS = (512, 256, 128)
# The backward pass keeps a KV head's dq (group x t rows, float32 scratch and
# the output block twice) in VMEM: at most this many bytes of it.
DQ_RESIDENT_BYTES = 32 << 20
VMEM_LIMIT_BYTES = 64 << 20
# exp(MASKED - max) is 0 and MASKED - MASKED is no NaN, which -inf's is.
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)

# What the backward kernels read of the forward pass, under the names a
# caller's checkpoint policy keeps them by (`jax.checkpoint_policies.
# save_only_these_names`): q, k and v as the kernels take them, the output,
# the rows' log-sum-exp. Without them a recomputed block runs the forward
# kernel twice.
KEPT = ("attention_q", "attention_k", "attention_v", "attention_out",
        "attention_lse")

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_said = set()


def say(line):
    """Each distinct line once a process: a step's trace passes here once a
    slot and again where a block is recomputed."""
    if line not in _said:
        _said.add(line)
        from ..utils import tools

        tools.info(line)


def _blocks(t, block):
    """``(bq, bk)``: rows of q a head and keys a block, from ``block`` (one
    int or a pair; None: the largest of ``BLOCKS`` that divides t, or None
    where none does)."""
    if block is None:
        block = next((b for b in BLOCKS if t % b == 0), None)
    return (block, block) if block is None or isinstance(block, int) else (
        tuple(block))


def misfit(shape, kv_heads, dtype, block=None, lowered=True):
    """Why the kernels cannot take q of ``shape`` (n, t, heads, head) with
    ``kv_heads`` and ``dtype``, or None. ``lowered``: for the chip, whose
    tiles a block has to fill; interpret mode takes any block dividing t."""
    _, t, heads, head = shape
    if heads % kv_heads:
        return f"{heads} heads do not share {kv_heads} KV heads evenly"
    bq, bk = _blocks(t, block)
    if bq is None:
        return f"t = {t} is not a multiple of the block {BLOCKS[-1]}"
    if t % bq or t % bk:
        return f"t = {t} is not a multiple of the blocks ({bq}, {bk})"
    if not lowered:
        return None
    if bq % LANES or bk % LANES:
        return f"blocks ({bq}, {bk}) are no multiples of {LANES} lanes"
    if jnp.dtype(dtype).name not in ("bfloat16", "float32"):
        return (f"dtype {jnp.dtype(dtype).name} (the kernels take bfloat16 "
                "and float32)")
    if head % 64:
        return f"head size {head} is no multiple of 64"
    resident = heads // kv_heads * t * max(head, LANES) * 8
    if resident > DQ_RESIDENT_BYTES:
        return (f"t = {t} x group {heads // kv_heads}: dq of a KV head "
                f"({resident >> 20} MiB) does not stay in VMEM")
    return None


def blocks_run(t, bq, bk, window=None):
    """``(run, above, below)``: the (q block, key block) pairs the kernels
    compute — at or under the diagonal and, with a ``window``, reaching into
    the band —, those skipped above the diagonal and those skipped below the
    band."""
    pairs = [(i, j) for i in range(t // bq) for j in range(t // bk)]
    above = sum(1 for i, j in pairs if j * bk >= (i + 1) * bq)
    below = 0 if window is None else sum(
        1 for i, j in pairs if i * bq - (j + 1) * bk + 1 >= window)
    return len(pairs) - above - below, above, below


def _across(stat, width):
    """A row statistic held lane-replicated ``(rows, LANES)``, as ``(rows,
    width)``."""
    return jnp.tile(stat, (1, pl.cdiv(width, LANES)))[:, :width]


def _visible(i, j, bq, bk, window, keys_first):
    """0 <= q position - key position (< ``window``, where there is one)
    over block (i, j), q rows by keys or (``keys_first``) keys by q rows."""
    shape, q_axis = ((bk, bq), 1) if keys_first else ((bq, bk), 0)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (q_pos - k_pos < window)


def _when_in_band(i, j, bq, bk, window, step):
    """Run ``step(masked)`` for block (i, j) unless every key of it lies
    after every q row or, with a ``window``, a window or more before every
    one: masked where the diagonal or the band's far edge crosses it."""
    crossed = (j + 1) * bk - 1 > i * bq
    runs = j * bk < (i + 1) * bq
    if window is not None:
        crossed |= (i + 1) * bq - 1 - j * bk >= window
        runs &= i * bq - (j + 1) * bk + 1 < window
    pl.when(runs & crossed)(functools.partial(step, True))
    pl.when(runs & ~crossed)(functools.partial(step, False))


def _store_rows(ref, stat, group, bq):
    """Write a lane-replicated row statistic ``(group x bq, LANES)`` into
    block ``(1, 1, group, bq)`` of ``ref``, rows along the lanes: LANES
    rows at a time, the diagonal of their ``(LANES, LANES)`` tile summed
    down the sublanes (no transpose, and exact: one term a lane)."""
    width = min(bq, LANES)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (width, width), 1))
    for h in range(group):
        for c in range(0, bq, width):
            tile = stat[h * bq + c:h * bq + c + width, :width]
            ref[0, 0, h:h + 1, c:c + width] = jnp.sum(
                jnp.where(eye, tile, 0.0), axis=0, keepdims=True)


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                    acc_ref, *, scale, group, bq, bk, window):
    i, j = pl.program_id(2), pl.program_id(3)
    rows, head = group * bq, q_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        q = q_ref[0, 0].reshape(rows, head)
        s = jax.lax.dot_general(
            q, k_ref[0, 0], _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(i, j, bq, bk, window, False)[None],
                          s.reshape(group, bq, bk), MASKED).reshape(rows, bk)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = _across(alpha, head) * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)

    _when_in_band(i, j, bq, bk, window, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / _across(l, head)).reshape(
            group, bq, head).astype(o_ref.dtype)
        _store_rows(lse_ref, m_ref[...] + jnp.log(l), group, bq)


def _backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                     scale, group, bq, bk, window):
    j, i = pl.program_id(2), pl.program_id(3)
    last_j, last_i = pl.num_programs(2) - 1, pl.num_programs(3) - 1

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        visible = _visible(i, j, bq, bk, window, True) if masked else None
        q_rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        for h in range(group):
            q, do = q_ref[0, 0, h], do_ref[0, 0, h]
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(visible, s, MASKED)
            p = jnp.exp(s - lse_ref[0, 0, h:h + 1, :])  # (bk, bq)
            dv_acc[...] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v, do, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0, h:h + 1, :])
            dk_acc[...] += jnp.dot(
                ds.astype(q.dtype), q, preferred_element_type=jnp.float32)
            dq_acc[h, q_rows, :] += jnp.dot(
                ds.T.astype(k.dtype), k, preferred_element_type=jnp.float32)

    _when_in_band(i, j, bq, bk, window, step)

    # The scores' scale, left out of ds above, goes onto the sums.
    @pl.when(i == last_i)
    def _():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((j == last_j) & (i == last_i))
    def _():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _forward(q, k, v, block, window, interpret):
    """q (n, kv, group, t, head), k and v (n, kv, t, head) -> the output in
    q's shape and dtype and the rows' log-sum-exp (n, kv, group, t)."""
    n, kv, group, t, head = q.shape
    bq, bk = block
    rows = group * bq

    def needed(i, j):  # key block j, or the nearest that q block i sees
        j = jnp.minimum(j, ((i + 1) * bq - 1) // bk)
        if window is None:
            return j
        return jnp.maximum(j, jnp.maximum(i * bq - window + 1, 0) // bk)

    q_spec = pl.BlockSpec(
        (1, 1, group, bq, head), lambda b, h, i, j: (b, h, 0, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, head), lambda b, h, i, j: (b, h, needed(i, j), 0))
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, scale=head ** -0.5, group=group, bq=bq, bk=bk,
            window=window),
        grid=(n, kv, t // bq, t // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, pl.BlockSpec(
            (1, 1, group, bq), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((n, kv, group, t), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, head), jnp.float32)],
        compiler_params=_params(
            "parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="causal_attention_forward",
    )(q, k, v)


def _backward(q, k, v, o, lse, do, block, window, interpret):
    n, kv, group, t, head = q.shape
    bq, bk = block
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def needed(j, i):  # q block i, or the nearest that sees key block j
        i = jnp.maximum(i, j * bk // bq)
        if window is None:
            return i
        return jnp.minimum(
            i, jnp.minimum((j + 1) * bk + window - 2, t - 1) // bq)

    q_spec = pl.BlockSpec(
        (1, 1, group, bq, head),
        lambda b, h, j, i: (b, h, 0, needed(j, i), 0))
    row_spec = pl.BlockSpec(
        (1, 1, group, bq), lambda b, h, j, i: (b, h, 0, needed(j, i)))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, head), lambda b, h, j, i: (b, h, j, 0))
    dq_spec = pl.BlockSpec(
        (1, 1, group, t, head), lambda b, h, j, i: (b, h, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _backward_kernel, scale=head ** -0.5, group=group, bq=bq, bk=bk,
            window=window),
        grid=(n, kv, t // bk, t // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[dq_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((group, t, head), jnp.float32),
            pltpu.VMEM((bk, head), jnp.float32),
            pltpu.VMEM((bk, head), jnp.float32)],
        compiler_params=_params(
            "parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="causal_attention_backward",
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _core(q, k, v, block, window, interpret):
    return _forward(q, k, v, block, window, interpret)[0]


def _core_fwd(q, k, v, block, window, interpret):
    # Named here, in the rule: a policy sees the residuals where they are
    # made, and a name on `_core`'s result would mark another value. The
    # output goes on under its name too, or what reads it downstream would
    # ask for the kernel again.
    _, _, _, o, _ = kept = tuple(
        checkpoint_name(x, name) for x, name in zip(
            (q, k, v, *_forward(q, k, v, block, window, interpret)), KEPT))
    return o, kept


def _core_bwd(block, window, interpret, kept, do):
    return tuple(_backward(*kept, do, block, window, interpret))


_core.defvjp(_core_fwd, _core_bwd)


def _band(window, t):
    """``window``, or None where it hides nothing a causal mask shows."""
    return None if window is None or window >= t else int(window)


def blockwise(q, k, v, *, window=None, block=None, interpret=False):
    """The kernels on q (n, t, heads, head) and k, v (n, t, kv_heads, head),
    heads of one group adjacent; the output in q's shape and dtype.
    ``window``: keys a query sees, itself included (None: all before it);
    ``block``: rows of q a head and keys a block, one int or a pair."""
    n, t, heads, head = q.shape
    kv = k.shape[2]
    block = _blocks(t, block)
    grouped = q.reshape(n, t, kv, heads // kv, head).transpose(0, 2, 3, 1, 4)
    out = _core(grouped, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                block, _band(window, t), interpret)
    return out.transpose(0, 3, 1, 2, 4).reshape(q.shape)


def causal_gqa(q, k, v, fallback, *, window=None, block=None,
               interpret=False, kept=None):
    """Causal grouped-query attention of q (n, t, heads, head) over k, v
    (n, t, kv_heads, head), a query seeing the ``window`` keys up to its own
    (None: all of them), by the kernels where they apply (module docstring),
    else ``fallback(q, k, v, window)``; says which once. Where the kernels
    run, ``kept(KEPT, bytes)`` is told what they keep for the backward pass,
    reckoned from the shapes."""
    n, t, heads, head = q.shape
    kv, window = k.shape[2], _band(window, t)
    fallback = functools.partial(fallback, window=window)
    why = misfit(q.shape, kv, q.dtype, block, lowered=not interpret)
    if why is None and not interpret and not coordinate.use_pallas():
        why = "no TPU lowering"
    if why is not None:
        say(f"[attention] einsum: {why}")
        return fallback(q, k, v)
    bq, bk = _blocks(t, block)
    run, above, below = blocks_run(t, bq, bk, window)
    of = run + above + below
    say(f"[attention] blockwise: (n, heads, kv_heads, t, head) = "
        f"({n}, {heads}, {kv}, {t}, {head}) {jnp.dtype(q.dtype).name}, "
        f"blocks ({bq}, {bk}), "
        + (f"causal blocks skipped {above} of {of}" if window is None else
           f"window {window}, blocks run {run} of {of} (skipped {above} "
           f"above the diagonal, {below} below the band)")
        + (", interpret mode" if interpret else ""))
    if kept is not None:
        kept(KEPT, (2 * q.size + k.size + v.size) * q.dtype.itemsize
             + 4 * n * heads * t)
    kernels = functools.partial(
        blockwise, window=window, block=block, interpret=interpret)
    if interpret:
        return kernels(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, tpu=kernels, default=fallback)
