"""Slot-twin layer library: composable primitives for slot-fused models.

The slot-fused formulation (see ``models/slotfused.py`` for the design
provenance and measurements) computes per-worker ("per-slot") gradients by
running the model ONCE on the flat ``(slots * b)`` batch and making only
the parameter-cotangent contractions slot-resolved. r5 proved the idea on
two hand-written monolithic forwards (ResNet, Cifarnet); this module
factors the per-layer machinery out so a twin for a new model family is a
thin graph description over these primitives (the per-model assemblies and
the ``SLOTFUSED_MODELS`` registry live in ``slotfused.py``):

  - ``slot_conv``       — custom-vjp convolution: primal and dx run fused
    on the flat batch with the shared kernel (``w_st[0]``); only the dw
    rule is slot-resolved. Supports ``feature_group_count`` so the
    depthwise families (mobilenet/v2) fold too.
  - ``bn_train``        — per-slot BatchNorm statistics over the flat
    batch, flax-numerics-compatible (f32 stats, compute-dtype normalize).
  - ``layer_norm``      — per-example feature-axis statistics (flax
    ``nn.LayerNorm`` numerics: f32 fast-variance stats, compute-dtype
    normalize) with PER-SLOT scale/bias; the stats need no slot
    resolution — only the affine parameters are worker-resolved.
  - ``dense``           — slot-batched matmul head ('sbf,sfo->sbo').
  - ``seq_dense``       — the sequence-layout sibling: (slots*b, T, F)
    through a per-slot kernel via the same slot-batched einsum with T
    beside the batch rows ('sbtf,sfo->sbto').
  - ``attn_core``       — the multi-head attention core (QK^T -> masked
    softmax -> PV) on per-example arithmetic, SHARED VERBATIM by the
    flax transformer modules and the slot twins (models/transformer.py
    imports it), so the fused flat batch and the unrolled per-slot
    reference run bit-identical attention math. Softmax statistics in
    f32 with an explicit in-order add chain for the denominator (the
    GARFIELD_SORTNET-era bitwise discipline: no backend reassociation),
    and a finite large-negative causal mask (never -inf — a masked-row
    ``exp(-inf - -inf)`` NaNs).
  - ``embed`` / ``pos_embed`` — token-embedding gather from the STACKED
    table (the autodiff transpose is a per-slot scatter-add — the
    embedding's per-slot gradient) and the learned-positional broadcast
    add (transpose: per-slot sum over the batch rows).
  - ``gelu``            — re-exported ``jax.nn.gelu`` so model and twin
    share one callable.
  - ``bias_add``        — per-slot bias broadcast onto the flat batch.
  - ``max_pool`` / ``avg_pool`` / ``global_avg_pool`` — plain flat-batch
    ops (no slot resolution needed; kept here so twins import one module).

Every primitive takes a ``SlotCtx``: the per-trace context holding the
slot geometry plus the PRECOMPUTED slot-membership machinery — the
``(slots, slots*nb)`` one-hot matrix and the segment-id vector are built
once per trace and shared by all ~20 BN layers of a deep twin, instead of
re-emitted per layer.

The ORDER of the examples inside the flat batch is a property of the
context too (``flat_batch_order``, chosen once per trace from ``slots``,
``nb`` and the compute dtype): slot-major (example ``n = s*nb + b``)
or slot-minor (``n = b*slots + s``). On the TPU XLA keeps the twin's
activations with the batch in the sublane dimension (layout
``{3,0,2,1}``: physically H, W, N, C, one (16, 128) bf16 tile over
(N, C)), so splitting N into (slots, nb) is a bitcast only where the
MINOR factor fills the sublane tile. Where ``nb`` misses it and
``slots`` fills it (16 x 25 in bf16) the slot-major split costs one
transposing copy of every activation and every cotangent per dw
(``_slot_conv_bwd``), and slot-minor is free; where ``nb`` fills it
(8 x 256) it is the other way round. No primitive spells the mapping
itself: ``SlotCtx.slot_view`` / ``SlotCtx.flat`` (and ``seg_ids`` /
``slot_matrix``) hold it. What leaves the twin is slot-leading in
either order.

Two env knobs select the per-slot reduction formulations for on-chip A/B
(both read at TRACE time — a change needs a fresh trace, i.e. a new jit or
an unjitted call):

  - ``GARFIELD_SLOTFUSED_BN=matmul|segsum`` (default matmul): per-slot BN
    statistics as the one-hot slot matmul ``S @ (spatial reduce)`` (the r5
    formulation) or as a segment sum over slot ids
    (``jax.ops.segment_sum``, ``indices_are_sorted`` where the flat
    batch is slot-major). The matmul
    keeps everything on the MXU; the segment sum avoids materializing the
    ``(slots, slots*b)`` operand and lowers to an in-order add — which of
    the two schedules better against the backward's grouped dw convs is a
    chip question (PERF.md round 7).
  - ``GARFIELD_SLOTFUSED_DW=grouped|unroll|segsum`` (default grouped):
    the dw formulation of ``slot_conv``'s backward plus its epilogue.
    ``grouped`` and ``unroll`` are the r5 modes (one batch-grouped conv
    vs n per-slot convs + stack); ``segsum`` keeps the grouped dw convs
    but routes the EPILOGUE — the per-slot bias/BN cotangent reductions,
    i.e. the transpose of every ``slot_expand`` broadcast — through the
    same segment machinery (gather forward, sorted segment-sum
    transpose) instead of the ``S.T`` matmul twin, so the ~20 BN
    slot-stat reductions of a deep twin stop competing for the MXU
    against the grouped convs they are scheduled with.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "SlotCtx",
    "flat_batch_order",
    "slot_conv",
    "conv",
    "bn_train",
    "layer_norm",
    "dense",
    "seq_dense",
    "attn_core",
    "softmax_chain",
    "embed",
    "pos_embed",
    "gelu",
    "bias_add",
    "relu",
    "max_pool",
    "avg_pool",
    "global_avg_pool",
]

_DN = ("NHWC", "HWIO", "NHWC")


def bn_stats_mode():
    """BN per-slot statistics formulation (read at trace time)."""
    return os.environ.get("GARFIELD_SLOTFUSED_BN", "matmul")


def dw_mode():
    """slot_conv dw / epilogue formulation (read at trace time)."""
    return os.environ.get("GARFIELD_SLOTFUSED_DW", "grouped")


def sublane_rows(dtype):
    """Rows of the TPU's packed sublane tile for ``dtype``: 8 for 32-bit
    (and wider) elements, 16 for bf16, 32 for 8-bit."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def flat_batch_order(slots, nb, dtype):
    """The order of examples in the twin's flat batch: ``(order, why)``.

    One function of what a trace can see, no model and no platform in it.
    ``"slot-major"`` (example ``n = s*nb + b``) wherever ``nb`` is a
    multiple of the sublane tile of the compute dtype, and wherever
    ``slots`` is not; ``"slot-minor"`` (``n = b*slots + s``) where ``nb``
    misses the tile and ``slots`` fills it. The minor factor of the batch
    split has to fill the tile for the split to be a bitcast of the
    batch-in-sublanes layout XLA gives the activations (module
    docstring); where neither does (8 x 25 in bf16) the compile is mixed
    and the order stays slot-major (PERF.md section 7).
    """
    rows, name = sublane_rows(dtype), jnp.dtype(dtype).name
    if nb % rows == 0:
        return "slot-major", f"nb={nb} fills the {rows}-row {name} tile"
    if slots % rows:
        return "slot-major", (
            f"neither nb={nb} nor slots={slots} fills the {rows}-row "
            f"{name} tile"
        )
    return "slot-minor", (
        f"nb={nb} misses the {rows}-row {name} tile, slots={slots} fills it"
    )


def _slot_view(x, slots, slot_minor):
    """Flat (slots*nb, ...) -> slot-leading (slots, nb, ...).

    Slot-major: a reshape. Slot-minor: the (nb, slots, ...) reshape with
    its two leading axes swapped — a transposed VIEW that the consuming
    contraction takes in its dimension numbers (the dw convolution fuses
    the swap into its operand), never a (slots, nb)-major copy.
    """
    nb = x.shape[0] // slots
    if slot_minor:
        return jnp.swapaxes(x.reshape((nb, slots) + x.shape[1:]), 0, 1)
    return x.reshape((slots, nb) + x.shape[1:])


def _flat(x_st, slot_minor):
    """Slot-leading (slots, nb, ...) -> flat (slots*nb, ...): the inverse
    of ``_slot_view``."""
    if slot_minor:
        x_st = jnp.swapaxes(x_st, 0, 1)
    return x_st.reshape((-1,) + x_st.shape[2:])


class SlotCtx:
    """Per-trace slot geometry + precomputed membership machinery.

    Built once per ``slot_grad_fn`` trace (``slotfused.build_slot_grad_fn``)
    and threaded through every primitive, so the slot matrix / segment ids
    exist once in the traced graph no matter how many layers consume them.

    The context also owns the ORDER of the flat batch (``order`` /
    ``order_why`` from ``flat_batch_order``; ``slot_minor`` as a bool):
    ``seg_ids`` and ``slot_matrix`` say which slot a flat row belongs to,
    ``slot_view`` and ``flat`` map between the flat batch and a
    slot-leading ``(slots, nb, ...)`` array. Every primitive and every
    twin assembly goes through these four, so the order is decided in one
    place and nothing downstream of the twin sees it.
    """

    def __init__(self, slots, nb, dtype):
        self.slots = int(slots)
        self.nb = int(nb)
        self.dtype = dtype
        self.bn_mode = bn_stats_mode()
        self.dw = dw_mode()
        if self.bn_mode not in ("matmul", "segsum"):
            raise ValueError(
                f"GARFIELD_SLOTFUSED_BN must be matmul|segsum, "
                f"got {self.bn_mode!r}"
            )
        if self.dw not in ("grouped", "unroll", "segsum"):
            raise ValueError(
                f"GARFIELD_SLOTFUSED_DW must be grouped|unroll|segsum, "
                f"got {self.dw!r}"
            )
        self.order, self.order_why = flat_batch_order(
            self.slots, self.nb, dtype
        )
        self.slot_minor = self.order == "slot-minor"
        # Slot-membership ids of the flat batch — a host constant; jnp ops
        # lift it once. Slot-major: example k belongs to slot k // nb
        # (sorted); slot-minor: to slot k % slots (not sorted).
        ids = np.arange(self.slots)
        self.seg_ids = (
            np.tile(ids, self.nb) if self.slot_minor
            else np.repeat(ids, self.nb)
        )
        self._S = {}

    def slot_matrix(self, dtype):
        """Constant (slots, slots*nb) one-hot membership matrix, built at
        most once per dtype per trace.

        Per-slot segment reductions over the flat batch are expressed as
        this tiny matmul instead of a (slots, nb, ...) reshaped reduce:
        XLA lowers the grouped reduce over the MAJOR dim through
        transposing copies (traced 1.4 ms/step at ResNet-18 n=8), while
        ``S @ (per-example reduction)`` stays in natural layouts — and its
        autodiff transpose, ``S.T @ _``, is the equally clean per-slot
        broadcast. The columns follow the context's flat-batch order.
        """
        key = jnp.dtype(dtype).name
        if key not in self._S:
            eye = jnp.eye(self.slots, dtype=dtype)
            self._S[key] = (
                jnp.tile(eye, (1, self.nb)) if self.slot_minor
                else jnp.repeat(eye, self.nb, axis=1)
            )
        return self._S[key]

    def slot_view(self, x):
        """Flat (slots*nb, ...) -> slot-leading (slots, nb, ...) view."""
        return _slot_view(x, self.slots, self.slot_minor)

    def flat(self, x_st):
        """Slot-leading (slots, nb, ...) -> flat batch in this context's
        order (the inverse of ``slot_view``)."""
        return _flat(x_st, self.slot_minor)


def slot_reduce(ctx, e):
    """Per-slot segment reduction: (slots*nb, C) f32 -> (slots, C) f32.

    ``matmul`` mode: ``S @ e`` (MXU). ``segsum`` mode: segment sum over
    the slot ids (no (slots, slots*nb) operand; in-order adds, so the
    two modes are f32-rounding-equal for equal-length segments summed in
    index order — equality-pinned in tests/test_slotfused.py). The ids
    are sorted only where the flat batch is slot-major.
    """
    if ctx.bn_mode == "segsum":
        return jax.ops.segment_sum(
            e, ctx.seg_ids, num_segments=ctx.slots,
            indices_are_sorted=not ctx.slot_minor,
        )
    return ctx.slot_matrix(e.dtype) @ e


def slot_expand(ctx, v_st, spatial_dims):
    """(slots, C) per-slot vector -> flat per-example (slots*nb, 1..1, C).

    ``grouped``/``unroll`` dw modes: the ``S.T`` matmul twin of the stats
    reduction — its autodiff transpose is (spatial reduce -> ``S @ _``),
    the same copy-free route as the forward stats (a broadcast+reshape
    formulation transposes to the 5-D grouped reduce this library avoids).
    ``segsum`` dw mode: a row gather over the slot ids, whose
    transpose is a segment-sum scatter-add — the dw-epilogue
    formulation (module docstring): per-slot bias/BN cotangents leave the
    MXU to the grouped dw convs.
    """
    if ctx.dw == "segsum":
        flat = v_st[ctx.seg_ids]  # gather; transpose = segment sum
    else:
        flat = ctx.slot_matrix(v_st.dtype).T @ v_st  # (slots*nb, C)
    return flat.reshape(
        (flat.shape[0],) + (1,) * spatial_dims + (flat.shape[-1],)
    )


# --------------------------------------------------------------------------
# Convolution: fused primal/dx, per-slot dw (custom vjp)
# --------------------------------------------------------------------------

def _conv(x, w, stride, padding, groups):
    return lax.conv_general_dilated(
        x, w, window_strides=stride, padding=padding,
        dimension_numbers=_DN, feature_group_count=groups,
    )


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _slot_conv(x, w_st, stride, padding, slots, groups, slot_minor):
    return _conv(x, w_st[0], stride, padding, groups)


def _slot_conv_fwd(x, w_st, stride, padding, slots, groups, slot_minor):
    return _conv(x, w_st[0], stride, padding, groups), (x, w_st[0])


def _slot_conv_bwd(stride, padding, slots, groups, slot_minor, res, dy):
    """dx fused over the flat batch; dw slot-resolved.

    dw formulations (``GARFIELD_SLOTFUSED_DW``, read at trace time):
    ``grouped`` (default) and ``segsum`` run ONE batch-grouped conv via
    the transpose of the slot-vmapped conv over the slot-leading view of
    the flat activations and cotangents, so the (slots, ...) result needs
    no stacking DUS (``segsum`` differs only in the epilogue reductions
    around the convs — see ``slot_expand``).

    That view is free only in the right flat-batch order
    (``flat_batch_order``; ``slot_minor`` is its static answer). XLA:TPU
    keeps these activations batch-in-sublanes — in the compiled ResNet-50
    gradient pass at 16 x 25 bf16, ``bf16[400,32,32,256]{3,0,2,1:
    T(8,128)(2,1)}``, physically H, W, N, C with a 16-row tile over N — and
    the slot-major split N -> (16, 25) cuts tiles apart: the compiled text
    puts a ``copy`` to ``{3,2,1,0}`` in front of the ``bitcast`` to
    ``bf16[16,25,32,32,256]`` of every activation and every cotangent
    (125 top-level copies in that text, 40 slot-minor; 15 ms of an 86 ms
    step on the chip, PERF.md section 6). Slot-minor splits N -> (25, 16):
    the slots fill the tile, the split is a bitcast and the (b, s) swap
    rides in the dw convolution's operand. At 8 x 256 the slot-major
    split is the bitcast and slot-minor would add the copies.

    ``unroll`` is the r5 A/B escape hatch: n per-slot convs + stack over
    the same view (traced 3.0 ms/step of operand copies + 1.6 ms of stack
    DUS at n=8 ResNet-18, slot-major — the b=25 slot slices misalign with
    the (8,128) tile).
    """
    x, w0 = res
    # dx: one fused transposed conv over the whole n*b batch.
    dx = jax.linear_transpose(
        lambda x_: _conv(x_, w0, stride, padding, groups), x
    )(dy)[0]
    xs = _slot_view(x, slots, slot_minor)
    dys = _slot_view(dy, slots, slot_minor)
    if dw_mode() != "unroll":
        def vconv(w_st_):
            return jax.vmap(
                lambda xi, wi: _conv(xi, wi, stride, padding, groups)
            )(xs, w_st_)

        w_like = jnp.broadcast_to(w0[None], (slots,) + w0.shape)
        dw_st = jax.linear_transpose(vconv, w_like)(dys)[0]
        return dx, dw_st
    dws = [
        jax.linear_transpose(
            lambda w_: _conv(xs[i], w_, stride, padding, groups), w0
        )(dys[i])[0]
        for i in range(slots)
    ]
    return dx, jnp.stack(dws)


_slot_conv.defvjp(_slot_conv_fwd, _slot_conv_bwd)


def slot_conv(x, w_st, stride, padding, slots, groups=1, slot_minor=False):
    """Convolution over the flat (slots*b) batch with a STACKED kernel.

    ``w_st`` is (slots, kh, kw, ci/groups, co) with all slot rows equal (a
    broadcast of the shared kernel); the primal and dx use ``w_st[0]`` at
    the fused batch, and the custom vjp returns the PER-SLOT weight
    gradients as ``w_st``'s cotangent — the only place worker-resolved
    arithmetic is actually required. ``groups`` is
    ``lax.conv_general_dilated``'s ``feature_group_count`` (depthwise
    convs pass ``groups == in_channels``). ``slot_minor`` is the flat
    batch's order (``SlotCtx.slot_minor``; only the dw rule reads it).
    """
    return _slot_conv(x, w_st, stride, padding, slots, groups, slot_minor)


def conv(ctx, x, p_st, stride, padding, groups=1):
    """Layer-level conv: stacked kernel + optional per-slot bias.

    ``p_st`` is the stacked flax param dict (``kernel`` and optionally
    ``bias``); strides/padding accept ints like ``models/_layers.conv``.
    """
    if isinstance(stride, int):
        stride = (stride, stride)
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    y = slot_conv(
        x, p_st["kernel"].astype(ctx.dtype), stride, padding, ctx.slots,
        groups, ctx.slot_minor,
    )
    if "bias" in p_st:
        y = y + slot_expand(ctx, p_st["bias"].astype(ctx.dtype), x.ndim - 2)
    return y


# --------------------------------------------------------------------------
# BatchNorm (train mode), per-slot statistics
# --------------------------------------------------------------------------

def bn_train(ctx, x, p_st, stats, momentum=0.9, eps=1e-5):
    """Per-slot BatchNorm (train mode), flax-numerics-compatible.

    Statistics are computed in f32 over each slot's (b, H, W) block (flax
    nn.BatchNorm computes f32 stats with the fast mean-of-squares
    variance) via ``slot_reduce`` — the one-hot slot matmul or the sorted
    segment sum, per ``GARFIELD_SLOTFUSED_BN``; the normalize runs on the
    FLAT batch in the compute dtype with the per-slot stats expanded back.
    Returns ``(y, {"mean": (slots, C), "var": (slots, C)})`` where the new
    running stats follow flax's ``m*old + (1-m)*batch`` per slot — the
    per-worker semantics the unroll path produces.
    """
    # Stats width follows flax _compute_stats: at least f32, wider if the
    # activations are wider (f64 under an x64 pipeline — what the tight
    # structural equality pins in tests/test_slotfused.py run under).
    xf = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    spatial = tuple(range(1, xf.ndim - 1))
    denom = 1.0 / (ctx.nb * int(np.prod([x.shape[a] for a in spatial])))
    e1 = jnp.sum(xf, axis=spatial)          # (slots*nb, C)
    e2 = jnp.sum(xf * xf, axis=spatial)     # (slots*nb, C)
    mean = slot_reduce(ctx, e1) * denom     # (slots, C)
    var = slot_reduce(ctx, e2) * denom - mean * mean
    new_stats = {
        "mean": momentum * stats["mean"][None] + (1.0 - momentum) * mean,
        "var": momentum * stats["var"][None] + (1.0 - momentum) * var,
    }
    new_stats = jax.tree.map(jax.lax.stop_gradient, new_stats)
    sd = x.ndim - 2
    # Exactly flax _normalize's association — y = (x - mean) * (rsqrt(var
    # + eps) * scale) + bias — so the twin's float rounding tracks the flax
    # path as closely as the fused batch allows (a reassociated scale/shift
    # form measured ~1e-3 relative after 20 layers of amplification).
    # Stats stay f32 (flax _compute_stats); the elementwise normalize runs
    # in the COMPUTE dtype like flax _normalize — an f32 normalize would
    # double the HBM traffic of every BN under the bf16 pipeline.
    dtype = ctx.dtype
    mul = (jax.lax.rsqrt(var + eps)
           * p_st["scale"].astype(xf.dtype)).astype(dtype)
    y = (
        (x.astype(dtype) - slot_expand(ctx, mean.astype(dtype), sd))
        * slot_expand(ctx, mul, sd)
        + slot_expand(ctx, p_st["bias"].astype(dtype), sd)
    )
    return y, new_stats


# --------------------------------------------------------------------------
# Dense / bias / activations / pooling (flat-batch ops)
# --------------------------------------------------------------------------

def dense(ctx, x2, p_st):
    """(slots*b, ...) rows, flattened to (slots*b, F), @ per-slot kernel ->
    (slots, b, O) via a slot-batched matmul; autodiff's dk is a
    slot-batched matmul too (MXU-native)."""
    x3 = ctx.slot_view(x2.reshape(x2.shape[0], -1)).astype(ctx.dtype)
    y = jnp.einsum("sbf,sfo->sbo", x3, p_st["kernel"].astype(ctx.dtype))
    if "bias" in p_st:
        y = y + p_st["bias"].astype(ctx.dtype)[:, None, :]
    return y


def seq_dense(ctx, x, p_st):
    """Sequence-layout dense: (slots*b, T, F) @ per-slot kernel.

    The same MXU-native slot-batched contraction as ``dense`` with the T
    axis riding beside the per-slot batch rows ('sbtf,sfo->sbto') — flax
    ``nn.Dense`` on (b, T, F) contracts the last dim identically, so
    the twin-vs-unroll difference is only the slot batching of the
    kernel operand. Returns (slots*b, T, O).
    """
    y = jnp.einsum(
        "sbtf,sfo->sbto", ctx.slot_view(x).astype(ctx.dtype),
        p_st["kernel"].astype(ctx.dtype),
    )
    if "bias" in p_st:
        y = y + p_st["bias"].astype(ctx.dtype)[:, None, None, :]
    return ctx.flat(y)


# --------------------------------------------------------------------------
# Transformer primitives: LayerNorm / attention / embeddings
# --------------------------------------------------------------------------

#: Finite large-negative causal-mask value. NOT -inf: a masked score of
#: -inf makes ``exp(s - max)`` evaluate ``exp(-inf - -inf)`` = NaN the
#: moment a row is fully masked, and the softmax add chain propagates it.
#: exp(-1e30 - m) underflows to exact 0.0 in f32 and f64, so masked
#: positions contribute nothing to the denominator deterministically.
MASK_VALUE = -1e30

#: One shared GELU for models and twins (tanh approximation, the
#: ``jax.nn.gelu`` default) — sharing the callable is what keeps the
#: fused and unrolled pipelines on identical elementwise arithmetic.
gelu = jax.nn.gelu


def softmax_chain(s):
    """Softmax over the last axis with an EXPLICIT in-order add chain.

    Max-subtracted for range safety (statistics stay in the operand's
    dtype — callers promote to at least f32 first, the attention-numerics
    rule), with the denominator built as ``e_0 + e_1 + ... + e_{T-1}`` in
    index order instead of a ``jnp.sum`` the backend may reassociate —
    the same in-order-adds discipline ``slot_reduce``'s segsum mode pins,
    so fused-vs-unrolled softmax rows agree bitwise for any schedule.
    No zero-denominator guard is needed: the max subtraction guarantees
    one exact ``exp(0) = 1`` term per row.
    """
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - m)
    acc = e[..., 0]
    for t in range(1, e.shape[-1]):
        acc = acc + e[..., t]
    return e / acc[..., None]


def attn_core(q, k, v, causal=False):
    """Multi-head attention core on (..., T, H, Dh) q/k/v.

    Per-EXAMPLE arithmetic only — no slot resolution anywhere — so the
    flax transformer modules (models/transformer.py) call this exact
    function on (b, T, H, Dh) while the twins call it on the flat
    (slots*b, T, H, Dh): fused and unrolled attention are the same
    traced ops, and the twin equality pins only have to absorb the
    per-slot QKV/out projections around it.

    Numerics per the attention playbook: QK^T accumulates in (at least)
    f32 via ``preferred_element_type``, softmax statistics stay in that
    width (``softmax_chain``: max-subtract + in-order add chain), the
    causal mask is a finite ``MASK_VALUE`` where-select over an iota
    row/col comparison, and the probabilities are cast back to the
    compute dtype only for the PV contraction.
    """
    dh = q.shape[-1]
    sf = jnp.promote_types(jnp.float32, q.dtype)
    s = jnp.einsum(
        "...qhd,...khd->...hqk", q, k, preferred_element_type=sf
    ) * (1.0 / float(np.sqrt(dh)))
    if causal:
        T = s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (T, T), 0)
        col = lax.broadcasted_iota(jnp.int32, (T, T), 1)
        s = jnp.where(col <= row, s, jnp.asarray(MASK_VALUE, s.dtype))
    p = softmax_chain(s)
    return jnp.einsum("...hqk,...khd->...qhd", p.astype(q.dtype), v)


def layer_norm(ctx, x, p_st, eps=1e-6):
    """Per-slot-affine LayerNorm over the flat batch, flax numerics.

    The statistics are PER-EXAMPLE (feature-axis mean/fast-variance in
    at least f32, negative variances clipped — flax ``_compute_stats``),
    so unlike ``bn_train`` they need no slot resolution at all; only the
    scale/bias application is worker-resolved, via ``slot_expand``
    (whose autodiff transpose is the per-slot segment reduction — the
    per-slot LayerNorm parameter gradients). Association matches flax
    ``_normalize`` exactly: ``y = (x - mean) * (rsqrt(var + eps) *
    scale) + bias``, cast to the compute dtype at the end.
    """
    xf = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(
        0.0, jnp.mean(xf * xf, axis=-1, keepdims=True) - mu * mu
    )
    sd = x.ndim - 2
    mul = lax.rsqrt(var + eps) * slot_expand(
        ctx, p_st["scale"].astype(xf.dtype), sd
    )
    y = (xf - mu) * mul + slot_expand(
        ctx, p_st["bias"].astype(xf.dtype), sd
    )
    return y.astype(ctx.dtype)


def embed(ctx, tok, emb_st):
    """Token-embedding lookup from the STACKED (slots, vocab, D) table.

    Forward gathers each slot's rows from its own table copy (all rows
    equal by construction, so the values match the fused single-table
    lookup flax ``nn.Embed`` performs); the autodiff transpose of the
    slot-vmapped gather is a per-slot scatter-add — exactly the
    per-worker embedding gradient, with no custom vjp needed.
    """
    out = jax.vmap(lambda tab, t: jnp.take(tab, t, axis=0))(
        emb_st.astype(ctx.dtype), ctx.slot_view(tok)
    )
    return ctx.flat(out)


def pos_embed(ctx, x, pos_st):
    """Add learned per-slot positional embeddings (slots, T, D) onto the
    flat (slots*b, T, D) activations through the slot-leading view; the
    broadcast-add's transpose is a per-slot sum over the nb rows — the
    positional table's per-worker gradient."""
    y = ctx.slot_view(x) + pos_st[:, None].astype(ctx.dtype)
    return ctx.flat(y)


def bias_add(ctx, x, b_st):
    """Add a (slots, C) per-slot bias onto the flat (slots*b, ..., C)."""
    return x + slot_expand(ctx, b_st.astype(ctx.dtype), x.ndim - 2)


def relu(x):
    return jax.nn.relu(x)


def max_pool(x, window=2, stride=None, padding=0):
    """NHWC max pool over the flat batch (int padding like _layers)."""
    stride = window if stride is None else stride
    pad = (
        ((0, 0), (padding, padding), (padding, padding), (0, 0))
        if isinstance(padding, int) else padding
    )
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, window, window, 1), (1, stride, stride, 1), pad,
    )


def avg_pool(x, window=2, stride=None):
    """NHWC average pool (VALID), matching ``_layers.avg_pool``."""
    stride = window if stride is None else stride
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        (1, window, window, 1), (1, stride, stride, 1), "VALID",
    )
    return summed / (window * window)


def global_avg_pool(x):
    """NHWC global average pool -> (N, C)."""
    return jnp.mean(x, axis=(1, 2))
