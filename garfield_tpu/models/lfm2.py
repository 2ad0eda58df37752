"""LFM2-MoE language models (``model_type`` ``lfm2_moe``: LiquidAI's
LFM2-8B-A1B and its siblings): gated short-convolution and grouped-query
attention blocks, a dense SwiGLU MLP in the leading layers, a mixture of
experts in the others, a tied embedding.

The family differs from the CNN zoo in its signature: ``model(tokens, train)``
takes int token ids (batch, time) and returns logits (batch, time,
vocabulary) for the ``next-token`` loss (``utils.selectors.select_loss``).

**One chip's share of a layer.** The expert layer is told how many experts
the model has (``num_experts``: the router's width), which of them this
chip holds (``experts_held``) and how many a token chooses
(``experts_per_token``). It routes over all experts and computes the part of
the result its own experts give; what the absent experts would add is left
out and the partial result goes on to the next layer. On one chip the layer
runs without its exchange: nothing here stands in for the absent chips.
``vocab`` is likewise the slice of the vocabulary held here.

**Dropless.** Every (token, expert) pair whose expert is held is computed,
whatever the imbalance: the pairs are sorted by expert, those of absent
experts last, and three grouped matmuls run over the sorted rows with the
held experts' counts as group sizes: `ops.grouped.grouped_matmul`, Pallas
kernels of the repo's own (forward, the rows' gradient against the weights
read transposed, the weights' gradient group by group) whose tiles follow
the shapes, where the step is lowered for the TPU and the shapes fill the
tiles; ``jax.lax.ragged_dot`` — the one
copy, handed in as the fallback — elsewhere; it says which once. Either
visits only the row tiles its groups cover, so the matmul work follows the
pairs held (a quarter of 4 x tokens when 8 of 32 experts are held), not the
worst case. The rows move the same way: `ops.route` gathers the held rows
into the sorted order and sums them back per token with their weights,
Pallas kernels whose grids end at the last held pair, where the step is
lowered for the TPU and the shapes fit; elsewhere a token's row is
broadcast to its choices and all tokens x k rows are permuted there and
back (`_rows_permuted`, `_back_permuted`: the one copy, their spec). The
sorts and the buffers they fill are the static worst case of k x tokens
rows.

**Precision.** Parameters are float32; ``dtype`` is the compute dtype of
the matmuls and the residual stream. Norm statistics, the rotary embedding,
the attention softmax, the router (scores, selection, weights) and the
logits' consumer (the loss) are float32 whatever ``dtype`` is.

**Scopes** (``jax.named_scope``, always on, metadata only; they nest inside
``phase.grads`` of `parallel.core`): the vocabulary is ``SCOPES`` below, one
``model.<name>`` each, read by the benchmark's `harness/model_map.py`
(``moe_route_ms`` and the other ``model blocks`` metrics). An expert layer's
routing is split further by ``ROUTE_STEPS``, one ``route.<name>`` each,
nested inside ``model.moe_dispatch`` and ``model.moe_combine`` so that every
``model.*`` label reads what it read without them; `harness/route_map.py`
reads them (``moe_sort_ms``: ``order`` and ``inverse``; ``moe_permute_ms``:
``gather_rows`` and ``return_rows``, forward and backward — a
``custom_vjp``'s backward, `_permute`'s gather or `ops.route`'s kernels,
carries its forward's scope). **Counters**: an expert layer writes
``moe_pairs_held`` (pairs computed here), ``moe_rows_routed`` (the rows the
dispatch moves, taken where it is made: the held pairs where `ops.route`'s
kernels run, experts_per_token x tokens where the permutation does) and
``moe_rows_visited`` (the rows of the row tiles the forward
grouped-matmul kernel visits, a tile that holds rows of several experts
once for each: over ``moe_pairs_held`` it is the padding the tiles pay; 0
where ``ragged_dot`` runs) into the collection ``counters_sum`` and
``moe_max_expert_load`` (the fullest held expert's pairs) into
``counters_max`` — `parallel.core`'s
convention for any model's counters, by name as BatchNorm writes
``batch_stats`` — so they ride in ``model_state``, and a trainer that calls
``core.step_counters`` (`aggregathor.make_trainer` does) has them in the
step's ``metrics`` under those names, one entry per expert layer. The
benchmark's `harness/route_map.py` sums them over layers for
``moe_route_fill`` (held ÷ routed) and ``moe_tile_fill`` (held ÷ visited).
"""

import contextlib
import dataclasses
import functools
import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import attention, grouped, scan
from ..ops import route as route_ops

__all__ = [
    "SCOPES", "ROUTE_STEPS", "COUNTER_SUMS", "COUNTER_MAXES", "KEPT",
    "scope", "route", "keep",
    "recomputed", "RMSNorm", "ShortConv",
    "Yarn", "rope_table", "rotary", "einsum_attention", "Attention", "SwiGLU",
    "ExpertLayer", "Sizes", "Block", "Lfm2Moe", "lfm2_8b_a1b_ep4",
    "lfm2_moe_tiny",
]

# ``attention`` stands around a whole attention module (this family's
# blocks); a block that splits it (`Attention.core_scope`) has
# ``attention_proj`` around the projections and ``window_attention``,
# ``full_attention`` or ``cross_attention`` around the core;
# ``attention_gate`` around a per-head gate, ``shared_expert`` around a
# shared expert; ``ssm_*`` and ``gmu`` are `models/phi4flash.py`'s.
SCOPES = (
    "embed", "conv_mixer", "attention", "dense_mlp", "moe_router",
    "moe_dispatch", "moe_experts", "moe_combine", "head_loss",
    "attention_proj", "window_attention", "full_attention",
    "attention_gate", "shared_expert", "ssm_proj", "ssm_conv", "ssm_scan",
    "gmu", "cross_attention",
)
# The steps of an expert layer's routing, one ``route.<name>`` scope each,
# nested inside ``model.moe_dispatch`` (the first five) and
# ``model.moe_combine`` (the last two): the slot lookup, the sort of the
# slots, the sort that inverts it, the groups' sizes and their sum, the rows
# gathered into the sorted order, the rows gathered back (with the kernels,
# summed by token with their weights) and the weighted sum over a token's
# choices (with the kernels, what is left of it: the weights' cast). A
# namespace of their own, since a map labels an instruction by its
# outermost scope of a namespace.
ROUTE_STEPS = (
    "slots", "order", "inverse", "sizes", "gather_rows", "return_rows",
    "weigh",
)
# parallel.core's two collections for a model's counters, by name.
COUNTER_SUMS, COUNTER_MAXES = "counters_sum", "counters_max"
# Added to the sum of a token's selected scores before it divides them.
WEIGHT_EPS = 1e-6
# What a recomputed block (``remat``) keeps of its forward pass, by the name
# `keep` gives it where it is made: what a matmul, a kernel or a sort made,
# as far as the backward pass reads it. The operators' projections (the
# last of each, ``conv_out``, ``attention_o_proj``, ``ssm_out_proj``,
# ``gmu_out_proj``, is what the feed-forward's norm reads; ``v_proj``'s
# result reaches the backward pass as the kernels' ``attention_v``); the
# dense MLP's two inner products; of an expert layer the router's logits
# and choice, the sorts and sizes, the dispatched rows, the three grouped
# matmuls' results in sorted order; a gate's projection, a shared expert's
# inner products, a Mamba layer's memory (y silu(z)); `ops.attention`'s
# and `ops.scan`'s own. Norms, rotary embedding, gates, masks, casts, a
# convolution, the chunked scan and the permutation's rows gathered back are
# computed again. One set for every family made of this file's modules.
KEPT = (
    "conv_in", "conv_out", "attention_q_proj", "attention_k_proj",
    "attention_o_proj", "mlp_w1", "mlp_w3", "moe_logits", "moe_chosen",
    "moe_order", "moe_inverse", "moe_sizes", "moe_rows", "moe_w1", "moe_w3",
    "moe_out", "attention_gate_proj", "shared_w1", "shared_w3",
    "ssm_in_proj", "ssm_x_proj", "ssm_dt_proj", "ssm_memory", "ssm_out_proj",
    "gmu_gate", "gmu_out_proj",
) + attention.KEPT + scan.KEPT

_normal = nn.initializers.variance_scaling(1.0, "fan_in", "normal")


def scope(name):
    """``jax.named_scope("model.<name>")`` for a name of ``SCOPES``; no scope
    for None."""
    if name is None:
        return contextlib.nullcontext()
    if name not in SCOPES:
        raise ValueError(f"unknown model scope {name!r}; have {SCOPES}")
    return jax.named_scope("model." + name)


def route(name):
    """``jax.named_scope("route.<name>")`` for a name of ``ROUTE_STEPS``."""
    if name not in ROUTE_STEPS:
        raise ValueError(
            f"unknown routing step {name!r}; have {ROUTE_STEPS}")
    return jax.named_scope("route." + name)


# names -> bytes kept under them, of the model whose blocks are being traced.
_kept_bytes = {}


def _count(names, nbytes):
    _kept_bytes[names] = _kept_bytes.get(names, 0) + nbytes


def keep(x, name):
    """``x`` under ``name`` of ``KEPT``: the identity, and the mark by which
    `recomputed`'s policy keeps ``x`` for the backward pass. A float is kept
    as its bits (`_kept_bits`)."""
    if name not in KEPT:
        raise ValueError(f"unknown name {name!r}; a block keeps {KEPT}")
    _count((name,), x.size * x.dtype.itemsize)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return _kept_bits(x, name)
    return checkpoint_name(x, name)


def _as_bits(x, name):
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(checkpoint_name(
        jax.lax.bitcast_convert_type(x, bits), name), x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _kept_bits(x, name):
    """``x``, named as its bits: the policy puts a ``reduce_precision`` on
    every float it keeps, an identity that cannot fuse into a Pallas
    kernel's result and so copies it whole (the dispatched rows and the
    experts' output, two (tokens x k, hidden) arrays a layer and slot); an
    integer is kept as it is. The cotangent passes unchanged."""
    return _as_bits(x, name)


_kept_bits.defvjp(lambda x, name: (_as_bits(x, name), None),
                  lambda name, _, cotangent: (cotangent,))


@contextlib.contextmanager
def recomputed(block, remat):
    """``block`` itself, or with ``remat`` the class whose instances are
    recomputed in the backward pass but for what they made under a name of
    ``KEPT``. Leaving, says once what the blocks made inside keep: ``[remat]
    block keeps <k> names: <names>; per slot <x> GB (reckoned from
    shapes)``."""
    if not remat:
        yield block
        return
    _kept_bytes.clear()
    yield nn.remat(
        block, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
    names = [name for names in _kept_bytes for name in names]
    attention.say(
        f"[remat] block keeps {len(names)} names: {', '.join(names)}; per "
        f"slot {sum(_kept_bytes.values()) / 1e9:.3f} GB (reckoned from "
        "shapes)")


class RMSNorm(nn.Module):
    """x * scale / sqrt(mean(x^2) + eps), statistics in float32."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """A YaRN rotary table's parameters (Peng et al., arXiv:2309.00071), as
    a Hugging Face ``rope_parameters`` group of ``rope_type`` ``yarn``
    spells them."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def rope_table(head_dim, theta, yarn=None):
    """``(inverse frequencies (head_dim / 2,), scale)`` of a rotary table:
    theta ** (-2i / head_dim) and 1, or with ``yarn`` Hugging Face's
    ``_compute_yarn_parameters``: each frequency a blend of itself
    (extrapolation) and itself over ``factor`` (interpolation) by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original positions, cos and sin times
    ``attention_factor``. The table does not depend on the sequence's
    length. A table over part of a head (``partial_rotary_factor``) is the
    table of that many dimensions: ``head_dim`` is then the rotated width,
    and YaRN's ramp is reckoned over it, as Hugging Face does."""
    extrap = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if yarn is None:
        return extrap, 1.0

    def turns(rotations):  # the dimension that turns this often
        return head_dim * math.log(
            yarn.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(yarn.beta_fast)), 0)
    high = min(math.ceil(turns(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0, 1)
    return (extrap / yarn.factor * ramp + extrap * (1 - ramp),
            yarn.attention_factor)


def rotary(x, inv, scale=1.0):
    """Rotary embedding of ``x`` (batch, time, heads, head_dim) in float32,
    half-rotation convention, by the table ``inv`` (inverse frequencies,
    head_dim / 2) with cos and sin times ``scale`` (`rope_table`). A shorter
    table rotates the first 2 x len(inv) dimensions of each head among
    themselves and passes the others unchanged."""
    t, d = x.shape[1], 2 * inv.shape[0]
    if d < x.shape[-1]:
        return jnp.concatenate([
            rotary(x[..., :d], inv, scale), x[..., d:].astype(jnp.float32)], -1)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=_normal,
                    name=name)


class ShortConv(nn.Module):
    """Gated depthwise causal convolution: [B, C, X] = u W_in; z = B * X;
    y_t = sum_j k_j * z_{t-L+1+j}; out = (C * y) W_out. No bias."""

    length: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        hidden = u.shape[-1]
        b, c, x = jnp.split(
            keep(_dense(3 * hidden, self.dtype, "in_proj")(u), "conv_in"),
            3, axis=-1)
        taps = self.param("conv_kernel", _normal, (self.length, hidden))
        z = b * x
        padded = jnp.pad(z, ((0, 0), (self.length - 1, 0), (0, 0)))
        y = sum(taps[j].astype(self.dtype) * padded[:, j:j + z.shape[1]]
                for j in range(self.length))
        return keep(_dense(hidden, self.dtype, "out_proj")(c * y), "conv_out")


def einsum_attention(q, k, v, window=None):
    """Causal attention of q (n, t, heads, head) over k, v (n, t, kv_heads,
    head), each KV head serving heads / kv_heads adjacent query heads, as
    two einsums with the (t, t) scores between them: scores and softmax in
    float32, probabilities rounded to v's dtype. With a ``window``, key j is
    visible to query i iff 0 <= i - j < window. The spec of
    `ops.attention`'s kernels, and what runs where they do not."""
    n, t, heads, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(n, t, kv, heads // kv, hd)
    scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    if window is not None:
        causal &= ~jnp.tril(jnp.ones((t, t), bool), -window)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = jnp.einsum("nkgqs,nskd->nqkgd", probs.astype(v.dtype), v)
    return mixed.reshape(n, t, heads, hd)


class Attention(nn.Module):
    """Grouped-query causal attention with a per-head RMSNorm on q and k and
    a rotary embedding; each KV head serves heads / kv_heads query heads.
    Softmax in float32. The core between the projections is
    `ops.attention.causal_gqa`: blockwise kernels that write no (t, t)
    array where the step is lowered for the TPU and t fills their blocks,
    ``einsum_attention`` elsewhere; it says which once.

    ``window``: the keys a query sees, itself included (None: all before
    it). ``yarn``: the rotary table's YaRN parameters (None: the plain
    table of ``rope_theta``). ``core_scope``: a name of ``SCOPES`` to stand
    around the core alone, with ``attention_proj`` around the rest of the
    module (None: no scope of its own; the caller's stands around it all).
    ``rotary_dim``: the leading dimensions of each head that the rotary
    embedding turns, the table being that many wide (None: the whole head).
    ``gate``: a per-head output gate, g = sigmoid(u W_g) with W_g (hidden,
    heads), one number a head and position, times the core's output in
    front of ``o_proj`` (arXiv:2505.06708's per-head form); its scope is
    ``attention_gate``, beside the others and inside none of them."""

    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.float32
    window: Optional[int] = None
    yarn: Optional[Yarn] = None
    core_scope: Optional[str] = None
    rotary_dim: Optional[int] = None
    gate: bool = False

    @nn.compact
    def __call__(self, u):
        n, t, hidden = u.shape
        heads, kv, hd = self.heads, self.kv_heads, self.head_dim
        rest = "attention_proj" if self.core_scope else None
        # Made where it is used, once for q and once for k, as `rotary` made
        # it while it took a theta: the step keeps its operations' order.
        table = functools.partial(
            rope_table, self.rotary_dim or hd, self.rope_theta, self.yarn)
        with scope(rest):
            q = keep(_dense(heads * hd, self.dtype, "q_proj")(u),
                     "attention_q_proj").reshape(n, t, heads, hd)
            k = keep(_dense(kv * hd, self.dtype, "k_proj")(u),
                     "attention_k_proj").reshape(n, t, kv, hd)
            v = _dense(kv * hd, self.dtype, "v_proj")(u).reshape(n, t, kv, hd)
            q = rotary(RMSNorm(self.eps, jnp.float32, name="q_norm")(q),
                       *table()).astype(self.dtype)
            k = rotary(RMSNorm(self.eps, jnp.float32, name="k_norm")(k),
                       *table()).astype(self.dtype)
        with scope(self.core_scope):
            mixed = attention.causal_gqa(
                q, k, v, einsum_attention, window=self.window, kept=_count)
        if self.gate:
            with scope("attention_gate"):
                g = jax.nn.sigmoid(keep(
                    _dense(heads, self.dtype, "g_proj")(u),
                    "attention_gate_proj").astype(jnp.float32))
                mixed = mixed * g.astype(self.dtype)[..., None]
        with scope(rest):
            return keep(
                _dense(hidden, self.dtype, "o_proj")(
                    mixed.reshape(n, t, heads * hd)), "attention_o_proj")


class SwiGLU(nn.Module):
    """W_2 (silu(u W_1) * (u W_3)). No bias. ``kept``: the names of ``KEPT``
    its two inner products go by."""

    width: int
    dtype: Any = jnp.float32
    kept: Sequence[str] = ("mlp_w1", "mlp_w3")

    @nn.compact
    def __call__(self, u):
        w1, w3 = self.kept
        gate = nn.silu(keep(_dense(self.width, self.dtype, "w1")(u), w1))
        return _dense(u.shape[-1], self.dtype, "w2")(
            gate * keep(_dense(self.width, self.dtype, "w3")(u), w3))


@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` of the rows whose inverse is
    ``inverse``: the cotangent goes back by a gather too, not a scatter."""
    del inverse
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], (order, inverse)


def _permute_bwd(res, ct):
    _, inverse = res
    return ct[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _rows_permuted(x, order, inverse, total):
    """The dispatch by permutation: each token's row broadcast to its k
    pairs, all tokens x k rows gathered into the sorted order, those past
    ``total`` zeroed; with the rows it moved. The kernels' spec and their
    fallback."""
    tokens, hidden = x.shape
    with route("gather_rows"):
        rows = jnp.broadcast_to(
            x[:, None], (tokens, order.shape[0] // tokens, hidden))
        rows = _permute(rows.reshape(-1, hidden), order, inverse)
        here = (jnp.arange(order.shape[0]) < total)[:, None]
        return jnp.where(here, rows, 0), jnp.asarray(order.shape[0])


def _rows_held(x, order, inverse, total):
    """`_rows_permuted` by `ops.route.gather_held`: the held rows alone."""
    del inverse
    with route("gather_rows"):
        k = order.shape[0] // x.shape[0]
        return route_ops.gather_held(x, order // k, total), total


def _back_permuted(out, weights, order, inverse, total, dtype):
    """The combine by permutation: the sorted rows of ``out`` past
    ``total`` zeroed (the grouped matmul leaves them unvisited), all tokens
    x k rows gathered back in place, a token's k rows times their weights
    summed. The kernels' spec and their fallback."""
    tokens, k = weights.shape
    with route("return_rows"):
        here = (jnp.arange(order.shape[0]) < total)[:, None]
        out = _permute(jnp.where(here, out, 0), inverse, order)
    with route("weigh"):
        return jnp.sum(
            out.reshape(tokens, k, -1) * weights[..., None].astype(dtype),
            axis=1)


def _back_held(out, weights, order, inverse, total, dtype):
    """`_back_permuted` by `ops.route.combine_held`, which reads the held
    rows alone; what is left of the weighing is the weights' cast."""
    with route("weigh"):
        weights = weights.astype(dtype)
    with route("return_rows"):
        return route_ops.combine_held(out, weights, order, inverse, total)


class ExpertLayer(nn.Module):
    """The part of a mixture-of-experts feed-forward that the experts held
    here give (module docstring). ``score`` is the router's law over all
    ``num_experts``. ``sigmoid``: the top ``experts_per_token`` of score +
    bias are chosen (the bias is a constant leaf under ``stop_gradient``: it
    enters the selection only) and a token's weights are its chosen scores
    over their sum + ``WEIGHT_EPS``; without ``bias`` the top of the scores
    themselves, over their sum: no leaf, no epsilon. ``softmax``: the top of
    the softmax are chosen and renormalised over their sum; no bias leaf, no
    epsilon. The sums run over all the chosen, held here or not; weights
    times ``scaling``. ``shared_width``: beside the routed experts every
    token passes one shared `SwiGLU` of that width, ungated and unscaled,
    whole on every chip (0: none); its scope is ``shared_expert``."""

    num_experts: int
    experts_held: Sequence[int]
    experts_per_token: int
    width: int
    scaling: float = 1.0
    dtype: Any = jnp.float32
    score: str = "sigmoid"
    bias: bool = True
    shared_width: int = 0

    @nn.compact
    def __call__(self, u):
        hidden, k = u.shape[-1], self.experts_per_token
        held = len(self.experts_held)
        x = u.reshape(-1, hidden)
        tokens = x.shape[0]
        with scope("moe_router"):
            kernel = self.param(
                "router_kernel", _normal, (hidden, self.num_experts))
            logits = keep(jnp.matmul(
                x.astype(jnp.float32), kernel,
                precision=jax.lax.Precision.HIGHEST), "moe_logits")
            biased = self.score == "sigmoid" and self.bias
            if self.score == "sigmoid":
                scores = ranked = jax.nn.sigmoid(logits)
                if biased:
                    bias = self.param(
                        "expert_bias", nn.initializers.zeros,
                        (self.num_experts,))
                    ranked = scores + jax.lax.stop_gradient(bias)
                _, chosen = jax.lax.top_k(ranked, k)
            elif self.score == "softmax":
                scores = jax.nn.softmax(logits, axis=-1)
                _, chosen = jax.lax.top_k(scores, k)
            else:
                raise ValueError(f"unknown router law {self.score!r}")
            chosen = keep(chosen, "moe_chosen")
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            total = jnp.sum(picked, -1, keepdims=True)
            if biased:
                total = total + WEIGHT_EPS
            weights = picked / total * self.scaling
        with scope("moe_dispatch"):
            # The slot of each chosen expert among those held, ``held`` for
            # an absent one; pairs sorted by slot, absent pairs last.
            with route("slots"):
                slot_of = jnp.full((self.num_experts,), held, jnp.int32).at[
                    jnp.asarray(self.experts_held)].set(jnp.arange(held))
                slots = slot_of[chosen].reshape(-1)
            # Named before they enter the row movement, whose rules hand
            # them on as they come.
            with route("order"):
                order = keep(jnp.argsort(slots, stable=True), "moe_order")
            with route("inverse"):
                inverse = keep(jnp.argsort(order), "moe_inverse")
            with route("sizes"):
                sizes = keep(jnp.sum(
                    slots[:, None] == jnp.arange(held)[None], axis=0,
                    dtype=jnp.int32), "moe_sizes")
                pairs = jnp.sum(sizes)
            why = route_ops.path((tokens, k, hidden), self.dtype)
            rows, routed = route_ops.either(
                _rows_held, _rows_permuted, x, order, inverse, pairs, why=why)
            rows = keep(rows, "moe_rows")
        with scope("moe_experts"):
            w1 = self.param("w1", _stack_init, (held, hidden, self.width))
            w3 = self.param("w3", _stack_init, (held, hidden, self.width))
            w2 = self.param("w2", _stack_init, (held, self.width, hidden))
            dot = functools.partial(
                grouped.grouped_matmul, sizes=sizes,
                fallback=functools.partial(
                    jax.lax.ragged_dot, preferred_element_type=self.dtype))
            w1 = w1.astype(self.dtype)
            gate = nn.silu(keep(dot(rows, w1), "moe_w1"))
            # Kept in sorted order: the weights' gradient reads these rows,
            # and the grouped matmul does not run again.
            out = keep(dot(
                gate * keep(dot(rows, w3.astype(self.dtype)), "moe_w3"),
                w2.astype(self.dtype)), "moe_out")
        with scope("moe_combine"):
            out = route_ops.either(
                functools.partial(_back_held, dtype=self.dtype),
                functools.partial(_back_permuted, dtype=self.dtype),
                out, weights, order, inverse, pairs, why=why)
        if self.shared_width:
            with scope("shared_expert"):
                out = out + SwiGLU(
                    self.shared_width, self.dtype, ("shared_w1", "shared_w3"),
                    name="shared")(x)
        # The three grouped matmuls take one row tile: the first stands for
        # them.
        visited = grouped.rows_visited(rows, w1, sizes)
        for collection, name, value in (
                (COUNTER_SUMS, "moe_pairs_held", pairs),
                (COUNTER_SUMS, "moe_rows_routed", routed),
                (COUNTER_SUMS, "moe_rows_visited", visited),
                (COUNTER_MAXES, "moe_max_expert_load", jnp.max(sizes))):
            if self.is_mutable_collection(collection):
                self.variable(
                    collection, name, lambda: jnp.zeros((), jnp.float32)
                ).value = jnp.asarray(value, jnp.float32)
        return out.reshape(u.shape)


def _stack_init(key, shape, dtype=jnp.float32):
    """Expert stacks: the leading axis counts experts and is no fan-in."""
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(shape[1])


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every block of one model shares (hashable: a static field)."""

    heads: int
    kv_heads: int
    head_dim: int
    conv_length: int
    dense_width: int
    expert_width: int
    num_experts: int
    experts_held: tuple
    experts_per_token: int
    scaling: float
    eps: float
    rope_theta: float


class Block(nn.Module):
    """h += Op(RMSNorm(h)); h += FF(RMSNorm(h)): ``kind`` names the operator
    (``conv`` or ``full_attention``), ``moe`` the feed-forward (the expert
    layer, or the dense MLP)."""

    kind: str
    cfg: Sizes
    moe: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        u = RMSNorm(cfg.eps, self.dtype, name="operator_norm")(h)
        if self.kind == "conv":
            with scope("conv_mixer"):
                h = h + ShortConv(cfg.conv_length, self.dtype, name="conv")(u)
        elif self.kind == "full_attention":
            with scope("attention"):
                h = h + Attention(
                    cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.rope_theta,
                    cfg.eps, self.dtype, name="attn")(u)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        u = RMSNorm(cfg.eps, self.dtype, name="ffn_norm")(h)
        if self.moe:
            return h + ExpertLayer(
                cfg.num_experts, cfg.experts_held,
                cfg.experts_per_token, cfg.expert_width, cfg.scaling,
                self.dtype, name="moe")(u)
        with scope("dense_mlp"):
            return h + SwiGLU(cfg.dense_width, self.dtype, name="mlp")(u)


class Lfm2Moe(nn.Module):
    """The model: tied embedding, ``layer_types`` blocks of which the first
    ``num_dense_layers`` have a dense MLP and the others an expert layer, a
    final RMSNorm, logits over the ``vocab`` rows held (float32).

    ``num_classes`` is the vocabulary slice (``models.select_model`` passes
    the dataset's). ``remat`` recomputes each block in the backward pass but
    for what it made under a name of ``KEPT`` (`recomputed`)."""

    num_classes: int = 16384
    dtype: Any = jnp.float32
    hidden: int = 2048
    layer_types: Sequence[str] = ("conv", "full_attention", "conv", "conv",
                                  "conv")
    num_dense_layers: int = 1
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_length: int = 3
    dense_width: int = 7168
    expert_width: int = 1792
    num_experts: int = 32
    experts_held: Sequence[int] = tuple(range(8))
    experts_per_token: int = 4
    scaling: float = 1.0
    eps: float = 1e-5
    rope_theta: float = 1e6
    remat: bool = False

    def sizes(self):
        """What the blocks share, as their static field."""
        return Sizes(**{
            f.name: getattr(self, f.name) for f in dataclasses.fields(Sizes)
        } | {"experts_held": tuple(self.experts_held)})

    @nn.compact
    def __call__(self, tokens, train=False):
        del train  # no dropout, no batch statistics
        with scope("embed"):
            table = nn.Embed(
                self.num_classes, self.hidden, dtype=self.dtype,
                embedding_init=nn.initializers.normal(self.hidden ** -0.5),
                name="embed")
            h = table(tokens)
        with recomputed(Block, self.remat) as block:
            for i, kind in enumerate(self.layer_types):
                h = block(kind, self.sizes(), i >= self.num_dense_layers,
                          self.dtype, name=f"layer_{i}")(h)
        with scope("head_loss"):
            h = RMSNorm(self.eps, self.dtype, name="final_norm")(h)
            return table.attend(h).astype(jnp.float32)


def lfm2_8b_a1b_ep4(num_classes=16384, dtype=jnp.float32):
    """One chip's share of LFM2-8B-A1B where 4 chips share each layer by
    expert parallelism: published layers 1 and 3-6 (one leading dense layer
    and the first whole period of expert layers), experts 0-7 of 32, every width as
    published; ``num_classes`` is the vocabulary slice (16,384 of 65,536).
    ``remat``: without recomputation 4 workers' 16,384 tokens a step beside
    a 4 x 508M gradient stack do not fit one v5e (16.3 GiB of 15.75 at
    compile, PERF.md section 4), so each block is recomputed in the
    backward pass, but for ``KEPT``: what its matmuls, kernels and sorts
    made (1.48 GB a slot, reckoned from the shapes; the trainers' unroll
    runs the slots one after another, so one slot's is held at a time).
    What is computed again is elementwise: norms, rotary embedding, gates,
    masks, casts."""
    return Lfm2Moe(num_classes=num_classes, dtype=dtype, remat=True)


def lfm2_moe_tiny(num_classes=64, dtype=jnp.float32, experts_held=(0, 1),
                  **fields):
    """The family at a size the CPU tests hold: hidden 64, 8 experts of
    which ``experts_held`` are here, top-2."""
    sizes = dict(
        hidden=64, layer_types=("conv", "full_attention", "conv"),
        num_dense_layers=1, heads=4, kv_heads=2, head_dim=16, dense_width=96,
        expert_width=48, num_experts=8, experts_held=tuple(experts_held),
        experts_per_token=2)
    sizes.update(fields)
    return Lfm2Moe(num_classes=num_classes, dtype=dtype, **sizes)
