"""SambaY language models (``model_type`` ``phi4flash``: Microsoft's
Phi-4-mini-flash-reasoning; Ren et al., arXiv:2507.06607): a self-decoder of
Mamba layers (Gu & Dao, arXiv:2312.00752) and sliding-window attention, one
full-attention layer whose keys and values later layers share, then a
cross-decoder of gated memory units and cross-attention layers; every
attention layer is differential attention (Ye et al., arXiv:2410.05258); a
tied embedding.

The signature, ``num_classes`` as the vocabulary slice held, the precision
(parameters float32, ``dtype`` the matmuls' and the residual stream's; norm
statistics, the scan's state, the softmax, λ and the logits float32), the
``model.*`` scopes, the counters and ``remat`` are `models/lfm2.py`'s (its
module docstring); the dense MLP, the names a recomputed block keeps and the
attention kernels are that file's and `ops.attention`'s, not copies.

Every block is pre-norm with LayerNorm (scale and bias, eps ``eps``,
statistics in float32)::

    h += Mixer_l(LN1(h));  h += W_down (silu(v W_gate) * (v W_up)),  v = LN2(h)

The mixers, by ``layer_types`` entry (no bias but where named):

- ``mamba``: [x, z] = u W_in (hidden -> 2 x inner); x~ = silu(conv(x) +
  b_conv), a causal depthwise convolution of ``d_conv`` taps; [d, B, C] =
  x~ W_x (inner -> dt_rank + 2 x state); Δ = softplus(d W_dt + b_dt); A =
  -exp(A_log) (inner, state); s_t = exp(Δ_t A) s_{t-1} + (Δ_t x~_t) ⊗ B_t;
  y_t = s_t C_t + D x~_t (`ops.scan.selective_scan`); m = y * silu(z), the
  block's *memory*; out = m W_out.
- ``sliding_attention`` and ``full_attention``: differential attention.
  q = u W_q viewed (heads / 2, 2, head_dim) -> q1, q2; k = u W_k viewed
  (kv_heads / 2, 2, head_dim) -> k1, k2; v = u W_v viewed (kv_heads / 2, 2 x
  head_dim); differential head i reads key-value pair floor(i x kv_heads /
  heads); A_j = softmax(q_j k_j^T / sqrt(head_dim) + mask); o_i =
  RMSNorm_{2 head_dim}((A_1 - λ A_2) v) (1 - λ_init); out = concat(o) W_o;
  λ = exp(λ_q1 . λ_k1) - exp(λ_q2 . λ_k2) + λ_init, λ_init = 0.8 - 0.6
  exp(-0.3 l) at the published layer index l. No rotary embedding, no q/k
  norm. A sliding layer's key j is visible to query i iff 0 <= i - j <
  ``sliding_window``; a full layer's keys and values go on to the later
  blocks.
- ``gmu``: out = (silu(u W_1) * m) W_2 (hidden -> inner -> hidden), m the
  last Mamba layer's memory.
- ``cross_attention``: differential attention with W_q and W_o alone, over
  the keys and values of the last full-attention layer, causal.

So a block takes and returns (h, memory, kv), and the chain (`chain`) hands
them on. Logits = LN_f(h) E^T over the vocabulary rows held, E the
embedding.

**The core** calls `ops.attention.causal_gqa` twice: the kernels take a value
head as wide as the key head, and a differential head's value is twice as
wide. Each call takes both softmaxes' heads at once — q1's heads then q2's
over k1's pairs then k2's, so that the kernels' grouping (a KV head serves
adjacent query heads) gives each head its pair — against one half of v,
repeated for the two halves of the heads; the two calls' outputs are put
back together as A_1 v and A_2 v. That is DIFF Transformer's own flash form
(four calls of one softmax and half of v each), two heads' groups to a call.

**Scopes**: ``ssm_proj`` around a Mamba layer's projections and the
memory's gate, ``ssm_conv`` around its convolution, ``ssm_scan`` around the
scan; ``attention_proj`` around a differential layer's projections,
``window_attention``, ``full_attention`` or ``cross_attention`` around its
core with λ, the combine and the per-head norm; ``gmu``; ``dense_mlp``,
``embed``, ``head_loss``. **Counter**: each differential layer writes |λ| as
``diff_lambda_max`` into ``counters_max``.
"""

import dataclasses
import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import attention, scan
from . import lfm2

__all__ = ["KINDS", "layer_norm", "Mamba", "diff_core", "DiffAttention",
           "GatedMemory", "Sizes", "Block", "chain", "Phi4Flash",
           "lambda_init", "phi4_mini_flash_l15_19", "phi4flash_tiny"]

# ``layer_types`` entries, and the scope around each differential kind's
# core.
KINDS = ("mamba", "sliding_attention", "full_attention", "gmu",
         "cross_attention")
CORE_SCOPES = {"sliding_attention": "window_attention",
               "full_attention": "full_attention",
               "cross_attention": "cross_attention"}


def lambda_init(layer):
    """DIFF Transformer's λ_init at the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_norm(eps, dtype, name):
    """(x - mean) / sqrt(var + eps) * scale + bias, statistics in float32
    (flax's, the variance as the mean square of x - mean)."""
    return nn.LayerNorm(epsilon=eps, dtype=dtype, use_fast_variance=False,
                        name=name)


class CausalConv(nn.Module):
    """Depthwise causal convolution with a bias: y_t = b + sum_j k_j *
    x_{t - taps + 1 + j}."""

    taps: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        kernel = self.param("kernel", lfm2._normal, (self.taps, width))
        bias = self.param("bias", nn.initializers.zeros, (width,))
        padded = jnp.pad(x, ((0, 0), (self.taps - 1, 0), (0, 0)))
        return bias.astype(self.dtype) + sum(
            kernel[j].astype(self.dtype) * padded[:, j:j + x.shape[1]]
            for j in range(self.taps))


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba's start: A = -(1, 2, ..., state) in every channel."""
    del key
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=dtype), shape))


class Mamba(nn.Module):
    """The selective state-space mixer (module docstring): ``(out,
    memory)``."""

    inner: int
    state: int
    taps: int
    dt_rank: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        hidden = u.shape[-1]
        with lfm2.scope("ssm_proj"):
            x, z = jnp.split(lfm2.keep(lfm2._dense(
                2 * self.inner, self.dtype, "in_proj")(u), "ssm_in_proj"),
                2, axis=-1)
        with lfm2.scope("ssm_conv"):
            x = nn.silu(CausalConv(self.taps, self.dtype, name="conv")(x))
        with lfm2.scope("ssm_proj"):
            d, b, c = jnp.split(lfm2.keep(lfm2._dense(
                self.dt_rank + 2 * self.state, self.dtype, "x_proj")(x),
                "ssm_x_proj"), [self.dt_rank, self.dt_rank + self.state], -1)
            delta = jax.nn.softplus(lfm2.keep(nn.Dense(
                self.inner, dtype=self.dtype, kernel_init=lfm2._normal,
                name="dt_proj")(d), "ssm_dt_proj").astype(jnp.float32))
        with lfm2.scope("ssm_scan"):
            a_log = self.param("A_log", _a_log_init, (self.inner, self.state))
            skip = self.param("D", nn.initializers.ones, (self.inner,))
            y = scan.selective_scan(x, delta, -jnp.exp(a_log), b, c, skip,
                                    kept=lfm2._count)
        with lfm2.scope("ssm_proj"):
            memory = lfm2.keep(y * nn.silu(z), "ssm_memory")
            out = lfm2.keep(lfm2._dense(hidden, self.dtype, "out_proj")(
                memory), "ssm_out_proj")
        return out, memory


def diff_core(q, k, v, lam, window=None):
    """(A_1 - λ A_2) v in float32, (n, t, heads, 2 x head) for q (n, t,
    heads, 2, head), k (n, t, pairs, 2, head), v (n, t, pairs, 2 x head):
    two `ops.attention.causal_gqa` calls, one a half of v (module
    docstring), `lfm2.einsum_attention` their fallback; ``window`` as
    theirs."""
    heads, hd = q.shape[2], q.shape[-1]
    both_q = jnp.concatenate([q[:, :, :, 0], q[:, :, :, 1]], axis=2)
    both_k = jnp.concatenate([k[:, :, :, 0], k[:, :, :, 1]], axis=2)
    halves = []
    for half in (v[..., :hd], v[..., hd:]):
        halves.append(attention.causal_gqa(
            both_q, both_k, jnp.concatenate([half, half], axis=2),
            lfm2.einsum_attention, window=window,
            kept=lfm2._count).astype(jnp.float32))
    first = jnp.concatenate([h[:, :, :heads] for h in halves], axis=-1)
    second = jnp.concatenate([h[:, :, heads:] for h in halves], axis=-1)
    return first - lam * second


class DiffAttention(nn.Module):
    """Differential attention (module docstring) at published layer
    ``layer``: ``(out, (k, v))``. ``shared``: the keys and values are the
    caller's ``kv`` (a cross-attention layer: W_q and W_o alone)."""

    heads: int
    kv_heads: int
    head_dim: int
    layer: int
    core_scope: str
    eps: float = 1e-5
    dtype: Any = jnp.float32
    window: Optional[int] = None
    shared: bool = False

    @nn.compact
    def __call__(self, u, kv=None):
        n, t, hidden = u.shape
        heads, pairs, hd = self.heads // 2, self.kv_heads // 2, self.head_dim
        with lfm2.scope("attention_proj"):
            q = lfm2.keep(lfm2._dense(self.heads * hd, self.dtype, "q_proj")(
                u), "attention_q_proj").reshape(n, t, heads, 2, hd)
            if self.shared:
                k, v = kv
            else:
                k = lfm2.keep(lfm2._dense(
                    self.kv_heads * hd, self.dtype, "k_proj")(u),
                    "attention_k_proj").reshape(n, t, pairs, 2, hd)
                v = lfm2._dense(self.kv_heads * hd, self.dtype, "v_proj")(
                    u).reshape(n, t, pairs, 2 * hd)
        with lfm2.scope(self.core_scope):
            start = lambda_init(self.layer)
            vectors = [self.param(name, nn.initializers.normal(0.1), (hd,))
                       for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                                    "lambda_k2")]
            # Products and sums, not a dot: a float32 dot is one bf16 pass
            # on the TPU.
            lam = (jnp.exp(jnp.sum(vectors[0] * vectors[1]))
                   - jnp.exp(jnp.sum(vectors[2] * vectors[3])) + start)
            mixed = diff_core(q, k, v, lam, self.window)
            mixed = lfm2.RMSNorm(self.eps, jnp.float32, name="subln")(
                mixed) * (1 - start)
        if self.is_mutable_collection(lfm2.COUNTER_MAXES):
            self.variable(lfm2.COUNTER_MAXES, "diff_lambda_max",
                          lambda: jnp.zeros((), jnp.float32)).value = (
                jnp.abs(lam))
        with lfm2.scope("attention_proj"):
            out = lfm2.keep(lfm2._dense(hidden, self.dtype, "o_proj")(
                mixed.astype(self.dtype).reshape(n, t, heads * 2 * hd)),
                "attention_o_proj")
        return out, (k, v)


class GatedMemory(nn.Module):
    """The gated memory unit: (silu(u W_1) * memory) W_2, no bias."""

    inner: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, memory):
        gate = lfm2.keep(lfm2._dense(self.inner, self.dtype, "in_proj")(u),
                         "gmu_gate")
        return lfm2.keep(lfm2._dense(u.shape[-1], self.dtype, "out_proj")(
            nn.silu(gate) * memory), "gmu_out_proj")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every block of one model shares (hashable: a static field)."""

    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    sliding_window: int
    inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float


class Block(nn.Module):
    """One layer of ``kind`` at published index ``layer``: (h, memory, kv)
    in and out."""

    kind: str
    layer: int
    cfg: Sizes
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, memory, kv):
        m = self.cfg
        if self.kind not in KINDS:
            raise ValueError(f"unknown layer type {self.kind!r}")
        u = layer_norm(m.eps, self.dtype, "operator_norm")(h)
        if self.kind == "mamba":
            out, memory = Mamba(m.inner, m.d_state, m.d_conv, m.dt_rank,
                                self.dtype, name="mamba")(u)
        elif self.kind == "gmu":
            if memory is None:
                raise ValueError("a gated memory unit before any Mamba layer")
            with lfm2.scope("gmu"):
                out = GatedMemory(m.inner, self.dtype, name="gmu")(u, memory)
        else:
            cross = self.kind == "cross_attention"
            if cross and kv is None:
                raise ValueError("cross-attention before any full attention")
            out, made = DiffAttention(
                m.heads, m.kv_heads, m.head_dim, self.layer,
                CORE_SCOPES[self.kind], m.eps, self.dtype,
                window=m.sliding_window if self.kind == "sliding_attention"
                else None, shared=cross, name="attn")(u, kv)
            if self.kind == "full_attention":
                kv = made
        h = h + out
        u = layer_norm(m.eps, self.dtype, "ffn_norm")(h)
        with lfm2.scope("dense_mlp"):
            h = h + lfm2.SwiGLU(m.dense_width, self.dtype, name="mlp")(u)
        return h, memory, kv


def chain(h, layer_types, first_layer, cfg, dtype, remat):
    """The blocks ``layer_i`` of ``layer_types`` (published indices from
    ``first_layer``) on h, under the calling module, handing (h, memory,
    kv) on; the last h. ``remat`` as `lfm2.recomputed`'s."""
    memory = kv = None
    with lfm2.recomputed(Block, remat) as block:
        for i, kind in enumerate(layer_types):
            h, memory, kv = block(kind, first_layer + i, cfg, dtype,
                                  name=f"layer_{i}")(h, memory, kv)
    return h


class Phi4Flash(nn.Module):
    """The model: tied embedding, the blocks of ``layer_types`` (published
    layers from ``first_layer``), a final LayerNorm, logits over the
    ``num_classes`` vocabulary rows held (float32). ``remat`` recomputes
    each block in the backward pass but for what it made under a name of
    `lfm2.KEPT` (`lfm2.recomputed`)."""

    num_classes: int = 25008
    dtype: Any = jnp.float32
    hidden: int = 2560
    layer_types: Sequence[str] = ("sliding_attention", "mamba",
                                  "full_attention", "gmu", "cross_attention")
    first_layer: int = 15
    heads: int = 40
    kv_heads: int = 20
    head_dim: int = 64
    dense_width: int = 10240
    sliding_window: int = 512
    expand: int = 2
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    eps: float = 1e-5
    remat: bool = False

    def sizes(self):
        """What the blocks share, as their static field."""
        return Sizes(
            self.heads, self.kv_heads, self.head_dim, self.dense_width,
            self.sliding_window, self.expand * self.hidden, self.d_state,
            self.d_conv, self.dt_rank, self.eps)

    @nn.compact
    def __call__(self, tokens, train=False):
        del train  # no dropout, no batch statistics
        with lfm2.scope("embed"):
            table = nn.Embed(
                self.num_classes, self.hidden, dtype=self.dtype,
                embedding_init=nn.initializers.normal(self.hidden ** -0.5),
                name="embed")
            h = table(tokens)
        h = chain(h, self.layer_types, self.first_layer, self.sizes(),
                  self.dtype, self.remat)
        with lfm2.scope("head_loss"):
            h = layer_norm(self.eps, self.dtype, "final_norm")(h)
            return table.attend(h).astype(jnp.float32)


def phi4_mini_flash_l15_19(num_classes=25008, dtype=jnp.float32):
    """One pipeline stage of Phi-4-mini-flash-reasoning: published layers
    15-19 (window attention, the Mamba layer whose memory the cross-decoder
    reads, the full-attention layer whose keys and values it shares, a gated
    memory unit, a cross-attention layer), every width as published;
    ``num_classes`` is the vocabulary slice (25,008 of 200,064: an eighth).
    ``remat``: each block is recomputed in the backward pass but for
    `lfm2.KEPT`, so that 4 workers' 16,384 tokens a step fit beside a 4 x
    577M gradient stack on one v5e."""
    return Phi4Flash(num_classes=num_classes, dtype=dtype, remat=True)


def phi4flash_tiny(num_classes=64, dtype=jnp.float32, **fields):
    """The family at a size the CPU tests hold: hidden 64, 8 layers (Mamba,
    window, Mamba, window, the memory's Mamba, full, GMU, cross) whose
    layers 3-7 are a stage of the published stage's kinds, 8 query heads
    (4 differential) over 4 KV heads (2 pairs) of 8, a window of 4, a state
    of 4."""
    sizes = dict(
        hidden=64,
        layer_types=("mamba", "sliding_attention", "mamba",
                     "sliding_attention", "mamba", "full_attention", "gmu",
                     "cross_attention"),
        first_layer=0, heads=8, kv_heads=4, head_dim=8, dense_width=96,
        sliding_window=4, d_state=4, dt_rank=4)
    sizes.update(fields)
    return Phi4Flash(num_classes=num_classes, dtype=dtype, **sizes)
