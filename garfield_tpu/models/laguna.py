"""Laguna language models (``model_type`` ``laguna``: poolside's Laguna-XS.2
and its siblings): window and full attention in one model with a head count
and a rotary table of each kind's own, a per-head output gate, a dense SwiGLU
in the leading layer, then a mixture of experts with a shared expert beside
the routed ones, an untied head.

The signature, the share of a layer one chip holds (``experts_held``,
``num_classes`` as the vocabulary slice), the dropless expert layer, the
precision, the ``model.*`` scopes and the counters are `models/lfm2.py`'s
(its module docstring): this family is made of that file's ``RMSNorm``,
``Attention``, ``SwiGLU`` and ``ExpertLayer`` by their fields, not of copies.

- Block i: h += Attn_i(RMSNorm(h)); h += FF_i(RMSNorm(h)). No bias.
- Attn_i has ``heads_per_layer[i]`` query heads (the block's number, not the
  model's) over ``kv_heads`` KV heads, a per-head RMSNorm on q and k,
  half-rotation rotary by its kind's table, and a gate: one sigmoid a head
  and position, from the block's input, on the core's output
  (`lfm2.Attention.gate`). ``sliding_attention``: key j is visible to query i
  iff 0 <= i - j < ``sliding_window``; the plain table of ``sliding_theta``
  over the whole head. ``full_attention``: causal; the first
  ``full_rotary_dim`` dimensions of a head are rotated by the YaRN table of
  ``full_theta`` reckoned over that many dimensions, the others pass.
- FF_i: a dense SwiGLU of ``dense_width`` in the first ``num_dense_layers``
  layers; after them sigmoid scores over all ``num_experts``, the top
  ``experts_per_token`` weighted by their scores over their sum times
  ``scaling`` (no bias leaf, no epsilon), plus a shared SwiGLU of
  ``shared_width`` that every token passes, whole on every chip.
- Logits = RMSNorm_f(h) W_head^T over the vocabulary rows held; embedding and
  head are two leaves.

Scopes: as `models/mellum.py`'s blocks (``attention_proj`` around
projections, q/k norm and rotary embedding, ``window_attention`` or
``full_attention`` around the core alone), with ``attention_gate`` around
the gate and ``shared_expert`` around the shared expert.
"""

import dataclasses
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from . import lfm2

__all__ = ["KINDS", "Sizes", "Block", "Laguna", "laguna_xs2_ep16",
           "laguna_tiny"]

# ``layer_types`` entries, and the scope around each kind's attention core.
KINDS = {"sliding_attention": "window_attention",
         "full_attention": "full_attention"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every block of one model shares (hashable: a static field)."""

    kv_heads: int
    head_dim: int
    sliding_window: int
    sliding_theta: float
    full_theta: float
    full_rotary_dim: Optional[int]
    yarn: Optional[lfm2.Yarn]
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int
    experts_held: tuple
    experts_per_token: int
    scaling: float
    eps: float


class Block(nn.Module):
    """One layer: gated attention of its ``kind`` with its own number of
    ``heads``, then the expert layer (``moe``) or the dense MLP."""

    kind: str
    heads: int
    moe: bool
    cfg: Sizes
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        m = self.cfg
        if self.kind not in KINDS:
            raise ValueError(f"unknown layer type {self.kind!r}")
        sliding = self.kind == "sliding_attention"
        u = lfm2.RMSNorm(m.eps, self.dtype, name="operator_norm")(h)
        h = h + lfm2.Attention(
            self.heads, m.kv_heads, m.head_dim,
            m.sliding_theta if sliding else m.full_theta, m.eps, self.dtype,
            window=m.sliding_window if sliding else None,
            yarn=None if sliding else m.yarn,
            core_scope=KINDS[self.kind],
            rotary_dim=None if sliding else m.full_rotary_dim,
            gate=True, name="attn")(u)
        u = lfm2.RMSNorm(m.eps, self.dtype, name="ffn_norm")(h)
        if self.moe:
            return h + lfm2.ExpertLayer(
                m.num_experts, tuple(m.experts_held), m.experts_per_token,
                m.expert_width, m.scaling, self.dtype, bias=False,
                shared_width=m.shared_width, name="moe")(u)
        with lfm2.scope("dense_mlp"):
            return h + lfm2.SwiGLU(m.dense_width, self.dtype, name="mlp")(u)


class Laguna(nn.Module):
    """The model: embedding, ``layer_types`` blocks of which the first
    ``num_dense_layers`` have a dense MLP and the others an expert layer,
    block i with ``heads_per_layer[i]`` query heads, a final RMSNorm, logits
    over the ``num_classes`` vocabulary rows held (float32) through a head of
    its own. ``remat`` recomputes each block in the backward pass but for
    what it made under a name of `lfm2.KEPT` (`lfm2.recomputed`)."""

    num_classes: int = 12544
    dtype: Any = jnp.float32
    hidden: int = 2048
    layer_types: Sequence[str] = ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    heads_per_layer: Sequence[int] = (48, 64, 64, 64, 48)
    num_dense_layers: int = 1
    kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    sliding_theta: float = 1e4
    full_theta: float = 5e5
    full_rotary_dim: Optional[int] = 64
    yarn: Optional[lfm2.Yarn] = lfm2.Yarn(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    dense_width: int = 8192
    expert_width: int = 512
    shared_width: int = 512
    num_experts: int = 256
    experts_held: Sequence[int] = tuple(range(16))
    experts_per_token: int = 8
    scaling: float = 2.5
    eps: float = 1e-6
    remat: bool = False

    def sizes(self):
        """What the blocks share, as their static field."""
        return Sizes(**{
            f.name: getattr(self, f.name) for f in dataclasses.fields(Sizes)
        } | {"experts_held": tuple(self.experts_held)})

    @nn.compact
    def __call__(self, tokens, train=False):
        del train  # no dropout, no batch statistics
        if len(self.heads_per_layer) != len(self.layer_types):
            raise ValueError(
                f"{len(self.heads_per_layer)} head counts for "
                f"{len(self.layer_types)} layers")
        with lfm2.scope("embed"):
            # Rows of unit entries, as `mellum.Mellum`'s: the head is untied.
            h = nn.Embed(self.num_classes, self.hidden, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
        with lfm2.recomputed(Block, self.remat) as block:
            for i, (kind, heads) in enumerate(
                    zip(self.layer_types, self.heads_per_layer)):
                h = block(kind, heads, i >= self.num_dense_layers,
                          self.sizes(), self.dtype, name=f"layer_{i}")(h)
        with lfm2.scope("head_loss"):
            h = lfm2.RMSNorm(self.eps, self.dtype, name="final_norm")(h)
            head = nn.Embed(
                self.num_classes, self.hidden, dtype=self.dtype,
                embedding_init=nn.initializers.normal(self.hidden ** -0.5),
                name="lm_head")
            return head.attend(h).astype(jnp.float32)


def laguna_xs2_ep16(num_classes=12544, dtype=jnp.float32):
    """One chip's share of Laguna-XS.2 where 16 chips share each layer by
    expert parallelism: published layers 0-4 (the leading dense layer and the
    first whole period of expert layers: full; sliding, sliding, sliding,
    full), experts 0-15 of 256, every width as published; ``num_classes`` is
    the vocabulary slice (12,544 of 100,352: an eighth, over the 8 chips of
    a host). ``remat`` as in `lfm2_8b_a1b_ep4` (5 workers' 20,480 tokens a
    step beside a 5 x 490M gradient stack): each block is recomputed in the
    backward pass but for `lfm2.KEPT`."""
    return Laguna(num_classes=num_classes, dtype=dtype, remat=True)


def laguna_tiny(num_classes=64, dtype=jnp.float32, experts_held=(0, 1),
                **fields):
    """The family at a size the CPU tests hold: hidden 64, a dense layer and
    two expert layers, 6 and 4 query heads over 2 KV heads of 16 of which a
    full layer rotates 8 dimensions, a window of 4, 8 experts of which
    ``experts_held`` are here, top-2, a shared expert."""
    sizes = dict(
        hidden=64,
        layer_types=("full_attention", "sliding_attention", "full_attention"),
        heads_per_layer=(4, 6, 4), num_dense_layers=1, kv_heads=2,
        head_dim=16, sliding_window=4, full_rotary_dim=8,
        yarn=lfm2.Yarn(factor=4.0, original_max_position_embeddings=8,
                       beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.1386294361119891),
        dense_width=96, expert_width=48, shared_width=40, num_experts=8,
        experts_held=tuple(experts_held), experts_per_token=2)
    sizes.update(fields)
    return Laguna(num_classes=num_classes, dtype=dtype, **sizes)
