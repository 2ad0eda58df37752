"""Mellum language models (``model_type`` ``mellum``: JetBrains'
Mellum2-12B-A2.5B and its siblings): window and full attention in one model,
each kind with its own rotary table, a mixture of experts in every layer, an
untied head.

The signature, the share of a layer one chip holds (``experts_held``,
``num_classes`` as the vocabulary slice), the dropless expert layer, the
precision, the ``model.*`` scopes and the counters are `models/lfm2.py`'s
(its module docstring): this family is made of that file's ``RMSNorm``,
``Attention`` and ``ExpertLayer`` by their fields, not of copies.

- Block: h += Attn_kind(RMSNorm(h)); h += MoE(RMSNorm(h)). No bias, no dense
  feed-forward, no shared expert.
- ``sliding_attention``: key j is visible to query i iff 0 <= i - j <
  ``sliding_window``; the plain rotary table of ``rope_theta``.
  ``full_attention``: causal; the YaRN table (`lfm2.rope_table`). Both:
  grouped-query, a per-head RMSNorm on q and k, half-rotation rotary.
- Router: softmax over all ``num_experts``, the top ``experts_per_token``
  renormalised over their sum (``norm_topk_prob``).
- Logits = RMSNorm_f(h) W_head^T over the vocabulary rows held; embedding and
  head are two leaves.

Scopes: a block puts ``attention_proj`` around its projections, q/k norm and
rotary embedding and ``window_attention`` or ``full_attention`` around the
attention core alone, so a trace tells the two kinds of layer apart.
"""

import dataclasses
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from . import lfm2

__all__ = ["KINDS", "Sizes", "Block", "Mellum", "mellum2_12b_a2p5b_ep4",
           "mellum2_tiny"]

# ``layer_types`` entries, and the scope around each kind's attention core.
KINDS = {"sliding_attention": "window_attention",
         "full_attention": "full_attention"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What every block of one model shares (hashable: a static field)."""

    heads: int
    kv_heads: int
    head_dim: int
    sliding_window: int
    rope_theta: float
    yarn: Optional[lfm2.Yarn]
    expert_width: int
    num_experts: int
    experts_held: tuple
    experts_per_token: int
    eps: float


class Block(nn.Module):
    """One layer: attention of its ``kind``, then the expert layer."""

    kind: str
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
            m.heads, m.kv_heads, m.head_dim, m.rope_theta, m.eps, self.dtype,
            window=m.sliding_window if sliding else None,
            yarn=None if sliding else m.yarn,
            core_scope=KINDS[self.kind], name="attn")(u)
        u = lfm2.RMSNorm(m.eps, self.dtype, name="ffn_norm")(h)
        return h + lfm2.ExpertLayer(
            m.num_experts, tuple(m.experts_held), m.experts_per_token,
            m.expert_width, dtype=self.dtype, score="softmax", name="moe")(u)


class Mellum(nn.Module):
    """The model: embedding, ``layer_types`` blocks, a final RMSNorm, logits
    over the ``num_classes`` vocabulary rows held (float32) through a head of
    its own. ``remat`` recomputes each block in the backward pass but for
    what it made under a name of `lfm2.KEPT` (`lfm2.recomputed`)."""

    num_classes: int = 24576
    dtype: Any = jnp.float32
    hidden: int = 2304
    layer_types: Sequence[str] = ("sliding_attention",) * 3 + (
        "full_attention",)
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 5e5
    yarn: Optional[lfm2.Yarn] = lfm2.Yarn(
        factor=16.0, original_max_position_embeddings=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782)
    expert_width: int = 896
    num_experts: int = 64
    experts_held: Sequence[int] = tuple(range(16))
    experts_per_token: int = 8
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
        with lfm2.scope("embed"):
            # Rows of unit entries: the head is untied, so the embedding
            # need not give logits of unit size, and a token's own row
            # stays the larger part of the residual stream at the start.
            h = nn.Embed(self.num_classes, self.hidden, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
        with lfm2.recomputed(Block, self.remat) as block:
            for i, kind in enumerate(self.layer_types):
                h = block(kind, self.sizes(), self.dtype,
                          name=f"layer_{i}")(h)
        with lfm2.scope("head_loss"):
            h = lfm2.RMSNorm(self.eps, self.dtype, name="final_norm")(h)
            head = nn.Embed(
                self.num_classes, self.hidden, dtype=self.dtype,
                embedding_init=nn.initializers.normal(self.hidden ** -0.5),
                name="lm_head")
            return head.attend(h).astype(jnp.float32)


def mellum2_12b_a2p5b_ep4(num_classes=24576, dtype=jnp.float32):
    """One chip's share of Mellum2-12B-A2.5B where 4 chips share each layer
    by expert parallelism: published layers 0-3 (the first whole period:
    three sliding layers and one full), experts 0-15 of 64, every width as
    published; ``num_classes`` is the vocabulary slice (24,576 of 98,304).
    ``remat`` as in `lfm2_8b_a1b_ep4` (4 workers' 16,384 tokens a step
    beside a 4 x 595M gradient stack): each block is recomputed in the
    backward pass but for `lfm2.KEPT`, here 2.21 GB a slot reckoned from
    the shapes: the attention projections and the kernels' residuals of
    four layers, and of four expert layers the sorts, the dispatched rows
    (32,768 x 2,304) and the three grouped matmuls' results."""
    return Mellum(num_classes=num_classes, dtype=dtype, remat=True)


def mellum2_tiny(num_classes=64, dtype=jnp.float32, experts_held=(0, 1),
                 **fields):
    """The family at a size the CPU tests hold: hidden 64, 8 experts of
    which ``experts_held`` are here, top-2, a window of 4, a YaRN table over
    8 original positions."""
    sizes = dict(
        hidden=64, layer_types=("sliding_attention", "full_attention"),
        heads=4, kv_heads=2, head_dim=16, sliding_window=4,
        yarn=lfm2.Yarn(factor=4.0, original_max_position_embeddings=8,
                       beta_fast=32.0, beta_slow=1.0,
                       attention_factor=1.1386294361119891),
        expert_width=48, num_experts=8, experts_held=tuple(experts_held),
        experts_per_token=2)
    sizes.update(fields)
    return Mellum(num_classes=num_classes, dtype=dtype, **sizes)
