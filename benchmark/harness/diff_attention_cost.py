"""Operations of the differential attention cores of one training step,
from shapes alone (a configuration of a family whose attention layers are
DIFF Transformer's, arXiv:2410.05258, and which counts the (query, key)
pairs a head of each kind of layer sees: ``visible_pairs(kind, t,
window)``).

A visible pair goes, in every differential layer, through the two
softmaxes' scores — ``num_attention_heads`` heads of ``head_dim`` — and one
product (A_1 - λ A_2) v — half as many heads, of twice the width: 2 FLOP x
(heads x head_dim + heads / 2 x 2 head_dim) = 10,240 FLOP at 40 heads of
64. A training step does each three times over (forward, and the two
gradients each contraction has); recomputation is not counted, nor are the
projections, λ, the softmaxes and the per-head norm. The same count
whichever path implements the core (two kernel calls of both softmaxes,
four of one, or einsums), so the share it gives does not move when the
implementation does.
"""

import references

KINDS = ("sliding_attention", "full_attention", "cross_attention")


def pairs_per_sequence(config):
    """Visible (query, key) pairs of a head, summed over the differential
    layers, at the configuration's sequence length."""
    model = config["model"]
    family = references.family(model["family"])
    return sum(family.visible_pairs(kind, model["seq_len"],
                                    model["sliding_window"])
               for kind in model["layer_types"] if kind in KINDS)


def flops_per_pair(config):
    model = config["model"]
    heads, hd = model["num_attention_heads"], model["head_dim"]
    return 2 * (heads * hd + heads // 2 * 2 * hd)


def core_flops_per_step(config):
    sequences = config["num_workers"] * config["batch_per_worker"]
    return 3 * flops_per_pair(config) * pairs_per_sequence(config) * sequences
