"""Operations of the attention cores of one training step, from shapes alone
(a configuration whose family counts the (query, key) pairs a head of each
kind of layer sees: ``visible_pairs(kind, t, window)``).

A visible pair goes through two contractions of ``head_dim`` (scores,
probabilities x values). A training step does each three times over
(forward, and the two gradients each contraction has): 2 FLOP x 3 x 2 x
head_dim a pair and head; recomputation is not counted, nor are the
projections, norms, rotary embedding and softmax. The same count whichever
path implements the core (blockwise kernels or einsums), so the share it
gives does not move when the implementation does.
"""

import references


def pairs_per_head(config):
    """``{kind: (query, key) pairs a head of such a layer sees}`` for the
    kinds of ``layer_types``, at the configuration's sequence length."""
    model = config["model"]
    family = references.family(model["family"])
    return {kind: family.visible_pairs(
        kind, model["seq_len"], model.get("sliding_window"))
        for kind in set(model["layer_types"])}


def core_flops_per_step(config):
    model = config["model"]
    pairs = pairs_per_head(config)
    sequences = config["num_workers"] * config["batch_per_worker"]
    return (2 * 3 * 2 * model["head_dim"] * model["num_attention_heads"]
            * sequences * sum(pairs[kind] for kind in model["layer_types"]))

