"""Operations of the attention cores of one training step where a layer's
head count is its own, from shapes alone (a configuration whose ``model``
group has ``num_attention_heads_per_layer`` beside ``layer_types``, and whose
family counts the (query, key) pairs a head of each kind of layer sees:
``visible_pairs(kind, t, window)``).

A visible pair goes through two contractions of ``head_dim`` (scores,
probabilities x values). A training step does each three times over
(forward, and the two gradients each contraction has): 2 FLOP x 3 x 2 x
head_dim a pair and head; recomputation is not counted, nor are the
projections, the gate, norms, rotary embedding and softmax. The same count
whichever path implements the core (blockwise kernels or einsums), so the
share it gives does not move when the implementation does. `attention_cost`
is the same count for a model with one head count.
"""

import references


def head_pairs_per_sequence(config):
    """Sum over the layers of heads x the (query, key) pairs a head of the
    layer's kind sees, at the configuration's sequence length."""
    model = config["model"]
    family = references.family(model["family"])
    return sum(
        heads * family.visible_pairs(
            kind, model["seq_len"], model.get("sliding_window"))
        for kind, heads in zip(model["layer_types"],
                               model["num_attention_heads_per_layer"],
                               strict=True))


def core_flops_per_step(config):
    sequences = config["num_workers"] * config["batch_per_worker"]
    return (2 * 3 * 2 * config["model"]["head_dim"] * sequences
            * head_pairs_per_sequence(config))
