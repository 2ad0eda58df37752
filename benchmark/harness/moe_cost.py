"""Operations and bytes of an expert layer's grouped matmuls, from shapes
alone (a configuration of the ``lfm2_moe`` family).

A (token, expert) pair goes through three matmuls of hidden x width (up,
gate, down). A training step does each three times over (forward, gradient
of the rows, gradient of the weights): 2 x 3 x 3 x hidden x width FLOP a
pair; recomputation is not counted. The pairs are the uniform expectation
(`references/lfm2_moe.expected_pairs`): workers x tokens a worker x experts
per token x held / published, in every expert layer. Bytes, the least any
implementation moves: every held expert's three kernels read twice (forward,
gradient of the rows) and their gradient written once, in the compute
dtype's width, beside each pair's row in and out of each matmul. At 512
pairs an expert a pass the matmuls are bound by operations, not bytes
(`least_seconds` takes the larger).
"""

import references


def _sizes(config):
    model = config["model"]
    family = references.family(model["family"])
    tokens = config["batch_per_worker"] * model["seq_len"]
    layers = sum(1 for i in range(len(model["layer_types"]))
                 if i >= model["num_dense_layers"])
    pairs = config["num_workers"] * layers * family.expected_pairs(
        model, tokens)
    return model, layers, pairs


def expert_pairs_per_step(config):
    """(token, expert) pairs the held experts compute in one step, all
    workers and expert layers, at the uniform expectation."""
    return _sizes(config)[2]


def expert_flops_per_step(config):
    model, _, pairs = _sizes(config)
    return (2 * 3 * 3 * model["hidden_size"] * model["moe_intermediate_size"]
            * pairs)


def expert_bytes_per_step(config, itemsize=2):
    model, layers, pairs = _sizes(config)
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    kernels = 3 * len(model["experts_held"]) * h * w
    passes = config["num_workers"] * layers
    rows = 3 * pairs * (2 * h + 4 * w)  # in and out of each of three matmuls
    return itemsize * (3 * kernels * passes + rows)


def least_seconds(config, flops_per_s, hbm_bytes_per_s):
    return max(expert_flops_per_step(config) / flops_per_s,
               expert_bytes_per_step(config) / hbm_bytes_per_s)
