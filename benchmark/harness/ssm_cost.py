"""Bytes the selective scans of one training step must move, from shapes
alone (a configuration of a family with Mamba layers: ``layer_types``
entries ``mamba``, ``mamba_expand``, ``mamba_d_state``).

What the scan's equations read and write once, whatever implements them
(`ops/scan.py`'s chunks or a kernel that holds the state on chip): forward,
x~ and Δ (t x channels), B and C (t x state), A and D; y out. Backward, the
same four, dy and A and D in; dx~, dΔ, dB, dC, dA and dD out. 2 B a value
of a sequence's arrays (bf16, the program's activations), 4 B for A, D and
their gradients (float32 parameters). The state itself need never leave the
chip, and recomputation is not counted, so the share it gives does not move
when the implementation does.
"""


def _shape(config):
    model = config["model"]
    channels = model["mamba_expand"] * model["hidden_size"]
    return model["seq_len"], channels, model["mamba_d_state"]


def bytes_per_layer_and_sequence(config, itemsize=2):
    t, c, s = _shape(config)
    activations = (3 + 5) * t * c + (2 + 4) * t * s  # x~, Δ, y | 2x, 2Δ, dy
    parameters = 3 * (c * s + c)  # A and D read twice, dA and dD written
    return itemsize * activations + 4 * parameters


def bytes_per_step(config):
    layers = config["model"]["layer_types"].count("mamba")
    sequences = config["num_workers"] * config["batch_per_worker"]
    return layers * sequences * bytes_per_layer_and_sequence(config)
