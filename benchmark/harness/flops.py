"""Model operations of one training step, from shapes alone.

Forward multiply-adds of every conv and dense layer (the configuration's
plain reference family counts them from the configuration's sizes) x 2 FLOP
x 3 (forward, gradient of the input, gradient of the weights) x images. Model
work only: attack, rule, optimizer, BatchNorm and recomputation are not
counted, so the number does not move when the implementation does.
"""

import references


def forward_macs_per_image(config):
    model = config["model"]
    return references.family(model["family"]).forward_macs(model)


def train_flops_per_image(config):
    return 2 * 3 * forward_macs_per_image(config)


def images_per_step(config):
    return config["num_workers"] * config["batch_per_worker"]
