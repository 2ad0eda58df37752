"""Single server, n workers (`garfield_tpu.parallel.aggregathor`)."""

import jax.numpy as jnp


def make_trainer(module, loss_fn, optimizer, config, traffic):
    from garfield_tpu.parallel import aggregathor

    attack = traffic["attack"]
    init_fn, step_fn, _ = aggregathor.make_trainer(
        module, loss_fn, optimizer, traffic["rule"],
        num_workers=config["num_workers"], f=config["f"],
        attack=None if attack == "none" else attack,
        gar_dtype=jnp.dtype(config["gar_dtype"]))
    return init_fn, step_fn
