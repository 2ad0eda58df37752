"""Mean softmax cross-entropy of raw logits against integer labels."""

import jax
import jax.numpy as jnp


def loss(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
