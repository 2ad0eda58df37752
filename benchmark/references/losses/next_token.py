"""Mean softmax cross-entropy of raw logits (batch, time, vocabulary) against
the next token at every position (batch, time), in float32."""

import jax
import jax.numpy as jnp


def loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
