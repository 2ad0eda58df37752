"""Test-only input kind: int32 token ids with one label per sequence. Reads
``model["seq_len"]``, ``model["vocab"]`` and ``model["num_labels"]``. The
label is a function of the sequence (its first token's residue), so a sound
run's loss can fall."""

import jax
import jax.numpy as jnp


def example(model):
    return jnp.zeros((1, model["seq_len"]), jnp.int32)


def batches(key, model, n, batch, num_batches):
    """``(xs, ys)``: xs (num_batches, n, batch, T) int32 below the
    vocabulary, ys (num_batches, n, batch) int32 below the labels."""
    xs = jax.random.randint(
        jax.random.fold_in(key, 0x70CE), (num_batches, n, batch,
                                          model["seq_len"]),
        0, model["vocab"], jnp.int32)
    return xs, xs[..., 0] % model["num_labels"]
