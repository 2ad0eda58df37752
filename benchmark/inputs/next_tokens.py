"""Int32 token sequences with the next token as the label at every position.
Reads ``model["seq_len"]`` and ``model["vocab_size"]`` (the vocabulary rows
held: ids are drawn from the slice).

A sequence of T + 1 tokens gives x = its first T and y = its last T. A token
is, with probability ``COPY``, the token two places back, and otherwise a
fresh draw from the Zipf-Mandelbrot unigram law ``p(r) ~ 1 / (r + ZIPF_BETA)
** ZIPF_ALPHA`` over the ranks r = 1..vocab, the id being r - 1: Mandelbrot's
parameters for word frequencies (1953) as Piantadosi quotes them (Psychon.
Bull. Rev. 21, 2014, eq. 2: alpha about 1, beta about 2.7). At 16,384 ids the
most frequent is 3.2% of all tokens, the first ten 17%, the first hundred
41%: tokens of one id share a router input, so the held experts' share of
the (token, expert) pairs is uneven and differs from seed to seed, which a
dropless expert layer's time follows (PERF.md section 6, PR 28). A copy of a
token that follows the law follows it too; the next token depends on the
last ones, so a sound run's loss can fall, and no two rows are alike.

`sequences` is the program's own (`garfield_tpu/data/tokens.py`, which makes
the CLI's ``synthtokens`` dataset), copied here because the reference reads
its batches from this file and imports nothing of the program; the program's
tests hold the two to the same tokens from the same key."""

import jax
import jax.numpy as jnp

COPY = 0.5
ZIPF_ALPHA, ZIPF_BETA = 1.0, 2.7


def example(model):
    return jnp.zeros((1, model["seq_len"]), jnp.int32)


def unigram_cdf(vocab):
    """Cumulative Zipf-Mandelbrot probabilities of the ids 0..vocab-1."""
    mass = (jnp.arange(1, vocab + 1, dtype=jnp.float32)
            + ZIPF_BETA) ** -ZIPF_ALPHA
    return jnp.cumsum(mass) / jnp.sum(mass)


def sequences(key, shape, length, vocab):
    """Token ids ``(*shape, length)`` below ``vocab``; ``length`` is even."""
    k_fresh, k_draw = jax.random.split(key)
    draws = jnp.minimum(
        jnp.searchsorted(unigram_cdf(vocab),
                         jax.random.uniform(k_draw, (*shape, length)),
                         side="right"),
        vocab - 1).astype(jnp.int32)
    fresh = jax.random.uniform(k_fresh, (*shape, length)) >= COPY
    fresh = fresh.at[..., :2].set(True)
    # A copied token's value is that of the latest fresh draw of its own
    # parity at or before it: the running maximum of the fresh positions
    # along each parity class.
    at = jnp.where(fresh, jnp.arange(length, dtype=jnp.int32), -1)
    pairs = at.reshape(*shape, length // 2, 2)
    source = jax.lax.cummax(pairs, axis=pairs.ndim - 2).reshape(at.shape)
    return jnp.take_along_axis(draws, source, axis=-1)


def batches(key, model, n, batch, num_batches):
    """``(xs, ys)``, both (num_batches, n, batch, T) int32: ys is xs moved
    one place on."""
    t = model["seq_len"]
    tokens = sequences(jax.random.fold_in(key, 0x70CE),
                       (num_batches, n, batch), t + 2, model["vocab_size"])
    return tokens[..., :t], tokens[..., 1:t + 1]
