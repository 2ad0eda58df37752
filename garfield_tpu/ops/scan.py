"""The selective scan of a Mamba layer (Gu & Dao, arXiv:2312.00752), chunked.

``selective_scan(x, delta, A, B, C, D)`` runs, for every sequence and
channel c, a state of ``d_state`` numbers along the positions t::

    s_t = exp(delta_t[c] A[c]) * s_{t-1} + delta_t[c] x_t[c] B_t
    y_t[c] = s_t . C_t + D[c] x_t[c]

x, delta (n, t, channels); A (channels, state); B, C (n, t, state); D
(channels). The state is float32 whatever the operands' dtype; y comes back
in x's.

**Chunks.** Differentiated as written, a scan over the positions keeps
every position's state for the backward pass: t x channels x state float32
a sequence (1.34 GB at t 4,096, 5,120 channels, state 16). Here an outer
``lax.scan`` walks chunks of the sequence, carrying the state in float32,
and each chunk's body is recomputed in the backward pass
(``jax.checkpoint``): what the backward pass keeps is the state at each
chunk's start (a few MB a sequence), and one chunk's positions at a time
while it runs. Inside a chunk a loop over the positions carries the state
(``lax.scan``: one position's elementwise update a step, which XLA fuses
whole; on a v5e at (1, 4096, 5120, 16) it beat an associative scan inside
the chunk 25.0 ms to 52.6 forward and backward, DESIGN.md section 31). The
chunk's length comes from the shapes (`chunk_length`): the longest power of
two up to ``CHUNK_MAX`` whose per-position states stay within
``CHUNK_BYTES``; a sequence that is no multiple of it is padded with
positions whose delta is 0, which leave the state as it is.

Said once for each distinct line, on the ``info`` channel: ``[ssm] chunked:
(n, t, channels, state) = (1, 4096, 5120, 16), chunks of 64, loop over
positions, state float32``. The chunked body is the path on every backend;
a Pallas kernel that holds the state in VMEM would take its place where the
step is lowered for the TPU, as `ops.attention.causal_gqa` chooses its
kernels, and leave the entry as it is. `sequential_scan` is the plain spec.
"""

import functools

import jax
import jax.numpy as jnp

from .attention import say

__all__ = ["selective_scan", "sequential_scan", "chunk_length", "CHUNK_MAX",
           "CHUNK_BYTES"]

# 64 positions ran the cell's scan fastest of 64, 128 and 256 on a v5e.
CHUNK_MAX = 64
# Bytes of float32 states a chunk's positions may hold at once.
CHUNK_BYTES = 64 << 20


def chunk_length(n, t, channels, state):
    """Positions a chunk: the longest power of two up to ``CHUNK_MAX`` (and
    up to t) whose n x channels x state float32 states a position fit in
    ``CHUNK_BYTES`` together; at least 1."""
    per_position = n * channels * state * 4
    length = 1
    while (2 * length <= min(t, CHUNK_MAX)
           and 2 * length * per_position <= CHUNK_BYTES):
        length *= 2
    return length


def _step(a_t, s, inputs):
    """One position: the state (n, state, channels) and y (n, channels)."""
    x_t, d_t, b_t, c_t = (v.astype(jnp.float32) for v in inputs)
    s = (jnp.exp(d_t[:, None, :] * a_t[None]) * s
         + (d_t * x_t)[:, None, :] * b_t[:, :, None])
    return s, jnp.sum(s * c_t[:, :, None], axis=1)


def sequential_scan(x, delta, A, B, C, D):
    """The spec: one ``lax.scan`` over every position, float32, nothing
    recomputed."""
    n, _, channels = x.shape
    s0 = jnp.zeros((n, A.shape[1], channels), jnp.float32)
    time_major = [jnp.swapaxes(v, 0, 1) for v in (x, delta, B, C)]
    _, y = jax.lax.scan(
        functools.partial(_step, A.T.astype(jnp.float32)), s0, time_major)
    y = jnp.swapaxes(y, 0, 1) + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype)


def _chunked(x, delta, A, B, C, D, length):
    """The scan's y in x's dtype: chunks of ``length`` positions, each
    recomputed in the backward pass."""
    n, t, channels = x.shape
    chunks = -(-t // length)
    pad = chunks * length - t
    a_t = A.T.astype(jnp.float32)

    def arrange(v):  # (n, t, m) -> (chunks, length, n, m), zeros after t
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return v.reshape(n, chunks, length, -1).transpose(1, 2, 0, 3)

    @jax.checkpoint
    def chunk(s, inputs):
        return jax.lax.scan(functools.partial(_step, a_t), s, inputs)

    s0 = jnp.zeros((n, A.shape[1], channels), jnp.float32)
    _, y = jax.lax.scan(chunk, s0, tuple(map(arrange, (x, delta, B, C))))
    y = y.transpose(2, 0, 1, 3).reshape(n, chunks * length, channels)[:, :t]
    y = y + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype)


def selective_scan(x, delta, A, B, C, D):
    """y of the selective scan (module docstring) in x's dtype, in chunks of
    `chunk_length` positions. Says its line once."""
    n, t, channels = x.shape
    state = A.shape[1]
    length = chunk_length(n, t, channels, state)
    say(f"[ssm] chunked: (n, t, channels, state) = ({n}, {t}, {channels}, "
        f"{state}), chunks of {length}, loop over positions, state float32")
    return _chunked(x, delta, A, B, C, D, length)
