"""Weights and input batches, made on the device from ``--seed``.

The harness makes them (not the program), so the timed program and the plain
reference start from the same values while the reference takes nothing the
program has made. Both are pure functions of (seed, shapes): the same seed
gives the same arrays in any process.
"""

import math
import zlib

import jax
import jax.numpy as jnp

NUM_BATCHES = 8
# Strength of the per-class template added to the noise images: the labels
# are learnable, so the loss of a sound run falls instead of blowing up.
CLASS_SIGNAL = 0.5


def seed_key(seed):
    """PRNG key of a whole-number seed (any size up to 2**32 - 1)."""
    return jax.random.PRNGKey(int(seed))


def _leaf_key(key, path):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def make_params(key, shapes, scales=None):
    """``{path: f32 array}`` for ``{path: shape}``: He-normal kernels
    (variance 2 / fan_in), unit scales, zero biases — the usual start of a
    BatchNorm ResNet — each leaf times ``scales.get(path, 1)``."""
    scales = scales or {}
    unknown = set(scales) - set(shapes)
    if unknown:
        raise ValueError(f"scales for leaves that do not exist: {unknown}")
    params = {}
    for path in sorted(shapes):
        shape = tuple(shapes[path])
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "kernel":
            fan_in = math.prod(shape[:-1])
            made = jax.random.normal(
                _leaf_key(key, path), shape, jnp.float32
            ) * math.sqrt(2.0 / fan_in)
        elif leaf == "scale":
            made = jnp.ones(shape, jnp.float32)
        elif leaf == "bias":
            made = jnp.zeros(shape, jnp.float32)
        else:
            raise ValueError(f"no rule to make the weight leaf {path!r}")
        params[path] = made * scales.get(path, 1.0)
    return params


def make_batches(key, n, batch, image, num_classes, num_batches=NUM_BATCHES):
    """``(xs, ys)``: ``num_batches`` distinct global batches,
    xs (num_batches, n, batch, H, W, C) f32 and ys (num_batches, n, batch)
    int32. Every row differs: unit noise plus a per-class template."""
    kt, kx, ky = jax.random.split(jax.random.fold_in(key, 0xDA7A), 3)
    templates = jax.random.normal(kt, (num_classes, *image), jnp.float32)
    ys = jax.random.randint(ky, (num_batches, n, batch), 0, num_classes,
                            jnp.int32)
    noise = jax.random.normal(kx, (num_batches, n, batch, *image),
                              jnp.float32)
    return noise + CLASS_SIGNAL * templates[ys], ys
