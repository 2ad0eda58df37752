"""Float32 images with one class label each: unit noise plus a per-class
template. Reads ``model["image"]`` (H, W, C) and ``model["num_classes"]``."""

import jax
import jax.numpy as jnp

# Strength of the per-class template added to the noise images: the labels
# are learnable, so the loss of a sound run falls instead of blowing up.
CLASS_SIGNAL = 0.5


def example(model):
    return jnp.zeros(model["image"], jnp.float32)[None]


def batches(key, model, n, batch, num_batches):
    """``(xs, ys)``: xs (num_batches, n, batch, H, W, C) f32 and ys
    (num_batches, n, batch) int32."""
    image, num_classes = model["image"], model["num_classes"]
    kt, kx, ky = jax.random.split(jax.random.fold_in(key, 0xDA7A), 3)
    templates = jax.random.normal(kt, (num_classes, *image), jnp.float32)
    ys = jax.random.randint(ky, (num_batches, n, batch), 0, num_classes,
                            jnp.int32)
    noise = jax.random.normal(kx, (num_batches, n, batch, *image),
                              jnp.float32)
    return noise + CLASS_SIGNAL * templates[ys], ys
