"""Weights, made on the device from ``--seed``.

The harness makes them (not the program), so the timed program and the plain
reference start from the same values while the reference takes nothing the
program has made. A pure function of (seed, shapes, what the family states):
the same seed gives the same arrays in any process. The input batches are
an input kind's (`inputs/<kind>.py`), from the same key.
"""

import math
import zlib

import jax
import jax.numpy as jnp

# Distinct global batches a run cycles through.
NUM_BATCHES = 8


def seed_key(seed):
    """PRNG key of a whole-number seed (any size up to 2**32 - 1)."""
    return jax.random.PRNGKey(int(seed))


def _leaf_key(key, path):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _made(key, path, shape, rule):
    """One leaf by its rule: ``("normal", fan_in)`` (variance 2 / fan_in),
    ``("ones",)`` or ``("zeros",)``."""
    kind = rule[0]
    if kind == "normal":
        return jax.random.normal(
            _leaf_key(key, path), shape, jnp.float32
        ) * math.sqrt(2.0 / rule[1])
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"no way to make a {kind!r} leaf ({path!r})")


def _default_rule(path, shape):
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "kernel":
        return ("normal", math.prod(shape[:-1]))
    if leaf == "scale":
        return ("ones",)
    if leaf == "bias":
        return ("zeros",)
    raise ValueError(f"no rule to make the weight leaf {path!r}")


def make_params(key, shapes, scales=None, rules=None):
    """``{path: f32 array}`` for ``{path: shape}``: He-normal kernels
    (variance 2 / fan_in, fan_in the product of all axes but the last), unit
    scales, zero biases — the usual start of a BatchNorm ResNet — unless
    ``rules`` states the leaf's own (``{path: ("normal", fan_in) | ("ones",)
    | ("zeros",)}``: an embedding, a stack of expert kernels whose leading
    axis is no fan-in, a leaf by another name); each leaf times
    ``scales.get(path, 1)``. A leaf with neither raises."""
    scales, rules = scales or {}, rules or {}
    unknown = (set(scales) | set(rules)) - set(shapes)
    if unknown:
        raise ValueError(
            f"scales or rules for leaves that do not exist: {unknown}")
    params = {}
    for path in sorted(shapes):
        shape = tuple(shapes[path])
        rule = rules[path] if path in rules else _default_rule(path, shape)
        params[path] = _made(key, path, shape, tuple(rule)) * scales.get(
            path, 1.0)
    return params


def stated(family, model, init=None):
    """``(scales, rules)`` as a reference family states them for
    `make_params`: its ``init_scales(model, init)`` and, where it has one,
    its ``leaf_rules(model)``."""
    rules = family.leaf_rules(model) if hasattr(family, "leaf_rules") else None
    return family.init_scales(model, init), rules
