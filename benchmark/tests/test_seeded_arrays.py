"""What the harness makes from the seed must not move: the weights of both
committed configurations and their first batch are the arrays PR 26's tree
made, to the bit; and a family's stated leaf rules add to the defaults
without touching them.

The digests were taken on XLA:CPU from a clone of commit 26c6b76 (before
input kinds and leaf rules existed) through ``weights.make_params(key,
resnet.param_shapes(model), resnet.init_scales(model, init))`` — eagerly, as
the reference calls it, and under one ``jax.jit``, as the program's state is
made; the two differ in the last bit of some leaves, then as now — and
``jax.jit(weights.make_batches)`` at ``batch_per_worker`` 2."""

import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import inputs  # noqa: E402
from harness import weights  # noqa: E402
from references import resnet  # noqa: E402

# (configuration, seed): sha256 of the eager weights, the jitted weights, the
# first global batch's xs and its ys.
PARENT = {
    ("resnet18-cifar10-n8", 7): (
        "aaf517b5eeb86ec8dd2e6c3d25d8108aedc955d3e7f2cd768309559a758ad325",
        "80b091bc664082eba77ed826af270ee7c70efe1cf85515fab4b6bd12684a9396",
        "4a5048e04b806427f21481ac93b225c60b2b5c5834578411c0318a80dfbe66c1",
        "09ddba9c2f18047784ace7f329f5fd345d111e9eb60a194fa8d8fa0cd9147fe3"),
    ("resnet18-cifar10-n8", 3000000019): (
        "81fceb8c8a3607358545b5904122f21809f71f23ddd25fed4d36ab8ad80ac608",
        "2e55585cef531d477576adda04cbaee67c2cf50d03095e26bc3077093a859559",
        "6b9e0e42cdbcfa80b95c1fd28612171af34ff4f0ad9399cf0045a283843f2564",
        "755f1cccc0b5dbeeb0d4d0bfd2ec860fdceb9277aa747b2c47d88f79cef03b9c"),
    ("resnet50-cifar100-n16", 7): (
        "af05e35ccd0ff10e5f1adb0223d7249918495eecded7b5c0d7f89293f7210930",
        "078af1713d23f768c91c90c35073670a6d589b0deafb5282d310bcb1db442ca5",
        "dc8bce003445b6f96a5bf50c7cd7f76238045aaec56477eeedcffb94e4d8d8cb",
        "e739b26190691ef5952a58a8138a05e307a9a41fe2e8a86cb1011a7c4aa3b7da"),
    ("resnet50-cifar100-n16", 3000000019): (
        "35823c39a86f184fdd7ef583ba72f78724174f500e07676002b4c2250119506c",
        "bd66ba8c6ec9c6ea6f8569722896f008fe6bb07d6d9db0c4a2598845dc00fa46",
        "dcaceccce0e1c3b44d8128e953742ff65bbd8ec8c567efa9018edc494161726a",
        "81bf76095f2c2e5e748ef0bd7159d91a77c021f07f59ed9840806bb4b7dc5f81"),
}


def _sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_weights_and_batches_are_the_parents_to_the_bit(name, seed):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    model, n = cfg["model"], cfg["num_workers"]
    shapes = resnet.param_shapes(model)
    stated = weights.stated(resnet, model, cfg.get("init"))
    assert stated[1] is None  # the family states no rule: the defaults
    key = weights.seed_key(seed)
    eager = weights.make_params(key, shapes, *stated)
    jitted = jax.jit(lambda k: weights.make_params(k, shapes, *stated))(key)
    kind = inputs.kind(resnet.INPUT)
    xs, ys = jax.jit(
        lambda k: kind.batches(k, model, n, 2, weights.NUM_BATCHES))(key)
    assert xs.shape == (8, n, 2, 32, 32, 3) and xs.dtype == jnp.float32
    assert ys.shape == (8, n, 2) and ys.dtype == jnp.int32
    assert kind.example(model).shape == (1, 32, 32, 3)
    assert (_sha(*[eager[p] for p in sorted(eager)]),
            _sha(*[jitted[p] for p in sorted(jitted)]),
            _sha(xs[0]), _sha(ys[0])) == PARENT[name, seed]


SHAPES = {"a/kernel": (3, 3, 4, 8), "a/scale": (8,), "a/bias": (8,),
          "experts": (4, 16, 32), "gate": (16,)}
RULES = {"experts": ("normal", 16), "gate": ("zeros",)}


def test_a_stated_rule_makes_its_leaf_and_leaves_the_defaults_alone():
    key = weights.seed_key(5)
    with pytest.raises(ValueError, match="no rule to make the weight leaf"):
        weights.make_params(key, SHAPES)
    made = weights.make_params(key, SHAPES, {"experts": 0.5}, RULES)
    plain = weights.make_params(
        key, {p: s for p, s in SHAPES.items() if p.startswith("a/")})
    for path, value in plain.items():
        np.testing.assert_array_equal(made[path], value)
    assert made["experts"].shape == (4, 16, 32)
    # Variance 2 / 16 by the stated fan-in (not 2 / 64), times the scale.
    assert float(jnp.std(made["experts"])) == pytest.approx(
        0.5 * (2 / 16) ** 0.5, rel=0.05)
    np.testing.assert_array_equal(made["gate"], np.zeros(16, np.float32))


def test_a_stated_rule_may_take_the_place_of_a_default():
    made = weights.make_params(
        weights.seed_key(5), SHAPES,
        rules={**RULES, "a/kernel": ("ones",), "a/scale": ("normal", 2)})
    np.testing.assert_array_equal(made["a/kernel"], np.ones((3, 3, 4, 8)))
    assert float(jnp.std(made["a/scale"])) > 0.5


@pytest.mark.parametrize("scales,rules,match", [
    (None, {"nowhere": ("ones",)}, "do not exist"),
    ({"nowhere": 2.0}, None, "do not exist"),
    (None, {**RULES, "gate": ("uniform", 1)}, "no way to make"),
])
def test_what_cannot_be_made_raises(scales, rules, match):
    with pytest.raises(ValueError, match=match):
        weights.make_params(weights.seed_key(5), SHAPES, scales, rules)
