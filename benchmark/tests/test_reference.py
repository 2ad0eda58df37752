"""The plain references against the program's own pieces at a small size on
the CPU in float32: the model family's forward pass against the flax module,
and each rule and attack against the program's. The benchmark's reference
imports nothing of the program; only these tests see both."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from harness import system, weights  # noqa: E402
from references import resnet  # noqa: E402
from references.attacks import lie  # noqa: E402
from references.rules import average, krum, median  # noqa: E402

MODELS = {
    "resnet18": {"family": "resnet", "block": "basic",
                 "stage_sizes": [2, 2, 2, 2], "stem_width": 64,
                 "num_classes": 10, "image": [16, 16, 3]},
    "resnet50": {"family": "resnet", "block": "bottleneck",
                 "stage_sizes": [3, 4, 6, 3], "stem_width": 64,
                 "num_classes": 100, "image": [16, 16, 3]},
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_forward_equals_the_flax_module_in_training_mode(name):
    from garfield_tpu import models

    model = MODELS[name]
    dataset = "cifar10" if model["num_classes"] == 10 else "cifar100"
    module = models.select_model(name, dataset)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, *model["image"]))
    variables = module.init(jax.random.PRNGKey(1), x, train=False)
    shapes = {p: v.shape for p, v in
              system.flat_paths(variables["params"]).items()}
    assert shapes == {p: tuple(s) for p, s in
                      resnet.param_shapes(model).items()}
    made = weights.make_params(jax.random.PRNGKey(2), shapes)
    tree = jax.tree.unflatten(
        jax.tree.structure(variables["params"]),
        [made[p] for p in system.flat_paths(variables["params"])])
    theirs, _ = module.apply(
        {"params": tree, "batch_stats": variables["batch_stats"]}, x,
        train=True, mutable=["batch_stats"])
    ours = resnet.forward(made, x, model)
    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-3)


def _stack(n, sizes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(sizes))
    return {f"leaf{i}": jax.random.normal(k, (n, s))
            for i, (k, s) in enumerate(zip(keys, sizes))}


def _flat(stack):
    return jnp.concatenate([stack[p] for p in sorted(stack)], axis=1)


def test_lie_rows_equal_the_programs_attack():
    from garfield_tpu.attacks import lie_attack

    stack = _stack(8, (5, 7))
    byz = lie.byzantine(8, 2)
    assert byz == [False] * 6 + [True] * 2
    ours = _flat(lie.apply(stack, byz))
    theirs = lie_attack(_flat(stack), jnp.asarray(byz))
    np.testing.assert_allclose(ours, theirs, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rule,name,n,f", [
    (krum, "krum", 8, 2), (krum, "krum", 11, 3), (median, "median", 16, 3),
    (median, "median", 7, 2), (average, "average", 8, 2)])
def test_rule_equals_the_programs(rule, name, n, f):
    from garfield_tpu import aggregators

    stack = _stack(n, (33, 9), seed=n)
    ours = rule.aggregate(stack, f)
    theirs = aggregators.gars[name].unchecked(_flat(stack), f=f)
    np.testing.assert_allclose(
        jnp.concatenate([ours[p] for p in sorted(ours)]), theirs,
        rtol=1e-5, atol=1e-6)
