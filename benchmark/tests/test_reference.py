"""The plain references against the program's own pieces at a small size on
the CPU in float32: the model family's forward pass against the flax module,
and each rule and attack against the program's. The benchmark's reference
imports nothing of the program; only these tests see both. And the
reference against itself: the path that keeps the workers' gradients on the
host against the one-program path."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[0]))
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE / "toy_token"))
sys.path.insert(0, str(HERE))

import toy  # noqa: E402
import toy_gpt  # noqa: E402
from harness import reference, system, weights  # noqa: E402
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


def test_token_family_forward_equals_the_flax_module():
    """The test-only token family (`toy_token/toy_gpt.py`) against the
    program's ``gpt_tiny``, on weights made by the rules the family states
    for the two tables and the harness's defaults for the rest."""
    from garfield_tpu import models

    model = toy.TOY_TOKEN_CONFIG["model"]
    module = models.select_model("gpt_tiny", "copytask")
    x = jax.random.randint(
        jax.random.PRNGKey(0), (6, model["seq_len"]), 0, model["vocab"])
    variables = module.init(jax.random.PRNGKey(1), x, train=False)
    paths = system.flat_paths(variables["params"])
    shapes = {p: v.shape for p, v in paths.items()}
    assert shapes == toy_gpt.param_shapes(model)
    with pytest.raises(ValueError, match="no rule to make"):
        weights.make_params(jax.random.PRNGKey(2), shapes)
    made = weights.make_params(
        jax.random.PRNGKey(2), shapes, *weights.stated(toy_gpt, model))
    # Zero biases would hide a bias the forward pass forgets.
    made = {p: v + 0.1 if p.endswith("/bias") else v for p, v in made.items()}
    tree = jax.tree.unflatten(
        jax.tree.structure(variables["params"]), [made[p] for p in paths])
    theirs = module.apply({"params": tree}, x, train=True)
    ours = toy_gpt.forward(made, x, model)
    assert float(jnp.std(ours)) > 0.1
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-4)


SMALL_RESNET = {
    **toy.TOY_CONFIG,
    "model": {"family": "resnet", "block": "basic", "stage_sizes": [1, 1],
              "stem_width": 8, "num_classes": 10, "image": [8, 8, 3]},
}


@pytest.mark.parametrize("rule,attack,where", [
    ("median", "lie", "leaf by leaf on the device"),
    ("average", "none", "leaf by leaf on the device"),
    ("krum", "lie", "on the whole stack, on the host's CPU device")])
def test_the_stack_on_the_host_gives_what_the_stack_on_the_device_gives(
        rule, attack, where, capfd):
    """`reference.run` with the budget forced to nothing (each worker's
    gradient fetched; attack and rule one leaf at a time where both work so,
    else on the whole stack on the host's CPU device) against the
    one-program path: the same losses and norms to float32 round-off."""
    traffic = {"rule": rule, "attack": attack}
    device = reference.run(SMALL_RESNET, traffic, 11)
    assert "the stack stays on the device" in capfd.readouterr().err
    host = reference.run(SMALL_RESNET, traffic, 11, budget=0)
    said = capfd.readouterr().err
    assert "the stack goes to the host" in said and where in said
    np.testing.assert_allclose(host["loss"], device["loss"], rtol=1e-5)
    for key in ("grad1", "dparam"):
        assert set(host[key]) == set(device[key])
        for path, value in device[key].items():
            assert host[key][path] == pytest.approx(value, rel=2e-4), path


def test_the_threshold_is_reckoned_from_n_d_and_the_devices_memory():
    # r50n16's stack stays on a 16.9 GB chip; half a billion parameters at
    # n = 4 or 8 do not.
    limit = 16.9e9 * reference.DEVICE_SHARE
    assert reference.resident_bytes(16, 23_705_252) == 4 * 54 * 23_705_252
    assert reference.stack_on_device(16, 23_705_252, limit)
    assert reference.stack_on_device(8, 11_173_962, limit)
    assert not reference.stack_on_device(4, 500_000_000, limit)
    assert not reference.stack_on_device(8, 500_000_000, limit)
    assert reference.stack_on_device(8, 500_000_000, None)


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
