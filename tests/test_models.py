"""Tests for the model zoo — registry parity + forward shapes on tiny inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu import models

# Reference-registered names (garfieldpp/tools.py:66-88) that must exist.
REFERENCE_NAMES = [
    "convnet", "cifarnet", "cnn", "resnet18", "resnet34", "resnet50",
    "resnet152", "inception", "vgg16", "vgg19", "preactresnet18",
    "googlenet", "densenet121", "resnext29", "mobilenet", "mobilenetv2",
    "dpn92", "shufflenetg2", "senet18", "efficientnetb0", "regnetx200",
    "pimanet",
]


def test_registry_covers_reference_names():
    for name in REFERENCE_NAMES:
        assert name in models.models, f"missing model {name}"


def test_num_classes_dict_parity():
    # garfieldpp/tools.py:89 — plus copytask, the token-sequence task
    # behind the transformer family, and synthtokens, whose "classes" are
    # the vocabulary slice of the lfm2 family's next-token labels (no
    # reference counterpart for either).
    assert models.num_classes_dict == {
        "cifar10": 10, "cifar100": 100, "mnist": 10, "imagenet": 1000, "pima": 1,
        "copytask": 10, "synthtokens": 16384, "synthtokens24k": 24576,
        "synthtokens12k": 12544, "synthtokens25k": 25008,
    }


def test_select_model_errors():
    with pytest.raises(ValueError):
        models.select_model("nope", "cifar10")
    with pytest.raises(ValueError):
        models.select_model("resnet18", "nope")


def _forward(model, shape, train=False):
    x = jnp.zeros(shape, jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    if train:
        out, _ = model.apply(
            variables, x, train=True,
            mutable=["batch_stats"], rngs={"dropout": jax.random.PRNGKey(1)},
        )
        return out
    return model.apply(variables, x, train=False)


# Small/cheap models: full forward both modes.
@pytest.mark.parametrize("name,shape", [
    ("convnet", (2, 28, 28, 1)),
    ("cifarnet", (2, 32, 32, 3)),
    ("lenet", (2, 32, 32, 3)),
    ("cnn", (2, 32, 32, 3)),
])
def test_small_model_forward(name, shape):
    model = models.models[name](num_classes=10)
    out = _forward(model, shape, train=True)
    assert out.shape == (2, 10)
    out = _forward(model, shape, train=False)
    assert np.isfinite(np.asarray(out)).all()


def test_pimanet_forward():
    model = models.models["pimanet"](num_classes=1)
    out = _forward(model, (4, 8))
    assert out.shape == (4, 1)
    o = np.asarray(out)
    assert ((o >= 0) & (o <= 1)).all()  # sigmoid output (pimanet.py:14)


# Mid-size models: eval forward only, tiny batch. The heaviest zoo
# members (deep-graph compiles of 5-30s each) carry a slow mark — off
# the tier-1 fast shard for wall-time budget; a fast representative per
# architecture style stays tier-1.
_SLOW_FWD = pytest.mark.slow
@pytest.mark.parametrize("name", [
    "resnet18", "preactresnet18", "vgg11", "mobilenet",
    pytest.param("mobilenetv2", marks=_SLOW_FWD),
    "senet18",
    pytest.param("shufflenetg2", marks=_SLOW_FWD),
    pytest.param("shufflenetv2", marks=_SLOW_FWD),
    pytest.param("regnetx200", marks=_SLOW_FWD),
    pytest.param("efficientnetb0", marks=_SLOW_FWD),
    pytest.param("densenet_cifar", marks=_SLOW_FWD),
    pytest.param("dpn26", marks=_SLOW_FWD),
    pytest.param("googlenet", marks=_SLOW_FWD),
    "resnext29",
])
def test_cifar_model_forward(name):
    model = models.models[name](num_classes=10)
    out = _forward(model, (1, 32, 32, 3))
    assert out.shape == (1, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_batchnorm_collections_exist():
    model = models.models["resnet18"](num_classes=10)
    x = jnp.zeros((1, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" in variables
    # train step must be able to mutate the running stats
    _, new_state = model.apply(
        variables, x, train=True, mutable=["batch_stats"])
    assert "batch_stats" in new_state


def test_select_model_dtype_threading():
    model = models.select_model("cifarnet", "cifar10", dtype=jnp.bfloat16)
    x = jnp.zeros((1, 32, 32, 3), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.dtype == jnp.bfloat16
