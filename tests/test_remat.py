"""What a recomputed block keeps (`lfm2.KEPT`, `lfm2.recomputed`): the two
token families, `models/lfm2.py` and `models/mellum.py`, at their tiny
presets.

``remat=True`` wraps every block in ``nn.remat`` under a policy that keeps
what the block made under a name of ``KEPT``; the tests hold it to the same
gradients as no recomputation and as the bare ``nn.remat(Block)`` it
replaced, count what the gradient's jaxpr runs twice, and read what it
saves.
"""

import collections
import contextlib
import logging
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_lfm2 as shared
from garfield_tpu.models import lfm2, mellum
from garfield_tpu.ops import attention, scan

VOCAB = shared.VOCAB
FAMILIES = {"lfm2": lfm2.lfm2_moe_tiny, "mellum": mellum.mellum2_tiny}
# One block between embedding and head: the family, its fields, and the
# names the block's sites give, in the order the trace meets them, on the
# einsum path; the kernels add `attention.KEPT` after the projections'.
CONV = ("conv_in", "conv_out")
PROJ = ("attention_q_proj", "attention_k_proj", "attention_o_proj")
MLP = ("mlp_w1", "mlp_w3")
MOE = ("moe_logits", "moe_chosen", "moe_order", "moe_inverse", "moe_sizes",
       "moe_rows", "moe_w1", "moe_w3", "moe_out")
BLOCKS = {
    "lfm2-conv-dense": ("lfm2", dict(
        layer_types=("conv",), num_dense_layers=1), CONV + MLP),
    "lfm2-conv-experts": ("lfm2", dict(
        layer_types=("conv",), num_dense_layers=0), CONV + MOE),
    "lfm2-attention-experts": ("lfm2", dict(
        layer_types=("full_attention",), num_dense_layers=0), PROJ + MOE),
    "mellum-sliding": ("mellum", dict(
        layer_types=("sliding_attention",)), PROJ + MOE),
    "mellum-full": ("mellum", dict(
        layer_types=("full_attention",)), PROJ + MOE),
}
# A residual that a jitted function of jax's hands on under its own name
# and not the one `keep` gave its argument: the same value.
HANDED_ON = {"silu": ("mlp_w1", "moe_w1"), "take_along_axis": ("moe_chosen",)}


@contextlib.contextmanager
def bare(block, remat):
    """`lfm2.recomputed` as it was before the policy: every block whole."""
    yield nn.remat(block) if remat else block


kernels = shared.kernel_path  # a fixture: the kernels, in interpret mode


def _remat_lines(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if "[remat]" in line]


def _count(jaxpr, tally=None):
    """Equations by primitive, through every jaxpr an equation holds; a
    kernel by its name, and not what it holds."""
    tally = collections.Counter() if tally is None else tally
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            tally[eqn.params["name"]] += 1
            continue
        tally[name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, tally)
    return tally


@pytest.mark.parametrize("path", ["einsum", "kernels"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_policy_the_bare_wrap_and_no_recomputation_give_one_gradient(
        family, path, request, monkeypatch, capsys):
    """The 4-slot trainer step's per-worker gradients (aggregathor, median
    under lie, as the benchmark's token cells run) with the policy, with
    ``remat=False`` and with the old bare ``nn.remat(Block)``: leaf by leaf
    and worker by worker, at the tolerance of
    `test_the_two_attention_paths_give_the_trainer_the_same_gradients`. On
    XLA:CPU the three are bit for bit equal where the kernels run (interpret
    mode: the calls stand between the fusions); on the einsum path XLA fuses
    the three programs differently and 0 to 12 of the leaves are (the rest
    within 1e-7 of the leaf's norm). Each trace says what it keeps once, not
    once a slot."""
    if path == "kernels":
        request.getfixturevalue("kernels")
    make = FAMILIES[family]
    attention._said.clear()
    got = shared._slot_gradients_of_one_step(
        monkeypatch, make(num_classes=VOCAB, remat=True))
    said = _remat_lines(capsys)
    assert len(said) == 1 and re.fullmatch(
        r"\[remat\] block keeps \d+ names: [a-z0-9_, ]+; per slot "
        r"\d\.\d{3} GB \(reckoned from shapes\)", said[0]), said
    names = said[0].split(": ")[1].split(";")[0].split(", ")
    assert int(said[0].split()[3]) == len(names) == len(set(names))
    assert set(names) <= set(lfm2.KEPT)
    assert ("attention_lse" in names) == (path == "kernels")
    assert ("conv_in" in names) == (family == "lfm2")

    off = shared._slot_gradients_of_one_step(
        monkeypatch, make(num_classes=VOCAB, remat=False))
    monkeypatch.setattr(lfm2, "recomputed", bare)
    whole = shared._slot_gradients_of_one_step(
        monkeypatch, make(num_classes=VOCAB, remat=True))
    assert _remat_lines(capsys) == []
    assert got.keys() == off.keys() == whole.keys() and len(got) > 20
    for leaf_path, leaf in got.items():
        assert leaf.shape[0] == 4, leaf_path
        assert float(jnp.linalg.norm(leaf)) > 0 or "bias" in leaf_path
        for other in (off[leaf_path], whole[leaf_path]):
            if path == "kernels":
                np.testing.assert_array_equal(leaf, other, leaf_path)
            for worker in range(4):
                np.testing.assert_allclose(
                    leaf[worker], other[worker],
                    atol=2e-5 * max(1.0, float(
                        jnp.linalg.norm(other[worker]))),
                    err_msg=f"{leaf_path} worker {worker}")


def _block_loss(family, fields, remat=True):
    module = FAMILIES[family](num_classes=VOCAB, remat=remat, **fields)
    x, _ = shared._tokens(batch=2)
    params = module.init(jax.random.PRNGKey(1), x)
    return (lambda p: jnp.sum(module.apply(p, x))), params


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_gradient_runs_once_what_the_block_keeps(
        block, kernels, monkeypatch):
    """One block's gradient (tiny sizes, interpret-mode kernels) as a
    jaxpr, policy against bare wrap. An expert layer: 9 ragged dots (3
    forward, 6 backward) against 12, 2 sorts against 4, 1 ``top_k`` against
    2; an attention layer: the forward kernel once against twice, the
    backward kernel once; every matmul once: the policy's gradient runs
    what the gradient without recomputation runs, the bare wrap's every
    forward matmul again but the last (``w2``, whose result the backward
    pass does not read). The issue reckoned the same counts."""
    family, fields, names = BLOCKS[block]
    counts = {}
    for wrap in ("policy", "bare", "off"):
        with monkeypatch.context() as patch:
            if wrap == "bare":
                patch.setattr(lfm2, "recomputed", bare)
            loss, params = _block_loss(family, fields, remat=wrap != "off")
            counts[wrap] = _count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    policy, whole, off = counts["policy"], counts["bare"], counts["off"]
    experts, attends = "moe_out" in names, "attention_o_proj" in names
    assert (policy["ragged_dot_general"], whole["ragged_dot_general"]) == (
        (9, 12) if experts else (0, 0))
    assert (policy["sort"], whole["sort"]) == ((2, 4) if experts else (0, 0))
    assert (policy["top_k"], whole["top_k"]) == (
        (1, 2) if experts else (0, 0))
    for kernel, want in (("causal_attention_forward", (1, 2)),
                         ("causal_attention_backward", (1, 1))):
        assert (policy[kernel], whole[kernel]) == (
            want if attends else (0, 0)), kernel
    # With no recomputation the gradient runs each of these as often as
    # under the policy; the bare wrap runs every forward matmul again.
    for name in ("ragged_dot_general", "sort", "top_k",
                 "causal_attention_forward", "causal_attention_backward"):
        assert policy[name] == off[name], name
    assert policy["dot_general"] == off["dot_general"]
    assert whole["dot_general"] - off["dot_general"] == (
        (4 if attends else 2) + (1 if experts else 2))


@pytest.fixture
def residuals_logged():
    """jax says what each ``checkpoint`` saves as it differentiates it."""
    jax.config.update("jax_log_checkpoint_residuals", True)
    yield
    jax.config.update("jax_log_checkpoint_residuals", False)


@pytest.mark.parametrize("path", ["einsum", "kernels"])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_saved_residuals_are_the_kept_set(
        block, path, request, residuals_logged, caplog, capsys):
    """What jax reports saved of one recomputed block as it takes the
    gradient (`jax.ad_checkpoint.print_saved_residuals` reads the same
    list, and spells a named float as the ``reduce_precision`` jax puts
    behind it): beside the block's inputs exactly what the block's sites
    named, each once; their bytes are what the ``[remat]`` line reckoned
    from the shapes."""
    if path == "kernels":
        request.getfixturevalue("kernels")
    family, fields, names = BLOCKS[block]
    if path == "kernels" and "attention_o_proj" in names:
        names = names[:2] + attention.KEPT + names[2:]
    attention._said.clear()
    loss, params = _block_loss(family, fields)
    with caplog.at_level(logging.WARNING, logger="jax._src.ad_checkpoint"):
        jax.make_jaxpr(jax.grad(loss))(params)
    (report,) = [r.getMessage() for r in caplog.records
                 if "remat-decorated" in r.getMessage()]
    found, nbytes = [], 0
    for line in report.split("saving these intermediates:\n")[1].splitlines():
        dtype, shape, why = re.fullmatch(
            r"  ([a-z]+\d+)\[([\d,]*)\] from (.*)", line).groups()
        named = re.match(r"named '(\w+)'", why)
        if named:
            found.append(named.group(1))
        else:
            handed = re.match(r"output of jitted function '(\w+)'", why)
            assert handed and handed.group(1) in HANDED_ON, line
            found.extend(n for n in HANDED_ON[handed.group(1)] if n in names)
        nbytes += jnp.dtype(dtype).itemsize * int(
            np.prod([int(d) for d in shape.split(",") if d]))
    assert sorted(found) == sorted(names)
    (said,) = _remat_lines(capsys)
    assert said.startswith(
        f"[remat] block keeps {len(names)} names: {', '.join(names)}; ")
    assert sum(lfm2._kept_bytes.values()) == nbytes > 0


def test_a_name_outside_the_kept_set_is_refused_and_off_changes_nothing():
    """`keep` takes the names of ``KEPT`` alone, so the policy and the sites
    cannot drift apart; outside a recomputed block it is the identity: with
    ``remat=False`` the gradient's jaxpr has no equation more than the same
    model traced with `keep` taken out but the names' and, a float being
    named as its bits, two bitcasts a float name."""
    with pytest.raises(ValueError, match="unknown name 'moe_gate'"):
        lfm2.keep(jnp.zeros(3), "moe_gate")
    assert len(set(lfm2.KEPT)) == len(lfm2.KEPT) == 33
    assert set(attention.KEPT) < set(lfm2.KEPT)
    assert set(scan.KEPT) < set(lfm2.KEPT)
    loss, params = _block_loss(
        "lfm2", dict(layer_types=("conv", "full_attention")), remat=False)
    with_names = _count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "keep", lambda x, name: x)
        without = _count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    names = with_names.pop("name")
    assert names > 0 and "name" not in without
    bitcasts = with_names.pop("bitcast_convert_type")
    assert 0 < bitcasts <= 2 * names and bitcasts % 2 == 0
    assert with_names == without
