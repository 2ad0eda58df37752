"""The LFM2-MoE family (models/lfm2.py) against its plain reference.

The reference is the benchmark's own file, imported by path
(benchmark/references/lfm2_moe.py): what these tests hold the program to
and what decides a benchmark cell's `correct` cannot drift apart. Float32,
``lfm2_moe_tiny``, weights made from the seed by the benchmark's
`weights.make_params` over the reference's ``param_shapes``.
"""

import functools
import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_grouped
from garfield_tpu import models
from garfield_tpu.aggregators import dataplane
from garfield_tpu.models import lfm2
from garfield_tpu.ops import attention, grouped
from garfield_tpu.parallel import aggregathor, core, make_mesh
from garfield_tpu.utils import selectors

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"


def _by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _by_path("_lfm2_reference", BENCH / "references/lfm2_moe.py")
ref_loss = _by_path(
    "_next_token_reference", BENCH / "references/losses/next_token.py").loss
weights = _by_path("_bench_weights", BENCH / "harness/weights.py")

VOCAB, SEQ = 64, 16
# The two collections a model's counters live in: the model writes them by
# name, the trainers read them by core's.
COUNTERS = (core.COUNTER_SUMS, core.COUNTER_MAXES)
assert COUNTERS == (lfm2.COUNTER_SUMS, lfm2.COUNTER_MAXES)


def _model(layer_types=("conv", "full_attention", "conv"), dense=1,
           held=(0, 1), published=8, top=2):
    """The reference's ``model`` group at the tiny preset's sizes."""
    return {
        "family": "lfm2_moe", "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 48, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "conv_L_cache": 3,
        "norm_eps": 1e-5, "rope_theta": 1e6,
        "layer_types": list(layer_types), "num_dense_layers": dense,
        "num_experts_published": published, "experts_held": list(held),
        "num_experts_per_tok": top, "routed_scaling_factor": 1,
        "vocab_size": VOCAB, "seq_len": SEQ,
    }


def _module(model):
    return lfm2.lfm2_moe_tiny(
        num_classes=model["vocab_size"],
        experts_held=tuple(model["experts_held"]),
        layer_types=tuple(model["layer_types"]),
        num_dense_layers=model["num_dense_layers"],
        num_experts=model["num_experts_published"],
        experts_per_token=model["num_experts_per_tok"])


def _paths(tree):
    return {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _tokens(seed=0, batch=3):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (batch, SEQ), 0, VOCAB),
            jax.random.randint(k2, (batch, SEQ), 0, VOCAB))


def _setup(model, seed=5):
    """``(module, variables, flat reference weights)`` with the program's
    parameters set to the reference's, leaf by leaf by path."""
    module = _module(model)
    variables = dict(module.init(jax.random.PRNGKey(0), _tokens()[0]))
    shapes = ref.param_shapes(model)
    have = {p: v.shape for p, v in _paths(variables["params"]).items()}
    assert have == {p: tuple(s) for p, s in shapes.items()}
    made = weights.make_params(
        jax.random.PRNGKey(seed), shapes, ref.init_scales(model),
        ref.leaf_rules(model))
    variables["params"] = jax.tree.unflatten(
        jax.tree.structure(variables["params"]), [made[p] for p in have])
    return module, variables, made


def _apply(module, variables, x):
    return module.apply(variables, x, mutable=list(COUNTERS))


BLOCKS = {
    "conv": dict(layer_types=("conv",), dense=1),
    "attention": dict(layer_types=("full_attention",), dense=1),
    "dense": dict(layer_types=("conv",), dense=1),
    "expert": dict(layer_types=("conv",), dense=0),
    "whole": dict(),
}


@pytest.mark.parametrize("block", list(BLOCKS))
def test_logits_and_gradient_equal_the_reference(block):
    """Each kind of block alone (one layer between embedding and head), and
    the whole tiny model: logits and ``jax.grad`` of the next-token loss."""
    model = _model(**BLOCKS[block])
    module, variables, made = _setup(model)
    x, y = _tokens()
    loss_fn = selectors.select_loss("next-token")

    def program(params):
        logits, _ = _apply(module, {**variables, "params": params}, x)
        return loss_fn(logits, y), logits

    (loss, logits), grads = jax.value_and_grad(program, has_aux=True)(
        variables["params"])
    with jax.default_matmul_precision("highest"):
        want_logits = ref.forward(made, x, model)
        want_loss, want = jax.value_and_grad(
            lambda p: ref_loss(ref.forward(p, x, model), y))(made)
    assert logits.dtype == jnp.float32 and logits.shape == (3, SEQ, VOCAB)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for path, grad in _paths(grads).items():
        np.testing.assert_allclose(
            grad, want[path], atol=2e-5 * max(1.0, float(
                jnp.linalg.norm(want[path]))), err_msg=path)
    if block == "expert":
        assert float(jnp.linalg.norm(want["layer_0/moe/w1"])) > 0


def test_the_four_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts of 8, with what every chip computes alike (the
    operator's output and the residual stream) counted once, equal the
    reference's layer that holds all 8."""
    uncut = _model(layer_types=("conv",), dense=0, held=range(8))
    made = weights.make_params(
        jax.random.PRNGKey(7), ref.param_shapes(uncut),
        ref.init_scales(uncut), ref.leaf_rules(uncut))
    h = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.block(made, 0, h, uncut, lambda t: t)
        u = ref.rms_norm(h, made["layer_0/operator_norm/scale"], 1e-5)
        alike = h + ref.conv_operator(made, "layer_0", u, uncut, lambda t: t)

    def share(held):
        held = jnp.asarray(held)
        params = {
            "operator_norm": {"scale": made["layer_0/operator_norm/scale"]},
            "ffn_norm": {"scale": made["layer_0/ffn_norm/scale"]},
            "conv": {
                "in_proj": {"kernel": made["layer_0/conv/in_proj/kernel"]},
                "conv_kernel": made["layer_0/conv/conv_kernel"],
                "out_proj": {"kernel": made["layer_0/conv/out_proj/kernel"]}},
            "moe": {
                "router_kernel": made["layer_0/moe/router_kernel"],
                "expert_bias": made["layer_0/moe/expert_bias"],
                **{w: made[f"layer_0/moe/{w}"][held]
                   for w in ("w1", "w2", "w3")}},
        }
        sizes = _module(dict(uncut, experts_held=held.tolist())).sizes()
        return lfm2.Block("conv", sizes, True).apply({"params": params}, h)

    shares = [share([2 * c, 2 * c + 1]) for c in range(4)]
    np.testing.assert_allclose(
        alike + sum(s - alike for s in shares), want, atol=2e-5)
    # No share alone is the layer: each adds its own experts' part.
    assert float(jnp.abs(shares[0] - want).max()) > 1e-3


def _expert_layer(held, published=8, top=2):
    return lfm2.ExpertLayer(published, tuple(held), top, 48)


def _expert_params(seed=3, held=2, published=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "router_kernel": jax.random.normal(keys[0], (64, published)) / 8,
        "expert_bias": jnp.zeros((published,)),
        "w1": jax.random.normal(keys[1], (held, 64, 48)) / 8,
        "w3": jax.random.normal(keys[2], (held, 64, 48)) / 8,
        "w2": jax.random.normal(keys[3], (held, 48, 64)) / 7,
    }


def test_the_bias_moves_the_selection_and_not_the_weights():
    model = _model(held=(0, 1))
    params = _expert_params()
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    chosen, w = ref.route(u, params["router_kernel"], params["expert_bias"],
                          model)
    bias = jnp.zeros((8,)).at[5].set(10.0)  # expert 5 wins every token
    chosen_b, w_b = ref.route(u, params["router_kernel"], bias, model)
    assert bool(jnp.all(jnp.any(chosen_b == 5, -1)))
    assert not bool(jnp.all(jnp.any(chosen == 5, -1)))
    # The weights are the scores' alone: each row's sum is its chosen
    # scores over themselves, 1 up to the 1e-6, bias or no bias.
    np.testing.assert_allclose(jnp.sum(w_b, -1), 1.0, atol=1e-4)
    scores = jax.nn.sigmoid(u @ params["router_kernel"])
    np.testing.assert_allclose(
        w_b, jnp.take_along_axis(scores, chosen_b, -1) / (
            jnp.take_along_axis(scores, chosen_b, -1).sum(-1, keepdims=True)
            + 1e-6), atol=1e-6)
    # The program: the same output as the reference under that bias, and no
    # gradient reaches the bias.
    layer = _expert_layer((0, 1))
    biased = {**params, "expert_bias": bias}
    out = layer.apply({"params": biased}, u)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ff(
            {f"l/moe/{k}": v for k, v in biased.items()}, "l", u, model,
            lambda t: t)
    np.testing.assert_allclose(out, want, atol=2e-5)
    grad = jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, u) ** 2))(
        biased)
    assert float(jnp.abs(grad["expert_bias"]).max()) == 0.0
    assert float(jnp.abs(grad["router_kernel"]).max()) > 0.0


def test_no_token_is_dropped_when_every_token_chooses_one_held_expert():
    """All 48 tokens choose expert 0 (held) and expert 7 (absent): one
    expert carries every pair, far above any even share, and each token's
    output is its weight times that expert's MLP."""
    params = _expert_params()
    bias = jnp.zeros((8,)).at[0].set(10.0).at[7].set(10.0)
    params = {**params, "expert_bias": bias}
    u = jax.random.normal(jax.random.PRNGKey(2), (3, SEQ, 64))
    layer = _expert_layer((0, 1))
    out, state = layer.apply(
        {"params": params, **{c: {} for c in COUNTERS}}, u,
        mutable=list(COUNTERS))
    sums, maxes = (state[c] for c in COUNTERS)
    assert float(sums["moe_pairs_held"]) == 3 * SEQ
    assert float(maxes["moe_max_expert_load"]) == 3 * SEQ
    assert float(sums["moe_rows_routed"]) == 2 * 3 * SEQ
    scores = jax.nn.sigmoid(u @ params["router_kernel"])
    weight = scores[..., 0] / (scores[..., 0] + scores[..., 7] + 1e-6)
    mlp = (jax.nn.silu(u @ params["w1"][0]) * (u @ params["w3"][0])
           ) @ params["w2"][0]
    np.testing.assert_allclose(out, weight[..., None] * mlp, atol=2e-5)
    assert float(jnp.abs(out).min(-1).max()) > 0  # no token left at zero


def test_the_expert_output_is_zero_when_every_choice_is_absent():
    params = _expert_params()
    bias = jnp.zeros((8,)).at[6].set(10.0).at[7].set(10.0)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    layer = _expert_layer((0, 1))
    fn = lambda p: layer.apply({"params": p}, u)
    out = fn({**params, "expert_bias": bias})
    assert float(jnp.abs(out).max()) == 0.0
    grads = jax.grad(lambda p: jnp.sum(fn(p)))({**params, "expert_bias": bias})
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["w1"]).max()) == 0.0


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_neither_operator_looks_ahead(kind):
    """Changing the tokens after position t leaves the logits up to t as
    they were."""
    model = _model(layer_types=(kind,), dense=1)
    module, variables, _ = _setup(model)
    x, _ = _tokens()
    later = x.at[:, 9:].set((x[:, 9:] + 1) % VOCAB)
    a, _ = _apply(module, variables, x)
    b, _ = _apply(module, variables, later)
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    assert float(jnp.abs(a[:, 9:] - b[:, 9:]).max()) > 0


def _attention_lines(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if "[attention]" in line]


@pytest.fixture
def kernel_path(monkeypatch):
    """`Attention` takes the blockwise kernels, in interpret mode with two
    blocks of 8 over the 16 positions (the second crossed by the diagonal,
    none skipped but the one above it): the test steers the choice that
    platform and shape make on the chip; the model has no argument for
    it."""
    attention._said.clear()
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, block=8, interpret=True))


@pytest.mark.parametrize("block", ["attention", "whole"])
def test_the_kernel_path_equals_the_reference_too(block, kernel_path, capsys):
    """Logits and gradients against ``ref.attention_operator``'s float32
    scores, under the einsum path's own tolerances."""
    test_logits_and_gradient_equal_the_reference(block)
    assert _attention_lines(capsys) == [
        "[attention] blockwise: (n, heads, kv_heads, t, head) = "
        "(3, 4, 2, 16, 16) float32, blocks (8, 8), causal blocks skipped "
        "1 of 4, interpret mode"]


def test_the_kernel_path_does_not_look_ahead_either(kernel_path, capsys):
    test_neither_operator_looks_ahead("full_attention")
    assert len(_attention_lines(capsys)) == 1


def test_the_einsum_path_says_why_it_was_taken(capsys):
    attention._said.clear()
    test_neither_operator_looks_ahead("full_attention")
    assert _attention_lines(capsys) == [
        "[attention] einsum: t = 16 is not a multiple of the block 128"]


def _expert_lines(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if "[experts]" in line]


@pytest.fixture
def expert_kernels(monkeypatch):
    """`ExpertLayer` takes the grouped-matmul kernels, in interpret mode
    with row tiles of 8 and the contraction and the columns in tiles of 16
    (64 and 48 wide, either way round): the test steers the choice that
    platform and shape make on the chip; the model has no argument for
    it."""
    attention._said.clear()
    for name in ("grouped_matmul", "rows_visited"):
        monkeypatch.setattr(grouped, name, functools.partial(
            getattr(grouped, name), tiles=(8, 16, 16), interpret=True))


@pytest.mark.parametrize("block", ["expert", "whole"])
def test_the_expert_kernels_equal_the_reference_too(
        block, expert_kernels, capsys):
    """Logits and gradients with the three grouped matmuls, their rows'
    and their weights' gradients in the kernels, under the ragged-dot
    path's own tolerances."""
    test_logits_and_gradient_equal_the_reference(block)
    assert _expert_lines(capsys) == [
        f"[experts] grouped: (m, k, n) = (96, {k}, {n}) g=2 float32, tiles "
        "(8, 16, 16), gradients (8, 16, 16) rows, (8, 16, 16) weights, "
        "interpret mode" for k, n in ((64, 48), (48, 64))]


def test_the_expert_kernels_drop_no_token_either(expert_kernels):
    test_no_token_is_dropped_when_every_token_chooses_one_held_expert()


def test_the_expert_kernels_give_zero_when_every_choice_is_absent(
        expert_kernels):
    """No group has a row: the kernels visit nothing, and what they leave
    in the result (NaN, in interpret mode) is masked forward and backward."""
    test_the_expert_output_is_zero_when_every_choice_is_absent()


def test_the_ragged_dot_path_says_why_it_was_taken(capsys):
    attention._said.clear()
    test_the_expert_output_is_zero_when_every_choice_is_absent()
    assert _expert_lines(capsys) == [
        "[experts] ragged_dot: m = 64 is not a multiple of the row tile 128"]


@pytest.mark.parametrize("path", ["ragged_dot", "kernels"])
def test_the_counters_equal_the_references_count(path, request):
    if path == "kernels":
        request.getfixturevalue("expert_kernels")
    model = _model()
    module, variables, made = _setup(model)
    x, _ = _tokens(seed=4)
    _, state = _apply(module, variables, x)
    with jax.default_matmul_precision("highest"):
        h = made["embed/embedding"][x]
        for i in range(len(model["layer_types"])):
            if i >= model["num_dense_layers"]:
                mid = _after_operator(made, i, h, model)
                chosen, _ = ref.route(
                    mid, made[f"layer_{i}/moe/router_kernel"],
                    made[f"layer_{i}/moe/expert_bias"], model)
                held = ref.pairs_held(chosen, model)
                sums, maxes = (
                    state[c][f"layer_{i}"]["moe"] for c in COUNTERS)
                assert float(sums["moe_pairs_held"]) == float(held.sum())
                assert float(maxes["moe_max_expert_load"]) == float(held.max())
                assert float(sums["moe_rows_routed"]) == x.size * 2
                # The rows of the row tiles of 8 that hold a pair of a held
                # expert, once for each expert in them.
                assert float(sums["moe_rows_visited"]) == (
                    8 * test_grouped.visits(held, x.size * 2, 8)
                    if path == "kernels" else 0)
            h = ref.block(made, i, h, model, lambda t: t)


def _after_operator(made, i, h, model):
    """The normed input of layer i's feed-forward, by the reference."""
    p, eps = f"layer_{i}", model["norm_eps"]
    u = ref.rms_norm(h, made[f"{p}/operator_norm/scale"], eps)
    op = ref.conv_operator if model["layer_types"][i] == "conv" else (
        ref.attention_operator)
    mid = h + op(made, p, u, model, lambda t: t)
    return ref.rms_norm(mid, made[f"{p}/ffn_norm/scale"], eps)


def _trainer(module, num_workers=4, f=1, **kwargs):
    return aggregathor.make_trainer(
        module, selectors.select_loss("next-token"),
        selectors.select_optimizer("sgd", lr=0.05, momentum=0.9,
                                   weight_decay=5e-4),
        "median", num_workers=num_workers, f=f, attack="lie",
        # One device holds the slots, as the chip does: the unroll.
        mesh=make_mesh({"workers": 1}, devices=jax.devices()[:1]), **kwargs)


def _worker_batches(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (4, 2, SEQ), 0, VOCAB),
            jax.random.randint(k2, (4, 2, SEQ), 0, VOCAB))


@pytest.fixture
def metadata_in_cache_key():
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    yield
    jax.config.update(name, before)


def test_the_model_scopes_stand_inside_the_gradient_phase(
        metadata_in_cache_key):
    module = models.select_model("lfm2_moe_tiny", "synthtokens")
    init_fn, step_fn, _ = _trainer(module)
    x, y = _worker_batches()
    state = init_fn(jax.random.PRNGKey(0), x[0])
    text = step_fn.lower(state, x, y).compile().as_text()
    # This family's blocks put ``attention`` around the whole module; the
    # three names that split a module are the other families'
    # (tests/test_mellum.py), the gate and the shared expert the third's
    # (tests/test_laguna.py).
    split = {"attention_proj", "window_attention", "full_attention",
             "attention_gate", "shared_expert"}
    for name in lfm2.SCOPES:
        assert (f"model.{name}/" in text or f"model.{name}\"" in text) == (
            name not in split), name
    # Wherever an instruction names a model scope, phase.grads stands
    # outside it.
    for op_name in re.findall(r'op_name="([^"]*model\.[^"]*)"', text):
        for part in op_name.split(";"):
            if "model." in part:
                assert part.index("phase.grads") < part.index("model."), part
    with pytest.raises(ValueError, match="nonsense"):
        lfm2.scope("nonsense")


@pytest.fixture(scope="module", params=[False, True], ids=["whole", "remat"])
def routed_text(request):
    """The tiny preset's compiled step with its metadata, with and without
    the benchmark presets' recomputed blocks; and the rows a slot routes."""
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        module = models.select_model(
            "lfm2_moe_tiny", "synthtokens").clone(remat=request.param)
        init_fn, step_fn, _ = _trainer(module)
        x, y = _worker_batches()
        state = init_fn(jax.random.PRNGKey(0), x[0])
        text = step_fn.lower(state, x, y).compile().as_text()
    finally:
        jax.config.update(name, before)
    return text, x[0].size * module.experts_per_token, module.hidden


def _op_names(text, opcode, *marks):
    """The ``op_name`` of every ``opcode`` instruction whose line holds each
    of ``marks`` and whose op_name names a model scope."""
    return [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in text.splitlines()
        if f" {opcode}(" in line and "model." in line
        and all(mark in line for mark in marks)]


def test_every_routing_step_appears_in_the_step_text(routed_text):
    text, _, _ = routed_text
    for name in lfm2.ROUTE_STEPS:
        assert f"route.{name}/" in text, name


def test_every_sort_of_the_model_is_a_routing_sort(routed_text):
    """The expert layers' two sorts a layer and slot, each under its step;
    the rule's sorts stand outside the model."""
    text, _, _ = routed_text
    sorts = _op_names(text, "sort")
    assert sorts
    assert all("route.order/" in s or "route.inverse/" in s for s in sorts)
    for step in ("order", "inverse"):
        assert any(f"route.{step}/" in s for s in sorts), step


def test_the_row_gathers_hold_a_permutation_step_forward_and_backward(
        routed_text):
    """Every gather of whole rows (tokens x k of them) in the model is one
    of the two permutations, and `_permute`'s backward gather carries its
    forward's step."""
    text, rows, hidden = routed_text
    gathers = _op_names(
        text, "gather", f"= f32[{rows},", f"slice_sizes={{1,{hidden}}}")
    assert gathers
    for op_name in gathers:
        assert "route.gather_rows/" in op_name or (
            "route.return_rows/" in op_name), op_name
    for step in ("gather_rows", "return_rows"):
        forward = [g for g in gathers
                   if f"route.{step}/" in g and "transpose(" not in g]
        backward = [g for g in gathers
                    if f"route.{step}/" in g and "transpose(" in g]
        assert forward and backward, step


def test_every_routing_step_stands_inside_dispatch_or_combine(routed_text):
    """So that every ``model.*`` label reads what it read without them."""
    text, _, _ = routed_text
    parts = [part for op_name in re.findall(r'op_name="([^"]*)"', text)
             for part in op_name.split(";") if "route." in part]
    assert parts
    for part in parts:
        scopes = re.findall(r"model\.(\w+)", part)
        assert scopes and scopes[-1] in ("moe_dispatch", "moe_combine"), part
        assert part.rindex("model.") < part.index("route."), part


def test_an_unknown_routing_step_raises():
    with pytest.raises(ValueError, match="nonsense"):
        lfm2.route("nonsense")
    with pytest.raises(ValueError):
        lfm2.route(None)


@pytest.mark.parametrize("remat", [False, True])
def test_three_trainer_steps_equal_slot_by_slot_gradients(monkeypatch, remat):
    """aggregathor (n = 4, f = 1, median under lie): the unroll over the 4
    slots against the same gradients taken one slot after another
    (``lax.map``, what ``vmap`` computes), three steps, without
    recomputation and with the blocks recomputed but for `lfm2.KEPT`, as
    the benchmark's preset runs; the step's metrics carry the expert
    layers' counters. ``vmap`` itself cannot take this family yet: jax 0.9.0
    has no batching rule for a ragged dot whose group sizes are batched,
    which is what more than ``UNROLL_MAX_SLOTS`` slots a shard would ask
    for."""
    module = _module(_model()).clone(remat=remat)
    x, y = _worker_batches()

    def three_steps():
        init_fn, step_fn, _ = _trainer(module)
        state = init_fn(jax.random.PRNGKey(0), x[0])
        out = []
        for i in range(3):
            state, metrics = step_fn(state, jnp.roll(x, i, 0), jnp.roll(y, i, 0))
            out.append(metrics)
        return state, out

    unrolled, metrics = three_steps()

    def slot_by_slot(grad_fn, params, ms, xs, ys, keys, **_):
        with core.phase("grads"):
            return jax.lax.map(
                lambda a: grad_fn(params, ms, *a), (xs, ys, keys))

    monkeypatch.setattr(core, "per_slot_grads", slot_by_slot)
    mapped, metrics_m = three_steps()
    for a, b in zip(jax.tree.leaves(unrolled.params),
                    jax.tree.leaves(mapped.params)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for m, mm in zip(metrics, metrics_m):
        np.testing.assert_allclose(m["loss"], mm["loss"], rtol=1e-5)
        assert m["moe_rows_routed"].tolist() == [4 * 2 * SEQ * 2] * 2
        assert m["moe_pairs_held"].shape == (2,)
        assert bool(jnp.all(m["moe_pairs_held"] <= m["moe_rows_routed"]))
        assert bool(jnp.all(m["moe_max_expert_load"] <= 2 * SEQ * 2))
        np.testing.assert_array_equal(
            m["moe_pairs_held"], mm["moe_pairs_held"])
    monkeypatch.undo()
    monkeypatch.setattr(core, "UNROLL_MAX_SLOTS", 1)
    with pytest.raises(NotImplementedError, match="ragged_dot vmap"):
        three_steps()


def _slot_gradients_of_one_step(monkeypatch, module):
    """The per-worker gradients `make_trainer`'s step takes, as
    `core.per_slot_grads` hands them to the attack and the rule."""
    seen = []
    unrolled = core.per_slot_grads

    def tapped(*args, **kwargs):
        grads, aux = unrolled(*args, **kwargs)
        jax.debug.callback(seen.append, grads)
        return grads, aux

    with monkeypatch.context() as patch:
        patch.setattr(core, "per_slot_grads", tapped)
        init_fn, step_fn, _ = _trainer(module)
        x, y = _worker_batches()
        state, _ = step_fn(init_fn(jax.random.PRNGKey(0), x[0]), x, y)
        jax.block_until_ready(state)
    (grads,) = seen
    return _paths(grads)


def test_the_two_attention_paths_give_the_trainer_the_same_gradients(
        monkeypatch, capsys):
    """The 4-slot unroll with the blocks recomputed but for `lfm2.KEPT`
    (``remat=True``, as the benchmark's preset runs): forward and backward
    kernels, the forward's output and log-sum-exp kept, against the einsum
    path, worker by worker and leaf by leaf; each trace says its path once,
    not once a slot."""
    module = lfm2.lfm2_moe_tiny(num_classes=VOCAB, remat=True)
    attention._said.clear()
    want = _slot_gradients_of_one_step(monkeypatch, module)
    assert _attention_lines(capsys) == [
        "[attention] einsum: t = 16 is not a multiple of the block 128"]
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, block=8, interpret=True))
    got = _slot_gradients_of_one_step(monkeypatch, module)
    said = _attention_lines(capsys)
    assert len(said) == 1 and said[0].startswith(
        "[attention] blockwise: (n, heads, kv_heads, t, head) = "
        "(2, 4, 2, 16, 16) float32, blocks (8, 8)")
    assert got.keys() == want.keys() and len(got) > 20
    for path, leaf in got.items():
        assert leaf.shape[0] == 4, path
        for worker in range(4):
            np.testing.assert_allclose(
                leaf[worker], want[path][worker],
                atol=2e-5 * max(1.0, float(
                    jnp.linalg.norm(want[path][worker]))),
                err_msg=f"{path} worker {worker}")
    assert float(jnp.linalg.norm(want["layer_1/attn/q_proj/kernel"])) > 0


def test_the_next_token_loss_is_the_mean_over_all_positions():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 7), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 7)
    loss = selectors.select_loss("next-token")(logits, labels)
    assert loss.dtype == jnp.float32
    np.testing.assert_allclose(loss, ref_loss(logits, labels), rtol=1e-6)
    with pytest.raises(ValueError, match="next-token"):
        selectors.select_loss("no-such-loss")


def test_the_cli_and_the_benchmark_draw_the_same_tokens_by_the_stated_law():
    """`data/tokens.py` and the benchmark's copy (its reference imports
    nothing of the program) give the same tokens from the same key; the
    dataset is x and x moved one place on; the ids follow the
    Zipf-Mandelbrot law both state, copies and all."""
    from garfield_tpu import data
    from garfield_tpu.data import tokens

    bench = _by_path("_bench_next_tokens", BENCH / "inputs/next_tokens.py")
    assert (bench.COPY, bench.ZIPF_ALPHA, bench.ZIPF_BETA) == (
        tokens.COPY, tokens.ZIPF_ALPHA, tokens.ZIPF_BETA) == (0.5, 1.0, 2.7)
    key = jax.random.PRNGKey(7)
    np.testing.assert_array_equal(
        tokens.sequences(key, (2, 3), 66, 512),
        bench.sequences(key, (2, 3), 66, 512))
    (tx, ty), (ex, ey) = data.load_dataset("synthtokens", 64)
    assert tx.shape == ty.shape == (64, data.SYNTHTOKENS_SEQ)
    assert ex.shape[1] == 2048 and tx.dtype == np.int32
    np.testing.assert_array_equal(tx[:, 1:], ty[:, :-1])
    assert 0 <= tx.min() and ex.max() < data.SYNTHTOKENS_VOCAB == 16384
    # 278,528 tokens: the first id is 3.17% of them, the first hundred
    # 40.6%; half of all tokens repeat the one two places back.
    share = np.bincount(ex.ravel(), minlength=16384) / ex.size
    law = np.diff(np.asarray(tokens.unigram_cdf(16384)), prepend=0)
    np.testing.assert_allclose(share[:3], law[:3], rtol=0.1)
    np.testing.assert_allclose(share[:100].sum(), law[:100].sum(), rtol=0.02)
    np.testing.assert_allclose(law[0], 1 / 3.7 / (1 / (
        np.arange(1, 16385) + 2.7)).sum(), rtol=1e-4)
    assert abs((ex[:, 2:] == ex[:, :-2]).mean() - 0.5) < 0.02


def test_a_models_counters_reach_the_metrics_by_cores_convention():
    """`core.step_counters` knows no model: scalars in ``counters_sum`` are
    summed over the slots, those in ``counters_max`` maxed, one entry per
    module that writes the name, in the natural order of the paths; a model
    state without the collections gives nothing."""
    slots = jnp.arange(3.0)
    ms = {
        "batch_stats": {"bn": {"mean": jnp.ones((3, 4))}},
        core.COUNTER_SUMS: {
            f"layer_{i}": {"moe": {"seen": slots + i}} for i in (10, 2)},
        core.COUNTER_MAXES: {"layer_2": {"moe": {"peak": slots * 2}}},
    }
    got = core.step_counters(ms)
    assert sorted(got) == core.counter_names(ms) == ["peak", "seen"]
    assert got["seen"].tolist() == [3 + 3 * 2, 3 + 3 * 10]
    assert got["peak"].tolist() == [4.0]
    assert core.step_counters({"batch_stats": ms["batch_stats"]}) == {}
    assert core.counter_names({}) == []


def test_the_presets_and_the_token_dataset_are_registered():
    module = models.select_model("lfm2_8b_a1b_ep4", "synthtokens")
    assert module.num_classes == models.num_classes_dict["synthtokens"] == 16384
    assert (module.hidden, module.heads, module.kv_heads, module.head_dim,
            module.dense_width, module.expert_width, module.num_experts,
            module.experts_per_token, module.conv_length) == (
                2048, 32, 8, 64, 7168, 1792, 32, 4, 3)
    assert tuple(module.experts_held) == tuple(range(8)) and module.remat
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes)) == (
        507820288)


def test_the_data_plane_defense_refuses_the_tied_family_by_name():
    """The family's head is its embedding: the data-plane defense refuses it
    and says so; with the defense off nothing asks."""
    module = _module(_model())
    params = module.init(jax.random.PRNGKey(0), _tokens()[0])["params"]
    with pytest.raises(ValueError, match="embedding-tied.*lfm2"):
        dataplane.head_spec(params)
    x, y = _worker_batches()
    init_fn, step_fn, _ = _trainer(
        module, defense={"weighted": False, "data": {}})
    with pytest.raises(ValueError, match="embedding-tied"):
        step_fn(init_fn(jax.random.PRNGKey(0), x[0]), x, y)
