"""The Mellum family (models/mellum.py) against its plain reference.

The reference is the benchmark's own file, imported by path
(benchmark/references/mellum.py): what these tests hold the program to and
what decides a benchmark cell's `correct` cannot drift apart. Float32,
``mellum2_tiny`` (16 positions, a window of 4, so a sliding layer really
hides keys), weights made from the seed by the benchmark's
`weights.make_params` over the reference's ``param_shapes``. The family is
made of `models/lfm2.py`'s modules; tests/test_lfm2.py holds those for the
other family, and the helpers here are its own.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_lfm2 as shared
from garfield_tpu import data, models
from garfield_tpu.models import lfm2, mellum
from garfield_tpu.ops import attention
from garfield_tpu.utils import selectors

ref = shared._by_path(
    "_mellum_reference", shared.BENCH / "references/mellum.py")
ref_loss, weights, COUNTERS = shared.ref_loss, shared.weights, shared.COUNTERS
metadata_in_cache_key = shared.metadata_in_cache_key  # a fixture
VOCAB, SEQ, WINDOW = shared.VOCAB, shared.SEQ, 4
# The published group and the tiny preset's: one law, two sizes.
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 5e5, "factor": 4.0,
        "original_max_position_embeddings": 8, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.1386294361119891},
    "sliding_attention": {"rope_type": "default", "rope_theta": 5e5},
}
PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _model(layer_types=("sliding_attention", "full_attention"), held=(0, 1),
           published=8, top=2):
    """The reference's ``model`` group at the tiny preset's sizes."""
    return {
        "family": "mellum", "hidden_size": 64, "moe_intermediate_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "norm_eps": 1e-6, "rope_parameters": ROPE, "sliding_window": WINDOW,
        "layer_types": list(layer_types), "num_dense_layers": 0,
        "num_experts_published": published, "experts_held": list(held),
        "num_experts_per_tok": top, "vocab_size": VOCAB, "seq_len": SEQ,
    }


def _module(model, **fields):
    return mellum.mellum2_tiny(
        num_classes=model["vocab_size"],
        experts_held=tuple(model["experts_held"]),
        layer_types=tuple(model["layer_types"]),
        num_experts=model["num_experts_published"],
        experts_per_token=model["num_experts_per_tok"], **fields)


def _setup(model, seed=5):
    """``(module, variables, flat reference weights)`` with the program's
    parameters set to the reference's, leaf by leaf by path."""
    module = _module(model)
    variables = dict(module.init(jax.random.PRNGKey(0), shared._tokens()[0]))
    shapes = ref.param_shapes(model)
    have = {p: v.shape for p, v in shared._paths(variables["params"]).items()}
    assert have == {p: tuple(s) for p, s in shapes.items()}
    made = weights.make_params(
        jax.random.PRNGKey(seed), shapes, ref.init_scales(model),
        ref.leaf_rules(model))
    variables["params"] = jax.tree.unflatten(
        jax.tree.structure(variables["params"]), [made[p] for p in have])
    return module, variables, made


def _apply(module, variables, x):
    return module.apply(variables, x, mutable=list(COUNTERS))


@pytest.fixture
def kernel_path(monkeypatch):
    """`lfm2.Attention` takes the blockwise kernels, in interpret mode with
    blocks of 4 over the 16 positions: at a window of 4 a sliding layer runs
    7 of 16 blocks (3 wholly below the band), a full layer 10."""
    attention._said.clear()
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, block=4, interpret=True))


def _attention_lines(capsys):
    return [line.split("blocks (4, 4), ")[-1]
            for line in capsys.readouterr().err.splitlines()
            if "[attention]" in line]


LAYERS = {
    "sliding": ("sliding_attention",),
    "full": ("full_attention",),
    "whole": ("sliding_attention", "full_attention"),
}
# What the kernels say of each kind of layer, after the blocks' sizes.
SAID = {
    "sliding": ["window 4, blocks run 7 of 16 (skipped 6 above the "
                "diagonal, 3 below the band), interpret mode"],
    "full": ["causal blocks skipped 6 of 16, interpret mode"],
}


expert_kernels = shared.expert_kernels  # a fixture


@pytest.mark.parametrize("path", ["einsum", "kernels", "expert_kernels"])
@pytest.mark.parametrize("layers", list(LAYERS))
def test_logits_and_gradient_equal_the_reference(layers, path, request,
                                                 capsys):
    """Each kind of layer alone (one between embedding and head) and the
    tiny model, by the einsum path, by the attention kernels and by the
    expert layer's grouped-matmul kernels: logits and ``jax.grad`` of the
    next-token loss."""
    if path != "einsum":
        request.getfixturevalue(
            "kernel_path" if path == "kernels" else path)
    model = _model(LAYERS[layers])
    module, variables, made = _setup(model)
    x, y = shared._tokens()
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
    for leaf, grad in shared._paths(grads).items():
        np.testing.assert_allclose(
            grad, want[leaf], atol=2e-5 * max(1.0, float(
                jnp.linalg.norm(want[leaf]))), err_msg=leaf)
    assert float(jnp.linalg.norm(want["lm_head/embedding"])) > 0
    assert float(jnp.linalg.norm(want["embed/embedding"])) > 0
    if path == "kernels":
        assert _attention_lines(capsys) == (
            SAID["sliding"] + SAID["full"] if layers == "whole"
            else SAID[layers])
    if path == "expert_kernels":
        said = shared._expert_lines(capsys)
        assert len(said) == 2 and all(
            line.startswith("[experts] grouped: ") and line.endswith(
                "weights, interpret mode") for line in said)


def test_the_ragged_dot_path_says_why_it_was_taken_once(capsys):
    """Two expert layers, three grouped matmuls each, one line."""
    attention._said.clear()
    module, variables, _ = _setup(_model())
    _apply(module, variables, shared._tokens()[0])
    said = shared._expert_lines(capsys)
    assert len(said) == 1 and re.fullmatch(
        r"\[experts\] ragged_dot: m = \d+ is not a multiple of the row tile "
        "128", said[0])


@pytest.mark.parametrize("path", ["einsum", "kernels"])
@pytest.mark.parametrize("kind", list(mellum.KINDS))
def test_neither_kind_looks_ahead_and_a_window_hides_what_lies_behind_it(
        kind, path, request):
    """Changing the tokens from position 9 on leaves the logits up to 9 as
    they were. Changing token 2 moves position 5 (three back: inside a
    window of 4) in both kinds, and position 6 and later (four back or
    more) in a full layer alone."""
    if path == "kernels":
        request.getfixturevalue("kernel_path")
    model = _model((kind,))
    module, variables, _ = _setup(model)
    x, _ = shared._tokens()
    a, _ = _apply(module, variables, x)
    b, _ = _apply(module, variables,
                  x.at[:, 9:].set((x[:, 9:] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    assert float(jnp.abs(a[:, 9:] - b[:, 9:]).max()) > 0
    c, _ = _apply(module, variables, x.at[:, 2].set((x[:, 2] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :2], c[:, :2])
    assert float(jnp.abs(a[:, 5] - c[:, 5]).min(axis=0).max()) > 0
    moved = float(jnp.abs(a[:, 2 + WINDOW:] - c[:, 2 + WINDOW:]).max())
    assert (moved == 0) if kind == "sliding_attention" else (moved > 0)


def test_the_yarn_table_is_the_references_and_reads_18_and_35_as_published():
    """The program's table (`lfm2.rope_table`) and the reference's, at the
    published numbers and at the tiny preset's; low 18 and high 35 of 64
    dimensions; a sliding layer's table is the plain one; the model hands
    each kind its own."""
    inv, scale, low, high = ref.yarn_table(128, PUBLISHED_YARN)
    assert (low, high) == (18, 35) and scale == 1.2772588722239782
    plain, one = ref.rope_table(128, {"rope_type": "default",
                                      "rope_theta": 500000})
    assert one == 1.0
    np.testing.assert_array_equal(inv[:19], plain[:19])
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert bool(jnp.all((inv[19:35] < plain[19:35])
                        & (inv[19:35] > plain[19:35] / 16)))
    preset = models.select_model("mellum2_12b_a2p5b_ep4", "synthtokens24k")
    got, got_scale = lfm2.rope_table(128, preset.rope_theta, preset.yarn)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    assert got_scale == scale
    np.testing.assert_allclose(
        lfm2.rope_table(128, preset.rope_theta)[0], plain, rtol=1e-6)
    tiny = mellum.mellum2_tiny()
    np.testing.assert_allclose(
        lfm2.rope_table(16, tiny.rope_theta, tiny.yarn)[0],
        ref.rope_table(16, ROPE["full_attention"])[0], rtol=1e-6)
    # The scale is on cos and sin, so scores carry its square.
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    np.testing.assert_allclose(
        lfm2.rotary(x, plain[:8], 1.5), 1.5 * lfm2.rotary(x, plain[:8]),
        rtol=1e-5, atol=1e-6)


def test_router_weights_sum_to_one_over_the_chosen():
    model = _model()
    kernel = jax.random.normal(jax.random.PRNGKey(3), (64, 8)) / 8
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    chosen, w = ref.route(u, kernel, model)
    assert chosen.shape == w.shape == (2, SEQ, 2)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-6)
    p = jax.nn.softmax(u @ kernel, axis=-1)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(p, 2)[1])
    # The program's layer: no bias leaf, the reference's output.
    layer = lfm2.ExpertLayer(8, (0, 1), 2, 48, score="softmax")
    params = {"router_kernel": kernel, **{
        k: v for k, v in shared._expert_params().items()
        if k.startswith("w")}}
    assert set(jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)[
        "params"]) == set(params)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ff(
            {f"l/moe/{k}": v for k, v in params.items()}, "l", u, model,
            lambda t: t)
    np.testing.assert_allclose(
        layer.apply({"params": params}, u), want, atol=2e-5)
    with pytest.raises(ValueError, match="router law"):
        lfm2.ExpertLayer(8, (0, 1), 2, 48, score="tanh").init(
            jax.random.PRNGKey(0), u)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts of 8, with what every chip computes alike (the
    attention's output and the residual stream) counted once, equal the
    reference's layer that holds all 8."""
    uncut = _model(("sliding_attention",), held=range(8))
    made = weights.make_params(
        jax.random.PRNGKey(7), ref.param_shapes(uncut),
        ref.init_scales(uncut), ref.leaf_rules(uncut))
    h = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.block(made, 0, h, uncut, lambda t: t)
        u = ref.rms_norm(h, made["layer_0/operator_norm/scale"], 1e-6)
        alike = h + ref.attention_operator(
            made, "layer_0", u, "sliding_attention", uncut, lambda t: t)
    tree = {}
    for path, leaf in made.items():
        if path.startswith("layer_0/"):
            node = tree
            *parents, last = path.split("/")[1:]
            for name in parents:
                node = node.setdefault(name, {})
            node[last] = leaf

    def share(held):
        held = jnp.asarray(held)
        params = {**tree, "moe": {
            "router_kernel": tree["moe"]["router_kernel"],
            **{w: tree["moe"][w][held] for w in ("w1", "w2", "w3")}}}
        sizes = _module(dict(uncut, experts_held=held.tolist())).sizes()
        return mellum.Block("sliding_attention", sizes).apply(
            {"params": params}, h)

    shares = [share([2 * c, 2 * c + 1]) for c in range(4)]
    np.testing.assert_allclose(
        alike + sum(s - alike for s in shares), want, atol=2e-5)
    # No share alone is the layer: each adds its own experts' part.
    assert float(jnp.abs(shares[0] - want).max()) > 1e-3


def test_the_counters_equal_the_references_count():
    model = _model()
    module, variables, made = _setup(model)
    x, _ = shared._tokens(seed=4)
    _, state = _apply(module, variables, x)
    with jax.default_matmul_precision("highest"):
        h = made["embed/embedding"][x]
        for i, kind in enumerate(model["layer_types"]):
            p = f"layer_{i}"
            u = ref.rms_norm(h, made[f"{p}/operator_norm/scale"], 1e-6)
            mid = h + ref.attention_operator(
                made, p, u, kind, model, lambda t: t)
            chosen, _ = ref.route(
                ref.rms_norm(mid, made[f"{p}/ffn_norm/scale"], 1e-6),
                made[f"{p}/moe/router_kernel"], model)
            held = ref.pairs_held(chosen, model)
            sums, maxes = (state[c][p]["moe"] for c in COUNTERS)
            assert float(sums["moe_pairs_held"]) == float(held.sum())
            assert float(maxes["moe_max_expert_load"]) == float(held.max())
            assert float(sums["moe_rows_routed"]) == x.size * 2
            h = ref.block(made, i, h, model, lambda t: t)


def test_the_scopes_stand_inside_the_gradient_phase(
        metadata_in_cache_key):
    """The family's own scopes (the two kinds of core apart, the
    projections under a third) and the ones it shares; ``model.attention``,
    which stands around the other family's whole module, is not here."""
    module = models.select_model("mellum2_tiny", "synthtokens")
    init_fn, step_fn, _ = shared._trainer(module)
    x, y = shared._worker_batches()
    state = init_fn(jax.random.PRNGKey(0), x[0])
    text = step_fn.lower(state, x, y).compile().as_text()
    ours = {"embed", "attention_proj", "window_attention", "full_attention",
            "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
            "head_loss"}
    assert ours <= set(lfm2.SCOPES)
    for name in lfm2.SCOPES:
        assert (f"model.{name}/" in text or f"model.{name}\"" in text) == (
            name in ours), name
    for op_name in re.findall(r'op_name="([^"]*model\.[^"]*)"', text):
        for part in op_name.split(";"):
            if "model." in part:
                assert part.index("phase.grads") < part.index("model."), part


def test_three_trainer_steps_equal_slot_by_slot_gradients(monkeypatch):
    """aggregathor (n = 4, f = 1, median under lie): the unroll over the 4
    slots against the same gradients taken one slot after another, three
    steps, the blocks recomputed but for `lfm2.KEPT` as the benchmark's
    preset runs; the
    step's metrics carry the expert layers' counters, one entry a layer."""
    from garfield_tpu.parallel import core

    module = _module(_model(), remat=True)
    x, y = shared._worker_batches()

    def three_steps():
        init_fn, step_fn, _ = shared._trainer(module)
        state = init_fn(jax.random.PRNGKey(0), x[0])
        out = []
        for i in range(3):
            state, metrics = step_fn(
                state, jnp.roll(x, i, 0), jnp.roll(y, i, 0))
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
        assert bool(jnp.all(m["moe_pairs_held"] <= m["moe_rows_routed"]))
        assert bool(jnp.all(m["moe_max_expert_load"] <= 2 * SEQ * 2))
        np.testing.assert_array_equal(
            m["moe_pairs_held"], mm["moe_pairs_held"])


def test_the_presets_and_their_token_dataset_are_registered():
    """24,576 ids and 4,096 positions reach the preset through
    `select_model` and `load_dataset`, by the dataset's name alone."""
    module = models.select_model("mellum2_12b_a2p5b_ep4", "synthtokens24k")
    assert module.num_classes == models.num_classes_dict["synthtokens24k"]
    assert data.TOKEN_DATASETS["synthtokens24k"] == (24576, 4096)
    assert data.TOKEN_DATASETS["synthtokens"] == (
        models.num_classes_dict["synthtokens"], data.SYNTHTOKENS_SEQ)
    assert (module.hidden, module.heads, module.kv_heads, module.head_dim,
            module.expert_width, module.num_experts,
            module.experts_per_token, module.sliding_window, module.eps) == (
                2304, 32, 4, 128, 896, 64, 8, 1024, 1e-6)
    assert tuple(module.layer_types) == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert tuple(module.experts_held) == tuple(range(16)) and module.remat
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes)) == (
        595154176)
    (tx, ty), (ex, _) = data.load_dataset("synthtokens24k", 4)
    assert tx.shape == ty.shape == (4, 4096) and ex.shape[1] == 4096
    np.testing.assert_array_equal(tx[:, 1:], ty[:, :-1])
    assert 16384 < ex.max() < 24576
