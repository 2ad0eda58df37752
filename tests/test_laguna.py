"""The Laguna family (models/laguna.py) against its plain reference.

The reference is the benchmark's own file, imported by path
(benchmark/references/laguna.py): what these tests hold the program to and
what decides a benchmark cell's `correct` cannot drift apart. Float32,
``laguna_tiny`` (16 positions, a window of 4; 4 query heads on the full
layers and 6 on the sliding one over 2 KV heads of 16, of which a full layer
rotates 8 dimensions; a dense layer, then expert layers with a shared
expert), weights made from the seed by the benchmark's `weights.make_params`
over the reference's ``param_shapes``. The family is made of
`models/lfm2.py`'s modules; the helpers are tests/test_lfm2.py's.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_lfm2 as shared
from garfield_tpu import data, models
from garfield_tpu.models import laguna, lfm2
from garfield_tpu.ops import attention
from garfield_tpu.utils import selectors

ref = shared._by_path(
    "_laguna_reference", shared.BENCH / "references/laguna.py")
lie = shared._by_path(
    "_lie_reference", shared.BENCH / "references/attacks/lie.py")
median = shared._by_path(
    "_median_reference", shared.BENCH / "references/rules/median.py")
ref_loss, weights, COUNTERS = shared.ref_loss, shared.weights, shared.COUNTERS
metadata_in_cache_key = shared.metadata_in_cache_key  # a fixture
VOCAB, SEQ, WINDOW = shared.VOCAB, shared.SEQ, 4
# The published group and the tiny preset's: one law, two sizes.
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 5e5, "factor": 4.0,
        "original_max_position_embeddings": 8, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.1386294361119891,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 1e4,
                          "partial_rotary_factor": 1},
}
PUBLISHED_YARN = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
    "original_max_position_embeddings": 4096, "beta_slow": 1,
    "beta_fast": 64, "attention_factor": 1.4158883083359672,
    "partial_rotary_factor": 0.5}
LAYERS = {
    "dense_full": dict(layer_types=("full_attention",), heads=(4,), dense=1),
    "sparse_sliding": dict(
        layer_types=("sliding_attention",), heads=(6,), dense=0),
    "whole": dict(),
}


def _model(layer_types=("full_attention", "sliding_attention",
                        "full_attention"), heads=(4, 6, 4), dense=1,
           held=(0, 1), published=8, top=2):
    """The reference's ``model`` group at the tiny preset's sizes."""
    return {
        "family": "laguna", "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 48, "shared_expert_intermediate_size": 40,
        "num_attention_heads_per_layer": list(heads),
        "num_key_value_heads": 2, "head_dim": 16, "norm_eps": 1e-6,
        "rope_parameters": ROPE, "sliding_window": WINDOW,
        "layer_types": list(layer_types), "num_dense_layers": dense,
        "num_experts_published": published, "experts_held": list(held),
        "num_experts_per_tok": top, "moe_routed_scaling_factor": 2.5,
        "vocab_size": VOCAB, "seq_len": SEQ,
    }


def _module(model, **fields):
    return laguna.laguna_tiny(
        num_classes=model["vocab_size"],
        experts_held=tuple(model["experts_held"]),
        layer_types=tuple(model["layer_types"]),
        heads_per_layer=tuple(model["num_attention_heads_per_layer"]),
        num_dense_layers=model["num_dense_layers"],
        num_experts=model["num_experts_published"],
        experts_per_token=model["num_experts_per_tok"], **fields)


def _made(model, seed=5):
    return weights.make_params(
        jax.random.PRNGKey(seed), ref.param_shapes(model),
        ref.init_scales(model), ref.leaf_rules(model))


def _setup(model, seed=5, **fields):
    """``(module, variables, flat reference weights)`` with the program's
    parameters set to the reference's, leaf by leaf by path."""
    module = _module(model, **fields)
    variables = dict(module.init(jax.random.PRNGKey(0), shared._tokens()[0]))
    shapes = ref.param_shapes(model)
    have = {p: v.shape for p, v in shared._paths(variables["params"]).items()}
    assert have == {p: tuple(s) for p, s in shapes.items()}
    made = _made(model, seed)
    variables["params"] = jax.tree.unflatten(
        jax.tree.structure(variables["params"]), [made[p] for p in have])
    return module, variables, made


def _apply(module, variables, x):
    return module.apply(variables, x, mutable=list(COUNTERS))


def _subtree(made, prefix):
    """The leaves under ``prefix/`` of a flat ``{path: leaf}`` as a tree."""
    tree = {}
    for path, leaf in made.items():
        if path.startswith(prefix + "/"):
            node = tree
            *parents, last = path[len(prefix) + 1:].split("/")
            for name in parents:
                node = node.setdefault(name, {})
            node[last] = leaf
    return tree


@pytest.fixture
def kernel_path(monkeypatch):
    """`lfm2.Attention` takes the blockwise kernels, in interpret mode with
    blocks of 4 over the 16 positions: the window equals the block, so a
    sliding layer runs 7 of 16 blocks, every one of them crossed by an
    edge; groups of 2 and of 3 query heads a KV head in one model."""
    attention._said.clear()
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, block=4, interpret=True))


expert_kernels = shared.expert_kernels  # a fixture


@pytest.mark.parametrize("path", ["einsum", "kernels", "expert_kernels"])
@pytest.mark.parametrize("layers", list(LAYERS))
def test_logits_loss_and_gradient_equal_the_reference(layers, path, request,
                                                      capsys):
    """A dense full-attention layer alone, a sparse sliding layer alone (one
    between embedding and head) and the tiny model — both kinds of layer,
    two head counts, a dense layer, a shared expert —, by the einsum path
    and by the kernels: logits, loss and ``jax.grad`` of every leaf."""
    if path != "einsum":
        request.getfixturevalue(
            "kernel_path" if path == "kernels" else path)
    model = _model(**LAYERS[layers])
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
    got = shared._paths(grads)
    assert set(got) == set(want)
    for leaf, grad in got.items():
        np.testing.assert_allclose(
            grad, want[leaf], atol=2e-5 * max(1.0, float(
                jnp.linalg.norm(want[leaf]))), err_msg=leaf)
        assert float(jnp.linalg.norm(want[leaf])) > 0, leaf
    if path == "kernels":
        said = [line for line in capsys.readouterr().err.splitlines()
                if "[attention]" in line]
        assert all("blockwise" in line for line in said) and said
        if layers == "whole":  # groups of 2 and of 3, a band and a triangle
            assert {re.search(r"= \(\d+, (\d+), 2,", s).group(1)
                    for s in said} == {"4", "6"}
            assert any("window 4, blocks run 7 of 16" in s for s in said)
    if path == "expert_kernels":
        # The expert layer's grouped matmuls in the kernels (a dense layer
        # alone has none); the shared expert is a plain `SwiGLU`.
        said = shared._expert_lines(capsys)
        assert len(said) == (0 if layers == "dense_full" else 2), said
        assert all(line.startswith("[experts] grouped: ") and line.endswith(
            "weights, interpret mode") for line in said)


def test_the_ragged_dot_path_says_why_it_was_taken_once(capsys):
    attention._said.clear()
    module, variables, _ = _setup(_model())
    _apply(module, variables, shared._tokens()[0])
    said = shared._expert_lines(capsys)
    assert len(said) == 1 and said[0].startswith(
        "[experts] ragged_dot: m = ") and said[0].endswith(
            "is not a multiple of the row tile 128")


@pytest.mark.parametrize("kind", list(laguna.KINDS))
def test_neither_kind_looks_ahead_and_a_window_hides_what_lies_behind_it(
        kind):
    """As tests/test_mellum.py's, with the gate on: the gate reads the
    block's input at its own position alone."""
    model = _model((kind,), (4,), dense=0)
    module, variables, _ = _setup(model)
    x, _ = shared._tokens()
    a, _ = _apply(module, variables, x)
    b, _ = _apply(module, variables,
                  x.at[:, 9:].set((x[:, 9:] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    c, _ = _apply(module, variables, x.at[:, 2].set((x[:, 2] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :2], c[:, :2])
    assert float(jnp.abs(a[:, 5] - c[:, 5]).min(axis=0).max()) > 0
    moved = float(jnp.abs(a[:, 2 + WINDOW:] - c[:, 2 + WINDOW:]).max())
    assert (moved == 0) if kind == "sliding_attention" else (moved > 0)


def test_the_half_rotated_yarn_table_is_hugging_faces_written_out_by_hand():
    """``_compute_yarn_parameters`` with ``partial_rotary_factor`` 0.5 at the
    published numbers, in plain Python floats: dim = 64 rotated dimensions,
    32 frequencies, the ramp between dimensions 8 and 19 of them. The
    program's table and the reference's are it; the other 64 dimensions of
    a head pass through `rotary` unchanged; the sliding table is the plain
    one over the whole head at theta 10,000."""
    dim, theta, factor, original = 64, 500000.0, 64.0, 4096

    def c(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = max(math.floor(c(64)), 0), min(math.ceil(c(1)), dim - 1)
    assert (low, high) == (5, 16)
    by_hand = []
    for j in range(dim // 2):
        extrap = theta ** (-2 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        by_hand.append(extrap / factor * ramp + extrap * (1 - ramp))
    inv, scale, got_low, got_high = ref.yarn_table(64, PUBLISHED_YARN)
    assert (got_low, got_high, scale) == (5, 16, 1.4158883083359672)
    np.testing.assert_allclose(inv, by_hand, rtol=2e-6)
    table = ref.rope_table(128, PUBLISHED_YARN)
    assert table[0].shape == (32,)
    np.testing.assert_array_equal(table[0], inv)
    preset = models.select_model("laguna_xs2_ep16", "synthtokens12k")
    got, got_scale = lfm2.rope_table(
        preset.full_rotary_dim, preset.full_theta, preset.yarn)
    np.testing.assert_allclose(got, by_hand, rtol=2e-6)
    assert got_scale == scale and preset.full_rotary_dim == 64
    sliding, one = lfm2.rope_table(preset.head_dim, preset.sliding_theta)
    assert one == 1.0 and sliding.shape == (64,)
    np.testing.assert_allclose(
        sliding, [1e4 ** (-2 * j / 128) for j in range(64)], rtol=2e-6)
    np.testing.assert_allclose(sliding, ref.rope_table(128, {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1})[0], rtol=1e-6)
    # A table of 32 frequencies turns dimensions 0-63 among themselves
    # (j with j + 32) and passes 64-127, in program and reference alike.
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 128))
    turned = lfm2.rotary(x, got, got_scale)
    np.testing.assert_array_equal(turned[..., 64:], x[..., 64:])
    np.testing.assert_allclose(
        turned[..., :64], lfm2.rotary(x[..., :64], got, got_scale))
    np.testing.assert_allclose(
        turned, ref.rotary(x, inv, scale), rtol=1e-5, atol=1e-5)
    angle = 3 * by_hand[1]
    np.testing.assert_allclose(
        turned[0, 3, 0, 1], scale * (x[0, 3, 0, 1] * math.cos(angle)
                                     - x[0, 3, 0, 33] * math.sin(angle)),
        rtol=1e-4, atol=1e-6)


def test_a_zero_gate_halves_the_core_and_one_heads_gate_moves_that_head():
    """g = sigmoid(u W_g): with W_g = 0 every head's output is halved; a
    W_g with one non-zero column changes what that head adds and no other;
    the gate's leaf is (hidden, heads)."""
    gated = lfm2.Attention(4, 2, 16, window=WINDOW, gate=True)
    plain = lfm2.Attention(4, 2, 16, window=WINDOW)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    params = gated.init(jax.random.PRNGKey(0), u)["params"]
    assert params["g_proj"]["kernel"].shape == (64, 4)
    assert set(params) == set(plain.init(jax.random.PRNGKey(0), u)[
        "params"]) | {"g_proj"}
    ungated = {k: v for k, v in params.items() if k != "g_proj"}
    zero = {**params, "g_proj": {"kernel": jnp.zeros((64, 4))}}
    np.testing.assert_allclose(
        gated.apply({"params": zero}, u),
        0.5 * plain.apply({"params": ungated}, u), atol=1e-6)
    # o_proj rows of head 2 alone carry what head 2's gate changes.
    column = zero["g_proj"]["kernel"].at[:, 2].set(
        params["g_proj"]["kernel"][:, 2] * 4)
    moved = gated.apply({"params": {**zero, "g_proj": {"kernel": column}}}, u)
    o = params["o_proj"]["kernel"].reshape(4, 16, 64)
    only = {**zero, "o_proj": {"kernel": o.at[2].set(0).reshape(64, 64)}}
    np.testing.assert_allclose(
        gated.apply({"params": {**only, "g_proj": {"kernel": column}}}, u),
        gated.apply({"params": only}, u), atol=1e-6)
    assert float(jnp.abs(moved - gated.apply({"params": zero}, u)).max()) > (
        1e-3)


def test_router_weights_sum_to_the_scaling_and_the_layer_has_no_bias_leaf():
    model = _model()
    kernel = jax.random.normal(jax.random.PRNGKey(3), (64, 8)) / 8
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    chosen, w = ref.route(u, kernel, model)
    assert chosen.shape == w.shape == (2, SEQ, 2)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-6)
    s = jax.nn.sigmoid(u @ kernel)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(s, 2)[1])
    layer = lfm2.ExpertLayer(8, (0, 1), 2, 48, 2.5, bias=False,
                             shared_width=40)
    names = set(jax.eval_shape(layer.init, jax.random.PRNGKey(0), u)[
        "params"])
    assert names == {"router_kernel", "w1", "w2", "w3", "shared"}


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """4 shares of 2 experts each of an 8-expert layer: the routed parts of
    all shares, plus what every chip computes alike — the attention's
    output, the residual stream and the shared expert — counted once, equal
    the reference's layer that holds all 8."""
    uncut = _model(("sliding_attention",), (6,), dense=0, held=range(8))
    made = _made(uncut, seed=7)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    identity = lambda t: t
    with jax.default_matmul_precision("highest"):
        want = ref.block(made, 0, h, uncut, identity)
        u = ref.rms_norm(h, made["layer_0/operator_norm/scale"], 1e-6)
        mid = h + ref.attention_operator(
            made, "layer_0", u, "sliding_attention", 6, uncut, identity)
        shared_part = ref.shared_ff(
            made, "layer_0",
            ref.rms_norm(mid, made["layer_0/ffn_norm/scale"], 1e-6), identity)
    tree = _subtree(made, "layer_0")

    def share(held):
        held = jnp.asarray(held)
        params = {**tree, "moe": {
            **tree["moe"],
            **{w: tree["moe"][w][held] for w in ("w1", "w2", "w3")}}}
        sizes = _module(dict(uncut, experts_held=held.tolist())).sizes()
        return laguna.Block("sliding_attention", 6, True, sizes).apply(
            {"params": params}, h)

    shares = [share([2 * c, 2 * c + 1]) for c in range(4)]
    alike = mid + shared_part
    np.testing.assert_allclose(
        alike + sum(s - alike for s in shares), want, atol=2e-5)
    # No share alone is the layer, and the shared expert is part of it.
    assert float(jnp.abs(shares[0] - want).max()) > 1e-3
    assert float(jnp.abs(shared_part).max()) > 1e-3


def test_the_counters_equal_the_references_count():
    model = _model()
    module, variables, made = _setup(model)
    x, _ = shared._tokens(seed=4)
    _, state = _apply(module, variables, x)
    identity = lambda t: t
    with jax.default_matmul_precision("highest"):
        h = made["embed/embedding"][x]
        for i, p, kind, heads, sparse in ref._layers(model):
            if sparse:
                u = ref.rms_norm(h, made[f"{p}/operator_norm/scale"], 1e-6)
                mid = h + ref.attention_operator(
                    made, p, u, kind, heads, model, identity)
                chosen, _ = ref.route(
                    ref.rms_norm(mid, made[f"{p}/ffn_norm/scale"], 1e-6),
                    made[f"{p}/moe/router_kernel"], model)
                held = ref.pairs_held(chosen, model)
                sums, maxes = (state[c][p]["moe"] for c in COUNTERS)
                assert float(sums["moe_pairs_held"]) == float(held.sum())
                assert float(maxes["moe_max_expert_load"]) == float(
                    held.max())
                assert float(sums["moe_rows_routed"]) == x.size * 2
            else:
                assert p not in state[COUNTERS[0]]
            h = ref.block(made, i, h, model, identity)


def test_the_scopes_stand_inside_the_gradient_phase(
        metadata_in_cache_key, capsys):
    """The family's scopes: the two kinds of core apart, the projections
    under a third, the gate and the shared expert each under its own and
    inside no other model scope; ``model.attention`` and ``conv_mixer`` are
    the first family's, ``ssm_*``, ``gmu`` and ``cross_attention`` the
    SambaY family's (tests/test_phi4flash.py). A recomputed block says what it keeps, the gate's
    projection and the shared expert's inner products among it."""
    attention._said.clear()
    module = models.select_model("laguna_tiny", "synthtokens").clone(
        remat=True)
    init_fn, step_fn, _ = shared._trainer(module)
    x, y = shared._worker_batches()
    state = init_fn(jax.random.PRNGKey(0), x[0])
    text = step_fn.lower(state, x, y).compile().as_text()
    ours = set(lfm2.SCOPES) - {
        "attention", "conv_mixer", "ssm_proj", "ssm_conv", "ssm_scan", "gmu",
        "cross_attention"}
    for name in lfm2.SCOPES:
        assert (f"model.{name}/" in text or f"model.{name}\"" in text) == (
            name in ours), name
    for op_name in re.findall(r'op_name="([^"]*model\.[^"]*)"', text):
        for part in op_name.split(";"):
            if "model." in part:
                assert part.index("phase.grads") < part.index("model."), part
            for own in ("attention_gate", "shared_expert"):
                if f"model.{own}" in part:
                    assert part.count("model.") == 1, part
    said = [line for line in capsys.readouterr().err.splitlines()
            if "[remat]" in line]
    assert said and all(
        name in said[-1] for name in (
            "attention_gate_proj", "shared_w1", "shared_w3", "mlp_w1",
            "moe_rows", "attention_o_proj"))


def test_a_five_worker_step_under_two_liars_has_a_finite_fake_row():
    """aggregathor, n = 5, f = 2, median under lie, the blocks recomputed as
    the benchmark's preset runs them: the cohort's Bessel-corrected
    deviation is over 2 rows, so the fake row is a number (at f = 1 it is
    0 / 0), it pulls the median away from the honest rows' own, and the
    aggregate the optimizer got equals the reference's — per-worker
    gradients of the plain forward pass, the attack written out as rows,
    the median by a sort. The step's metrics carry the three counters of
    each of the expert layers."""
    model = _model(
        ("full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"), (4, 6, 6, 6, 4))
    module, variables, made = _setup(model, remat=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.randint(k1, (5, 2, SEQ), 0, VOCAB)
    y = jax.random.randint(k2, (5, 2, SEQ), 0, VOCAB)
    init_fn, step_fn, _ = shared._trainer(module, num_workers=5, f=2)
    state = init_fn(jax.random.PRNGKey(0), x[0])
    state = state.replace(params=variables["params"])
    new, metrics = step_fn(state, x, y)
    # SGD's first step: params' = params - lr (g + wd params).
    got = {p: (made[p] - v) / 0.05 - 5e-4 * made[p]
           for p, v in shared._paths(new.params).items()}
    with jax.default_matmul_precision("highest"):
        grads = [jax.grad(lambda p, w=w: ref_loss(
            ref.forward(p, x[w], model), y[w]))(made) for w in range(5)]
    stack = {p: jnp.stack([g[p].reshape(-1) for g in grads]) for p in made}
    byz = lie.byzantine(5, 2)
    assert byz == [False, False, False, True, True]
    attacked = lie.apply(stack, byz)
    want = median.aggregate(attacked, 2)
    honest = {p: jnp.sort(rows[:3], axis=0)[1] for p, rows in stack.items()}
    pulled = 0
    for p in made:
        assert bool(jnp.all(jnp.isfinite(attacked[p][3]))), p
        np.testing.assert_array_equal(attacked[p][3], attacked[p][4])
        scale = max(1.0, float(jnp.linalg.norm(want[p])))
        np.testing.assert_allclose(
            got[p].reshape(-1), want[p], atol=3e-4 * scale, err_msg=p)
        pulled += int(jnp.sum(want[p] != honest[p]))
    assert pulled > 0
    assert math.isfinite(float(metrics["loss"]))
    assert metrics["moe_rows_routed"].tolist() == [5 * 2 * SEQ * 2] * 4
    assert metrics["moe_pairs_held"].shape == (4,)
    assert metrics["moe_max_expert_load"].shape == (4,)
    assert bool(jnp.all(metrics["moe_pairs_held"]
                        <= metrics["moe_rows_routed"]))


def test_the_presets_and_their_token_dataset_are_registered():
    """12,544 ids and 4,096 positions reach the preset through
    `select_model` and `load_dataset`, by the dataset's name alone; the
    preset's leaves are the reference's at the published widths:
    d = 490,298,624."""
    module = models.select_model("laguna_xs2_ep16", "synthtokens12k")
    assert module.num_classes == models.num_classes_dict["synthtokens12k"]
    assert data.TOKEN_DATASETS["synthtokens12k"] == (12544, 4096)
    assert (module.hidden, module.kv_heads, module.head_dim,
            module.dense_width, module.expert_width, module.shared_width,
            module.num_experts, module.experts_per_token,
            module.sliding_window, module.scaling, module.eps) == (
                2048, 8, 128, 8192, 512, 512, 256, 8, 512, 2.5, 1e-6)
    assert tuple(module.heads_per_layer) == (48, 64, 64, 64, 48)
    assert tuple(module.layer_types) == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert tuple(module.experts_held) == tuple(range(16)) and module.remat
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32)))["params"]
    have = {p: v.shape for p, v in shared._paths(shapes).items()}
    assert sum(math.prod(s) for s in have.values()) == 490298624
    with pytest.raises(ValueError, match="head counts"):
        laguna.laguna_tiny(heads_per_layer=(4, 4)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    (tx, ty), (ex, _) = data.load_dataset("synthtokens12k", 4)
    assert tx.shape == ty.shape == (4, 4096) and ex.shape[1] == 4096
    np.testing.assert_array_equal(tx[:, 1:], ty[:, :-1])
    assert 8192 < ex.max() < 12544
