"""The SambaY family (models/phi4flash.py) and the chunked selective scan
(ops/scan.py) against their plain references.

The model's reference is the benchmark's own file, imported by path
(benchmark/references/phi4flash.py): what these tests hold the program to and
what decides a benchmark cell's `correct` cannot drift apart. Float32,
``phi4flash_tiny`` (16 positions, a window of 4, 4 differential heads over 2
key-value pairs of 8, a state of 4), weights made from the seed by the
benchmark's `weights.make_params` over the reference's ``param_shapes``. The
helpers are tests/test_lfm2.py's.
"""

import functools
import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_lfm2 as shared
from garfield_tpu import data, models
from garfield_tpu.models import lfm2, phi4flash
from garfield_tpu.ops import attention, scan
from garfield_tpu.utils import selectors

ref = shared._by_path(
    "_phi4flash_reference", shared.BENCH / "references/phi4flash.py")
ref_loss, weights, COUNTERS = shared.ref_loss, shared.weights, shared.COUNTERS
metadata_in_cache_key = shared.metadata_in_cache_key  # a fixture
VOCAB, SEQ, WINDOW = shared.VOCAB, shared.SEQ, 4
TINY = ("mamba", "sliding_attention", "mamba", "sliding_attention", "mamba",
        "full_attention", "gmu", "cross_attention")
NEW_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "gmu", "cross_attention")


def _model(layer_types=TINY, first=0):
    """The reference's ``model`` group at the tiny preset's sizes."""
    return {
        "family": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 8,
        "sliding_window": WINDOW, "layer_types": list(layer_types),
        "layer_indices": list(range(first, first + len(layer_types))),
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_dt_rank": 4, "norm_eps": 1e-5, "vocab_size": VOCAB,
        "seq_len": SEQ,
    }


def _module(model, **fields):
    return phi4flash.phi4flash_tiny(
        num_classes=model["vocab_size"],
        layer_types=tuple(model["layer_types"]),
        first_layer=model["layer_indices"][0], **fields)


def _made(model, seed=5):
    return weights.make_params(
        jax.random.PRNGKey(seed), ref.param_shapes(model),
        ref.init_scales(model, {"residual_out_scale": 0.5}),
        ref.leaf_rules(model))


def _setup(model, seed=5, **fields):
    """``(module, variables, flat reference weights)`` with the program's
    parameters set to the reference's, leaf by leaf by path."""
    module = _module(model, **fields)
    variables = dict(module.init(jax.random.PRNGKey(0), shared._tokens()[0]))
    have = {p: v.shape for p, v in shared._paths(variables["params"]).items()}
    assert have == {p: tuple(s) for p, s in ref.param_shapes(model).items()}
    made = _made(model, seed)
    variables["params"] = jax.tree.unflatten(
        jax.tree.structure(variables["params"]), [made[p] for p in have])
    return module, variables, made


def _apply(module, variables, x):
    return module.apply(variables, x, mutable=list(COUNTERS))


@pytest.fixture
def kernel_path(monkeypatch):
    """`ops.attention.causal_gqa` takes the blockwise kernels, in interpret
    mode with blocks of 4 over the 16 positions: a window layer's band is
    one block wide; `ops.scan.selective_scan` takes its kernels, in
    interpret mode (the tiny preset's state of 4 fills no sublanes, which
    only the chip asks for)."""
    attention._said.clear()
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, block=4, interpret=True))
    monkeypatch.setattr(scan, "selective_scan", functools.partial(
        scan.selective_scan, interpret=True))


@pytest.mark.parametrize("path", ["einsum", "kernels"])
@pytest.mark.parametrize("layers", ["whole", "stage"])
def test_logits_loss_and_gradient_equal_the_reference(layers, path, request,
                                                      capsys):
    """The tiny model (8 layers) and a stage of its last five (published
    indices 3-7, so λ_init follows them), by the einsum path and the
    chunked scan and by the kernels (the attention's and the scan's):
    logits, loss and ``jax.grad`` of every leaf. Float32 both: the
    program sums in another order (the scan by chunks, the attention
    block by block, the combine after the two calls), so each number is
    held to a few float32 ulps of its largest entry, 2e-5 as the other
    families' tests hold theirs."""
    if path == "kernels":
        request.getfixturevalue("kernel_path")
    model = _model() if layers == "whole" else _model(TINY[3:], 3)
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
    np.testing.assert_allclose(logits, want_logits, atol=2e-5 * float(
        jnp.abs(want_logits).max()))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    got = shared._paths(grads)
    assert set(got) == set(want)
    for leaf, grad in got.items():
        np.testing.assert_allclose(
            grad, want[leaf], atol=2e-5 * max(1.0, float(
                jnp.linalg.norm(want[leaf]))), err_msg=leaf)
        assert float(jnp.linalg.norm(want[leaf])) > 0, leaf
    if path == "kernels":
        err = capsys.readouterr().err.splitlines()
        said = [line for line in err if "[attention]" in line]
        assert said and all("blockwise" in line for line in said)
        # Both softmaxes' heads in one call: 8 over 4 KV heads.
        assert all("= (3, 8, 4, 16, 8)" in line for line in said), said
        assert [line for line in err if "[ssm]" in line] == [
            "[ssm] kernels: (n, t, channels, state) = (3, 16, 128, 4), "
            "channel tile 128, chunks of 128, state float32; kept y + chunk "
            "states 0.0676 MB a sequence, interpret mode"]


def _scan_inputs(n=2, t=37, channels=8, state=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (n, t, channels)),
            jax.nn.softplus(jax.random.normal(k[1], (n, t, channels))),
            -jnp.exp(jax.random.normal(k[2], (channels, state))),
            jax.random.normal(k[3], (n, t, state)),
            jax.random.normal(k[4], (n, t, state)),
            jax.random.normal(k[5], (channels,)),
            jax.random.normal(k[6], (n, t, channels)))


@pytest.mark.parametrize("chunk", [None, 1, 4, 5, 16, 37, 64])
def test_the_chunked_scan_equals_the_sequential_one(chunk):
    """Forward and the gradients of all six operands, over chunks that
    divide t = 37 (1, 37), that do not (4, 5, 16: the last chunk padded
    with delta 0) and longer than t; None is the entry, whose chunk the
    shapes give (32 here). Float32 both: the chunked form runs the same
    position updates in the same order, so y agrees to the bit and the
    gradients to the sums' order (a few ulps)."""
    *args, w = _scan_inputs()
    scanned = scan.selective_scan if chunk is None else functools.partial(
        scan._chunked, length=chunk)
    np.testing.assert_array_equal(scanned(*args), scan.sequential_scan(*args))

    def loss(f):
        return lambda *a: jnp.sum(f(*a) * w)

    grads = jax.grad(loss(scanned), argnums=range(6))(*args)
    want = jax.grad(loss(scan.sequential_scan), argnums=range(6))(*args)
    for g, v in zip(grads, want):
        np.testing.assert_allclose(g, v, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(v).max()))


def test_the_sequential_scan_is_the_reference_recurrence_and_keeps_bf16():
    """`sequential_scan` against the benchmark reference's scan (another
    layout of the state: channels by state) plus D x; bf16 operands give a
    bf16 y from a float32 state."""
    *args, _ = _scan_inputs(t=16)
    x, delta, a, b, c, d = args
    with jax.default_matmul_precision("highest"):
        want = ref.selective_scan(x, delta, a, b, c) + d * x
    np.testing.assert_allclose(scan.sequential_scan(*args), want,
                               rtol=1e-6, atol=1e-6)
    half = [v.astype(jnp.bfloat16) for v in (x, delta)]
    y = scan.selective_scan(half[0], half[1], a, b.astype(jnp.bfloat16),
                            c.astype(jnp.bfloat16), d)
    assert y.dtype == jnp.bfloat16


def test_the_chunk_length_follows_the_shapes(capsys):
    """64 positions a chunk at the cell's (1, 4096, 5120, 16): 21 MB of
    float32 states a chunk; fewer where a position's states are larger;
    never more than t. The line is said once."""
    assert scan.chunk_length(1, 4096, 5120, 16) == scan.CHUNK_MAX == 64
    assert scan.chunk_length(8, 4096, 5120, 16) == 16
    assert scan.chunk_length(1, 37, 8, 4) == 32
    assert scan.chunk_length(1, 1, 8, 4) == 1
    attention._said.clear()
    *args, _ = _scan_inputs(n=1, t=48)
    for _ in range(2):
        scan.selective_scan(*args)
    said = [line for line in capsys.readouterr().err.splitlines()
            if line.startswith("[ssm]")]
    assert said == ["[ssm] chunked: channels = 8 is not a multiple of 128; "
                    "(n, t, channels, state) = (1, 48, 8, 4), chunks of 32, "
                    "loop over positions, state float32"]


def _einsum_diff(q, k, v, lam, window):
    """The spec of `diff_core` written out: per differential head, two
    softmaxes over its own pair's keys, their difference times v."""
    n, t, heads, _, hd = q.shape
    pairs = k.shape[2]
    out = []
    for i in range(heads):
        p = i * pairs // heads
        probs = []
        for j in (0, 1):
            s = jnp.einsum("nqd,nkd->nqk", q[:, :, i, j], k[:, :, p, j],
                           precision="highest") / math.sqrt(hd)
            gap = jnp.arange(t)[:, None] - jnp.arange(t)[None]
            seen = (gap >= 0) & (gap < (window or t))
            probs.append(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1))
        out.append(jnp.einsum("nqk,nke->nqe", probs[0] - lam * probs[1],
                              v[:, :, p], precision="highest"))
    return jnp.stack(out, axis=2)


@pytest.mark.parametrize("path", ["einsum", "kernels"])
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_the_differential_core_equals_the_einsum_spec(kind, path, request):
    """(A_1 - λ A_2) v of `diff_core`, the two calls' outputs put back
    together, against the spec per head: a window of 4, causal, and a cross
    layer's core, which is the causal core over another layer's k and v."""
    if path == "kernels":
        request.getfixturevalue("kernel_path")
    ks = jax.random.split(jax.random.PRNGKey({"window": 1, "full": 2,
                                              "cross": 3}[kind]), 3)
    q = jax.random.normal(ks[0], (2, SEQ, 4, 2, 8))
    k = jax.random.normal(ks[1], (2, SEQ, 2, 2, 8))
    v = jax.random.normal(ks[2], (2, SEQ, 2, 16))
    window = WINDOW if kind == "window" else None
    got = phi4flash.diff_core(q, k, v, 0.37, window)
    assert got.shape == (2, SEQ, 4, 16) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _einsum_diff(q, k, v, 0.37, window),
                               atol=2e-5)


class _Stage(nn.Module):
    """The block chain alone, on a residual stream."""

    layer_types: tuple
    first_layer: int

    @nn.compact
    def __call__(self, h):
        tiny = _module(_model(self.layer_types, self.first_layer))
        return phi4flash.chain(h, self.layer_types, self.first_layer,
                               tiny.sizes(), jnp.float32, False)


def test_the_stage_ties_to_the_model():
    """The tiny model's residual stream after its layer 7, given the one
    after its layer 2, equals the 5-layer stage's (published layers 3-7:
    window, the memory's Mamba, full, GMU, cross) on that input: the stage
    makes its own memory and keys and values, and nothing of layers 0-2
    reaches past them."""
    _, variables, made = _setup(_model())
    params = variables["params"]
    x, _ = shared._tokens()
    h0 = made["embed/embedding"][x]
    layers = lambda lo, hi: {f"layer_{i - lo}": params[f"layer_{i}"]
                             for i in range(lo, hi)}
    h2 = _Stage(TINY[:3], 0).apply({"params": layers(0, 3)}, h0)
    h7 = _Stage(TINY, 0).apply({"params": layers(0, 8)}, h0)
    stage = _Stage(TINY[3:], 3).apply({"params": layers(3, 8)}, h2)
    np.testing.assert_array_equal(stage, h7)
    renamed = {}
    for path, leaf in made.items():
        layer = re.match(r"layer_(\d+)/(.*)", path)
        if layer and int(layer.group(1)) >= 3:
            renamed[f"layer_{int(layer.group(1)) - 3}/{layer.group(2)}"] = leaf
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            ref.hidden(renamed, h2, _model(TINY[3:], 3)), h7,
            atol=2e-5 * float(jnp.abs(h7).max()))
    # Another memory into the GMU moves the result: layer 4's is read.
    other = dict(layers(3, 8))
    other["layer_1"] = jax.tree.map(lambda v: v * 1.5, other["layer_1"])
    moved = _Stage(TINY[3:], 3).apply({"params": other}, h2)
    assert float(jnp.abs(moved - stage).max()) > 1e-3


@pytest.mark.parametrize("kind", ["mamba", "sliding_attention", "whole"])
def test_no_layer_looks_ahead_and_a_window_hides_what_lies_behind_it(kind):
    """Changing the tokens from position 9 on leaves the logits before 9 as
    they were; a token changed at 2 reaches 5 and, through a window layer
    alone, nothing from 2 + window on."""
    types = TINY if kind == "whole" else (kind,)
    module, variables, _ = _setup(_model(types))
    x, _ = shared._tokens()
    a, _ = _apply(module, variables, x)
    b, _ = _apply(module, variables, x.at[:, 9:].set((x[:, 9:] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :9], b[:, :9])
    c, _ = _apply(module, variables, x.at[:, 2].set((x[:, 2] + 1) % VOCAB))
    np.testing.assert_array_equal(a[:, :2], c[:, :2])
    assert float(jnp.abs(a[:, 5] - c[:, 5]).max()) > 0
    moved = float(jnp.abs(a[:, 2 + WINDOW:] - c[:, 2 + WINDOW:]).max())
    assert (moved == 0) if kind == "sliding_attention" else (moved > 0)


def test_the_lambda_counter_equals_the_references_lambda():
    """Each differential layer writes |λ| into ``counters_max`` under
    ``diff_lambda_max``; λ_init follows the published index."""
    model = _model()
    module, variables, made = _setup(model)
    _, state = _apply(module, variables, shared._tokens()[0])
    assert COUNTERS[0] not in state
    written = state[COUNTERS[1]]
    attention_layers = [i for i, k in enumerate(TINY)
                        if k not in ("mamba", "gmu")]
    assert sorted(written) == [f"layer_{i}" for i in attention_layers]
    for i in attention_layers:
        lam, start = ref.lambda_of(made, f"layer_{i}/attn", i)
        assert start == phi4flash.lambda_init(i)
        np.testing.assert_allclose(
            written[f"layer_{i}"]["attn"]["diff_lambda_max"], abs(lam),
            rtol=1e-6)
    assert phi4flash.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * math.exp(-5.1))


def test_the_compiled_step_names_the_five_new_scopes(
        metadata_in_cache_key, capsys):
    """aggregathor, n = 4, median under lie, the blocks recomputed as the
    benchmark's preset runs them: the step's text names the five new
    scopes inside ``phase.grads`` beside ``attention_proj``, the two older
    core scopes, ``dense_mlp``, ``embed`` and ``head_loss``, and none of the
    other families' own; the λ counter reaches the step's metrics, one
    entry a differential layer. A recomputed block says what it keeps, and
    the scan says its chunks."""
    attention._said.clear()
    module = models.select_model("phi4flash_tiny", "synthtokens").clone(
        remat=True)
    init_fn, step_fn, _ = shared._trainer(module)
    x, y = shared._worker_batches()
    state = init_fn(jax.random.PRNGKey(0), x[0])
    text = step_fn.lower(state, x, y).compile().as_text()
    ours = set(NEW_SCOPES) | {"attention_proj", "window_attention",
                              "full_attention", "dense_mlp", "embed",
                              "head_loss"}
    for name in lfm2.SCOPES:
        assert (f"model.{name}/" in text or f"model.{name}\"" in text) == (
            name in ours), name
    for op_name in re.findall(r'op_name="([^"]*model\.[^"]*)"', text):
        for part in op_name.split(";"):
            if "model." in part:
                assert part.index("phase.grads") < part.index("model."), part
    _, metrics = step_fn(state, x, y)
    assert metrics["diff_lambda_max"].shape == (4,)
    assert math.isfinite(float(metrics["loss"]))
    err = capsys.readouterr().err.splitlines()
    remat = [line for line in err if line.startswith("[remat]")]
    assert remat and all(name in remat[-1] for name in (
        "ssm_in_proj", "ssm_x_proj", "ssm_dt_proj", "ssm_memory",
        "ssm_out_proj", "gmu_gate", "gmu_out_proj", "attention_q_proj",
        "attention_o_proj", "mlp_w1", "mlp_w3"))
    assert "moe_" not in remat[-1]
    assert re.search(r"keeps 12 names: .*; per slot [0-9.]+ GB", remat[-1])
    ssm = [line for line in err if line.startswith("[ssm]")]
    assert ssm == ["[ssm] chunked: state = 4 is not a multiple of 8 "
                   "sublanes; (n, t, channels, state) = (2, 16, 128, 4), "
                   "chunks of 16, loop over positions, state float32"]


def test_the_preset_and_its_token_dataset_are_registered():
    """25,008 ids and 4,096 positions reach the preset through
    `select_model` and `load_dataset`, by the dataset's name alone; the
    preset's leaves are the reference's at the published widths:
    d = 577,178,752."""
    module = models.select_model("phi4_mini_flash_l15_19", "synthtokens25k")
    assert module.num_classes == models.num_classes_dict["synthtokens25k"]
    assert data.TOKEN_DATASETS["synthtokens25k"] == (25008, 4096)
    assert (module.hidden, module.heads, module.kv_heads, module.head_dim,
            module.dense_width, module.sliding_window, module.d_state,
            module.d_conv, module.expand, module.dt_rank, module.eps,
            module.first_layer) == (
                2560, 40, 20, 64, 10240, 512, 16, 4, 2, 160, 1e-5, 15)
    assert tuple(module.layer_types) == (
        "sliding_attention", "mamba", "full_attention", "gmu",
        "cross_attention") and module.remat
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32)))["params"]
    have = {p: v.shape for p, v in shared._paths(shapes).items()}
    assert sum(math.prod(s) for s in have.values()) == 577178752
    (tx, ty), _ = data.load_dataset("synthtokens25k", 2)
    assert tx.shape == ty.shape == (2, 4096) and tx.max() < 25008
    np.testing.assert_array_equal(tx[:, 1:], ty[:, :-1])
    with pytest.raises(ValueError, match="before any Mamba"):
        _module(_model(("gmu",))).init(jax.random.PRNGKey(0),
                                       shared._tokens()[0])
