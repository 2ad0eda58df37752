"""Plain LFM2-MoE (``model_type`` ``lfm2_moe``): one chip's share of a layer
that several chips divide by expert parallelism, in straightforward
``jax.numpy``.

Written from the published ``config.json`` of LiquidAI/LFM2-8B-A1B and the
description of its blocks (gated short convolutions and grouped-query
attention in the pattern of ``layer_types``, a dense SwiGLU MLP in the
leading layers, a mixture of experts in the others, tied embedding).
Float32, matmuls at highest precision; imports nothing of the program under
test. Parameters are a flat ``{path: array}`` dict whose paths are the names
the program's parameter tree uses: names are structure, not values.

The equations (u the normed input, h the residual stream):

- RMSNorm: x * w / sqrt(mean(x^2) + eps). Block: h += Op(RMSNorm(h));
  h += FF(RMSNorm(h)).
- ``conv``: [B, C, X] = u W_in; z = B * X; y_t = sum_j k_j * z_{t-L+1+j}
  (depthwise, causal, zeros to the left); out = (C * y) W_out. No bias.
- ``full_attention``: q, k, v = u W_q, u W_k, u W_v; RMSNorm over each q and
  k head; rotary embedding (half-rotation convention); causal
  softmax(q k^T / sqrt(head_dim)) v, each KV head serving
  heads / kv_heads query heads; out = concat W_o. No bias.
- Dense FF: W_2 (silu(u W_1) * (u W_3)).
- Expert FF: s = sigmoid(u W_r) over all published experts; S = top-k of
  (s + b), b the expert bias (it enters the selection only); w_e = s_e /
  (sum_{e in S} s_e + 1e-6) * routed_scaling_factor; out = sum over the
  selected experts HELD HERE of w_e W_2e (silu(u W_1e) * (u W_3e)). The
  weights' sum is over all k selected experts, held here or not; what the
  absent experts would add is left out, and the partial result goes on.
- Logits = RMSNorm_f(h) E^T over the vocabulary rows held here (tied).

Departures from the published model, each also in the configuration's
``assumed``: the head size is hidden / heads (the config gives none); the
1e-6 in the weights' denominator and the half-rotation rotary convention are
the Hugging Face implementation's; the expert bias is a constant zero leaf
(the config names no rate for its update); every expert here is computed for
every token and masked by its weight, which is the same sum written densely.

``model``: ``{"family": "lfm2_moe", "hidden_size", "intermediate_size",
"moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
"head_dim", "conv_L_cache", "norm_eps", "rope_theta", "layer_types",
"num_dense_layers", "num_experts_published", "experts_held",
"num_experts_per_tok", "routed_scaling_factor", "vocab_size", "seq_len"}``;
the configuration's ``init`` group may hold ``"residual_out_scale"``
(`init_scales`).
"""

import jax
import jax.numpy as jnp

# The kind of input this family reads (`inputs/next_tokens.py`).
INPUT = "next-tokens"
HIGHEST = jax.lax.Precision.HIGHEST
WEIGHT_EPS = 1e-6


def _is_moe(model, i):
    return i >= model["num_dense_layers"]


def param_shapes(model):
    """``{path: shape}`` of every leaf of the parameter tree."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    kv, hd = model["num_key_value_heads"], model["head_dim"]
    held = len(model["experts_held"])
    width = model["moe_intermediate_size"]
    shapes = {"embed/embedding": (model["vocab_size"], h)}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        shapes[f"{p}/operator_norm/scale"] = (h,)
        shapes[f"{p}/ffn_norm/scale"] = (h,)
        if kind == "conv":
            shapes[f"{p}/conv/in_proj/kernel"] = (h, 3 * h)
            shapes[f"{p}/conv/conv_kernel"] = (model["conv_L_cache"], h)
            shapes[f"{p}/conv/out_proj/kernel"] = (h, h)
        else:
            shapes[f"{p}/attn/q_proj/kernel"] = (h, heads * hd)
            shapes[f"{p}/attn/k_proj/kernel"] = (h, kv * hd)
            shapes[f"{p}/attn/v_proj/kernel"] = (h, kv * hd)
            shapes[f"{p}/attn/o_proj/kernel"] = (heads * hd, h)
            shapes[f"{p}/attn/q_norm/scale"] = (hd,)
            shapes[f"{p}/attn/k_norm/scale"] = (hd,)
        if _is_moe(model, i):
            shapes[f"{p}/moe/router_kernel"] = (
                h, model["num_experts_published"])
            shapes[f"{p}/moe/expert_bias"] = (model["num_experts_published"],)
            shapes[f"{p}/moe/w1"] = (held, h, width)
            shapes[f"{p}/moe/w3"] = (held, h, width)
            shapes[f"{p}/moe/w2"] = (held, width, h)
        else:
            m = model["intermediate_size"]
            shapes[f"{p}/mlp/w1/kernel"] = (h, m)
            shapes[f"{p}/mlp/w3/kernel"] = (h, m)
            shapes[f"{p}/mlp/w2/kernel"] = (m, h)
    shapes["final_norm/scale"] = (h,)
    return shapes


def leaf_rules(model):
    """The leaves the harness's defaults have no rule for, or get wrong: the
    embedding (standard deviation 1 / sqrt(hidden): a stated fan-in of
    2 x hidden under the harness's variance 2 / fan_in, so that the tied
    head's logits start near unit size); the expert stacks, whose leading
    axis counts experts and is no fan-in; the depthwise kernel, whose
    fan-in is its L taps; the expert bias, zeros."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    rules = {"embed/embedding": ("normal", 2 * h)}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        if kind == "conv":
            rules[f"{p}/conv/conv_kernel"] = ("normal", model["conv_L_cache"])
        if _is_moe(model, i):
            rules[f"{p}/moe/router_kernel"] = ("normal", h)
            rules[f"{p}/moe/expert_bias"] = ("zeros",)
            rules[f"{p}/moe/w1"] = ("normal", h)
            rules[f"{p}/moe/w3"] = ("normal", h)
            rules[f"{p}/moe/w2"] = ("normal", width)
    return rules


def init_scales(model, init=None):
    """``{path: factor}``: ``residual_out_scale`` on the last matmul of every
    operator and feed-forward (what each adds to the residual stream)."""
    scale = (init or {}).get("residual_out_scale", 1.0)
    scales = {}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        op = "conv/out_proj/kernel" if kind == "conv" else "attn/o_proj/kernel"
        scales[f"{p}/{op}"] = scale
        ff = "moe/w2" if _is_moe(model, i) else "mlp/w2/kernel"
        scales[f"{p}/{ff}"] = scale
    return scales


def expected_pairs(model, tokens):
    """(token, expert) pairs an expert layer here computes for ``tokens``
    tokens if the router chose uniformly: tokens x k x held / published."""
    return (tokens * model["num_experts_per_tok"] * len(model["experts_held"])
            / model["num_experts_published"])


def forward_macs(model):
    """Multiply-adds of one sequence's forward pass: every matmul (the
    depthwise convolution's taps and the router included), the expert
    matmuls at the uniform expectation (`expected_pairs`), attention's two
    contractions at the causal half (t x t / 2 scores a head), the tied
    head over the vocabulary rows held. Norms, rotary embedding, softmax,
    gates and the loss are not counted."""
    t, h = model["seq_len"], model["hidden_size"]
    heads, kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    total = t * h * model["vocab_size"]
    for i, kind in enumerate(model["layer_types"]):
        if kind == "conv":
            total += t * (h * 3 * h + h * h + model["conv_L_cache"] * h)
        else:
            total += t * (2 * h * heads * hd + 2 * h * kv * hd)
            total += 2 * (t * t // 2) * heads * hd
        if _is_moe(model, i):
            total += t * h * model["num_experts_published"]
            total += int(expected_pairs(model, t)) * 3 * h * model[
                "moe_intermediate_size"]
        else:
            total += t * 3 * h * model["intermediate_size"]
    return total


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """Rotary embedding of ``x`` (batch, time, heads, head_dim), half-rotation
    convention: the second half of a head is the first half's partner."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def route(u, kernel, bias, model):
    """``(chosen, weights)`` of every token: the ids (…, k) of the top-k of
    sigmoid scores plus bias, and their weights, normalised over all k."""
    s = jax.nn.sigmoid(jnp.matmul(u, kernel, precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias),
                              model["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + WEIGHT_EPS)
    return chosen, weights * model["routed_scaling_factor"]


def pairs_held(chosen, model):
    """How many (token, expert) choices fall on each expert held here."""
    return jnp.stack([jnp.sum(chosen == e) for e in model["experts_held"]])


def _dot(q, a, b):
    return q(jnp.matmul(q(a), q(b), precision=HIGHEST))


def conv_operator(params, p, u, model, q):
    bcx = _dot(q, u, params[f"{p}/conv/in_proj/kernel"])
    b, c, x = jnp.split(bcx, 3, axis=-1)
    z = q(b * x)
    taps = params[f"{p}/conv/conv_kernel"]
    length = taps.shape[0]
    padded = jnp.pad(z, ((0, 0), (length - 1, 0), (0, 0)))
    y = sum(taps[j] * padded[:, j:j + z.shape[1]] for j in range(length))
    return _dot(q, q(c * q(y)), params[f"{p}/conv/out_proj/kernel"])


def attention_operator(params, p, u, model, q):
    n, t, _ = u.shape
    heads, kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    eps = model["norm_eps"]
    qh = _dot(q, u, params[f"{p}/attn/q_proj/kernel"]).reshape(n, t, heads, hd)
    kh = _dot(q, u, params[f"{p}/attn/k_proj/kernel"]).reshape(n, t, kv, hd)
    vh = _dot(q, u, params[f"{p}/attn/v_proj/kernel"]).reshape(n, t, kv, hd)
    qh = rotary(rms_norm(qh, params[f"{p}/attn/q_norm/scale"], eps),
                model["rope_theta"])
    kh = rotary(rms_norm(kh, params[f"{p}/attn/k_norm/scale"], eps),
                model["rope_theta"])
    # Each KV head serves heads / kv consecutive query heads.
    kh = jnp.repeat(kh, heads // kv, axis=2)
    vh = jnp.repeat(vh, heads // kv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q(qh), q(kh),
                        precision=HIGHEST) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = q(jnp.einsum("nhqk,nkhd->nqhd", q(probs), q(vh),
                         precision=HIGHEST))
    return _dot(q, mixed.reshape(n, t, heads * hd),
                params[f"{p}/attn/o_proj/kernel"])


def swiglu(u, w1, w3, w2, q):
    return _dot(q, q(jax.nn.silu(_dot(q, u, w1)) * _dot(q, u, w3)), w2)


def expert_ff(params, p, u, model, q):
    """The part of the expert layer's result that the experts held here
    give: every held expert on every token, weighted by that token's weight
    for it (zero where the token did not choose it)."""
    chosen, weights = route(
        u, params[f"{p}/moe/router_kernel"], params[f"{p}/moe/expert_bias"],
        model)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(model["experts_held"]):
        gate = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)
        out = out + gate[..., None] * swiglu(
            u, params[f"{p}/moe/w1"][slot], params[f"{p}/moe/w3"][slot],
            params[f"{p}/moe/w2"][slot], q)
    return out


def block(params, i, h, model, q):
    """One layer: the operator of its kind, then its feed-forward."""
    p, eps = f"layer_{i}", model["norm_eps"]
    u = rms_norm(h, params[f"{p}/operator_norm/scale"], eps)
    op = conv_operator if model["layer_types"][i] == "conv" else (
        attention_operator)
    h = q(h + op(params, p, u, model, q))
    u = rms_norm(h, params[f"{p}/ffn_norm/scale"], eps)
    if _is_moe(model, i):
        ff = expert_ff(params, p, u, model, q)
    else:
        ff = swiglu(u, params[f"{p}/mlp/w1/kernel"],
                    params[f"{p}/mlp/w3/kernel"],
                    params[f"{p}/mlp/w2/kernel"], q)
    return q(h + ff)


def forward(params, x, model, quant=None):
    """Logits (N, T, vocabulary rows held) of token ids ``x`` (N, T).

    ``quant`` (the control of the correctness check) rounds every tensor a
    half-precision program rounds: both operands and the result of every
    matmul, the gated products, every block's output. None is the
    reference. Each block is recomputed in the backward pass
    (``jax.checkpoint``: the same numbers, less memory)."""
    q = quant or (lambda t: t)
    h = q(params["embed/embedding"])[x]
    for i in range(len(model["layer_types"])):
        h = jax.checkpoint(
            lambda p, hh, i=i: block(p, i, hh, model, q))(params, h)
    h = rms_norm(h, params["final_norm/scale"], model["norm_eps"])
    return _dot(q, h, params["embed/embedding"].T)
