"""Plain Laguna (``model_type`` ``laguna``): one chip's share of a layer that
several chips divide by expert parallelism, in straightforward ``jax.numpy``.

Written from the published ``config.json`` of poolside/Laguna-XS.2:
``layer_types`` of sliding-window and full attention with a head count of
each layer's own (``num_attention_heads_per_layer``) and an entry of
``rope_parameters`` by kind, ``gating: true``, a dense SwiGLU in the leading
layer (``mlp_layer_types``), then routed experts with a shared expert beside
them, an untied head. Float32, matmuls at highest precision; imports nothing
of the program under test and nothing of another family's reference.
Parameters are a flat ``{path: array}`` dict whose paths are the names the
program's parameter tree uses: names are structure, not values.

The equations (u the normed input, h the residual stream, no bias anywhere):

- RMSNorm: x * w / sqrt(mean(x^2) + eps). Block i: h += Attn_i(RMSNorm(h));
  h += FF_i(RMSNorm(h)). Logits = RMSNorm_f(h) W_head^T over the vocabulary
  rows held here; the embedding and the head are two leaves.
- Attn_i, H_i = ``num_attention_heads_per_layer[i]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``: q, k, v = u W_q, u W_k,
  u W_v; RMSNorm over each q and k head; rotary embedding (half-rotation
  convention) by the kind's table (`rope_table`); o = softmax(q k^T /
  sqrt(head_dim) + mask) v, each KV head serving H_i / KV adjacent query
  heads; g = sigmoid(u W_g), W_g (hidden, H_i), one number a head and
  position; out = concat_h(g_h o_h) W_o.
  ``sliding_attention``: key j is visible to query i iff 0 <= i - j <
  ``sliding_window``; every dimension of a head rotated, inverse frequencies
  theta^(-2j / head_dim). ``full_attention``: causal; the first
  ``partial_rotary_factor x head_dim`` dimensions of each head are rotated
  among themselves by the YaRN table reckoned over that many dimensions
  (`yarn_table`), cos and sin times ``attention_factor``; the others pass
  unchanged.
- FF_i, i < ``num_dense_layers``: W_2 (silu(u W_1) * (u W_3)).
- FF_i after them: s = sigmoid(u W_r) over all published experts; S = top-k
  of s; w_e = ``moe_routed_scaling_factor`` s_e / sum_{e in S} s_e, the sum
  over all k chosen, held here or not; out = Shared(u) + sum over the chosen
  experts HELD HERE of w_e W_2e (silu(u W_1e) * (u W_3e)); Shared a SwiGLU
  of ``shared_expert_intermediate_size``. What the absent experts would add
  is left out, and the partial result goes on.

Departures from the published model, each also in the configuration's
``assumed``, because the config has no key for them. The per-head q/k
RMSNorm: the convention of the family that spells ``layer_types``,
``mlp_layer_types`` and ``rope_parameters`` by kind the same way. The gate:
``gating: true`` says that there is one; the sibling Laguna-S-2.1 spells it
``per-head``; a sigmoid of a projection of the block's input on the core's
output is the per-head form of arXiv:2505.06708. The router's score: 256
routed experts, 8 a token, one shared and a scaling of 2.5 are the shape of
the family that scores by sigmoid and renormalises the chosen; no bias leaf
and no epsilon (no key). The shared expert ungated and unscaled
(``moe_apply_router_weight_on_input: false`` is published; a gate on the
shared expert has no key). No multi-token-prediction head (no key, none
described). Every expert here is computed for every token and masked by its
weight, which is the same sum written densely.

The (t, t) scores of a layer are written out ``ROW_BLOCK`` query rows at a
time (each under ``jax.checkpoint``): the same numbers, and at 4,096
positions and 64 heads a quarter of 4.3 GB at once.

``model``: ``{"family": "laguna", "hidden_size", "intermediate_size",
"moe_intermediate_size", "shared_expert_intermediate_size",
"num_attention_heads_per_layer", "num_key_value_heads", "head_dim",
"norm_eps", "rope_parameters", "sliding_window", "layer_types",
"num_dense_layers", "num_experts_published", "experts_held",
"num_experts_per_tok", "moe_routed_scaling_factor", "vocab_size",
"seq_len"}``; the configuration's ``init`` group may hold
``"residual_out_scale"`` (`init_scales`).
"""

import math

import jax
import jax.numpy as jnp

# The kind of input this family reads (`inputs/next_tokens.py`).
INPUT = "next-tokens"
HIGHEST = jax.lax.Precision.HIGHEST
# Query rows whose scores are written out at once.
ROW_BLOCK = 1024


def _layers(model):
    """``(i, path, kind, heads, sparse)`` of every layer."""
    return [(i, f"layer_{i}", kind, model["num_attention_heads_per_layer"][i],
             i >= model["num_dense_layers"])
            for i, kind in enumerate(model["layer_types"])]


def param_shapes(model):
    """``{path: shape}`` of every leaf of the parameter tree."""
    h, kv, hd = (model["hidden_size"], model["num_key_value_heads"],
                 model["head_dim"])
    held = len(model["experts_held"])
    width = model["moe_intermediate_size"]
    shapes = {"embed/embedding": (model["vocab_size"], h),
              "lm_head/embedding": (model["vocab_size"], h),
              "final_norm/scale": (h,)}
    for _, p, _, heads, sparse in _layers(model):
        shapes[f"{p}/operator_norm/scale"] = (h,)
        shapes[f"{p}/ffn_norm/scale"] = (h,)
        shapes[f"{p}/attn/q_proj/kernel"] = (h, heads * hd)
        shapes[f"{p}/attn/k_proj/kernel"] = (h, kv * hd)
        shapes[f"{p}/attn/v_proj/kernel"] = (h, kv * hd)
        shapes[f"{p}/attn/g_proj/kernel"] = (h, heads)
        shapes[f"{p}/attn/o_proj/kernel"] = (heads * hd, h)
        shapes[f"{p}/attn/q_norm/scale"] = (hd,)
        shapes[f"{p}/attn/k_norm/scale"] = (hd,)
        if not sparse:
            dense = model["intermediate_size"]
            shapes[f"{p}/mlp/w1/kernel"] = (h, dense)
            shapes[f"{p}/mlp/w3/kernel"] = (h, dense)
            shapes[f"{p}/mlp/w2/kernel"] = (dense, h)
            continue
        shared = model["shared_expert_intermediate_size"]
        shapes[f"{p}/moe/router_kernel"] = (h, model["num_experts_published"])
        shapes[f"{p}/moe/w1"] = (held, h, width)
        shapes[f"{p}/moe/w3"] = (held, h, width)
        shapes[f"{p}/moe/w2"] = (held, width, h)
        shapes[f"{p}/moe/shared/w1/kernel"] = (h, shared)
        shapes[f"{p}/moe/shared/w3/kernel"] = (h, shared)
        shapes[f"{p}/moe/shared/w2/kernel"] = (shared, h)
    return shapes


def leaf_rules(model):
    """The leaves the harness's defaults have no rule for, or get wrong: the
    embedding (unit standard deviation: a stated fan-in of 2 under the
    harness's variance 2 / fan_in; the head is untied, and rows of unit
    entries keep a token's own row the larger part of the residual stream
    beside what the layers add: PERF.md section 6, PR 32); the head, whose
    fan-in is the hidden size (its leaf is rows of the vocabulary by
    hidden); the router; the expert stacks, whose leading axis counts
    experts and is no fan-in."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    rules = {"embed/embedding": ("normal", 2),
             "lm_head/embedding": ("normal", h)}
    for _, p, _, _, sparse in _layers(model):
        if sparse:
            rules[f"{p}/moe/router_kernel"] = ("normal", h)
            rules[f"{p}/moe/w1"] = ("normal", h)
            rules[f"{p}/moe/w3"] = ("normal", h)
            rules[f"{p}/moe/w2"] = ("normal", width)
    return rules


def init_scales(model, init=None):
    """``{path: factor}``: ``residual_out_scale`` on the last matmul of every
    attention, dense, routed and shared feed-forward (what each adds to the
    residual stream)."""
    scale = (init or {}).get("residual_out_scale", 1.0)
    scales = {}
    for _, p, _, _, sparse in _layers(model):
        scales[f"{p}/attn/o_proj/kernel"] = scale
        if sparse:
            scales[f"{p}/moe/w2"] = scale
            scales[f"{p}/moe/shared/w2/kernel"] = scale
        else:
            scales[f"{p}/mlp/w2/kernel"] = scale
    return scales


def expected_pairs(model, tokens):
    """(token, expert) pairs an expert layer here computes for ``tokens``
    tokens if the router chose uniformly: tokens x k x held / published."""
    return (tokens * model["num_experts_per_tok"] * len(model["experts_held"])
            / model["num_experts_published"])


def visible_pairs(kind, t, window):
    """(query, key) pairs a head of a layer of ``kind`` sees at ``t``
    positions: sum_i min(i + 1, window) a sliding head, t^2 / 2 a full one
    (the causal half, as the other token families count it)."""
    if kind == "sliding_attention":
        w = min(window, t)
        return w * (w + 1) // 2 + (t - w) * w
    return t * t // 2


def forward_macs(model):
    """Multiply-adds of one sequence's forward pass: every matmul (gate,
    router, shared expert and dense layer included), the held experts'
    matmuls at the uniform expectation (`expected_pairs`), attention's two
    contractions at each layer's own head count and the visible pairs of its
    kind (`visible_pairs`), the untied head over the vocabulary rows held.
    Norms, rotary embedding, softmax, sigmoids and the loss are not
    counted."""
    t, h = model["seq_len"], model["hidden_size"]
    kv, hd = model["num_key_value_heads"], model["head_dim"]
    total = t * h * model["vocab_size"]
    for _, _, kind, heads, sparse in _layers(model):
        total += t * h * (2 * heads * hd + 2 * kv * hd + heads)
        total += 2 * visible_pairs(kind, t, model["sliding_window"]) * (
            heads * hd)
        if not sparse:
            total += t * 3 * h * model["intermediate_size"]
            continue
        total += t * h * model["num_experts_published"]
        total += t * 3 * h * model["shared_expert_intermediate_size"]
        total += int(expected_pairs(model, t)) * 3 * h * model[
            "moe_intermediate_size"]
    return total


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_table(dim, rope):
    """``(inverse frequencies (dim / 2,), scale, low, high)`` of a
    ``rope_type`` ``yarn`` group over ``dim`` rotated dimensions, as Hugging
    Face's ``_compute_yarn_parameters`` (where ``dim`` is ``head_dim x
    partial_rotary_factor``): extrap_j = theta^(-2j / dim), interp_j =
    extrap_j / factor; c(r) = dim ln(original / (2 pi r)) / (2 ln theta);
    low = floor(c(beta_fast)), high = ceil(c(beta_slow)), clamped to [0,
    dim - 1]; ramp_j = clip((j - low) / (high - low), 0, 1); inv_j =
    interp_j ramp_j + extrap_j (1 - ramp_j). cos and sin are multiplied by
    ``attention_factor``."""
    theta, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    extrap = theta ** -(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def c(rotations):
        return dim * math.log(original / (2 * math.pi * rotations)) / (
            2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extrap / factor * ramp + extrap * (1.0 - ramp),
            rope["attention_factor"], low, high)


def rope_table(head_dim, rope):
    """``(inverse frequencies, scale)`` of one entry of ``rope_parameters``
    (``default`` or ``yarn``) over the ``partial_rotary_factor x head_dim``
    dimensions it rotates: half as many frequencies."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope["rope_type"] == "yarn":
        return yarn_table(dim, rope)[:2]
    if rope["rope_type"] != "default":
        raise ValueError(f"no rotary table of type {rope['rope_type']!r}")
    return rope["rope_theta"] ** -(
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim), 1.0


def rotary(x, inv, scale):
    """Rotary embedding of ``x`` (batch, time, heads, head_dim), half-rotation
    convention over the first 2 x len(inv) dimensions of each head (the
    second half of those is the first half's partner); the dimensions after
    them pass unchanged."""
    t, d = x.shape[1], 2 * inv.shape[0]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = scale * jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = scale * jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    turned, passed = x[..., :d], x[..., d:]
    x1, x2 = turned[..., :d // 2], turned[..., d // 2:]
    return jnp.concatenate(
        [turned * cos + jnp.concatenate([-x2, x1], -1) * sin, passed], -1)


def route(u, kernel, model):
    """``(chosen, weights)`` of every token: the ids (…, k) of the top-k of
    the sigmoid scores over all published experts, and their scores over the
    sum of the k, times ``moe_routed_scaling_factor``."""
    s = jax.nn.sigmoid(jnp.matmul(u, kernel, precision=HIGHEST))
    picked, chosen = jax.lax.top_k(s, model["num_experts_per_tok"])
    return chosen, model["moe_routed_scaling_factor"] * picked / jnp.sum(
        picked, -1, keepdims=True)


def pairs_held(chosen, model):
    """How many (token, expert) choices fall on each expert held here."""
    return jnp.stack([jnp.sum(chosen == e) for e in model["experts_held"]])


def _dot(q, a, b):
    return q(jnp.matmul(q(a), q(b), precision=HIGHEST))


def _rows_attend(qh, kh, vh, first, window, q):
    """Query rows ``first``.. of one block (n, rows, heads, hd) over all keys
    (n, t, heads, hd): the (rows, t) scores written out."""
    rows, t, hd = qh.shape[1], kh.shape[1], qh.shape[-1]
    scores = jnp.einsum("nqhd,nkhd->nhqk", qh, kh,
                        precision=HIGHEST) / math.sqrt(hd)
    gap = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return q(jnp.einsum("nhqk,nkhd->nqhd", q(probs), vh, precision=HIGHEST))


def attention_core(params, p, u, kind, heads, model, q):
    """The heads' outputs (n, t, heads, head_dim) in front of the gate."""
    n, t, _ = u.shape
    kv, hd, eps = (model["num_key_value_heads"], model["head_dim"],
                   model["norm_eps"])
    table = rope_table(hd, model["rope_parameters"][kind])
    window = model["sliding_window"] if kind == "sliding_attention" else None
    qh = _dot(q, u, params[f"{p}/attn/q_proj/kernel"]).reshape(n, t, heads, hd)
    kh = _dot(q, u, params[f"{p}/attn/k_proj/kernel"]).reshape(n, t, kv, hd)
    vh = _dot(q, u, params[f"{p}/attn/v_proj/kernel"]).reshape(n, t, kv, hd)
    qh = q(rotary(rms_norm(qh, params[f"{p}/attn/q_norm/scale"], eps), *table))
    kh = q(rotary(rms_norm(kh, params[f"{p}/attn/k_norm/scale"], eps), *table))
    # Each KV head serves heads / kv consecutive query heads.
    kh = jnp.repeat(kh, heads // kv, axis=2)
    vh = q(jnp.repeat(vh, heads // kv, axis=2))
    return jnp.concatenate([
        jax.checkpoint(
            lambda a, b, c, first=first: _rows_attend(
                a, b, c, first, window, q))(
            qh[:, first:first + ROW_BLOCK], kh, vh)
        for first in range(0, t, ROW_BLOCK)], axis=1)


def attention_operator(params, p, u, kind, heads, model, q):
    n, t, _ = u.shape
    mixed = attention_core(params, p, u, kind, heads, model, q)
    gate = jax.nn.sigmoid(_dot(q, u, params[f"{p}/attn/g_proj/kernel"]))
    mixed = q(mixed * gate[..., None])
    return _dot(q, mixed.reshape(n, t, heads * model["head_dim"]),
                params[f"{p}/attn/o_proj/kernel"])


def swiglu(u, w1, w3, w2, q):
    return _dot(q, q(jax.nn.silu(_dot(q, u, w1)) * _dot(q, u, w3)), w2)


def routed_ff(params, p, u, model, q):
    """The part of the routed experts' result that the experts held here
    give: every held expert on every token, weighted by that token's weight
    for it (zero where the token did not choose it)."""
    chosen, weights = route(u, params[f"{p}/moe/router_kernel"], model)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(model["experts_held"]):
        gate = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)
        out = out + gate[..., None] * swiglu(
            u, params[f"{p}/moe/w1"][slot], params[f"{p}/moe/w3"][slot],
            params[f"{p}/moe/w2"][slot], q)
    return out


def shared_ff(params, p, u, q):
    """The shared expert: every token, whole on every chip."""
    return swiglu(u, *(params[f"{p}/moe/shared/{w}/kernel"]
                       for w in ("w1", "w3", "w2")), q)


def block(params, i, h, model, q):
    """One layer: gated attention of its kind and head count, then the dense
    MLP or the shared expert plus the held experts' part."""
    _, p, kind, heads, sparse = _layers(model)[i]
    eps = model["norm_eps"]
    u = rms_norm(h, params[f"{p}/operator_norm/scale"], eps)
    h = q(h + attention_operator(params, p, u, kind, heads, model, q))
    u = rms_norm(h, params[f"{p}/ffn_norm/scale"], eps)
    if sparse:
        return q(h + shared_ff(params, p, u, q)
                 + routed_ff(params, p, u, model, q))
    return q(h + swiglu(u, *(params[f"{p}/mlp/{w}/kernel"]
                             for w in ("w1", "w3", "w2")), q))


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
    return _dot(q, h, params["lm_head/embedding"].T)
