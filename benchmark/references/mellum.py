"""Plain Mellum (``model_type`` ``mellum``): one chip's share of a layer that
several chips divide by expert parallelism, in straightforward ``jax.numpy``.

Written from the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct: ``layer_types`` of sliding-window and
full attention, each kind with its own entry of ``rope_parameters``, a
mixture of experts in every layer (``mlp_layer_types`` all ``sparse``), an
untied head. Float32, matmuls at highest precision; imports nothing of the
program under test and nothing of another family's reference. Parameters are
a flat ``{path: array}`` dict whose paths are the names the program's
parameter tree uses: names are structure, not values.

The equations (u the normed input, h the residual stream):

- RMSNorm: x * w / sqrt(mean(x^2) + eps). Block: h += Attn(RMSNorm(h));
  h += MoE(RMSNorm(h)). No bias anywhere.
- Attention, both kinds: q, k, v = u W_q, u W_k, u W_v; RMSNorm over each q
  and k head; rotary embedding (half-rotation convention) by the kind's
  table; softmax(q k^T / sqrt(head_dim) + mask) v, each KV head serving
  heads / kv_heads adjacent query heads; out = concat W_o.
  ``sliding_attention``: key j is visible to query i iff 0 <= i - j <
  ``sliding_window``; inverse frequencies theta^(-2i / head_dim), cos and
  sin as they are. ``full_attention``: causal; the YaRN table
  (`yarn_table`), cos and sin times ``attention_factor``.
- MoE: p = softmax(u W_r) over all published experts; S = top-k of p;
  w_e = p_e / sum_{e in S} p_e; out = sum over the chosen experts HELD HERE
  of w_e W_2e (silu(u W_1e) * (u W_3e)). The sum in w_e runs over all k
  chosen, held here or not; what the absent experts would add is left out,
  and the partial result goes on.
- Logits = RMSNorm_f(h) W_head^T over the vocabulary rows held here; the
  embedding and the head are two leaves.

Departures from the published model, each also in the configuration's
``assumed``: the per-head q/k RMSNorm and the router's law (softmax, then
top-k, renormalised, no epsilon) are the convention of the family whose keys
the config carries (``max_window_layers``, ``use_sliding_window``,
``norm_topk_prob``, ``moe_intermediate_size``); half-rotation rotary; the
window's edge (i - j < ``sliding_window``); the multi-token-prediction head
the model card mentions has no key in the config and is left out; every
expert here is computed for every token and masked by its weight, which is
the same sum written densely.

The (t, t) scores of a layer are written out ``ROW_BLOCK`` query rows at a
time (each under ``jax.checkpoint``): the same numbers, and at 4,096
positions and 32 heads a sixth of 2.1 GB at once.

``model``: ``{"family": "mellum", "hidden_size", "moe_intermediate_size",
"num_attention_heads", "num_key_value_heads", "head_dim", "norm_eps",
"rope_parameters", "sliding_window", "layer_types", "num_dense_layers" (0),
"num_experts_published", "experts_held", "num_experts_per_tok",
"vocab_size", "seq_len"}``; the configuration's ``init`` group may hold
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


def param_shapes(model):
    """``{path: shape}`` of every leaf of the parameter tree."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    kv, hd = model["num_key_value_heads"], model["head_dim"]
    held = len(model["experts_held"])
    width = model["moe_intermediate_size"]
    shapes = {"embed/embedding": (model["vocab_size"], h),
              "lm_head/embedding": (model["vocab_size"], h),
              "final_norm/scale": (h,)}
    for i in range(len(model["layer_types"])):
        p = f"layer_{i}"
        shapes[f"{p}/operator_norm/scale"] = (h,)
        shapes[f"{p}/ffn_norm/scale"] = (h,)
        shapes[f"{p}/attn/q_proj/kernel"] = (h, heads * hd)
        shapes[f"{p}/attn/k_proj/kernel"] = (h, kv * hd)
        shapes[f"{p}/attn/v_proj/kernel"] = (h, kv * hd)
        shapes[f"{p}/attn/o_proj/kernel"] = (heads * hd, h)
        shapes[f"{p}/attn/q_norm/scale"] = (hd,)
        shapes[f"{p}/attn/k_norm/scale"] = (hd,)
        shapes[f"{p}/moe/router_kernel"] = (h, model["num_experts_published"])
        shapes[f"{p}/moe/w1"] = (held, h, width)
        shapes[f"{p}/moe/w3"] = (held, h, width)
        shapes[f"{p}/moe/w2"] = (held, width, h)
    return shapes


def leaf_rules(model):
    """The leaves the harness's defaults have no rule for, or get wrong: the
    embedding (unit standard deviation: a stated fan-in of 2 under the
    harness's variance 2 / fan_in; the head is untied, so nothing asks the
    embedding for logits of unit size, and rows of unit entries keep a
    token's own row the larger part of the residual stream beside what the
    layers add, which at a random start is much the same for every position
    of a sequence: PERF.md section 6, PR 32); the head, whose fan-in is the
    hidden size (its leaf is rows of the vocabulary by hidden); the router;
    the expert stacks, whose leading axis counts experts and is no
    fan-in."""
    h, width = model["hidden_size"], model["moe_intermediate_size"]
    rules = {"embed/embedding": ("normal", 2),
             "lm_head/embedding": ("normal", h)}
    for i in range(len(model["layer_types"])):
        p = f"layer_{i}"
        rules[f"{p}/moe/router_kernel"] = ("normal", h)
        rules[f"{p}/moe/w1"] = ("normal", h)
        rules[f"{p}/moe/w3"] = ("normal", h)
        rules[f"{p}/moe/w2"] = ("normal", width)
    return rules


def init_scales(model, init=None):
    """``{path: factor}``: ``residual_out_scale`` on the last matmul of every
    attention and expert layer (what each adds to the residual stream)."""
    scale = (init or {}).get("residual_out_scale", 1.0)
    scales = {}
    for i in range(len(model["layer_types"])):
        scales[f"layer_{i}/attn/o_proj/kernel"] = scale
        scales[f"layer_{i}/moe/w2"] = scale
    return scales


def expected_pairs(model, tokens):
    """(token, expert) pairs an expert layer here computes for ``tokens``
    tokens if the router chose uniformly: tokens x k x held / published."""
    return (tokens * model["num_experts_per_tok"] * len(model["experts_held"])
            / model["num_experts_published"])


def visible_pairs(kind, t, window):
    """(query, key) pairs a head of a layer of ``kind`` sees at ``t``
    positions: sum_i min(i + 1, window) a sliding head, t^2 / 2 a full one
    (the causal half, as the other token family counts it)."""
    if kind == "sliding_attention":
        w = min(window, t)
        return w * (w + 1) // 2 + (t - w) * w
    return t * t // 2


def forward_macs(model):
    """Multiply-adds of one sequence's forward pass: every matmul (the
    router included), the expert matmuls at the uniform expectation
    (`expected_pairs`), attention's two contractions at the visible pairs of
    each layer's kind (`visible_pairs`), the untied head over the vocabulary
    rows held. Norms, rotary embedding, softmax, gates and the loss are not
    counted."""
    t, h = model["seq_len"], model["hidden_size"]
    heads, kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    total = t * h * model["vocab_size"]
    for kind in model["layer_types"]:
        total += t * (2 * h * heads * hd + 2 * h * kv * hd)
        total += 2 * visible_pairs(kind, t, model["sliding_window"]) * (
            heads * hd)
        total += t * h * model["num_experts_published"]
        total += int(expected_pairs(model, t)) * 3 * h * model[
            "moe_intermediate_size"]
    return total


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_table(head_dim, rope):
    """``(inverse frequencies, scale, low, high)`` of a ``rope_type``
    ``yarn`` group, as Hugging Face's ``_compute_yarn_parameters``:
    extrap_i = theta^(-2i / head_dim), interp_i = extrap_i / factor;
    c(r) = head_dim ln(original / (2 pi r)) / (2 ln theta); low =
    floor(c(beta_fast)), high = ceil(c(beta_slow)), clamped to [0,
    head_dim - 1]; ramp_i = clip((i - low) / (high - low), 0, 1); inv_i =
    interp_i ramp_i + extrap_i (1 - ramp_i). cos and sin are multiplied by
    ``attention_factor``."""
    theta, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    extrap = theta ** -(
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)

    def c(rotations):
        return head_dim * math.log(original / (2 * math.pi * rotations)) / (
            2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), head_dim - 1)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extrap / factor * ramp + extrap * (1.0 - ramp),
            rope["attention_factor"], low, high)


def rope_table(head_dim, rope):
    """``(inverse frequencies (head_dim / 2,), scale)`` of one entry of
    ``rope_parameters``: ``default`` or ``yarn``."""
    if rope["rope_type"] == "yarn":
        return yarn_table(head_dim, rope)[:2]
    if rope["rope_type"] != "default":
        raise ValueError(f"no rotary table of type {rope['rope_type']!r}")
    return rope["rope_theta"] ** -(
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim), 1.0


def rotary(x, inv, scale):
    """Rotary embedding of ``x`` (batch, time, heads, head_dim), half-rotation
    convention: the second half of a head is the first half's partner."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = scale * jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = scale * jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def route(u, kernel, model):
    """``(chosen, weights)`` of every token: the ids (…, k) of the top-k of
    the softmax over all published experts, and their probabilities over
    the sum of the k."""
    p = jax.nn.softmax(jnp.matmul(u, kernel, precision=HIGHEST), axis=-1)
    picked, chosen = jax.lax.top_k(p, model["num_experts_per_tok"])
    return chosen, picked / jnp.sum(picked, -1, keepdims=True)


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


def attention_operator(params, p, u, kind, model, q):
    n, t, _ = u.shape
    heads, kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    eps = model["norm_eps"]
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
    mixed = jnp.concatenate([
        jax.checkpoint(
            lambda a, b, c, first=first: _rows_attend(
                a, b, c, first, window, q))(
            qh[:, first:first + ROW_BLOCK], kh, vh)
        for first in range(0, t, ROW_BLOCK)], axis=1)
    return _dot(q, mixed.reshape(n, t, heads * hd),
                params[f"{p}/attn/o_proj/kernel"])


def swiglu(u, w1, w3, w2, q):
    return _dot(q, q(jax.nn.silu(_dot(q, u, w1)) * _dot(q, u, w3)), w2)


def expert_ff(params, p, u, model, q):
    """The part of the expert layer's result that the experts held here
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


def block(params, i, h, model, q):
    """One layer: attention of its kind, then the expert layer."""
    p, eps = f"layer_{i}", model["norm_eps"]
    u = rms_norm(h, params[f"{p}/operator_norm/scale"], eps)
    h = q(h + attention_operator(
        params, p, u, model["layer_types"][i], model, q))
    u = rms_norm(h, params[f"{p}/ffn_norm/scale"], eps)
    return q(h + expert_ff(params, p, u, model, q))


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
