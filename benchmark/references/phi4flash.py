"""Plain SambaY (``model_type`` ``phi4flash``): one pipeline stage of
Phi-4-mini-flash-reasoning, in straightforward ``jax.numpy``.

Written from the published ``config.json`` of
microsoft/Phi-4-mini-flash-reasoning and the papers it names: SambaY (Ren et
al., arXiv:2507.06607), DIFF Transformer (Ye et al., arXiv:2410.05258), Mamba
(Gu & Dao, arXiv:2312.00752). Float32, matmuls at highest precision; imports
nothing of the program under test and nothing of another family's reference.
Parameters are a flat ``{path: array}`` dict whose paths are the names the
program's parameter tree uses: names are structure, not values.

The equations (u the normed input, h the residual stream):

- LayerNorm: (x - mean) / sqrt(var + eps) * w + b. Block l: h += Mixer_l(
  LN1(h)); h += W_down (silu(v W_gate) * (v W_up)), v = LN2(h), no bias.
  Logits = LN_f(h) E^T over the vocabulary rows held, E the embedding (tied).
- ``mamba``: [x, z] = u W_in; x~ = silu(conv(x) + b_conv), depthwise and
  causal over ``mamba_d_conv`` taps; [d, B, C] = x~ W_x; Δ = softplus(d
  W_dt + b_dt); A = -exp(A_log); s_t = exp(Δ_t A) s_{t-1} + (Δ_t x~_t) ⊗
  B_t; y_t = s_t C_t + D x~_t; the memory m = y * silu(z); out = m W_out.
- ``sliding_attention``, ``full_attention`` (differential attention at
  published layer l): q = u W_q viewed (H, 2, head_dim), H = heads / 2; k =
  u W_k viewed (P, 2, head_dim), P = kv_heads / 2; v = u W_v viewed (P, 2 x
  head_dim); head i reads pair i P / H; A_j = softmax(q_j k_j^T /
  sqrt(head_dim) + mask); o_i = RMSNorm_{2 head_dim}((A_1 - λ A_2) v) w
  (1 - λ_init); out = concat(o) W_o; λ = exp(λ_q1 . λ_k1) - exp(λ_q2 .
  λ_k2) + λ_init, λ_init = 0.8 - 0.6 exp(-0.3 l). Sliding: key j visible to
  query i iff 0 <= i - j < ``sliding_window``; full: causal, and its k, v
  are the later cross layers'.
- ``gmu``: out = (silu(u W_1) * m) W_2, m the last Mamba layer's memory.
- ``cross_attention``: the differential attention above with W_q and W_o of
  its own over the last full layer's k and v, causal.

Departures from the published model, each also in the configuration's
``assumed``, because the model's modeling file is not at hand and the config
has no key for them: Mamba's own defaults for the mixer (d_state 16, d_conv
4, expand 2, dt_rank ceil(2,560 / 16) = 160, a bias on the convolution and
on dt, none on the in and out projections); the layer layout (Mamba at even
indices and window attention at odd ones up to layer 16, full attention at
17, then GMU at even and cross attention at odd indices: ``mb_per_layer`` 2
and SambaY's description); DIFF Transformer's pairing of q and k heads, its
λ_init by layer and its per-head RMSNorm with a learnt scale; differential
attention on the cross layers too; the memory taken as y * silu(z) in front
of W_out; no positional encoding (NoPE, as SambaY describes its
self-decoder); the window's edge, a query sees 512 keys itself included.

A layer's (t, t) scores are written out ``ROW_BLOCK`` query rows at a time,
and the scan runs over the positions in segments of ``SCAN_SEGMENT``, each
under ``jax.checkpoint``: the same numbers, in memory that fits.

``model``: ``{"family": "phi4flash", "hidden_size", "intermediate_size",
"num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
"layer_types", "layer_indices", "mamba_d_state", "mamba_d_conv",
"mamba_expand", "mamba_dt_rank", "norm_eps", "vocab_size", "seq_len"}``;
the configuration's ``init`` group may hold ``"residual_out_scale"``
(`init_scales`).
"""

import math

import jax
import jax.numpy as jnp

# The kind of input this family reads (`inputs/next_tokens.py`).
INPUT = "next-tokens"
HIGHEST = jax.lax.Precision.HIGHEST
ATTENTION = ("sliding_attention", "full_attention", "cross_attention")
# Query rows whose scores are written out at once; positions a scan segment.
ROW_BLOCK = 1024
SCAN_SEGMENT = 256


def _inner(model):
    return model["mamba_expand"] * model["hidden_size"]


def param_shapes(model):
    """``{path: shape}`` of every leaf of the parameter tree."""
    h, hd = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    inner, state = _inner(model), model["mamba_d_state"]
    rank, dense = model["mamba_dt_rank"], model["intermediate_size"]
    shapes = {"embed/embedding": (model["vocab_size"], h),
              "final_norm/scale": (h,), "final_norm/bias": (h,)}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        for norm in ("operator_norm", "ffn_norm"):
            shapes[f"{p}/{norm}/scale"] = (h,)
            shapes[f"{p}/{norm}/bias"] = (h,)
        shapes[f"{p}/mlp/w1/kernel"] = (h, dense)
        shapes[f"{p}/mlp/w3/kernel"] = (h, dense)
        shapes[f"{p}/mlp/w2/kernel"] = (dense, h)
        if kind == "mamba":
            m = f"{p}/mamba"
            shapes.update({
                f"{m}/in_proj/kernel": (h, 2 * inner),
                f"{m}/conv/kernel": (model["mamba_d_conv"], inner),
                f"{m}/conv/bias": (inner,),
                f"{m}/x_proj/kernel": (inner, rank + 2 * state),
                f"{m}/dt_proj/kernel": (rank, inner),
                f"{m}/dt_proj/bias": (inner,),
                f"{m}/A_log": (inner, state), f"{m}/D": (inner,),
                f"{m}/out_proj/kernel": (inner, h)})
        elif kind == "gmu":
            shapes[f"{p}/gmu/in_proj/kernel"] = (h, inner)
            shapes[f"{p}/gmu/out_proj/kernel"] = (inner, h)
        elif kind in ATTENTION:
            a = f"{p}/attn"
            shapes[f"{a}/q_proj/kernel"] = (h, heads * hd)
            if kind != "cross_attention":
                shapes[f"{a}/k_proj/kernel"] = (h, kv * hd)
                shapes[f"{a}/v_proj/kernel"] = (h, kv * hd)
            shapes[f"{a}/o_proj/kernel"] = (heads * hd, h)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                shapes[f"{a}/{name}"] = (hd,)
            shapes[f"{a}/subln/scale"] = (2 * hd,)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
    return shapes


def leaf_rules(model):
    """The leaves the harness's defaults have no rule for, or get wrong: the
    embedding (standard deviation 1 / sqrt(hidden): a stated fan-in of 2 x
    hidden under the harness's variance 2 / fan_in, so that the tied head's
    logits start near unit size); A_log (unit standard deviation: the
    channels' decay rates spread over e^-2..e^2); D (ones, Mamba's start);
    the λ vectors (standard deviation 0.1, DIFF Transformer's start)."""
    rules = {"embed/embedding": ("normal", 2 * model["hidden_size"])}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        if kind == "mamba":
            rules[f"{p}/mamba/A_log"] = ("normal", 2)
            rules[f"{p}/mamba/D"] = ("ones",)
        elif kind in ATTENTION:
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                rules[f"{p}/attn/{name}"] = ("normal", 200)
    return rules


def init_scales(model, init=None):
    """``{path: factor}``: ``residual_out_scale`` on the last matmul of every
    mixer and dense MLP (what each adds to the residual stream)."""
    scale = (init or {}).get("residual_out_scale", 1.0)
    last = {"mamba": "mamba/out_proj", "gmu": "gmu/out_proj"}
    scales = {}
    for i, kind in enumerate(model["layer_types"]):
        p = f"layer_{i}"
        scales[f"{p}/{last.get(kind, 'attn/o_proj')}/kernel"] = scale
        scales[f"{p}/mlp/w2/kernel"] = scale
    return scales


def visible_pairs(kind, t, window):
    """(query, key) pairs a head of an attention layer of ``kind`` sees at
    ``t`` positions: sum_i min(i + 1, window) a sliding head, t^2 / 2 a full
    or cross one (the causal half, as the other token families count it)."""
    if kind == "sliding_attention":
        w = min(window, t)
        return w * (w + 1) // 2 + (t - w) * w
    return t * t // 2


def forward_macs(model):
    """Multiply-adds of one sequence's forward pass: every matmul (the
    convolution's taps included), the attention cores at the equations'
    count — a visible pair costs heads x head_dim for the two softmaxes'
    scores and heads / 2 x 2 head_dim for (A_1 - λ A_2) v —, the tied head
    over the vocabulary rows held. Norms, the scan, softmax, λ, gates and
    the loss are not counted."""
    t, h = model["seq_len"], model["hidden_size"]
    heads, kv, hd = (model["num_attention_heads"],
                     model["num_key_value_heads"], model["head_dim"])
    inner, state = _inner(model), model["mamba_d_state"]
    rank, dense = model["mamba_dt_rank"], model["intermediate_size"]
    total = t * h * model["vocab_size"]
    for kind in model["layer_types"]:
        total += t * 3 * h * dense
        if kind == "mamba":
            total += t * (h * 2 * inner + model["mamba_d_conv"] * inner
                          + inner * (rank + 2 * state) + rank * inner
                          + inner * h)
        elif kind == "gmu":
            total += t * 2 * h * inner
        else:
            own = 0 if kind == "cross_attention" else 2 * kv * hd
            total += t * h * (2 * heads * hd + own)
            total += visible_pairs(kind, t, model["sliding_window"]) * (
                heads * hd + heads // 2 * 2 * hd)
    return total


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _dot(q, a, b):
    return q(jnp.matmul(q(a), q(b), precision=HIGHEST))


def selective_scan(x, delta, A, B, C):
    """y_t = s_t C_t of the recurrence s_t = exp(Δ_t A) s_{t-1} + (Δ_t x_t)
    ⊗ B_t from s_0 = 0: a ``lax.scan`` over the positions, the state (n,
    channels, state); segments of ``SCAN_SEGMENT`` positions under
    ``jax.checkpoint`` (the whole sequence where t is no multiple)."""
    n, t, channels = x.shape
    segment = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    def position(s, inputs):
        x_t, d_t, b_t, c_t = inputs
        s = (jnp.exp(d_t[..., None] * A) * s
             + (d_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def run(s, inputs):
        return jax.lax.scan(position, s, inputs)

    inputs = tuple(
        jnp.moveaxis(v, 1, 0).reshape(t // segment, segment, n, -1)
        for v in (x, delta, B, C))
    s0 = jnp.zeros((n, channels, A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(run, s0, inputs)
    return jnp.moveaxis(y.reshape(t, n, channels), 0, 1)


def mamba(params, p, u, model, q):
    """``(out, memory)`` of a Mamba layer."""
    m, t = f"{p}/mamba", u.shape[1]
    state, rank = model["mamba_d_state"], model["mamba_dt_rank"]
    x, z = jnp.split(_dot(q, u, params[f"{m}/in_proj/kernel"]), 2, axis=-1)
    taps = params[f"{m}/conv/kernel"]
    padded = jnp.pad(x, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    x = q(jax.nn.silu(params[f"{m}/conv/bias"] + sum(
        taps[j] * padded[:, j:j + t] for j in range(taps.shape[0]))))
    dbc = _dot(q, x, params[f"{m}/x_proj/kernel"])
    d, b, c = dbc[..., :rank], dbc[..., rank:rank + state], dbc[
        ..., rank + state:]
    delta = jax.nn.softplus(q(jnp.matmul(
        q(d), q(params[f"{m}/dt_proj/kernel"]), precision=HIGHEST)
        + params[f"{m}/dt_proj/bias"]))
    y = q(selective_scan(x, delta, -jnp.exp(params[f"{m}/A_log"]), b, c)
          + params[f"{m}/D"] * x)
    memory = q(y * jax.nn.silu(z))
    return _dot(q, memory, params[f"{m}/out_proj/kernel"]), memory


def lambda_of(params, a, layer):
    """``(λ, λ_init)`` of a differential layer at published index
    ``layer``."""
    start = 0.8 - 0.6 * math.exp(-0.3 * layer)
    v = {n: params[f"{a}/{n}"] for n in (
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    lam = (jnp.exp(jnp.sum(v["lambda_q1"] * v["lambda_k1"]))
           - jnp.exp(jnp.sum(v["lambda_q2"] * v["lambda_k2"])) + start)
    return lam, start


def _rows_attend(qh, kh, vh, first, window, lam, q):
    """Query rows ``first``.. (n, rows, H, 2, hd) of both softmaxes over all
    keys (n, t, H, 2, hd) and values (n, t, H, 2 hd): A_1 v - λ A_2 v."""
    rows, t, hd = qh.shape[1], kh.shape[1], qh.shape[-1]
    gap = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    mixed = []
    for j in (0, 1):
        scores = jnp.einsum("nqhd,nkhd->nhqk", qh[..., j, :], kh[..., j, :],
                            precision=HIGHEST) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        mixed.append(q(jnp.einsum("nhqk,nkhe->nqhe", q(probs), vh,
                                  precision=HIGHEST)))
    return mixed[0] - lam * mixed[1]


def diff_attention(params, p, u, kind, layer, model, kv, q):
    """``(out, (k, v))`` of a differential attention layer; a cross layer
    reads ``kv``."""
    n, t, _ = u.shape
    a, hd = f"{p}/attn", model["head_dim"]
    heads = model["num_attention_heads"] // 2
    pairs = model["num_key_value_heads"] // 2
    qh = _dot(q, u, params[f"{a}/q_proj/kernel"]).reshape(n, t, heads, 2, hd)
    if kind == "cross_attention":
        kh, vh = kv
    else:
        kh = _dot(q, u, params[f"{a}/k_proj/kernel"]).reshape(
            n, t, pairs, 2, hd)
        vh = _dot(q, u, params[f"{a}/v_proj/kernel"]).reshape(
            n, t, pairs, 2 * hd)
    lam, start = lambda_of(params, a, layer)
    window = model["sliding_window"] if kind == "sliding_attention" else None
    # Head i reads pair i x pairs / heads: each pair serves heads / pairs
    # consecutive heads.
    k_all = jnp.repeat(kh, heads // pairs, axis=2)
    v_all = jnp.repeat(vh, heads // pairs, axis=2)
    mixed = jnp.concatenate([
        jax.checkpoint(
            lambda a_, b_, c_, first=first: _rows_attend(
                a_, b_, c_, first, window, lam, q))(
            qh[:, first:first + ROW_BLOCK], k_all, v_all)
        for first in range(0, t, ROW_BLOCK)], axis=1)
    mixed = q(rms_norm(mixed, params[f"{a}/subln/scale"], model["norm_eps"])
              * (1 - start))
    out = _dot(q, mixed.reshape(n, t, heads * 2 * hd),
               params[f"{a}/o_proj/kernel"])
    return out, (kh, vh)


def swiglu(u, w1, w3, w2, q):
    return _dot(q, q(jax.nn.silu(_dot(q, u, w1)) * _dot(q, u, w3)), w2)


def block(params, i, h, memory, kv, model, q):
    """One layer: its mixer, then the dense MLP; (h, memory, kv) in and
    out."""
    p, eps = f"layer_{i}", model["norm_eps"]
    kind, layer = model["layer_types"][i], model["layer_indices"][i]

    def norm(x, name):
        return q(layer_norm(x, params[f"{p}/{name}/scale"],
                            params[f"{p}/{name}/bias"], eps))

    u = norm(h, "operator_norm")
    if kind == "mamba":
        out, memory = mamba(params, p, u, model, q)
    elif kind == "gmu":
        gate = _dot(q, u, params[f"{p}/gmu/in_proj/kernel"])
        out = _dot(q, q(jax.nn.silu(gate) * memory),
                   params[f"{p}/gmu/out_proj/kernel"])
    else:
        out, made = diff_attention(params, p, u, kind, layer, model, kv, q)
        if kind == "full_attention":
            kv = made
    h = q(h + out)
    h = q(h + swiglu(norm(h, "ffn_norm"), *(
        params[f"{p}/mlp/{w}/kernel"] for w in ("w1", "w3", "w2")), q))
    return h, memory, kv


def hidden(params, h, model, quant=None):
    """The blocks on the residual stream ``h`` (N, T, hidden): the last h."""
    q = quant or (lambda t: t)
    memory = kv = None
    for i in range(len(model["layer_types"])):
        h, memory, kv = jax.checkpoint(
            lambda p, hh, mm, kk, i=i: block(p, i, hh, mm, kk, model, q))(
            params, h, memory, kv)
    return h


def forward(params, x, model, quant=None):
    """Logits (N, T, vocabulary rows held) of token ids ``x`` (N, T).

    ``quant`` (the control of the correctness check) rounds every tensor a
    half-precision program rounds: both operands and the result of every
    matmul, the convolution's and the scan's results, the gated products,
    the attention's probabilities and outputs, every block's output. None
    is the reference. Each block is recomputed in the backward pass
    (``jax.checkpoint``: the same numbers, less memory)."""
    q = quant or (lambda t: t)
    table = params["embed/embedding"]
    h = hidden(params, q(table)[x], model, quant)
    h = layer_norm(h, params["final_norm/scale"], params["final_norm/bias"],
                   model["norm_eps"])
    return _dot(q, h, table.T)
