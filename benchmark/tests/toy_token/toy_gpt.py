"""Test-only plain reference family: a small causal transformer that
classifies from the last position (Radford et al. 2019 blocks, pre-LN):
token embedding plus learned positions, ``depth`` blocks of causal
multi-head attention and a GELU MLP, a final LayerNorm, a dense head on the
last position's state. Float32 ``jax.numpy``; imports nothing of the program.
Paths are the names the program's parameter tree uses.

``model``: ``{"family": "toy_gpt", "vocab", "seq_len", "dim", "depth",
"heads", "mlp_dim", "num_labels"}``.
"""

import math

import jax
import jax.numpy as jnp

INPUT = "toy-tokens"
LN_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def _dense_shapes(path, cin, cout):
    return {f"{path}/kernel": (cin, cout), f"{path}/bias": (cout,)}


def _ln_shapes(path, dim):
    return {f"{path}/scale": (dim,), f"{path}/bias": (dim,)}


def param_shapes(model):
    d, m = model["dim"], model["mlp_dim"]
    shapes = {"Embed_0/embedding": (model["vocab"], d),
              "pos_embedding": (model["seq_len"], d)}
    for i in range(model["depth"]):
        b = f"EncoderBlock_{i}"
        shapes.update(_ln_shapes(f"{b}/LayerNorm_0", d))
        shapes.update(_dense_shapes(f"{b}/Dense_0", d, 3 * d))
        shapes.update(_dense_shapes(f"{b}/Dense_1", d, d))
        shapes.update(_ln_shapes(f"{b}/LayerNorm_1", d))
        shapes.update(_dense_shapes(f"{b}/Dense_2", d, m))
        shapes.update(_dense_shapes(f"{b}/Dense_3", m, d))
    shapes.update(_ln_shapes("LayerNorm_0", d))
    shapes.update(_dense_shapes("Dense_0", d, model["num_labels"]))
    return shapes


def leaf_rules(model):
    """The leaves the harness's defaults have no rule for: both tables are
    normal with standard deviation 1 / sqrt(dim) (a stated fan-in of
    2 x dim under the harness's variance 2 / fan_in)."""
    rule = ("normal", 2 * model["dim"])
    return {"Embed_0/embedding": rule, "pos_embedding": rule}


def init_scales(model, init=None):
    return {}


def forward_macs(model):
    """Multiply-adds of one sequence's forward pass: the dense layers and
    the two attention contractions."""
    t, d, m = model["seq_len"], model["dim"], model["mlp_dim"]
    block = t * (d * 3 * d + d * d + 2 * d * m) + 2 * t * t * d
    return model["depth"] * block + d * model["num_labels"]


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu(x):
    """The tanh form (Hendrycks & Gimpel 2016)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, x, model, quant=None):
    """Logits (N, num_labels) of token ids ``x`` (N, T). ``quant`` (the
    control) rounds the operands and the result of every matmul."""
    q = quant or (lambda t: t)
    heads = model["heads"]

    def dense(path, h):
        out = jnp.matmul(q(h), q(params[f"{path}/kernel"]), precision=HIGHEST)
        return q(out + params[f"{path}/bias"])

    def ln(path, h):
        return _layer_norm(h, params[f"{path}/scale"], params[f"{path}/bias"])

    n, t = x.shape
    h = params["Embed_0/embedding"][x] + params["pos_embedding"][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(model["depth"]):
        b = f"EncoderBlock_{i}"
        qkv = dense(f"{b}/Dense_0", ln(f"{b}/LayerNorm_0", h))
        qh, kh, vh = (a.reshape(n, t, heads, -1)
                      for a in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("nqhd,nkhd->nhqk", q(qh), q(kh),
                            precision=HIGHEST) / math.sqrt(qh.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        mixed = jnp.einsum("nhqk,nkhd->nqhd", q(probs), q(vh),
                           precision=HIGHEST)
        h = h + dense(f"{b}/Dense_1", mixed.reshape(n, t, -1))
        mlp = _gelu(dense(f"{b}/Dense_2", ln(f"{b}/LayerNorm_1", h)))
        h = h + dense(f"{b}/Dense_3", mlp)
    return dense("Dense_0", ln("LayerNorm_0", h)[:, -1])
