"""The plain reference of a training cell: the first steps, written out.

For each worker the model family's plain forward pass under ``jax.grad`` on
that worker's own rows (its own BatchNorm statistics), the attack written out
as rows, the rule in plain ``jax.numpy``, the optimizer by hand (`references/optimizers`) — float32 throughout, matmuls at highest precision, one
worker at a time so that it fits. Imports nothing of the program and reads
nothing the program made: weights and batches come from the seed.

`quant` computes the same in a lower precision (the control of the
correctness check); `rows` keeps part of each worker's batch (the planted
half-batch fault).
"""

import functools
import json

import jax
import jax.numpy as jnp

import references

from . import weights

STEPS = 3


def _round(x, exponent_bits, mantissa_bits, top):
    """``x`` rounded to an 8-bit float, one scale per tensor: the largest
    magnitude goes to ``top``, the format's largest number, and the rest is
    rounded to nearest even at that format's exponent and mantissa bits
    (``lax.reduce_precision``: arithmetic the compiler keeps on every
    backend; a cast to an fp8 type and back is removed by XLA on the TPU,
    or, where it stays, overflows on a quotient one ulp over ``top``)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    kept = jax.lax.reduce_precision(
        jnp.clip(x / scale, -top, top), exponent_bits, mantissa_bits)
    return kept * scale


@jax.custom_vjp
def fp8(x):
    """What an fp8 pipeline keeps of a tensor: e4m3 forward (IEEE-style,
    largest number 240), e5m2 for the cotangent coming back (largest 57344;
    the usual fp8 training recipe), one scale per tensor each way. The
    control's forward pass applies it wherever the configuration's bf16
    program rounds to bf16: operands and results of every conv and of the
    dense head, BatchNorm outputs, block outputs — so every activation kept
    for the backward pass, every cotangent and every gradient on its way to
    the rule is an 8-bit float."""
    return _round(x, 4, 3, 240.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, ct: (_round(ct, 5, 2, 57344.0),))

QUANT = {"none": None, "fp8": fp8}


@functools.lru_cache(maxsize=None)
def _step(cfg_json, traffic_json, quant, rows):
    """One jitted training step of the reference: one trace per (cell,
    precision, rows kept) however many seeds a process follows. Workers run
    one after another (``lax.map``), so one worker's activations are live at
    a time."""
    cfg, traffic = json.loads(cfg_json), json.loads(traffic_json)
    model, opt = cfg["model"], cfg["optimizer"]
    family = references.family(model["family"])
    loss_fn = references.loss(cfg["loss"])
    optimizer = references.optimizer(opt["name"])
    attack = references.attack(traffic["attack"])
    rule = references.rule(traffic["rule"])
    n, f = cfg["num_workers"], cfg["f"]
    byz = attack.byzantine(n, f)
    honest = jnp.asarray([not b for b in byz], jnp.float32)

    def worker(params, xy):
        x, y = xy
        if rows is not None:
            x, y = x[:rows], y[:rows]

        def loss_of(p):
            return loss_fn(family.forward(p, x, model, QUANT[quant]), y)
        return jax.value_and_grad(loss_of)(params)

    @jax.jit
    def step(params, slots, x, y):
        losses, grads = jax.lax.map(
            functools.partial(worker, params), (x, y))
        stack = {p: g.reshape(n, -1) for p, g in grads.items()}
        agg = rule.aggregate(attack.apply(stack, byz), f)
        agg = {p: g.reshape(params[p].shape) for p, g in agg.items()}
        norms = {p: jnp.linalg.norm(g) for p, g in agg.items()}
        params, slots = optimizer.update(params, slots, agg, opt)
        loss = jnp.sum(losses * honest) / jnp.sum(honest)
        return params, slots, loss, norms

    return step


def run(cfg, traffic, seed, *, steps=STEPS, quant="none", rows=None):
    """Follow `steps` training steps. Returns ``{"loss": [per step],
    "grad1": {path: norm of the first aggregated gradient},
    "dparam": {path: norm of the parameters' change after the steps}}``."""
    model = cfg["model"]
    family = references.family(model["family"])
    key = weights.seed_key(seed)
    start = weights.make_params(
        key, family.param_shapes(model), family.init_scales(model, cfg.get("init")))
    xs, ys = jax.jit(
        lambda k: weights.make_batches(
            k, cfg["num_workers"], cfg["batch_per_worker"], model["image"],
            model["num_classes"])
    )(key)
    step = _step(json.dumps(cfg, sort_keys=True),
                 json.dumps(traffic, sort_keys=True), quant, rows)
    params = start
    slots = references.optimizer(cfg["optimizer"]["name"]).init(params)
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            b = i % xs.shape[0]
            params, slots, loss, norms = step(params, slots, xs[b], ys[b])
            losses.append(loss)
            if i == 0:
                grad1 = norms
    dparam = {p: jnp.linalg.norm(params[p] - start[p]) for p in params}
    losses, grad1, dparam = jax.device_get((losses, grad1, dparam))
    return {
        "loss": [float(l) for l in losses],
        "grad1": {p: float(v) for p, v in grad1.items()},
        "dparam": {p: float(v) for p, v in dparam.items()},
    }
