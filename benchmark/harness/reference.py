"""The plain reference of a training cell: the first steps, written out.

For each worker the model family's plain forward pass under ``jax.grad`` on
that worker's own rows (its own BatchNorm statistics), the attack written out
as rows, the rule in plain ``jax.numpy``, the optimizer by hand
(`references/optimizers`) — float32 throughout, matmuls at highest precision,
one worker at a time so that it fits. Imports nothing of the program and
reads nothing the program made: weights and batches come from the seed.

The workers' gradients are a float32 stack of n rows. Where that fits the
device beside the rest (`stack_on_device`) the whole step is one program
there; where it does not, each worker's gradient is fetched to the host as
it comes and the host only keeps them: an attack and a rule that both say
they work leaf by leaf (``LEAFWISE``) get one leaf's rows at a time back on
the device; any other gets the whole stack on the host's CPU device.

`quant` computes the same in a lower precision (the control of the
correctness check); `rows` keeps part of each worker's batch (the planted
half-batch fault).
"""

import functools
import json
import math
import sys
import time
import types

import jax
import jax.numpy as jnp

import inputs
import references

from . import weights

STEPS = 3
# What the one-program path keeps on the device for each parameter, beside
# one worker's activations: three float32 stacks of n rows (the workers'
# gradients as ``lax.map`` returns them, the attacked rows, the rule's sorted
# copy) and six arrays of the parameters' size (parameters, momentum, the
# start, the aggregate, the new parameters and the new momentum).
STACKS, PARAM_SIZED = 3, 6
# The share of the device's ``bytes_limit`` those may take; the rest is left
# to a worker's activations.
DEVICE_SHARE = 0.5


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


def resident_bytes(n, d):
    """Bytes the one-program path keeps on the device at n workers and d
    parameters, activations aside."""
    return 4 * (STACKS * n + PARAM_SIZED) * d


def device_budget():
    """``DEVICE_SHARE`` of the first device's ``bytes_limit``; None where the
    backend reports none (XLA:CPU), which is no limit."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return None if not limit else DEVICE_SHARE * limit


def stack_on_device(n, d, budget):
    """Whether the stack of n float32 gradients of d parameters stays on the
    device (one program a step) or goes to the host; says which, and why, on
    standard error (`_host_step` says what becomes of it there)."""
    need = resident_bytes(n, d)
    fits = budget is None or need <= budget
    room = "no limit" if budget is None else f"{budget / 1e9:.2f} GB"
    print(f"reference: n={n} d={d} keeps {need / 1e9:.2f} GB beside the "
          f"activations, budget {room}: the stack "
          f"{'stays on the device' if fits else 'goes to the host'}",
          file=sys.stderr)
    return fits


@functools.lru_cache(maxsize=None)
def _parts(cfg_json, traffic_json, quant, rows):
    """The pieces of one training step of the reference, one trace per
    (cell, precision, rows kept) however many seeds a process follows:
    ``worker(params, (x, y))`` — one worker's loss and gradient;
    ``aggregate(stack)`` — attack, rule, the aggregate in the parameters'
    shapes and its norms, of the leaves ``stack`` holds;
    ``update(params, slots, agg)``; ``loss(losses)`` — the honest workers'
    mean; with them ``n``, the number of workers, and ``leafwise``: whether
    attack and rule both say that a leaf's result needs that leaf alone."""
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
    shapes = family.param_shapes(model)

    def worker(params, xy):
        x, y = xy
        if rows is not None:
            x, y = x[:rows], y[:rows]

        def loss_of(p):
            return loss_fn(family.forward(p, x, model, QUANT[quant]), y)
        return jax.value_and_grad(loss_of)(params)

    def aggregate(stack):
        agg = rule.aggregate(attack.apply(stack, byz), f)
        agg = {p: g.reshape(shapes[p]) for p, g in agg.items()}
        return agg, {p: jnp.linalg.norm(g) for p, g in agg.items()}

    def update(params, slots, agg):
        return optimizer.update(params, slots, agg, opt)

    def loss(losses):
        return jnp.sum(losses * honest) / jnp.sum(honest)

    leafwise = all(getattr(m, "LEAFWISE", False) for m in (attack, rule))
    return types.SimpleNamespace(
        n=n, leafwise=leafwise, worker=worker, aggregate=aggregate,
        update=update, loss=loss)


@functools.lru_cache(maxsize=None)
def _step(cfg_json, traffic_json, quant, rows):
    """One jitted training step with the stack on the device. Workers run
    one after another (``lax.map``), so one worker's activations are live at
    a time."""
    parts = _parts(cfg_json, traffic_json, quant, rows)

    @jax.jit
    def step(params, slots, x, y):
        losses, grads = jax.lax.map(
            functools.partial(parts.worker, params), (x, y))
        agg, norms = parts.aggregate(
            {p: g.reshape(parts.n, -1) for p, g in grads.items()})
        params, slots = parts.update(params, slots, agg)
        return params, slots, parts.loss(losses), norms

    return step


@functools.lru_cache(maxsize=None)
def _host_step(cfg_json, traffic_json, quant, rows):
    """The same step with the stack on the host: one worker at a time on the
    device, each gradient fetched as it comes (the device holds one). Then,
    where attack and rule work leaf by leaf, one leaf's n rows at a time go
    back to the device for both (one small program per leaf); where either
    takes the whole stack, both run once on the host's CPU device and the
    aggregate goes to the device. The update is the device's."""
    parts = _parts(cfg_json, traffic_json, quant, rows)
    print("reference: attack and rule "
          + ("leaf by leaf on the device" if parts.leafwise else
             "on the whole stack, on the host's CPU device"), file=sys.stderr)
    one_worker = jax.jit(lambda params, x, y: parts.worker(params, (x, y)))
    update, loss = jax.jit(parts.update), jax.jit(parts.loss)

    def stacked(rows_of):
        return parts.aggregate({
            p: jnp.stack([r.reshape(-1) for r in rows])
            for p, rows in rows_of.items()})

    one_leaf = jax.jit(lambda rows, path: stacked({path: rows}),
                       static_argnames="path")
    whole = jax.jit(stacked)

    def step(params, slots, x, y):
        t0 = time.perf_counter()
        losses, grads = [], []
        for w in range(parts.n):
            l, g = one_worker(params, x[w], y[w])
            losses.append(l)
            grads.append(jax.device_get(g))
            del g
        rows_of = {p: [g.pop(p) for g in grads] for p in list(grads[0])}
        t1 = time.perf_counter()
        if parts.leafwise:
            agg, norms = {}, {}
            for path in list(rows_of):
                # One leaf's rows on the device at a time: wait for each, or
                # the dispatches run ahead and every leaf's rows are there.
                a, nrm = jax.block_until_ready(
                    one_leaf(rows_of.pop(path), path=path))
                agg.update(a)
                norms.update(nrm)
        else:
            # Committed to the host's device, so the program runs there.
            agg, norms = whole(
                jax.device_put(rows_of, jax.devices("cpu")[0]))
            del rows_of
            agg = jax.device_put(agg, jax.devices()[0])
        t2 = time.perf_counter()
        params, slots = jax.block_until_ready(update(params, slots, agg))
        print(f"reference: a step with the stack on the host: gradients "
              f"{t1 - t0:.1f} s, attack and rule {t2 - t1:.1f} s, update "
              f"{time.perf_counter() - t2:.1f} s", file=sys.stderr)
        return params, slots, loss(jnp.stack(losses)), norms

    return step


def run(cfg, traffic, seed, *, steps=STEPS, quant="none", rows=None,
        budget=None):
    """Follow `steps` training steps. Returns ``{"loss": [per step],
    "grad1": {path: norm of the first aggregated gradient},
    "dparam": {path: norm of the parameters' change after the steps}}``.
    ``budget`` (bytes) takes the place of `device_budget` (a test forces
    the stack to the host with 0)."""
    model = cfg["model"]
    family = references.family(model["family"])
    key = weights.seed_key(seed)
    shapes = family.param_shapes(model)
    start = weights.make_params(
        key, shapes, *weights.stated(family, model, cfg.get("init")))
    n = cfg["num_workers"]
    xs, ys = jax.jit(
        lambda k: inputs.kind(family.INPUT).batches(
            k, model, n, cfg["batch_per_worker"], weights.NUM_BATCHES)
    )(key)
    d = sum(math.prod(shape) for shape in shapes.values())
    on_device = stack_on_device(
        n, d, device_budget() if budget is None else budget)
    step = (_step if on_device else _host_step)(
        json.dumps(cfg, sort_keys=True), json.dumps(traffic, sort_keys=True),
        quant, rows)
    params = start
    if not on_device:
        # The host keeps the start too: the device holds what a step needs.
        start = jax.device_get(start)
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
