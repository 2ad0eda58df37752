"""Experiment: how to compute 8 per-worker ResNet-18 gradients on one chip.

The logical-worker fold (n workers emulated on 1 chip) pays a 36-63% relayout
tax when done with vmap: the 5-D (worker, batch, H, W, C) intermediates get
transposed/sliced between convs (PERF.md "Known frontier", xplane-confirmed).
This script times the candidate structures on the real chip:

  vmap     — round-1 production path (the taxed one)
  unroll   — Python loop over workers: 8 independent 4-D fwd+bwd subgraphs,
             no 5-D tensors anywhere; XLA schedules/interleaves them
  scan     — lax.scan over stacked worker batches (sequential, one program)
  fused200 — single batch-200 fwd+bwd (NOT per-worker semantics: the lower
             bound on compute)

Run from the repo root:
  python scripts/experiments/fold_tax.py
"""

import functools
import os
import sys
import time

# Make garfield_tpu importable when run as a script from anywhere.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from garfield_tpu import models
from garfield_tpu.parallel import core
from garfield_tpu.utils import profiling, selectors


def build(variant, num_workers=8, batch=25, model="resnet18"):
    platform = jax.devices()[0].platform
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    module = models.select_model(model, "cifar10", dtype=dtype)
    loss_fn = selectors.select_loss("cross-entropy")
    init_fn, grad_fn, _ = core.make_worker_fns(module, loss_fn)

    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((num_workers, batch, 32, 32, 3)), jnp.float32
    )
    y = jnp.asarray(rng.integers(0, 10, (num_workers, batch)), jnp.int32)
    params, ms = init_fn(jax.random.PRNGKey(0), x[0])
    keys = jax.random.split(jax.random.PRNGKey(1), num_workers)

    if variant == "vmap":
        def step(params, ms, x, y):
            g, (loss, _) = jax.vmap(
                grad_fn, in_axes=(None, None, 0, 0, 0)
            )(params, ms, x, y, keys)
            return core.flatten_rows(g), jnp.mean(loss)
    elif variant == "unroll":
        def step(params, ms, x, y):
            flats, losses = [], []
            for w in range(num_workers):
                g, (loss, _) = grad_fn(params, ms, x[w], y[w], keys[w])
                flats.append(ravel_pytree(g)[0])
                losses.append(loss)
            return jnp.stack(flats), jnp.mean(jnp.stack(losses))
    elif variant == "scan":
        def step(params, ms, x, y):
            def body(carry, xs):
                xw, yw, kw = xs
                g, (loss, _) = grad_fn(params, ms, xw, yw, kw)
                return carry, (ravel_pytree(g)[0], loss)
            _, (flats, losses) = jax.lax.scan(body, 0, (x, y, keys))
            return flats, jnp.mean(losses)
    elif variant.startswith("hybrid"):
        # unroll groups x vmap(width) inside: hybrid2 = 4 groups of width 2.
        width = int(variant[len("hybrid"):])
        assert num_workers % width == 0
        def step(params, ms, x, y):
            flats, losses = [], []
            for g0 in range(0, num_workers, width):
                g, (loss, _) = jax.vmap(
                    grad_fn, in_axes=(None, None, 0, 0, 0)
                )(params, ms, x[g0:g0 + width], y[g0:g0 + width],
                  keys[g0:g0 + width])
                flats.append(core.flatten_rows(g))
                losses.append(loss)
            return jnp.concatenate(flats), jnp.mean(jnp.stack(losses))
    elif variant == "fused200":
        def step(params, ms, x, y):
            xf = x.reshape((-1,) + x.shape[2:])
            yf = y.reshape((-1,) + y.shape[2:])
            g, (loss, _) = grad_fn(params, ms, xf, yf, keys[0])
            flat = ravel_pytree(g)[0]
            return jnp.broadcast_to(flat[None], (num_workers, flat.size)), loss
    else:
        raise ValueError(variant)

    # Chain iterations through the seed input so the host-side loop stays
    # ordered, and keep a live (1e-20-scaled, not 0.0 — XLA would constant-
    # fold that and dead-code-eliminate the whole backward) dependency on
    # the gradient stack so nothing is eliminated.
    @jax.jit
    def chained(seed, params, ms, x, y):
        flats, loss = step(params, ms, x, y)
        # Reduce the FULL stack: anything narrower (e.g. flats[:, :8]) lets
        # XLA prune the backward to the few params feeding those columns.
        live = jnp.sum(flats).astype(jnp.float32) * 1e-20
        return jnp.float32(loss) + live + seed * 1e-20

    return chained, (params, ms, x, y)


def time_variant(variant, reps=20, **kw):
    chained, (params, ms, x, y) = build(variant, **kw)
    seed = jnp.float32(0.0)
    out = chained(seed, params, ms, x, y)
    float(out)  # compile + drain

    def timed(k):
        s = jnp.float32(0.0)
        t0 = time.perf_counter()
        for _ in range(k):
            s = chained(s, params, ms, x, y)
        float(s)
        return time.perf_counter() - t0

    dt = profiling.paired_reps(timed, reps)
    return dt


if __name__ == "__main__":
    import sys

    variants = sys.argv[1:] or ["vmap", "unroll", "scan", "fused200"]
    for v in variants:
        dt = time_variant(v)
        ms_ = "below-noise" if dt is None else f"{dt * 1e3:7.2f} ms"
        print(f"{v:>9}: {ms_}", flush=True)
