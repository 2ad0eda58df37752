"""Functional training core shared by all topologies.

Counterpart of the reference's node-role machinery, re-designed for SPMD:

  - ``make_worker_fns``  — the Worker role (pytorch_impl/libs/garfieldpp/
    worker.py:50-96): forward + backward on a minibatch, gradients flattened
    into one 1-D vector (worker.py:93-94). Here it is a pure function
    ``(params, model_state, x, y, rng) -> (grads_tree, aux)`` built from a
    flax module; topologies vmap it over logical worker slots and shard the
    vmapped axis over the mesh.
  - ``TrainState``       — the Server role's mutable state (server.py:56-99:
    model, optimizer, iteration counter) as an immutable pytree; ``update``
    applies a flat aggregated gradient exactly like ``Server.update_model``
    (server.py:277-287 slices the flat vector back into per-param grads).
  - ``flatten_rows`` / ``subset_indices`` / ``mean_model_state`` — stack
    handling, wait-n-f emulation (server.py:118-119,134-155: proceed with the
    fastest n-f responses; bulk-synchronous XLA has no stragglers, so the
    sampled subset models *which* n-f arrived first), and cross-worker
    BatchNorm-statistics averaging (a deliberate improvement: the reference
    silently drops worker BN-buffer updates because only gradients travel
    over RPC).
"""

import os as _os
import re

import flax.struct
import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

__all__ = [
    "PHASES",
    "phase",
    "TrainState",
    "make_worker_fns",
    "make_chunked_step",
    "flatten_rows",
    "unflatten_like",
    "subset_indices",
    "mean_model_state",
    "COUNTER_SUMS",
    "COUNTER_MAXES",
    "counter_names",
    "step_counters",
    "default_byz_mask",
]


# The phases of one training step, as a device trace can name them: every
# instruction the compiler makes from code under ``phase(name)`` carries
# ``phase.<name>`` in its ``op_name`` metadata, fused or not. The metadata is
# no part of the optimized program, so the scopes are always on.
#   grads           per-slot forward and backward, the cast to gar_dtype
#   exchange        every all_gather of gradients, losses, aggregates
#   attack          row poisoning; a folded attack's fake row and plan
#   rule            gathered tree -> aggregated tree (Gram, selection, sums,
#                   coordinate kernels with upcast/pad/slice, audit taps)
#   update          optimizer.update, apply_updates, the carried state
#   model_exchange  LEARN gossip / ByzSGD gather of MODELS (all_gather)
#   model_rule      their aggregation
# Where scopes nest the OUTERMOST names the phase, so a topology can claim a
# shared helper's work for its own plane (``model_rule`` around fold.py).
PHASES = (
    "grads", "exchange", "attack", "rule", "update",
    "model_exchange", "model_rule",
)


def phase(name):
    """``jax.named_scope("phase.<name>")`` for a name of ``PHASES``; usable
    as a context manager and as a decorator."""
    if name not in PHASES:
        raise ValueError(
            f"unknown step phase {name!r}; the vocabulary is {PHASES}"
        )
    return jax.named_scope("phase." + name)


@flax.struct.dataclass
class TrainState:
    """Replicated (or ps/node-stacked) training state.

    ``model_state`` holds flax mutable collections (``batch_stats``);
    ``rng`` is the base PRNG key; per-step keys are derived by fold_in so a
    run is replayable from (seed, step) alone — the reference relies on
    ``torch.manual_seed(1234)`` + call order (Aggregathor/trainer.py:210-212).
    """

    step: jax.Array
    params: dict
    model_state: dict
    opt_state: object
    rng: jax.Array
    # Per-worker momentum stack (leading slot axis per leaf) when the
    # topology runs worker momentum (Karimireddy et al. 2021, the companion
    # of the cclip GAR); None otherwise. Sharded like the topology's node
    # state: aggregathor passes the whole state at P() (replicated — the
    # full num_workers x model stack costs HBM on EVERY device; budget
    # accordingly on large models), LEARN shards the leading axis at
    # P(axis) with params/opt_state.
    worker_mom: object = None
    # Carried aggregation state for stateful-center rules (cclip): the
    # previous step's aggregate tree, used as the next step's center v_0 —
    # the paper's actual recipe (Karimireddy et al. 2021 set v_0 to the
    # previous aggregate; a per-step robust median init costs a full
    # coordinate-median pass, ~4 ms at ResNet-18 scale, PERF.md r5).
    # None for stateless rules.
    gar_state: object = None
    # Adaptive-adversary controller state (attacks/adaptive.py, DESIGN.md
    # §16): the bisection bracket {lo, hi} over the attack magnitude,
    # updated each step from the rule's selection feedback. Riding in the
    # TrainState means the lax.scan chunk carry threads it for free
    # (core.make_chunked_step). None for oblivious attacks.
    attack_state: object = None
    # Closed-loop defense state (aggregators/defense.py): the carried
    # per-rank exclusion EMA {obs, exc} the in-graph suspicion weights
    # derive from — the on-mesh emulation of the host MetricsHub's
    # decayed suspicion. None when the defense is off.
    defense_state: object = None
    # Wire-compression emulation state (parallel/compress.py, DESIGN.md
    # §20): the per-worker error-feedback residual rows
    # {"resid": (n_workers, d) f32} when a lossy scheme runs with EF.
    # Riding in the TrainState is what makes chunked and mid-run-resumed
    # compressed trainings bitwise (scan carry + checkpoint tree). None
    # when compression is off or EF-free.
    wire_state: object = None


def make_worker_fns(module, loss_fn):
    """Build the pure Worker functions for a flax module.

    Returns ``(init_fn, grad_fn, eval_fn)``:
      - ``init_fn(key, example_x) -> (params, model_state)``
      - ``grad_fn(params, model_state, x, y, rng) -> (grads, (loss, new_ms))``
        where ``grads`` is a pytree shaped like params (flattening is the
        topology's job — per-layer GARs need the tree);
      - ``eval_fn(params, model_state, x) -> logits`` (train=False), used by
        ``compute_accuracy`` (server.py:235-254).
    """

    def init_fn(key, example_x):
        pkey, dkey = jax.random.split(key)
        variables = module.init(
            {"params": pkey, "dropout": dkey}, example_x, train=False
        )
        variables = dict(variables)
        params = variables.pop("params")
        return params, variables

    def loss_of(params, model_state, x, y, rng):
        out = module.apply(
            {"params": params, **model_state},
            x,
            train=True,
            mutable=list(model_state.keys()),
            rngs={"dropout": rng},
        )
        logits, new_ms = out
        return loss_fn(logits, y), new_ms

    def grad_fn(params, model_state, x, y, rng):
        (loss, new_ms), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params, model_state, x, y, rng
        )
        return grads, (loss, new_ms)

    def eval_fn(params, model_state, x):
        return module.apply({"params": params, **model_state}, x, train=False)

    return init_fn, grad_fn, eval_fn


def flatten_rows(stacked_tree):
    """(n, ...) stacked gradient pytree -> (n, d) matrix of flat rows.

    Equivalent of the reference's per-worker ``torch.cat([g.view(-1)])``
    (worker.py:93-94) applied to every row of the gathered stack.
    """
    return jax.vmap(lambda row: ravel_pytree(row)[0])(stacked_tree)


def unflatten_like(template_tree, flat_vec):
    """Inverse of ``ravel_pytree``: slice a flat vector into a params-shaped
    pytree (Server.update_model's slicing loop, server.py:277-287)."""
    _, unravel = ravel_pytree(template_tree)
    return unravel(flat_vec)


def leaf_segments(tree):
    """Static (start, end) column spans of each leaf in ravel order.

    ``ravel_pytree`` concatenates leaves in ``jax.tree.leaves`` order, so a
    flat (n, d) stack can be sliced back into per-parameter blocks — the
    basis for per-layer GAR granularity (Garfield_CC/trainer.py:55-204 loops
    over ``model.parameters()``).
    """
    import numpy as np

    spans, start = [], 0
    for leaf in jax.tree.leaves(tree):
        size = int(np.prod(jnp.shape(leaf))) if jnp.ndim(leaf) else 1
        spans.append((start, start + size))
        start += size
    return spans


def segmented_aggregate(agg_fn, stack, segments):
    """Apply ``agg_fn(segment, i)`` independently to each column segment of
    an (n, d) stack and concatenate — per-layer aggregation over a flat
    stack. The segment index lets randomized rules fold a distinct key per
    layer."""
    return jnp.concatenate(
        [agg_fn(stack[:, s:e], i) for i, (s, e) in enumerate(segments)],
        axis=0,
    )


# Above this many logical slots per shard, per-slot gradients fall back to
# vmap: the unroll duplicates the model's fwd+bwd graph per slot and compile
# time grows linearly. (On a real multi-chip mesh per-shard slot counts are
# 1-2 and the unroll is always used.)
#
# Measured end-to-end at n=64 on the chip (PERF.md r4: ResNet-18, b=25,
# krum+lie): vmap fallback 127 ms/step (12.6k img/s, compile 6 s) vs forced
# unroll 103 ms/step (15.6k img/s, compile 136 s) — the relayout tax at
# n=64 is ~19%, far below the 36-63% measured at n=8, and the unroll
# amortizes its compile in ~5.4k steps.
UNROLL_MAX_SLOTS = 16

# Steps at which the unroll's compile-time premium amortizes against its
# steady-state win over vmap. Both sides scale ~linearly in slots (compile
# ~2 s/slot premium, win ~0.38 ms/step/slot at ResNet-18 scale, PERF.md
# r4), so the breakeven is roughly slot-count independent.
UNROLL_AMORTIZE_STEPS = 6000


def step_donation():
    """``donate_argnums`` for the topology step functions: ``(0,)`` (donate
    the TrainState) on real device backends, ``()`` on XLA:CPU.

    XLA:CPU executes donation unsoundly when host views
    of the donated buffers are still alive — and on CPU both
    ``np.asarray(jax_array)`` and ``jax.device_put(np_array)`` are
    zero-copy, so checkpoint save/restore and the eval readback all
    create such views. Observed in the warm-compile-cache app suite as
    corrupted TrainState leaves (a resumed run's ``state.step`` reading
    an eval count) and native SIGSEGV/SIGABRT mid-run. Donation is only
    a memory-reuse optimization, so it is dropped on CPU; the device
    backends keep it.
    """
    return () if jax.default_backend() == "cpu" else (0,)


def chunk_unroll(chunk_steps):
    """Scan unroll factor for ``make_chunked_step``: the FULL chunk on
    XLA:CPU (the rolled while loop pins conv layouts at the loop boundary
    and per-iteration relayouts invert the chunk win — measured 2.6x
    WORSE than per-step on convnet/mnist, PERF.md r9), the rolled loop
    (factor 1) on device backends."""
    return chunk_steps if jax.default_backend() == "cpu" else 1


def make_chunked_step(step_fn, chunk_steps, num_batches, unroll=None):
    """Fuse ``chunk_steps`` training steps into ONE jitted dispatch.

    The per-step driver loop (apps/common.py) pays one Python dispatch and
    one host round-trip per training step, so XLA can never overlap step
    i's optimizer/GAR tail with step i+1's forward — the schedule-level
    gap every perf round since r2 has pointed at (PERF.md "Known
    frontier"). This wraps any topology's step in a ``jax.lax.scan`` over
    K on-device batch indices: K-1 of every K host dispatches disappear
    and the whole chunk is one XLA program with cross-step overlap.

    ``step_fn`` is a topology step from ``make_trainer`` (its un-jitted
    ``shard_map`` body is consumed via the ``inner`` attribute the
    topologies attach, so the scan body is not re-wrapped in a nested
    jit). Returns

        ``chunked(state, xs, ys, i0) -> (state, metrics)``

    where ``xs``/``ys`` are the FULL device-resident batch stacks with a
    ``num_batches`` axis at position 1 (the app loop's ``(slots, B, ...)``
    layout), ``i0`` is the global step index of the chunk's first step
    (traced, so one compiled program serves every chunk of this length),
    and each metrics leaf gains a leading ``chunk_steps`` axis — K losses
    (and K fixed-shape telemetry ``TapBundle``s, when taps are on) per
    dispatch, which the host loop fans back out into per-step records.

    Trajectory semantics are EXACTLY the per-step loop's:

      - the batch index is computed on device, ``b = (i0 + k) %
        num_batches`` — the same ``i % num_batches`` the host loop uses;
      - the ``TrainState`` is the scan carry (params, optimizer state,
        ``gar_state`` stateful-rule centers, ``worker_mom``, step
        counter), so stateful rules carry across scan iterations exactly
        as across dispatches;
      - per-step RNG needs no extra plumbing: every topology derives its
        attack/subset/dropout keys by ``fold_in(state.rng, state.step)``
        and ``step`` advances in the carry, so scan iteration k uses the
        bitwise-same keys the per-step loop used at step ``i0 + k``
        (asserted bitwise in tests/test_chunked.py).

    Donation follows ``step_donation()``: the carried TrainState is
    donated on real device backends, while the batch stacks (args 1-2)
    are never donated — they are reused by every chunk.

    ``unroll`` is the scan unroll factor (None = backend-aware default,
    see ``chunk_unroll``): XLA:CPU pins operand layouts at the while-loop
    boundary, so conv bodies inside a ROLLED scan pay per-iteration
    relayouts that measurably invert the chunk win (convnet/mnist
    measured 31 -> 80 ms/step rolled, 31 -> 24.5 ms/step fully unrolled,
    PERF.md r9); full unroll restores layout freedom and the cross-step
    overlap at a ~K-times compile cost — the same compile-vs-steady-state
    trade the slot unroll already navigates. Device backends keep the
    rolled loop (compile time at ResNet scale is precious; the chip A/B
    is the next live-backend task).
    """
    if chunk_steps < 1:
        raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
    if num_batches < 1:
        raise ValueError(f"num_batches must be >= 1, got {num_batches}")
    inner = getattr(step_fn, "inner", step_fn)
    out_shardings = getattr(step_fn, "out_shardings", None)
    if unroll is None:
        unroll = chunk_unroll(chunk_steps)
    unroll = max(1, min(int(unroll), chunk_steps))

    def scan_steps(state, xs, ys, i0):
        def body(st, k):
            b = jax.lax.rem(i0 + k, jnp.int32(num_batches))
            x = jax.lax.dynamic_index_in_dim(xs, b, 1, keepdims=False)
            y = jax.lax.dynamic_index_in_dim(ys, b, 1, keepdims=False)
            return inner(st, x, y)

        return jax.lax.scan(
            body, state, jnp.arange(chunk_steps, dtype=jnp.int32),
            unroll=unroll,
        )

    import functools

    jit_kwargs = {}
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    chunked = functools.partial(jax.jit, donate_argnums=step_donation(),
                                **jit_kwargs)(scan_steps)
    chunked.mesh = getattr(step_fn, "mesh", None)
    chunked.batch_sharding = getattr(step_fn, "batch_sharding", None)
    chunked.chunk_steps = chunk_steps
    return chunked


def slot_path_decision(slots, num_iter=None, fused_available=False):
    """Pick the per-slot gradient formulation (VERDICT r4 #8).

    Returns ``(path, reason)`` with path in {"fused", "unroll", "vmap"}:
    the slot-fused twin when the model has one (fastest at every n and the
    cheapest compile); otherwise the unroll below UNROLL_MAX_SLOTS; above
    the cap, a RUN-LENGTH-aware choice — the unroll's ~2 s/slot compile
    premium amortizes in ~UNROLL_AMORTIZE_STEPS steps against its ~24%
    steady-state win (measured n=64, PERF.md r4), so reference-scale runs
    (100k iters, Aggregathor/run_exp.sh:39-40) take the unroll
    automatically instead of silently losing it to a static cap.
    """
    if fused_available:
        return "fused", "slot-fused twin (fused fwd/dx, per-slot dw)"
    if slots <= UNROLL_MAX_SLOTS:
        return "unroll", f"{slots} slots <= cap {UNROLL_MAX_SLOTS}"
    if num_iter is not None and num_iter >= UNROLL_AMORTIZE_STEPS:
        return "unroll", (
            f"{num_iter} steps amortize the unroll compile premium "
            f"(breakeven ~{UNROLL_AMORTIZE_STEPS})"
        )
    return "vmap", (
        f"{slots} slots > cap {UNROLL_MAX_SLOTS} and "
        + (f"{num_iter} steps < breakeven {UNROLL_AMORTIZE_STEPS}"
           if num_iter is not None else "run length unknown")
    )


def resolve_slot_grad_fn(module, loss_fn, slots, shared_params=True):
    """Resolve the slot-fused gradient twin for a module, or None.

    The single front-end every topology consults (directly or via
    ``select_slot_path``): it checks the fold geometry (``slots > 1`` —
    one slot per shard has nothing to fuse), the escape hatch
    (``GARFIELD_NO_SLOTFUSED``), the parameter-sharing precondition, and
    the ``models.slotfused.SLOTFUSED_MODELS`` registry — so a model family
    added to the registry reaches aggregathor, LEARN and ByzSGD with no
    per-topology change.

    ``shared_params=False`` declares that the slots carry DISTINCT
    parameter trees (LEARN's per-node models): the twin's fused primal
    runs the flat batch against ONE shared kernel (``slot_conv`` uses
    ``w_st[0]``), so it is structurally inapplicable there and this
    returns None. If a stacked-params twin formulation ever lands, only
    this gate changes.
    """
    if slots <= 1 or not shared_params:
        return None
    if _os.environ.get("GARFIELD_NO_SLOTFUSED"):
        return None
    from ..models import slotfused

    return slotfused.build_slot_grad_fn(module, loss_fn)


def select_slot_path(module, loss_fn, slots, num_iter=None, log_tag=None,
                     shared_params=True):
    """Shared topology-builder front-end to ``slot_path_decision``.

    Resolves the slot-fused twin via ``resolve_slot_grad_fn``, logs the
    decision, and returns ``(fused_fn, force_unroll)`` ready to pass to
    ``per_slot_grads``.
    """
    fused_fn = resolve_slot_grad_fn(module, loss_fn, slots, shared_params)
    path, why = slot_path_decision(slots, num_iter, fused_fn is not None)
    if slots > 1:
        from ..utils import tools

        tools.info(
            f"[{log_tag or 'trainer'}] per-slot gradients: {path} ({why})"
        )
    return fused_fn, path == "unroll"


def per_slot_grads(grad_fn, params, ms, x, y, keys, fused_fn=None,
                   force_unroll=False, dtype=None):
    """Per-slot gradients over a leading logical-slot axis, vmap-compatible.

    Returns exactly what ``jax.vmap(grad_fn, in_axes=(None, None, 0, 0, 0))``
    returns — ``(grads, (loss, ms))`` trees with a leading slot axis —
    computed by the fastest available formulation:

      1. ``fused_fn`` (``models.slotfused.build_slot_grad_fn``) when the
         topology supplies one: the model runs ONCE on the flat (n*b)
         batch (fused forward + fused dx), and only the parameter-cotangent
         contractions are slot-resolved — the r5 hybrid (PERF.md).
      2. A Python unroll over the slots when their count is small: keeps
         every subgraph 4-D and batch-minor, without relayouts (r2; 12.9 ->
         9.1 ms for the 8-worker ResNet-18 stack). The slots run one after
         another: a slot's batch passes one ``optimization_barrier`` with
         the gradients of the slot before it, cast to ``dtype`` (the width
         the caller keeps them at; None: as they are), so what a slot's
         forward pass keeps for its backward pass is freed before the next
         slot's is made. Left to itself XLA:TPU overlaps the independent
         slots and holds three slots' kept activations at once (PR 33: 11.6
         GB of temporaries against 7.5 in the 4-slot token step).
      3. vmap above UNROLL_MAX_SLOTS — compile time of the unroll grows
         linearly with slots; the 5-D relayout tax shrinks with n
         (~19% at n=64, PERF.md r4).

    lax.scan was measured 2.6x worse (sequential small batches), the
    patches-einsum custom VJP 3-6x worse, and raveling each slot inside
    the unroll 12% worse end-to-end (PERF.md).
    """
    n = x.shape[0]
    with phase("grads"):
        if fused_fn is not None:
            return fused_fn(params, ms, x, y, keys)
        if n > UNROLL_MAX_SLOTS and not force_unroll:
            return jax.vmap(grad_fn, in_axes=(None, None, 0, 0, 0))(
                params, ms, x, y, keys
            )
        outs, xk = [], x[0]
        for k in range(n):
            grads, aux = grad_fn(params, ms, xk, y[k], keys[k])
            grads = cast_leaves(grads, dtype)
            if k + 1 < n:
                grads, xk = jax.lax.optimization_barrier((grads, x[k + 1]))
            outs.append((grads, aux))
        return jax.tree.map(lambda *ls: jnp.stack(ls), *outs)


def cast_leaves(tree, dtype):
    """Cast every leaf to ``dtype`` (no-op when dtype is None).

    The narrow-aggregation-pipeline cast-IN: applied to per-slot gradients
    at the backward epilogue so XLA fuses it into the backward's output
    writes (``gar_dtype`` in the topology builders).
    """
    if dtype is None:
        return tree
    return jax.tree.map(lambda l: l.astype(dtype), tree)


def cast_like(tree, ref_tree):
    """Cast every leaf of ``tree`` to the dtype of the matching ``ref_tree``
    leaf — the cast-BACK at the optimizer boundary (momentum/weight-decay
    state stays full width)."""
    return jax.tree.map(lambda a, p: a.astype(p.dtype), tree, ref_tree)


def worker_mom_init(params, num_slots, dtype=None):
    """Zeros momentum stack for ``worker_momentum`` topologies: one leading
    slot axis per leaf, at the aggregation pipeline's width (``gar_dtype``
    when narrowed — momentum is what workers exchange)."""
    return jax.tree.map(
        lambda p: jnp.zeros((num_slots,) + p.shape, dtype or p.dtype), params
    )


def worker_mom_update(beta, mom_tree, grads_tree):
    """EMA ``(1-beta) g + beta m`` per leaf, accumulated in f32 and cast
    back to the pipeline dtype (bf16 leaves would otherwise round the
    small ``(1-beta) g`` increments away)."""
    b = jnp.asarray(beta, jnp.float32)
    return jax.tree.map(
        lambda m, g: ((1.0 - b) * g.astype(jnp.float32)
                      + b * m.astype(jnp.float32)).astype(g.dtype),
        mom_tree, grads_tree,
    )


def subset_indices(key, n, q):
    """Uniformly sample q of n row indices (static shape (q,)).

    Emulates the wait-fastest-n-f path (server.py:134-155): the reference
    takes whichever q = n - f responses land first; arrival order on a real
    async cluster is effectively random, so a seeded uniform sample is the
    faithful bulk-synchronous stand-in (SURVEY §2.3 asynchrony row).
    """
    return jax.random.permutation(key, n)[:q]


def mean_model_state(stacked_ms, axis_name=None):
    """Average per-worker mutable collections (BatchNorm running stats) over
    the local slot axis and, if ``axis_name`` is given, over that mesh axis.
    """
    ms = jax.tree.map(lambda l: jnp.mean(l, axis=0), stacked_ms)
    if axis_name is not None:
        ms = jax.tree.map(lambda l: jax.lax.pmean(l, axis_name), ms)
    return ms


# A model's counters of one forward pass (pairs an expert layer computed, its
# fullest expert's load, ...): float32 scalars a module writes into one of
# these two flax collections, by name as BatchNorm writes ``batch_stats``.
# They ride in ``model_state`` per slot; a counter is called what its
# variable is called, and `step_counters` adds it up over the workers or
# takes their maximum, by the collection it is in.
COUNTER_SUMS, COUNTER_MAXES = "counters_sum", "counters_max"


def _counters(model_state, collection):
    """``{name: [leaf, ...]}`` of a counters collection, the modules that
    write one name in the natural order of their paths (layer_2 before
    layer_10)."""

    def natural(item):
        return [
            (0, int(t), "") if t.isdigit() else (1, 0, t)
            for k in item[0] for t in re.split(r"(\d+)", str(k.key))
        ]

    by_name = {}
    for path, leaf in sorted(
        jax.tree_util.tree_flatten_with_path(
            model_state.get(collection, {}))[0],
        key=natural,
    ):
        by_name.setdefault(str(path[-1].key), []).append(leaf)
    return by_name


def counter_names(model_state):
    """The names `step_counters` gives for a model state of this structure."""
    return sorted(
        name for c in (COUNTER_SUMS, COUNTER_MAXES)
        for name in _counters(model_state, c)
    )


def step_counters(stacked_ms, axis_name=None):
    """The step's counters from the per-slot model state (a leading slot
    axis per leaf), for the step's ``metrics``; ``{}``, and nothing traced,
    for a model that keeps none. ``{name: (writers,) float32}``: a counter
    of ``COUNTER_SUMS`` summed over the slots (and over ``axis_name``), one
    of ``COUNTER_MAXES`` their maximum, one entry per module that writes
    the name."""
    out = {}
    for collection, over_slots, over_axis in (
        (COUNTER_SUMS, jnp.sum, jax.lax.psum),
        (COUNTER_MAXES, jnp.max, jax.lax.pmax),
    ):
        for name, leaves in _counters(stacked_ms, collection).items():
            value = jnp.stack([over_slots(l, axis=0) for l in leaves])
            out[name] = (
                value if axis_name is None else over_axis(value, axis_name)
            )
    return out


def default_byz_mask(n, f):
    """Boolean (n,) mask with the *last* f slots Byzantine, matching the
    reference's rank layout (Aggregathor/trainer.py:217-268: Byzantine
    workers are the highest ranks)."""
    import numpy as np

    mask = np.zeros(n, dtype=bool)
    if f:
        mask[n - f :] = True
    return mask
