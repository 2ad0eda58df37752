"""AggregaThor topology: single trusted server, n workers, f Byzantine.

TPU-native re-design of ``pytorch_impl/applications/Aggregathor/trainer.py``
(train step :231-249) and the Server/Worker RPC machinery it drives
(server.py:112-159, worker.py:77-96). Per SURVEY §7, the whole PS round trip
collapses into one jit'd SPMD program over a "workers" mesh axis:

    grads  = vmap(worker_grad)(params, local_batches)     # worker.py:77-96
    stack  = lax.all_gather(grads, "workers")             # server.py:112-159
    stack  = attack(stack, byz_mask)                      # byzWorker.py:78-143
    stack  = stack[subset]                                # wait n-f, :134-155
    aggr   = gar(stack, f)                                # trainer.py:236
    params = optimizer(params, aggr)                      # server.py:277-287

The aggregation and update run redundantly on every shard (replicated
output), so there is no broadcast step: SPMD replication replaces
``write_model`` (server.py:289-297).

``granularity="layer"`` reproduces the Garfield_CC semantics of applying the
GAR per parameter tensor (Garfield_CC/trainer.py:55-204 loops over
``model.parameters()``) instead of over the whole flat gradient.

Centralized (pytorch_impl/applications/Centralized/trainer.py) is this
topology with num_workers=1, f=0, gar="average", attack=None.
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import aggregators
from ..attacks import (
    adaptive as adaptive_lib,
    apply_gradient_attack,
    apply_gradient_attack_tree,
    gradient_attacks,
    note_attack_fallback,
    targeted as targeted_lib,
)
from ..telemetry import taps as taps_lib
from . import core, fold, mesh as mesh_lib

__all__ = ["make_trainer"]

# The data-plane defense (aggregators/dataplane.py, DESIGN.md §18) is
# deployed in-graph on THIS topology only: the SSMW gather holds the full
# per-rank stack every step, which is the quorum the fingerprints need.
# apps/common.py keys on this flag instead of growing a per-topology arg.
SUPPORTS_DATAPLANE = True


def _resolve_gar(gar):
    if isinstance(gar, str):
        return aggregators.gars[gar]
    return gar


def _check_gar(gar, n_effective, f, d=2):
    """Run the rule's contract check once at build time (the reference checks
    on every call under __debug__, aggregators/__init__.py:53-61; here n and f
    are static so once suffices)."""
    import numpy as np

    dummy = np.zeros((n_effective, d), dtype=np.float32)
    message = gar.check(dummy, f=f)
    if message is not None:
        raise AssertionError(
            f"aggregation rule {gar.name!r} cannot be used: {message}"
        )


def _tree_path_ok(tree_path, subset, num_slots, granularity, gar,
                  subset_gram_ok=False):
    """Shared tree-fast-path eligibility gate (aggregathor AND byzsgd).

    A true wait-n-f subset forces the flat path for most rules: row
    selection on a TREE is one dynamic gather per leaf (62 x per-PS at
    ResNet-18 scale), measured 3.5x slower than the flat path's single
    (n, d) gather (PERF.md). EXCEPT Gram-form rules when the caller
    implements the sub-Gram composition (``subset_gram_ok`` —
    aggregathor): their selection needs only the (q, q) gather of the
    tiny Gram plus a weight scatter, so the async emulation keeps the
    tree/fold fast path (VERDICT r4 #5). subset >= num_slots never
    selects rows, so it stays tree-eligible everywhere. Layer granularity
    and rules without tree aggregation use the flat path.
    """
    subset_ok = (
        subset is None or subset >= num_slots
        or (subset_gram_ok and gar.gram_select is not None)
    )
    return (
        tree_path
        and subset_ok
        and granularity != "layer"
        and gar.tree_aggregate is not None
    )


def _attack_then_aggregate(
    flat_stack, byz_mask, atk_key, sub_key, gar_key, *, attack,
    attack_params, gar, f, subset, gar_params, center=None,
    row_weights=None,
):
    """Poison rows, optionally subsample (wait n-f), aggregate. Pure.
    ``gar_key`` seeds randomized rules (condense's Bernoulli mask);
    ``center`` threads a stateful rule's carried v_0 (cclip);
    ``row_weights`` is the bounded-staleness discount composed AFTER the
    attack and the subset — the rows the rule consumes are exactly what
    the host-plane PS aggregates: poisoned, quorum-selected, then
    staleness-weighted (utils/rounds.py, DESIGN.md §14)."""
    n = flat_stack.shape[0]
    with core.phase("attack"):
        stack = apply_gradient_attack(
            attack, flat_stack, byz_mask, key=atk_key, **attack_params
        )
    with core.phase("rule"):
        if subset is not None and subset < n:
            sel = core.subset_indices(sub_key, n, subset)
            stack = stack[sel]
            if row_weights is not None:
                row_weights = row_weights[sel]
        if row_weights is not None:
            stack = (stack * row_weights[:, None]).astype(stack.dtype)
        extra = {} if center is None else {"center": center}
        return gar.unchecked(stack, f=f, key=gar_key, **gar_params, **extra)


def make_trainer(
    module,
    loss_fn,
    optimizer,
    gar,
    *,
    num_workers,
    f=0,
    attack=None,
    attack_params=None,
    byz_mask=None,
    mesh=None,
    axis="workers",
    subset=None,
    granularity="model",
    tree_path=True,
    gar_dtype=None,
    worker_momentum=None,
    gar_params=None,
    num_iter=None,
    telemetry=False,
    staleness=None,
    defense=None,
    wire=None,
):
    """Build ``(init_fn, step_fn, eval_fn)`` for the SSMW topology.

    ``telemetry`` (default off) makes ``step_fn`` return a fixed-shape
    ``TapBundle`` under ``metrics["tap"]`` — per-rank selection evidence
    recomputed from the same poisoned stack and keys the GAR consumed
    (telemetry/taps.py). Off means NOTHING tap-shaped is traced: the
    step program is byte-identical to the pre-telemetry one, and the
    taps never write into TrainState, so taps-on trajectories are
    bitwise equal to taps-off (tests/test_telemetry.py).

    Args mirror the reference CLI (Aggregathor/trainer.py:62-135): ``f`` is
    the declared tolerance passed to the GAR; ``attack``/``byz_mask`` control
    actual fault injection (byzWorker.py); ``subset=q`` emulates the
    asynchronous wait-for-q path (server.py:134-155); ``granularity`` picks
    whole-model (trainer.py:236) vs per-layer (Garfield_CC) aggregation.
    ``tree_path`` (default on) lets rules that support tree-mode aggregation
    (average, krum) skip the (n, d) flat stack entirely — measured ~5 ms/
    step at ResNet-18 scale (PERF.md); set False to force the flat path
    (A/B tests).

    ``gar_dtype`` (e.g. ``jnp.bfloat16``) casts the per-worker gradients to
    that dtype at the backward's epilogue (XLA fuses the cast into its final
    writes, so the f32 gradients never hit HBM) and runs the attack + gather
    + GAR phase entirely at the narrow width — halving the HBM traffic of
    the whole aggregation pipeline, which is bandwidth-bound (PERF.md
    "Known frontier"). Gram/selection arithmetic still accumulates in f32
    (aggregators/_common.py), and the aggregate is cast back to the param
    dtype at the optimizer boundary — the standard bf16-gradient-exchange
    design on TPU. None keeps full width.

    ``worker_momentum`` (float beta in [0, 1)) makes every worker submit an
    exponential moving average ``m_i = (1-beta) g_i + beta m_i`` of its
    gradients instead of the raw gradient — Karimireddy, He & Jaggi (ICML
    2021): momentum shrinks honest-gradient variance over time, which is
    exactly the quantity the "little is enough" lie attack hides inside, so
    robust rules (their cclip, but also krum/median) regain their guarantees
    under attacks that defeat them on raw gradients (see BASELINE.md's TTA
    grid). The per-worker momentum stack lives in ``TrainState.worker_mom``
    (same dtype as the aggregation pipeline, i.e. ``gar_dtype`` when set);
    Byzantine rows are re-poisoned by the attack every step, after the
    honest update — a real Byzantine worker submits whatever it wants
    regardless of its declared state.

    Pair worker momentum with a PLAIN-SGD server (no heavy-ball momentum in
    ``optimizer``), as the paper's algorithm does — the worker EMA *is* the
    momentum. Stacking it on a momentum server double-smooths the update
    (two poles at ~0.9) and destabilizes training: measured on the hardened
    ResNet-18 task, fault-free accuracy stalls at chance with server
    momentum 0.9 but trains normally with momentum 0 at the
    gain-compensated lr (BASELINE.md TTA grid, the worker-momentum rows).

    ``staleness`` is the in-graph EMULATION of the host plane's
    bounded-staleness async mode (DESIGN.md §14) — the asynchrony analog
    of the seeded ``subset`` emulation: a dict with ``max_staleness``
    (hard cutoff, rounds), ``decay`` (geometric discount), and optional
    ``taus`` (a FIXED per-rank staleness assignment — "rank r lags tau_r
    rounds"; omitted, each step draws per-rank staleness uniformly from
    ``[0, max_staleness]`` with a seeded key). The resulting weights
    (``utils.rounds.staleness_weights`` — the same function the host
    plane's PS applies) scale the post-attack rows before the GAR on
    every dispatch path, composed into the folded-attack row scales on
    Gram-form rules so ``fold.plan_for`` still applies. At
    ``max_staleness=0`` (or an all-zero ``taus``) the emulation is
    dropped entirely and the step program is the synchronous one —
    trajectories are BITWISE equal, the emulated half of the
    ``--max_staleness 0`` contract (tests/test_staleness.py).

    ``attack`` may also name an ADAPTIVE controller (``adaptive-lie`` /
    ``adaptive-empire``, attacks/adaptive.py, DESIGN.md §16): the lie/
    empire magnitude becomes a bisection bracket carried in
    ``TrainState.attack_state`` (and therefore through the chunk-scan
    carry), fed back each step by whether the active cohort entered the
    rule's selection; ``attack_params`` carries the controller knobs
    (``f_pool``/``rotation``/``mag_min``/``mag_max``/``burst``). With a
    static cohort on a Gram-form rule the traced magnitude composes into
    the folded-attack fake row (``adaptive_lib.traced_fold_plan``) so
    the fast path survives; rotation (``f_pool > f`` cohort laundering)
    keeps the where-path (the remap itself becomes dynamic) — reported
    once via the ``attack_fallback`` telemetry event. In-graph bursts
    key on the staleness emulation: a round whose draw hard-cuts an
    honest rank is a quorum-degradation window and the cohort plays
    ``burst`` magnitude (no staleness emulation -> no bursts).

    ``defense`` (aggregators/defense.py) is the closed-loop counterpart:
    a dict with ``power``/``floor``/``halflife`` enabling SUSPICION
    WEIGHTING — a per-rank exclusion EMA carried in
    ``TrainState.defense_state`` (the in-graph emulation of the host
    MetricsHub's decayed suspicion), mapped through
    ``defense.suspicion_weights`` and composed into the SAME row-weight
    algebra as the staleness discount (fold ``row_weights`` on Gram
    rules, explicit row scaling elsewhere). ``defense=None`` (default)
    traces nothing — trajectories are bitwise the undefended ones. Rule
    ESCALATION lives above the trainer (apps/common.py rebuilds the step
    on level changes, like the crash-schedule re-jit), so one policy
    module serves both deployment scales.

    A ``defense`` dict may additionally (or instead — ``weighted:
    False``) carry ``data`` (``tau``/``power``/``floor``/``halflife``):
    the DATA-plane detectors (aggregators/dataplane.py, DESIGN.md §18)
    — per-class classifier-head gradient fingerprints, spectral
    filtering + 2-means cohort clustering over the gathered stack, a
    carried dp exclusion EMA (``dp_obs``/``dp_exc`` in
    ``TrainState.defense_state``), composed by CENTER-PULL onto the
    trusted mean (row scaling hands a data poisoner krum centrality —
    the negative result §18 records). Per-step scores/flags/weights
    surface as ``dataplane_*`` metrics (schema-v9 ``data_defense``
    events in the app loop).

    ``wire`` is the in-graph EMULATION of the host wire codec's lossy
    schemes (parallel/compress.py, DESIGN.md §20): a dict with ``dtype``
    (one of ``wire.WIRE_DTYPES``), ``topk`` (sparsification divisor, 0 =
    off; nonzero replaces the dense scheme on the gradient rows, the
    cluster's gradient-plane policy) and ``error_feedback`` (default
    True; effective for the lossy int8/int4/topk schemes only — bf16
    stays EF-free like the PR 4 wire, f32 is lossless). The round trip
    is applied to the gathered rows AFTER the worker-momentum update
    (momentum accumulates the uncompressed honest signal, exactly like
    a host worker's local state) and BEFORE the attack (a Byzantine
    process controls its wire bytes — compression constrains honest
    senders only). The EF residual rows ride
    ``TrainState.wire_state["resid"]`` through the chunk-scan carry and
    the checkpoint tree, so chunked and resumed compressed runs are
    bitwise (tests/test_compress.py). ``wire=None`` or
    ``{"dtype": "f32", "topk": 0}`` traces NOTHING — trajectories are
    bitwise the uncompressed ones. The quantizer grid is pinned
    bit-identical to the host codec (``utils/wire.py``), so what the
    matrix measures here is what compressed frames do to the GARs.

    ``step_fn(state, x, y) -> (state, metrics)`` expects ``x``/``y`` with a
    leading ``num_workers`` axis, sharded over ``axis``; it is jit'd with
    replicated state output, so calling it in a loop keeps everything
    on-device.
    """
    gar = _resolve_gar(gar)
    attack_params = dict(attack_params or {})
    gar_params = dict(gar_params or {})
    # Targeted data poisoning (DESIGN.md §17): the Byzantine cohort's
    # BATCHES are rewritten (label flips / trigger stamps) and its
    # gradient rows stay HONEST gradients of the poisoned task — no row
    # transform exists for the GAR paths to see, which is exactly the
    # blindness the per-class eval telemetry measures.
    targeted_cfg = None
    if targeted_lib.is_targeted(attack):
        if f < 1:
            raise ValueError(
                f"targeted attack {attack!r} needs f >= 1 poisoning "
                "workers"
            )
        targeted_cfg = targeted_lib.configure(
            attack, attack_params,
            num_classes=getattr(module, "num_classes", 2),
        )
        if byz_mask is None:
            byz_mask = core.default_byz_mask(num_workers, f)
        attack = None  # the rows are honest; the poison is in the data
        attack_params = {}
    # Adaptive attacks (DESIGN.md §16): resolve the controller config and
    # strip it down to the BASE attack + cleaned params; the magnitude is
    # supplied per step from the carried bracket, never from params.
    adaptive_cfg = None
    if adaptive_lib.is_adaptive(attack):
        if byz_mask is not None:
            raise ValueError(
                "adaptive attacks derive their own Byzantine pool from "
                'attack_params ("f_pool"/"pool"); an explicit byz_mask '
                "would silently fight the rotation schedule — remove it"
            )
        if granularity == "layer":
            raise ValueError(
                "adaptive attacks need whole-model selection feedback; "
                'granularity="layer" runs an independent GAR per tensor '
                "with no single per-rank verdict"
            )
        adaptive_cfg = adaptive_lib.configure(
            attack, attack_params, num_workers=num_workers, f=f
        )
        attack = adaptive_cfg.base
        attack_params = adaptive_lib.base_params(attack_params)
        byz_mask = adaptive_cfg.pool_mask()
    if defense is not None and granularity == "layer":
        raise ValueError(
            "the suspicion-weighted defense needs whole-model selection "
            'evidence; granularity="layer" has no per-rank verdict'
        )
    if gar.stateful_center and "center" in gar_params:
        raise ValueError(
            f"{gar.name!r} carries its center across steps "
            "(TrainState.gar_state); a fixed gar_params 'center' would "
            "silently fight the carried state — remove it (standalone "
            "gars[...](stack, center=...) calls still accept one)"
        )
    if mesh is None:
        mesh = mesh_lib.make_mesh({axis: -1})
    if subset is not None and not (1 <= subset <= num_workers):
        raise ValueError(
            f"subset (wait-for-q) must be in [1, num_workers], got {subset}"
        )
    n_eff = subset if subset is not None else num_workers
    _check_gar(gar, n_eff, f)
    if telemetry and granularity == "layer":
        raise ValueError(
            "telemetry taps report one whole-model selection per rank; "
            'granularity="layer" runs an independent GAR per tensor, '
            "which has no single per-rank mask — run taps at model "
            "granularity"
        )
    if worker_momentum is not None and not (0.0 <= worker_momentum < 1.0):
        raise ValueError(
            f"worker_momentum must be in [0, 1), got {worker_momentum}"
        )
    axis_size = mesh.shape[axis]
    per_shard = mesh_lib.fold(num_workers, axis_size, "workers")
    if attack is not None and attack != "none" and attack not in gradient_attacks:
        raise ValueError(f"unknown attack {attack!r}")
    if byz_mask is None:
        byz_mask = core.default_byz_mask(num_workers, f if attack else 0)
    # Folded attack plan: static for deterministic attacks on
    # fold-capable rules (Gram-form krum/average/bulyan; coordinate-wise
    # median/tmean via remapped-row kernels); None keeps the where-path
    # (fold.plan_for). Adaptive attacks fold only their TRACED-magnitude
    # fake row (per-trace plan below) on Gram-form rules with a static
    # cohort — rotation makes the remap dynamic, and the feedback needs
    # the gram_select weights anyway.
    fold_plan = None
    adaptive_fold = False
    if adaptive_cfg is not None:
        import os

        adaptive_fold = (
            gar.gram_select is not None
            and adaptive_cfg.rotation_period == 0
            and not os.environ.get("GARFIELD_NO_FOLD")
        )
        if not adaptive_fold:
            note_attack_fallback(
                f"adaptive-{adaptive_cfg.base}", path="where",
                why=(
                    "cohort rotation makes the row remap dynamic"
                    if adaptive_cfg.rotation_period > 0
                    else "rule has no gram_select fold form"
                ),
            )
    else:
        fold_plan = fold.plan_for(gar, attack, byz_mask, attack_params)
    byz_mask = jnp.asarray(byz_mask, dtype=bool)
    # Closed-loop defense (see docstring): normalized EMA/weighting knobs.
    # ``weighted`` (default True) enables the GAR-suspicion weighting;
    # ``data`` enables the DATA-plane detectors (aggregators/dataplane.py,
    # DESIGN.md §18) — per-class head-gradient fingerprints, spectral
    # filtering + 2-means cohort flags, folded into their OWN carried
    # exclusion EMA and composed through the same row-weight algebra.
    d_power = d_floor = d_decay = None
    d_weighted = False
    dp_tau = dp_power = dp_floor = dp_decay = None
    if defense is not None:
        from ..aggregators import dataplane as dataplane_lib
        from ..aggregators import defense as defense_lib

        dd = dict(defense)
        d_weighted = bool(dd.pop("weighted", True))
        data_d = dd.pop("data", None)
        if d_weighted:
            d_power = float(dd.pop("power", 2.0))
            d_floor = float(dd.pop("floor", 0.1))
            halflife = float(dd.pop("halflife", 16.0))
            if halflife <= 0.0:
                raise ValueError(
                    f"defense halflife must be > 0, got {halflife}"
                )
            # Per-step multiplicative decay of the carried exclusion EMA:
            # the in-graph twin of MetricsHub(suspicion_halflife=).
            d_decay = float(0.5 ** (1.0 / halflife))
            defense_lib.suspicion_weights(
                [0.0], power=d_power, floor=d_floor
            )  # validate the knobs once, loudly
        if dd:
            raise ValueError(f"unknown defense keys {sorted(dd)}")
        if data_d is not None:
            dpd = dict(data_d)
            dp_tau = float(dpd.pop("tau", dataplane_lib.DEFAULT_TAU))
            dp_power = float(dpd.pop("power", 4.0))
            dp_floor = float(dpd.pop("floor", 0.0))
            dp_halflife = float(dpd.pop("halflife", 8.0))
            if dpd:
                raise ValueError(
                    f"unknown defense.data keys {sorted(dpd)}"
                )
            if dp_tau <= 0.0:
                raise ValueError(f"dp tau must be > 0, got {dp_tau}")
            if dp_halflife <= 0.0:
                raise ValueError(
                    f"dp halflife must be > 0, got {dp_halflife}"
                )
            dp_decay = float(0.5 ** (1.0 / dp_halflife))
            defense_lib.suspicion_weights(
                [0.0], power=dp_power, floor=dp_floor
            )
        if not d_weighted and dp_decay is None:
            raise ValueError(
                "defense enabled with neither suspicion weighting nor "
                "data-plane detectors; pass weighted and/or data"
            )

    # Bounded-staleness emulation (see docstring). Normalized here so the
    # trivially-synchronous configs drop the machinery at BUILD time: the
    # step program is then literally the synchronous one — the bitwise
    # half of the --max_staleness 0 contract.
    stale_ms = stale_decay = stale_weights_static = None
    if staleness is not None:
        import numpy as np

        from ..utils import rounds as rounds_lib

        st = dict(staleness)
        stale_ms = int(st.pop(
            "max_staleness", rounds_lib.DEFAULT_MAX_STALENESS
        ))
        stale_decay = float(st.pop("decay", rounds_lib.DEFAULT_DECAY))
        taus = st.pop("taus", None)
        if st:
            raise ValueError(f"unknown staleness keys {sorted(st)}")
        rounds_lib.StalenessPolicy(stale_ms, stale_decay)  # validate
        if stale_ms == 0:
            staleness = None  # all weights exactly 1: synchronous program
        elif taus is not None:
            taus = np.clip(np.asarray(taus, np.int64), 0, stale_ms)
            if taus.shape != (num_workers,):
                raise ValueError(
                    f"staleness taus must have shape ({num_workers},), "
                    f"got {taus.shape}"
                )
            stale_weights_static = rounds_lib.staleness_weights(
                taus, decay=stale_decay, max_staleness=stale_ms
            )
            if np.all(stale_weights_static == 1.0):
                staleness = None  # all-fresh schedule: same program
        if (staleness is not None and fold_plan is not None
                and gar.gram_select is None):
            # Row weights compose with the fold only through the Gram
            # (fold.folded_tree_aggregate row_weights); the other fold
            # forms consume row values — route through the where-path,
            # which weights rows explicitly.
            fold_plan = None
    if (defense is not None and fold_plan is not None
            and gar.gram_select is None):
        # Suspicion weights are row weights too (defense.suspicion_weights
        # composes through the same algebra as the staleness discount) —
        # same Gram-only fold constraint, same where-path fallback.
        fold_plan = None

    # Wire-compression emulation (see docstring): resolve the scheme at
    # build time so the no-compression configs trace NOTHING — the
    # bitwise contract every other optional feature here honors.
    wire_scheme = wire_div = None
    wire_ef = False
    if wire is not None:
        from ..utils import wire as wire_lib
        from . import compress as compress_lib

        wc = dict(wire)
        w_dtype = str(wc.pop("dtype", "f32")).lower()
        w_topk = int(wc.pop("topk", 0))
        w_ef = bool(wc.pop("error_feedback", True))
        if wc:
            raise ValueError(f"unknown wire keys {sorted(wc)}")
        if w_dtype not in wire_lib.WIRE_DTYPES:
            raise ValueError(
                f"wire dtype must be one of {wire_lib.WIRE_DTYPES}, "
                f"got {w_dtype!r}"
            )
        if w_topk < 0:
            raise ValueError(
                f"wire topk divisor must be >= 0 (0 = off), got {w_topk}"
            )
        if w_topk > 0:
            wire_scheme, wire_div = "topk", w_topk
        elif w_dtype != "f32":
            wire_scheme = w_dtype
        # EF is only sound (and only needed) for the biased lossy
        # schemes; bf16 stays EF-free like the PR 4 host wire.
        wire_ef = w_ef and wire_scheme in ("int8", "int4", "topk")

    init_worker, grad_fn, eval_apply = core.make_worker_fns(module, loss_fn)
    # Slot-fused gradient twin (models/slotfused.py) when eligible, else
    # run-length-aware unroll/vmap (core.select_slot_path).
    slot_fused_fn, force_unroll = core.select_slot_path(
        module, loss_fn, per_shard, num_iter, log_tag="aggregathor"
    )
    repl = NamedSharding(mesh, P())
    shard_w = NamedSharding(mesh, P(axis))

    def init_fn(key, example_x, seed_rng=None):
        params, model_state = init_worker(key, example_x)
        opt_state = optimizer.init(params)
        worker_mom = None
        if worker_momentum is not None:
            worker_mom = core.worker_mom_init(params, num_workers, gar_dtype)
        gar_state = None
        if gar.stateful_center:
            # cclip's carried center (v_0 = previous aggregate, the
            # paper's recipe); zeros at step 0 — that first aggregate is
            # tau-bounded from the origin (cclip.py docstring).
            gar_state = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
        attack_state = None
        if adaptive_cfg is not None:
            # The bisection bracket starts wide open; the first rounds
            # ARE the controller's probes (attacks/adaptive.py).
            attack_state = adaptive_lib.init_state(adaptive_cfg)
        defense_state = None
        if defense is not None:
            # Carried exclusion EMAs: nothing observed yet, suspicion 0,
            # weights exactly 1.0 — the clean-history identity. The
            # data-plane detectors carry their OWN twins (independent
            # halflife; a GAR exclusion and a fingerprint flag are
            # different evidence).
            defense_state = {}
            if d_weighted:
                defense_state.update({
                    "obs": jnp.zeros((num_workers,), jnp.float32),
                    "exc": jnp.zeros((num_workers,), jnp.float32),
                })
            if dp_decay is not None:
                defense_state.update({
                    "dp_obs": jnp.zeros((num_workers,), jnp.float32),
                    "dp_exc": jnp.zeros((num_workers,), jnp.float32),
                })
        wire_state = None
        if wire_ef:
            # Zero EF residuals — checkpointed with the rest of the
            # state tree, so a resumed run carries them bitwise.
            d_flat = sum(
                int(l.size) for l in jax.tree.leaves(params)
            )
            wire_state = compress_lib.init_wire_state(num_workers, d_flat)
        state = core.TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            model_state=model_state,
            opt_state=opt_state,
            rng=key if seed_rng is None else seed_rng,
            worker_mom=worker_mom,
            gar_state=gar_state,
            attack_state=attack_state,
            defense_state=defense_state,
            wire_state=wire_state,
        )
        return jax.device_put(state, repl)

    def _local_step(state, x_local, y_local):
        """Body run per shard under shard_map."""
        params, ms = state.params, state.model_state
        base = jax.random.fold_in(state.rng, state.step)
        atk_key, sub_key, gar_key, drop_base = jax.random.split(base, 4)
        shard_idx = jax.lax.axis_index(axis)
        slot_ids = shard_idx * per_shard + jnp.arange(per_shard)
        drop_keys = jax.vmap(lambda i: jax.random.fold_in(drop_base, i))(slot_ids)

        if targeted_cfg is not None:
            # Targeted poisoning (DESIGN.md §17): rewrite the Byzantine
            # slots' batches BEFORE the gradient pass — label flips /
            # trigger stamps on their own data, honest gradients of the
            # poisoned task afterwards. Honest slots' batches are
            # selected back bitwise, and targeted_cfg None traces none
            # of this (the defense-off bitwise contract).
            byz_local = byz_mask[slot_ids]
            xs_p, ys_p = [], []
            with core.phase("attack"):
                for k in range(per_shard):
                    xk, yk = targeted_lib.poison_batch(
                        targeted_cfg, x_local[k], y_local[k], seed=k,
                        step=state.step,
                    )
                    xs_p.append(xk)
                    ys_p.append(yk)
                x_pois = jnp.stack(xs_p)
                y_pois = jnp.stack(ys_p)
                x_local = jnp.where(
                    byz_local.reshape(
                        (per_shard,) + (1,) * (x_local.ndim - 1)
                    ),
                    x_pois, x_local,
                )
                y_local = jnp.where(
                    byz_local.reshape(
                        (per_shard,) + (1,) * (y_local.ndim - 1)
                    ),
                    y_pois, y_local,
                )

        # Unrolled (not vmapped) per-slot gradients: kills the 5-D relayout
        # tax of the logical-worker fold (core.per_slot_grads docstring).
        # Keep the stacked TREE here and flatten after the gather — raveling
        # each slot inside the unroll (flat=True) measured 12% SLOWER
        # end-to-end (55 vs 62 steps/s): the 8 per-slot concats serialize
        # against the fwd+bwd graphs, while one vmapped ravel of the stacked
        # leaves fuses cleanly.
        grads_local, (loss_local, ms_local) = core.per_slot_grads(
            grad_fn, params, ms, x_local, y_local, drop_keys,
            fused_fn=slot_fused_fn, force_unroll=force_unroll,
            dtype=gar_dtype,
        )
        # Narrow the aggregation pipeline (see make_trainer docstring); the
        # cast fuses into the backward's output writes. No-op when None,
        # and where the unroll has cast slot by slot already.
        with core.phase("grads"):
            grads_local = core.cast_leaves(grads_local, gar_dtype)

        # all_gather over the mesh axis == Server.get_gradients (RPC gather).
        with core.phase("exchange"):
            grads = jax.tree.map(
                lambda l: jax.lax.all_gather(l, axis, tiled=True),
                grads_local,
            )
            losses = jax.lax.all_gather(loss_local, axis, tiled=True)
        new_ms = core.mean_model_state(ms_local, axis)

        # Worker momentum (see make_trainer docstring): every worker submits
        # its EMA instead of the raw gradient. Elementwise over the stacked
        # tree, so it composes with the tree-mode AND flat GAR paths below;
        # the honest update is stored, the attack poisons its rows after.
        new_mom = state.worker_mom
        if worker_momentum is not None:
            with core.phase("grads"):  # the workers' side of the exchange
                grads = core.worker_mom_update(
                    worker_momentum, state.worker_mom, grads
                )
            new_mom = grads

        # Wire-compression emulation (see docstring): encode->decode the
        # rows every honest worker would put on the wire. AFTER momentum
        # (the EMA is worker-local host state, accumulated uncompressed),
        # BEFORE the attack (a Byzantine sender controls its bytes — the
        # attack overwrites its rows downstream, exactly as on the
        # cluster). The GARs then consume dense f32-dequantized rows, so
        # fold/row-weight algebra is untouched by construction.
        new_wire = state.wire_state
        if wire_scheme is not None:
            with core.phase("exchange"):
                flat_w = core.flatten_rows(grads).astype(jnp.float32)
                w_k = (
                    wire_lib.topk_k(flat_w.shape[1], wire_div)
                    if wire_scheme == "topk" else None
                )
                if wire_ef:
                    sent_w, resid_w = compress_lib.ef_roundtrip_rows(
                        flat_w, state.wire_state["resid"], wire_scheme,
                        k=w_k,
                    )
                    new_wire = {"resid": resid_w}
                else:
                    sent_w = compress_lib.roundtrip_rows(
                        flat_w, wire_scheme, k=w_k
                    )
                grads = jax.vmap(
                    lambda r: core.unflatten_like(params, r)
                )(sent_w)
                grads = core.cast_leaves(grads, gar_dtype)

        honest = (~byz_mask).astype(losses.dtype)
        mean_loss = jnp.sum(losses * honest) / jnp.sum(honest)

        # Bounded-staleness weights (emulation; see docstring): fixed
        # per-rank schedule, or a fresh seeded draw each step. The key is
        # fold_in-derived (NOT an extra split) so synchronous configs'
        # key derivation — and therefore every pinned trajectory — is
        # untouched.
        stale_w = None
        if staleness is not None:
            if stale_weights_static is not None:
                stale_w = jnp.asarray(stale_weights_static)
            else:
                stale_taus = jax.random.randint(
                    jax.random.fold_in(base, 0x57A1E),
                    (num_workers,), 0, stale_ms + 1,
                )
                stale_w = rounds_lib.staleness_weights(
                    stale_taus, decay=stale_decay, max_staleness=stale_ms
                )

        # Adaptive controller (DESIGN.md §16): play the carried bracket's
        # midpoint, rotate the active cohort, and burst to full magnitude
        # when the staleness emulation opens a quorum-degradation window
        # (an honest rank hard-cut this round). All traced; nothing here
        # exists in the program when the attack is oblivious.
        act_mask = byz_mask
        eff_params = attack_params
        atk_mag = degraded = None
        a_lo = a_hi = None
        if adaptive_cfg is not None:
            with core.phase("attack"):
                a_lo = state.attack_state["lo"]
                a_hi = state.attack_state["hi"]
                atk_mag = adaptive_lib.played_magnitude(a_lo, a_hi)
                if stale_w is not None:
                    # Quorum-degradation window (emulated): an HONEST rank at
                    # the staleness cutoff's floor weight (or excluded
                    # outright) — the emulation clips taus to the cutoff, so
                    # the floor IS the hard-cut signature a host-plane
                    # straggler/partition produces.
                    floor_w = jnp.float32(
                        (stale_decay ** stale_ms) * (1.0 + 1e-5)
                    )
                    degraded = jnp.any((stale_w <= floor_w) & ~byz_mask)
                    atk_mag = jnp.where(
                        degraded, jnp.float32(adaptive_cfg.burst_mag), atk_mag
                    )
                act_mask = adaptive_lib.active_mask_traced(
                    adaptive_cfg, state.step
                )
                eff_params = dict(attack_params)
                eff_params[
                    adaptive_lib.magnitude_key(adaptive_cfg.base)
                ] = atk_mag

        # Closed-loop defense weights (aggregators/defense.py): suspicion
        # from the carried exclusion EMA, composed into the SAME row-
        # weight algebra as the staleness discount. Exactly 1.0 on a
        # clean history (the weighted identity contract).
        def_w = None
        if defense is not None and d_weighted:
            susp = state.defense_state["exc"] / jnp.maximum(
                state.defense_state["obs"], 1e-6
            )
            def_w = defense_lib.suspicion_weights(
                susp, power=d_power, floor=d_floor
            )

        # Data-plane defense (aggregators/dataplane.py, DESIGN.md §18):
        # fingerprint the classifier-head block of the SAME stacked tree
        # the rule consumes (post-momentum — the rows a data poisoner
        # actually submitted), run the spectral + 2-means detectors, map
        # the carried dp exclusion EMA through the suspicion-weight law,
        # and compose by CENTER-PULL: suspect rows collapse onto the
        # dp-weight-weighted TRUSTED mean instead of being scaled toward
        # the origin (toward-zero dampening hands a data poisoner krum
        # centrality — the inlier inversion measured in DEFBENCH; see
        # dataplane.center_pull_rows). The transform is per-leaf
        # elementwise like the momentum update, so every downstream path
        # (tree, fold, flat) is unchanged. Traced out entirely when off
        # (the TapBundle convention).
        dp_w = dp_scores = dp_flags = None
        if dp_decay is not None:
            head_k, head_b = dataplane_lib.head_leaves(grads)
            if head_k is None:
                raise ValueError(
                    "data-plane defense needs a classifier head (no "
                    "2-D parameter leaf in this model)"
                )
            with core.phase("rule"):
                dp_scores, flags_b = dataplane_lib.detect(
                    head_k, head_b, f=max(1, f), tau=dp_tau
                )
                dp_flags = flags_b.astype(jnp.float32)
                dp_susp = state.defense_state["dp_exc"] / jnp.maximum(
                    state.defense_state["dp_obs"], 1e-6
                )
                dp_w = defense_lib.suspicion_weights(
                    dp_susp, power=dp_power, floor=dp_floor
                )
                grads = dataplane_lib.center_pull_tree(grads, dp_w)
        row_w = stale_w
        if def_w is not None:
            row_w = def_w if row_w is None else row_w * def_w

        # Selection feedback the two carries consume: the rule's (n,)
        # selection weights (sel_w) and the observation mask (obs_vec).
        need_sel = adaptive_cfg is not None or d_weighted
        sel_w = quorum_idx = None

        agg_kwargs = dict(
            attack=attack, attack_params=eff_params, gar=gar, f=f,
            subset=subset, gar_params=gar_params, row_weights=row_w,
        )
        center_kw = (
            {"center": state.gar_state} if gar.stateful_center else {}
        )
        if _tree_path_ok(tree_path, subset, num_workers, granularity, gar,
                         subset_gram_ok=True):
            # Tree-mode fast path: no (n, d) flat stack (PERF.md: the
            # flatten + unflatten round trip costs ~5 ms/step at ResNet-18
            # scale on one chip). True subsets stay here for Gram-form
            # rules (sub-Gram composition); others go flat —
            # see _tree_path_ok.
            sel = None
            if subset is not None and subset < num_workers:
                # SAME key derivation as the flat path's
                # _attack_then_aggregate, so tree and flat trajectories
                # sample identical wait-n-f subsets.
                sel = core.subset_indices(sub_key, num_workers, subset)
            if fold_plan is not None or adaptive_fold:
                # Folded attack: poison the Gram, never the rows — the raw
                # per-leaf Grams keep fusing into the backward epilogue
                # like the fault-free step (parallel/fold.py; 1.16x on the
                # krum+lie north-star). Staleness/suspicion weights
                # compose into the fold's row scales (row_weights), and
                # the adaptive magnitude into the shared fake row
                # (traced_fold_plan), so the fast path survives both the
                # async emulation and the adaptive adversary.
                with core.phase("attack"):
                    plan_now = (
                        adaptive_lib.traced_fold_plan(adaptive_cfg, atk_mag)
                        if adaptive_fold else fold_plan
                    )
                out = fold.folded_tree_aggregate(
                    gar, plan_now, grads, f=f, key=gar_key,
                    gar_params={**gar_params, **center_kw},
                    subset_sel=sel, row_weights=row_w,
                    return_weights=need_sel,
                )
                if need_sel:
                    aggr_tree, sel_w = out
                    quorum_idx = sel
                else:
                    aggr_tree = out
            else:
                with core.phase("attack"):
                    poisoned = apply_gradient_attack_tree(
                        attack, grads, act_mask, key=atk_key, **eff_params
                    )
                if row_w is not None:
                    # Weight the post-attack rows — what the host-plane
                    # PS aggregates (poisoned arrivals, then discounted).
                    with core.phase("rule"):
                        poisoned = jax.tree.map(
                            lambda l: (l * row_w.reshape(
                                (num_workers,) + (1,) * (l.ndim - 1)
                            )).astype(l.dtype),
                            poisoned,
                        )
                if sel is not None:
                    # Wait-n-f on the Gram: select on the (q, q) sub-Gram,
                    # scatter the weights back — per-leaf row gathers never
                    # happen (the 3.5x regression _tree_path_ok documents).
                    from ..aggregators._common import (
                        tree_gram, tree_weighted_sum,
                    )

                    with core.phase("rule"):
                        gram = tree_gram(poisoned)
                        w_sub = gar.gram_select(
                            gram[sel][:, sel], f=f, key=gar_key,
                            **gar_params
                        )
                        w = jnp.zeros(
                            (num_workers,), jnp.float32
                        ).at[sel].set(w_sub)
                        aggr_tree = tree_weighted_sum(poisoned, w)
                    if need_sel:
                        sel_w = w
                        quorum_idx = sel
                else:
                    with core.phase("rule"):
                        aggr_tree = gar.tree_aggregate(
                            poisoned, f=f, key=gar_key, **gar_params,
                            **center_kw
                        )
        elif granularity == "layer":
            # Garfield_CC per-parameter aggregation: independent GAR (and
            # attack statistics) per tensor, like the reference's per-layer
            # gather->GAR loop (Garfield_CC/trainer.py:91-127). Each leaf is
            # reshaped in place (free) — no flat stack is built. Stateful
            # rules (cclip) get their carried center per leaf, so layer
            # aggregation keeps the same v_0 semantics as whole-model.
            leaves, treedef = jax.tree.flatten(grads)
            c_leaves = (
                jax.tree.leaves(state.gar_state) if gar.stateful_center
                else [None] * len(leaves)
            )
            out_leaves = []
            for i, (leaf, c) in enumerate(zip(leaves, c_leaves)):
                n = leaf.shape[0]
                flat = leaf.reshape(n, -1)
                akey = jax.random.fold_in(atk_key, i)
                gkey = jax.random.fold_in(gar_key, i)
                aggr = _attack_then_aggregate(
                    flat, act_mask, akey, sub_key, gkey,
                    **agg_kwargs,
                    **({"center": c.reshape(-1)} if c is not None else {}),
                )
                out_leaves.append(aggr.reshape(leaf.shape[1:]))
            aggr_tree = jax.tree.unflatten(treedef, out_leaves)
        else:
            with core.phase("rule"):
                flat_stack = core.flatten_rows(grads)  # (n_w, d)
                flat_center = (
                    {"center": ravel_pytree(state.gar_state)[0]}
                    if gar.stateful_center else {}
                )
            aggr = _attack_then_aggregate(
                flat_stack, act_mask, atk_key, sub_key, gar_key,
                **agg_kwargs, **flat_center,
            )
            with core.phase("rule"):
                aggr_tree = core.unflatten_like(params, aggr)

        if need_sel and sel_w is None:
            # Feedback fallback: the aggregation path exposed no selection
            # weights (non-Gram rule, flat path, or full-participation
            # tree aggregate) — recompute the rule's verdict over the
            # SAME poisoned, weighted rows via the audit-tap machinery
            # (exactly the telemetry recomputation below; XLA CSEs the
            # shared subgraphs). Adaptive/defense-only cost.
            with core.phase("rule"):
                flat_fb = core.flatten_rows(grads)
                poisoned_fb = apply_gradient_attack(
                    attack, flat_fb, act_mask, key=atk_key, **eff_params
                )
                if row_w is not None:
                    poisoned_fb = (poisoned_fb * row_w[:, None]).astype(
                        poisoned_fb.dtype
                    )
                fb_center = (
                    ravel_pytree(state.gar_state)[0]
                    if gar.stateful_center else None
                )
                if subset is not None and subset < num_workers:
                    quorum_idx = core.subset_indices(
                        sub_key, num_workers, subset
                    )
                    bundle = taps_lib.compute_flat(
                        gar.name, poisoned_fb[quorum_idx], f, key=gar_key,
                        params=gar_params, center=fb_center,
                    )
                    sel_w = jnp.zeros((num_workers,), jnp.float32).at[
                        quorum_idx
                    ].set(bundle["selected"])
                else:
                    bundle = taps_lib.compute_flat(
                        gar.name, poisoned_fb, f, key=gar_key,
                        params=gar_params, center=fb_center,
                    )
                    sel_w = bundle["selected"]

        obs_vec = None
        if need_sel:
            if quorum_idx is not None:
                obs_vec = jnp.zeros((num_workers,), jnp.float32).at[
                    quorum_idx
                ].set(1.0)
            else:
                obs_vec = jnp.ones((num_workers,), jnp.float32)

        new_attack_state = state.attack_state
        detected = None
        if adaptive_cfg is not None:
            # Feedback = was the active cohort admitted? Majority-excluded
            # among the OBSERVED colluders counts as detected; a round
            # that observed none (whole cohort outside the quorum) and a
            # burst round (not the bracket's probe) hold the bracket.
            with core.phase("attack"):
                act_f = act_mask.astype(jnp.float32) * obs_vec
                cnt = jnp.sum(act_f)
                admitted = jnp.sum((sel_w > 0).astype(jnp.float32) * act_f)
                detected = admitted * 2.0 < cnt
                upd_lo, upd_hi = adaptive_lib.update_bracket(
                    a_lo, a_hi, detected,
                    mag_min=adaptive_cfg.mag_min,
                    mag_max=adaptive_cfg.mag_max,
                    regrow=adaptive_cfg.regrow,
                )
                hold = cnt == 0.0
                if degraded is not None:
                    hold = hold | degraded
                new_attack_state = {
                    "lo": jnp.where(hold, a_lo, upd_lo),
                    "hi": jnp.where(hold, a_hi, upd_hi),
                }

        new_defense_state = state.defense_state
        if defense is not None:
            with core.phase("rule"):
                new_defense_state = dict(state.defense_state)
                if d_weighted:
                    # The hub's exclusion law (observed minus admitted),
                    # carried as an exponentially-decayed EMA — the in-graph
                    # twin of MetricsHub(suspicion_halflife=).
                    ind = (sel_w > 0).astype(jnp.float32) * obs_vec
                    dec = jnp.float32(d_decay)
                    new_defense_state["obs"] = (
                        state.defense_state["obs"] * dec + obs_vec
                    )
                    new_defense_state["exc"] = (
                        state.defense_state["exc"] * dec + (obs_vec - ind)
                    )
                if dp_decay is not None:
                    # Data-plane twins: the detectors observe the FULL
                    # gathered stack every step (the subset emulation applies
                    # at selection, after the gather), so every rank is
                    # observed and a flag is an exclusion.
                    dpdec = jnp.float32(dp_decay)
                    ones = jnp.ones((num_workers,), jnp.float32)
                    new_defense_state["dp_obs"] = (
                        state.defense_state["dp_obs"] * dpdec + ones
                    )
                    new_defense_state["dp_exc"] = (
                        state.defense_state["dp_exc"] * dpdec + dp_flags
                    )

        with core.phase("update"):
            new_gar_state = state.gar_state
            if gar.stateful_center:
                # Next step's v_0 = this step's aggregate (f32 — the carried
                # center should not round through the bf16 pipeline).
                new_gar_state = jax.tree.map(
                    lambda l: l.astype(jnp.float32), aggr_tree
                )
            aggr_tree = core.cast_like(aggr_tree, params)  # no-op at f32
            updates, new_opt = optimizer.update(
                aggr_tree, state.opt_state, params
            )
            new_params = optax.apply_updates(params, updates)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                model_state=new_ms,
                opt_state=new_opt,
                worker_mom=new_mom,
                gar_state=new_gar_state,
                attack_state=new_attack_state,
                defense_state=new_defense_state,
                wire_state=new_wire,
            )
        metrics = {"loss": mean_loss}
        # The counters of a model that keeps some (core.COUNTER_SUMS /
        # COUNTER_MAXES), under their own names; {} for every other model,
        # which traces nothing.
        metrics.update(core.step_counters(ms_local, axis))
        if wire_ef:
            # Per-rank EF residual L2 norms — the in-graph twin of the
            # wire event's ef_residual_norm field (schema v11).
            metrics["wire_resid_norm"] = jnp.sqrt(
                jnp.sum(new_wire["resid"] ** 2, axis=1)
            )
        if adaptive_cfg is not None:
            # Controller observability (the app loop surfaces these as
            # schema-v7 ``attack_adapt`` events): the magnitude actually
            # played and whether the rule caught it this round.
            metrics["attack_mag"] = jnp.asarray(atk_mag, jnp.float32)
            metrics["attack_detected"] = detected.astype(jnp.float32)
        if defense is not None and d_weighted:
            # The suspicion weights actually composed this step (the app
            # loop surfaces them as ``defense_weights`` events — the
            # summary's suspicion-weight digest at the on-mesh scale).
            metrics["defense_w"] = def_w
        if dp_decay is not None:
            # Data-plane observability (schema v9 ``data_defense``
            # events): the per-rank spectral outlier scores, this
            # round's detector flags, and the weights composed.
            metrics["dataplane_score"] = dp_scores.astype(jnp.float32)
            metrics["dataplane_flags"] = dp_flags
            metrics["dataplane_w"] = dp_w
        if telemetry:
            # In-graph audit tap (telemetry/taps.py): recompute the
            # poisoned flat stack with the SAME keys the aggregation used
            # — on the flat path XLA CSEs this against the rule's own
            # pass; on the tree/fold paths it is the enabled-only
            # overhead the docstring prices. Nothing here flows into
            # new_state, so the trajectory is untouched.
            with core.phase("rule"):  # a tap recomputes the rule's view
                flat_raw = core.flatten_rows(grads)
                poisoned = apply_gradient_attack(
                    attack, flat_raw, act_mask, key=atk_key, **eff_params
                )
                if row_w is not None:
                    # The tap audits the rule's selection over the SAME rows
                    # the rule consumed — staleness- and suspicion-weighted
                    # (and adaptively poisoned) included.
                    poisoned = (poisoned * row_w[:, None]).astype(
                        poisoned.dtype
                    )
                tap_center = (
                    ravel_pytree(state.gar_state)[0]
                    if gar.stateful_center else None
                )
                if subset is not None and subset < num_workers:
                    tap_sel = core.subset_indices(sub_key, num_workers, subset)
                    bundle = taps_lib.compute_flat(
                        gar.name, poisoned[tap_sel], f, key=gar_key,
                        params=gar_params, center=tap_center,
                    )
                    metrics["tap"] = taps_lib.scatter(
                        bundle, tap_sel, num_workers
                    )
                else:
                    metrics["tap"] = taps_lib.compute_flat(
                        gar.name, poisoned, f, key=gar_key, params=gar_params,
                        center=tap_center,
                    )
        return new_state, metrics

    sharded_step = jax.shard_map(
        _local_step,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @functools.partial(
        jax.jit,
        out_shardings=(repl, repl),
        donate_argnums=core.step_donation(),
    )
    def step_fn(state, x, y):
        return sharded_step(state, x, y)

    @jax.jit
    def eval_fn(state, x):
        return eval_apply(state.params, state.model_state, x)

    step_fn.mesh = mesh
    step_fn.batch_sharding = shard_w
    # The un-jitted shard_map body + this jit's output shardings, consumed
    # by core.make_chunked_step so a K-step chunk scans the SAME program
    # body instead of nesting jits (whose inner donation would be dropped).
    step_fn.inner = sharded_step
    step_fn.out_shardings = (repl, repl)
    return init_fn, step_fn, eval_fn
