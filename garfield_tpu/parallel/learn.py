"""LEARN topology: fully decentralized Byzantine-resilient collaborative
learning (every node is Worker + Server).

TPU-native re-design of ``pytorch_impl/applications/LEARN/trainer.py``
(node loop :224-257, ``avg_agree`` gossip :208-222): n peer nodes each hold
their own model and data shard; per step each node

    1. computes its own gradient                       (trainer.py:233-236)
    2. gathers everyone's gradients and aggregates     (:237-241)
    3. (non-iid) repeats ceil(log2 t) "agreement" rounds, re-gathering the
       peers' *aggregated* gradients and re-aggregating (:208-222, :251-252)
    4. applies its optimizer                            (:247-249)
    5. gossips models: gathers peer models, GAR-aggregates, writes back
                                                        (:255-257)

SPMD mapping (SURVEY §2.3 "Decentralized P2P" row): one "nodes" mesh axis;
model/optimizer state is stacked over it; every get_aggr_grads/get_models RPC
poll (server.py:202-233) becomes one all_gather. Byzantine nodes inject
gradient attacks (byzWorker.py) in phases 1-3 and model attacks
(byzServer.py) in phase 5 — value transforms on their rows of the gathered
stacks.

Wait-n-f semantics: the reference's LEARN never waits for everyone — each
node takes the *fastest* ``n - f`` peer responses at every exchange
(``ps.get_gradients(i, n-f)`` trainer.py:249, ``get_models(n-f)`` :255, and
``avg_agree``'s ``num_wait_ps`` :208-222). Arrival order is effectively
random, so the bulk-synchronous stand-in is a per-node seeded subset
(``core.subset_indices``, same pattern as byzsgd's per-PS subsets): each
node aggregates its OWN q-subset of the gathered stack. That is exactly why
honest nodes hold *different* aggregates — the disagreement the ceil(log2 t)
agreement rounds exist to reconcile (and without which they would be vacuous
re-aggregations of one vector).

The ceil(log2 t) round count is data-dependent on the step counter, so the
gossip loop is a ``lax.fori_loop`` over a static ``max_rounds`` with rounds
beyond the target masked to no-ops (XLA needs static trip structure).

Fast-path parity with aggregathor (the dispatch matrix that topology got in
r4-r5, ported here): both gradient exchanges (phase 2 and every agreement
round) AND the model gossip dispatch through the tree/fold stack when
eligible —

  - deterministic attacks (lie/empire/reverse/crash; byzServer's
    reverse/crash on the model plane) fold into a Gram remap
    (``fold.plan_for`` / ``fold.plan_for_model``): the poisoned rows are
    never written and the raw per-leaf Grams fuse like the fault-free step;
  - randomized attacks (random/drop) poison the stacked TREE via the
    where-path (``apply_gradient_attack_tree``) and the GAR still runs in
    tree mode — the (n, d) flat stack is never built;
  - per-node wait-n-f subsets COMPOSE with the fold for Gram-form rules:
    one extension + Gram build serves every local node slot, each adding
    only a (q, q) sub-Gram selection (``fold.folded_tree_aggregate_multi``
    — the multi-observer form of aggregathor's subset fast path); non-Gram
    rules under true subsets keep the flat path (the same
    ``_tree_path_ok`` gate as aggregathor/byzsgd);
  - stateful-center rules (cclip) carry a PER-NODE center in
    ``TrainState.gar_state``: v_0 of phase 2 is the node's previous final
    aggregate (robust coordinate-median init at step 0 only, under a
    ``lax.cond`` so the median pass executes exactly once per run), each
    agreement round re-centers on the node's current aggregate, and the
    model gossip centers on the node's OWN model — the ClippedGossip
    recipe (Karimireddy et al. 2021) — so the per-step median init
    (~5.3 ms at ResNet-18 scale, PERF.md r5) disappears from the
    decentralized defense config.

``tree_path=False`` forces the flat reference-shaped path everywhere (the
A/B lever the trajectory-equivalence tests drive).
"""

import functools
import math

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..attacks import (
    adaptive as adaptive_lib,
    apply_gradient_attack,
    apply_gradient_attack_tree,
    apply_model_attack_rows,
    model_attacks,
    model_collusion_attacks,
    note_attack_fallback,
)
from ..telemetry import taps as taps_lib
from . import core, fold, mesh as mesh_lib
from .aggregathor import _check_gar, _resolve_gar, _tree_path_ok

__all__ = ["make_trainer"]


def make_trainer(
    module,
    loss_fn,
    optimizer,
    gar,
    *,
    num_nodes,
    f=0,
    attack=None,
    attack_params=None,
    model_attack=None,
    model_attack_params=None,
    byz_mask=None,
    mesh=None,
    axis="nodes",
    non_iid=False,
    max_rounds=12,
    model_gossip=True,
    subset=None,
    track_spread=False,
    gar_dtype=None,
    worker_momentum=None,
    gar_params=None,
    tree_path=True,
    num_iter=None,
    telemetry=False,
    staleness=None,
    defense=None,
):
    """Build ``(init_fn, step_fn, eval_fn)`` for the LEARN topology.

    ``model_attack`` additionally accepts the model-plane COLLUSION
    attacks (``lie``/``empire`` over the gossiped stack, DESIGN.md §17)
    and their ADAPTIVE controllers (``adaptive-lie``/``adaptive-empire``):
    the gossip-poisoning magnitude becomes a bisection bracket carried in
    ``TrainState.attack_state``, fed back each step by whether the
    Byzantine nodes' gossiped models entered the model aggregation's
    selection — the decentralized twin of byzsgd's Byzantine-PS
    controller, attacking LEARN's plane-2 gossip.

    ``defense`` (aggregators/defense.py) deploys suspicion weighting on
    ALL THREE exchange phases: a dict with ``power``/``floor``/
    ``halflife`` enables a per-node exclusion EMA carried in
    ``TrainState.defense_state`` (fed by the phase-2 observer-mean
    selection — node identity is shared across the planes, so one
    history serves the gradient gather, every agreement round AND the
    model gossip), mapped through ``defense.suspicion_weights`` and
    composed into the SAME row-weight algebra as the per-phase staleness
    discount (fold ``row_weights`` on Gram rules, explicit row scaling
    elsewhere). ``defense=None`` (default) traces nothing — trajectories
    are bitwise the undefended ones. Rule escalation lives above the
    trainer (apps/common.py), which rebuilds the step at level changes.

    ``telemetry`` adds ``metrics["tap"]`` — the phase-2 gradient
    exchange's ``TapBundle`` (telemetry/taps.py). Under per-node
    wait-n-f subsets the exported bundle is the OBSERVER MEAN across all
    n nodes' views: ``observed`` is the fraction of nodes whose quorum
    contained the rank, ``selected`` the mean influence its gradient
    earned. Agreement rounds and the model gossip are not tapped (the
    phase-2 selection is the per-rank audit signal). Off by default:
    nothing tap-shaped is traced, and taps never enter TrainState —
    taps-on trajectories are bitwise equal to taps-off.

    ``non_iid=True`` enables the ceil(log2 t) agreement rounds
    (LEARN/trainer.py:251-252 runs them only for non-iid data); ``max_rounds``
    caps them (2^12 = 4096 steps of exact parity by default).
    ``subset=q`` enables wait-n-f: every node aggregates its own seeded
    q-subset of the gathered gradients / agreement aggregates / gossiped
    models, the stand-in for taking the q = n - f *fastest* peer responses
    (LEARN/trainer.py:249, :255, avg_agree :208-222). With it, honest nodes
    hold genuinely different aggregates between agreement rounds.
    ``track_spread=True`` adds ``aggr_spread_pre`` / ``aggr_spread_post``
    metrics — the max pairwise L-inf distance between honest nodes'
    aggregates before and after the agreement rounds (costs one extra
    (n, d) all_gather; leave off in production).
    ``gar_dtype`` narrows the gradient pipeline (cast at the backward
    epilogue; gathers, attacks, aggregation and agreement rounds run at
    the narrow width; cast back at the optimizer boundary) — aggregathor's
    flag, applied to LEARN's phases 2-4. Model gossip stays full width.
    ``worker_momentum`` (beta in [0, 1)): each node publishes the EMA of
    its OWN gradients instead of the raw gradient — the decentralized form
    of Karimireddy et al. 2021 (their ClippedGossip follow-up pairs exactly
    this with clipped aggregation; use ``gar="cclip"``). The per-node
    momentum stack lives in ``TrainState.worker_mom``, sharded over the
    nodes axis with the rest of the node state. Pair with a plain-SGD
    optimizer (see aggregathor.make_trainer — the EMA is the momentum).
    ``tree_path`` (default on) routes every exchange through the tree/fold
    fast path where eligible (see module docstring); False forces the flat
    (n, d) path everywhere (A/B tests).
    ``num_iter`` is the run-length hint for the unroll-vs-vmap per-slot
    gradient decision (``core.slot_path_decision``; the slot-FUSED twin is
    structurally inapplicable here — per-node params mean there is no
    single shared kernel for the fused forward to use).
    ``staleness`` is the in-graph EMULATION of the host plane's
    bounded-staleness async mode on the decentralized topology
    (DESIGN.md §15) — the asynchrony analog of the seeded ``subset``
    emulation, now PER PHASE: a dict with ``max_staleness`` (hard
    cutoff, rounds), ``decay`` (geometric discount), and optional
    ``taus`` (a FIXED per-node staleness assignment). Each exchange
    PHASE — the phase-2 gradient gather, every agreement round, and the
    phase-5 model gossip — draws its own seeded per-node staleness
    (fixed ``taus`` apply to every phase) and scales the gathered rows
    by ``utils.rounds.staleness_weights`` before the rule, composed into
    the folded-attack row scales on Gram-form rules
    (``fold.folded_tree_aggregate_multi`` ``row_weights``) so the fast
    path survives; non-Gram rules route to the flat path, which weights
    rows explicitly. At ``max_staleness=0`` (or all-zero ``taus``) the
    machinery is dropped at build time and trajectories are BITWISE the
    synchronous ones (tests/test_staleness.py).
    ``step_fn(state, x, y)``: leading ``num_nodes`` axis on x/y and on every
    params/opt_state leaf, all sharded over ``axis``.
    """
    gar = _resolve_gar(gar)
    attack_params = dict(attack_params or {})
    gar_params = dict(gar_params or {})
    model_attack_params = dict(model_attack_params or {})
    if gar.stateful_center and "center" in gar_params:
        raise ValueError(
            f"{gar.name!r} carries its center across steps "
            "(TrainState.gar_state); a fixed gar_params 'center' would "
            "silently fight the carried state — remove it (standalone "
            "gars[...](stack, center=...) calls still accept one)"
        )
    if mesh is None:
        mesh = mesh_lib.make_mesh({axis: -1})
    per_n = mesh_lib.fold(num_nodes, mesh.shape[axis], "nodes")
    if subset is not None and not (1 <= subset <= num_nodes):
        raise ValueError(f"subset must be in [1, {num_nodes}], got {subset}")
    # The GAR sees `subset` rows when waiting (reference passes the n-f
    # received gradients straight to the rule, LEARN/trainer.py:241).
    _check_gar(gar, subset if subset else num_nodes, f)
    if worker_momentum is not None and not (0.0 <= worker_momentum < 1.0):
        raise ValueError(
            f"worker_momentum must be in [0, 1), got {worker_momentum}"
        )
    from ..attacks import targeted as targeted_lib

    if targeted_lib.is_targeted(attack):
        raise ValueError(
            f"targeted attack {attack!r} poisons worker BATCHES and is "
            "deployed on the aggregathor topology in-graph (and on real "
            "cluster workers/nodes via apps/cluster.py); the LEARN "
            "in-graph twin does not support it"
        )
    # Adaptive GOSSIP poisoner (DESIGN.md §17): resolve the controller,
    # keep the base collusion attack; the magnitude comes from the
    # carried bracket each step.
    model_adaptive_cfg = None
    if adaptive_lib.is_adaptive(model_attack):
        if not model_gossip:
            raise ValueError(
                "adaptive gossip attacks poison the phase-5 model gossip; "
                "model_gossip=False leaves them nothing to attack"
            )
        if byz_mask is not None:
            raise ValueError(
                "adaptive gossip attacks derive their own Byzantine pool "
                'from model_attack_params ("f_pool"/"pool"); an explicit '
                "byz_mask would silently fight the rotation schedule"
            )
        model_adaptive_cfg = adaptive_lib.configure(
            model_attack, model_attack_params, num_workers=num_nodes, f=f
        )
        model_attack = model_adaptive_cfg.base
        model_attack_params = adaptive_lib.base_params(model_attack_params)
        byz_mask = model_adaptive_cfg.pool_mask()
    if (model_attack is not None and model_attack != "none"
            and model_attack not in model_attacks
            and model_attack not in model_collusion_attacks):
        raise ValueError(f"unknown model attack {model_attack!r}")
    # Closed-loop defense (see docstring): normalized knobs, the
    # aggregathor convention.
    d_power = d_floor = d_decay = None
    if defense is not None:
        from ..aggregators import defense as defense_lib

        dd = dict(defense)
        d_power = float(dd.pop("power", 2.0))
        d_floor = float(dd.pop("floor", 0.1))
        halflife = float(dd.pop("halflife", 16.0))
        if dd:
            raise ValueError(f"unknown defense keys {sorted(dd)}")
        if halflife <= 0.0:
            raise ValueError(f"defense halflife must be > 0, got {halflife}")
        d_decay = float(0.5 ** (1.0 / halflife))
        defense_lib.suspicion_weights([0.0], power=d_power, floor=d_floor)
    if byz_mask is None:
        byz_mask = core.default_byz_mask(
            num_nodes, f if (attack or model_attack) else 0
        )
    # Folded plans (static): the gradient plan serves phase 2 AND every
    # agreement round; the model plan serves the gossip. None -> where-path.
    fold_plan = fold.plan_for(gar, attack, byz_mask, attack_params)
    model_fold_plan = fold.plan_for_model(
        gar, model_attack, byz_mask, model_attack_params
    )
    byz_mask = jnp.asarray(byz_mask, bool)

    waiting = subset is not None and subset < num_nodes
    # Gradient-exchange eligibility: the aggregathor/byzsgd gate, with the
    # sub-Gram subset composition enabled (multi-observer form).
    grad_tree_ok = _tree_path_ok(
        tree_path, subset, num_nodes, "model", gar, subset_gram_ok=True
    )
    # Model-gossip eligibility: randomized MODEL attacks have no tree
    # where-path (their draws are defined on the flat model vector), so the
    # tree route additionally needs the attack to fold (or be absent).
    gossip_tree_ok = grad_tree_ok and (
        model_attack in (None, "none") or model_fold_plan is not None
    )
    if model_adaptive_cfg is not None:
        # The traced-magnitude collusion fake is stack-level (flat gossip
        # path only) — reported once so benches attribute the path.
        note_attack_fallback(
            f"adaptive-{model_adaptive_cfg.base}", path="where",
            why="model-plane collusion poisons the flat gossip stack",
        )
    if defense is not None and gar.gram_select is None:
        # Suspicion weights are row weights: they compose with the tree
        # route only through the Gram algebra — non-Gram rules take the
        # flat path, which weights rows explicitly (the staleness rule).
        grad_tree_ok = False
        gossip_tree_ok = False

    # Bounded-staleness emulation (see docstring). Normalized at build so
    # trivially-synchronous configs drop the machinery entirely — the step
    # program is then literally the synchronous one (the bitwise half of
    # the --max_staleness 0 contract, like aggregathor's normalization).
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
            if taus.shape != (num_nodes,):
                raise ValueError(
                    f"staleness taus must have shape ({num_nodes},), "
                    f"got {taus.shape}"
                )
            stale_weights_static = rounds_lib.staleness_weights(
                taus, decay=stale_decay, max_staleness=stale_ms
            )
            if np.all(stale_weights_static == 1.0):
                staleness = None  # all-fresh schedule: same program
        if staleness is not None and gar.gram_select is None:
            # Row weights compose with the tree route only through the
            # Gram algebra (fold row_weights); coordinate/iterative rules
            # consume row values — route every exchange to the flat path,
            # which weights the rows explicitly (the aggregathor rule).
            grad_tree_ok = False
            gossip_tree_ok = False
        if staleness is not None:
            stale_weights_fn = rounds_lib.staleness_weights

    init_worker, grad_fn, eval_apply = core.make_worker_fns(module, loss_fn)
    # Per-slot gradient formulation (VERDICT r5 #3): LEARN consults the
    # SAME registry front-end as aggregathor/byzsgd, declaring its
    # per-node DISTINCT params (shared_params=False) — the twin's fused
    # primal uses ONE shared kernel, so resolve_slot_grad_fn returns None
    # today and the run-length-aware unroll-vs-vmap choice applies; if a
    # stacked-params twin formulation ever lands, LEARN picks it up here
    # with no further change.
    slot_fused_fn = core.resolve_slot_grad_fn(
        module, loss_fn, per_n, shared_params=False
    )
    slot_path, slot_why = core.slot_path_decision(
        per_n, num_iter, fused_available=slot_fused_fn is not None
    )
    if per_n > 1:
        from ..utils import tools

        tools.info(f"[learn] per-slot gradients: {slot_path} ({slot_why})")
    unroll_grads = slot_path == "unroll"
    node_sharding = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def init_fn(key, example_x, seed_rng=None):
        params, model_state = init_worker(key, example_x)
        opt_state = optimizer.init(params)
        stack = lambda tree: jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (num_nodes,) + l.shape), tree
        )
        worker_mom = None
        if worker_momentum is not None:
            worker_mom = jax.device_put(
                core.worker_mom_init(params, num_nodes, gar_dtype),
                node_sharding,
            )
        gar_state = None
        if gar.stateful_center:
            # Per-NODE carried center (v_0 = that node's previous final
            # aggregate, f32). The zeros here are never consumed: step 0
            # takes the robust-median-init branch of the lax.cond below.
            gar_state = jax.device_put(
                jax.tree.map(
                    lambda p: jnp.zeros((num_nodes,) + p.shape, jnp.float32),
                    params,
                ),
                node_sharding,
            )
        attack_state = None
        if model_adaptive_cfg is not None:
            # The gossip-magnitude bisection bracket starts wide open.
            attack_state = jax.device_put(
                adaptive_lib.init_state(model_adaptive_cfg), repl
            )
        defense_state = None
        if defense is not None:
            # Carried per-node exclusion EMA: clean history, weights 1.0.
            defense_state = jax.device_put({
                "obs": jnp.zeros((num_nodes,), jnp.float32),
                "exc": jnp.zeros((num_nodes,), jnp.float32),
            }, repl)
        return core.TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), repl),
            params=jax.device_put(stack(params), node_sharding),
            model_state=jax.device_put(model_state, repl),
            opt_state=jax.device_put(stack(opt_state), node_sharding),
            rng=jax.device_put(key if seed_rng is None else seed_rng, repl),
            worker_mom=worker_mom,
            gar_state=gar_state,
            attack_state=attack_state,
            defense_state=defense_state,
        )

    def _local_step(state, x_local, y_local):
        base = jax.random.fold_in(state.rng, state.step)
        (atk_key, gossip_key, matk_key, drop_base,
         sub_key, msub_key) = jax.random.split(base, 6)
        shard = jax.lax.axis_index(axis)
        node_ids = shard * per_n + jnp.arange(per_n)

        def stale_w_for(phase_id):
            """Per-PHASE bounded-staleness weights (emulation; see the
            make_trainer docstring): the fixed ``taus`` schedule, or a
            seeded per-phase draw — each exchange phase (gradients, every
            agreement round, gossip) samples its own per-node staleness,
            like the host plane's per-plane gathers. fold_in-derived (NOT
            an extra split) so synchronous configs' key derivation — and
            every pinned trajectory — is untouched."""
            if staleness is None:
                return None
            if stale_weights_static is not None:
                return jnp.asarray(stale_weights_static)
            taus = jax.random.randint(
                jax.random.fold_in(
                    jax.random.fold_in(base, 0x57A1E), phase_id
                ),
                (num_nodes,), 0, stale_ms + 1,
            )
            return stale_weights_fn(
                taus, decay=stale_decay, max_staleness=stale_ms
            )

        def weight_rows(stack, w):
            """Flat-path staleness application: rows scaled after the
            attack, before subsets/aggregation — per-row weights commute
            with row selection, so each observer's subset sees exactly
            its members' discounts (the host-plane order)."""
            if w is None:
                return stack
            return (stack * w[:, None]).astype(stack.dtype)

        # Closed-loop defense weights (DESIGN.md §16/§17): per-node
        # suspicion from the carried exclusion EMA; exactly 1.0 on a
        # clean history. ONE history serves all three phases — node
        # identity is shared across the planes.
        def_w = None
        if defense is not None:
            susp = state.defense_state["exc"] / jnp.maximum(
                state.defense_state["obs"], 1e-6
            )
            def_w = defense_lib.suspicion_weights(
                susp, power=d_power, floor=d_floor
            )

        def row_w_for(phase_id):
            """Per-phase composed row weights: the bounded-staleness
            discount times the defense's suspicion weight — the shared
            row-scale algebra, so both ride the same fold/flat paths."""
            w = stale_w_for(phase_id)
            if def_w is None:
                return w
            return def_w if w is None else (
                (w * def_w).astype(jnp.float32)
            )

        # Adaptive GOSSIP controller (DESIGN.md §17): the collusion
        # magnitude played on the plane-2 model gossip is the carried
        # bracket's midpoint; rotation picks this round's active nodes.
        act_mask_m = byz_mask
        eff_m_params = model_attack_params
        m_mag = None
        m_lo = m_hi = None
        if model_adaptive_cfg is not None:
            m_lo = state.attack_state["lo"]
            m_hi = state.attack_state["hi"]
            m_mag = adaptive_lib.played_magnitude(m_lo, m_hi)
            act_mask_m = adaptive_lib.active_mask_traced(
                model_adaptive_cfg, state.step
            )
            eff_m_params = dict(model_attack_params)
            eff_m_params[
                adaptive_lib.magnitude_key(model_adaptive_cfg.base)
            ] = m_mag

        def node_subset_keys(key):
            """Per-node (sel, gar_key) for one exchange — the SAME key
            derivation as the flat path's ``node_aggregate`` (keyed by the
            global node id), so tree and flat trajectories sample identical
            wait-n-f subsets."""

            def one(nid):
                sel_key, gkey = jax.random.split(jax.random.fold_in(key, nid))
                return core.subset_indices(sel_key, num_nodes, subset), gkey

            return jax.vmap(one)(node_ids)

        def node_aggregate(stack, key, nid, center=None):
            """One node's view of an exchange: its own seeded arrival subset
            (the q fastest peers), then the GAR. Keyed by the global node id
            so every shard agrees on what node ``nid`` sampled."""
            sel_key, gkey = jax.random.split(jax.random.fold_in(key, nid))
            if waiting:
                sel = core.subset_indices(sel_key, stack.shape[0], subset)
                stack = stack[sel]
            extra = {} if center is None else {"center": center}
            return gar.unchecked(stack, f=f, key=gkey, **gar_params, **extra)

        def local_aggregates(stack, key, centers=None):
            """All of this shard's node slots aggregate the same gathered
            (n, d) stack through their own subsets -> (per_n, d). vmapped
            over the node ids (one subset+GAR graph regardless of per_n,
            the same shape as byzsgd's vmapped per-PS slot step).
            ``centers``: optional (per_n, d) per-node carried centers
            (stateful rules)."""
            if waiting:
                if centers is None:
                    return jax.vmap(
                        lambda nid: node_aggregate(stack, key, nid)
                    )(node_ids)
                return jax.vmap(
                    lambda nid, c: node_aggregate(stack, key, nid, c)
                )(node_ids, centers)
            # Full participation: one aggregate, identical for every node
            # (and identical carried centers, so slot 0's suffices).
            extra = {} if centers is None else {"center": centers[0]}
            one = gar.unchecked(stack, f=f, key=key, **gar_params, **extra)
            return jnp.broadcast_to(one[None], (per_n,) + one.shape)

        def tree_exchange(stacked_tree, plan, akey, key, attack_name,
                          attack_kw, center_tree=None, row_weights=None):
            """One exchange on the stacked TREE: folded deterministic
            attacks poison the Gram (never the rows); randomized attacks
            take the tree where-path first; per-node subsets compose onto
            the sub-Gram. Returns the per-node aggregates as a tree with a
            leading per_n axis. ``center_tree``: per-node carried centers
            (leading per_n axis) for stateful rules — consumed on the
            full-participation route only (the subset route is Gram-form,
            stateless). ``row_weights``: the bounded-staleness discount,
            composed into the Gram row-scale algebra (the tree route is
            gated to gram_select rules when weights are active)."""
            if plan is None and attack_name not in (None, "none"):
                with core.phase("attack"):
                    stacked_tree = apply_gradient_attack_tree(
                        attack_name, stacked_tree, byz_mask, key=akey,
                        **attack_kw,
                    )
            if waiting:
                with core.phase("rule"):
                    sels, gkeys = node_subset_keys(key)
                return fold.folded_tree_aggregate_multi(
                    gar, plan, stacked_tree, f=f, keys=gkeys,
                    gar_params=gar_params, subset_sels=sels,
                    row_weights=row_weights,
                )
            if row_weights is not None:
                # Weighted full participation: one observer view through
                # the multi form (it accepts plan None AND composes the
                # weights into the Gram; with neither subsets nor keys it
                # returns the single selection WITHOUT a leading axis),
                # broadcast to the local slots — gram_select rules are
                # stateless, so center_tree never reaches this route.
                one = fold.folded_tree_aggregate_multi(
                    gar, plan, stacked_tree, f=f,
                    gar_params=gar_params, row_weights=row_weights,
                )
                with core.phase("rule"):
                    return jax.tree.map(
                        lambda l: jnp.broadcast_to(
                            l[None], (per_n,) + l.shape
                        ),
                        one,
                    )
            center_kw = {}
            if center_tree is not None:
                # Full participation: every node's carried center is equal
                # (identical aggregates every step) — use slot 0's.
                center_kw = {
                    "center": jax.tree.map(lambda l: l[0], center_tree)
                }
            if plan is not None:
                one = fold.folded_tree_aggregate(
                    gar, plan, stacked_tree, f=f, key=key,
                    gar_params={**gar_params, **center_kw},
                )
            else:
                with core.phase("rule"):
                    one = gar.tree_aggregate(
                        stacked_tree, f=f, key=key, **gar_params,
                        **center_kw
                    )
            with core.phase("rule"):
                return jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (per_n,) + l.shape),
                    one,
                )

        def honest_spread(aggr_rows):
            """Max pairwise L-inf distance between honest nodes' aggregates:
            the disagreement the agreement rounds must shrink."""
            with core.phase("exchange"):
                # (n, d)
                rows = jax.lax.all_gather(aggr_rows, axis, tiled=True)
            byz = byz_mask[:, None]
            hi = jnp.max(jnp.where(byz, -jnp.inf, rows), axis=0)
            lo = jnp.min(jnp.where(byz, jnp.inf, rows), axis=0)
            return jnp.max(hi - lo)

        def aggr_rows_of(aggr):
            """(per_n, d) flat rows of the per-node aggregates, whichever
            representation the dispatch produced (spread metric only)."""
            return core.flatten_rows(aggr) if grad_tree_ok else aggr

        # Phase 1: per-node gradient on its own model + batch. Unrolled over
        # the static local slots below the slot_path_decision cap (vmapping
        # params over nodes trips conv batching rules at small n; keep the
        # stacked TREE through the gather and flatten once afterwards —
        # raveling each slot inside the unroll serializes the per-slot
        # concats against fwd+bwd, measured 12% slower in aggregathor;
        # core.per_slot_grads docstring). Above the cap (or when the run
        # length cannot amortize the unroll's compile premium) the per-node
        # gradients vmap with params mapped over the node axis.
        with core.phase("grads"):
            if unroll_grads:
                grads, losses_list, ms_list = [], [], []
                for k in range(per_n):
                    p_k = jax.tree.map(lambda l: l[k], state.params)
                    rng_k = jax.random.fold_in(drop_base, node_ids[k])
                    g, (loss, ms_out) = grad_fn(
                        p_k, state.model_state, x_local[k], y_local[k], rng_k
                    )
                    grads.append(g)
                    losses_list.append(loss)
                    ms_list.append(ms_out)
                grads_local = jax.tree.map(lambda *ls: jnp.stack(ls), *grads)
                losses = jnp.stack(losses_list)
                ms_stack = jax.tree.map(lambda *ls: jnp.stack(ls), *ms_list)
            else:
                rngs = jax.vmap(
                    lambda i: jax.random.fold_in(drop_base, i)
                )(node_ids)
                grads_local, (losses, ms_stack) = jax.vmap(
                    grad_fn, in_axes=(0, None, 0, 0, 0)
                )(state.params, state.model_state, x_local, y_local, rngs)
            grads_local = core.cast_leaves(grads_local, gar_dtype)

        # Per-node momentum (see make_trainer docstring): each node
        # publishes its EMA; the honest update is stored (sharded with the
        # node state), Byzantine rows are re-poisoned after the gather.
        new_mom = state.worker_mom
        if worker_momentum is not None:
            with core.phase("grads"):
                grads_local = core.worker_mom_update(
                    worker_momentum, state.worker_mom, grads_local
                )
            new_mom = grads_local
        new_ms = core.mean_model_state(ms_stack, axis)

        # Phase 2: gather + attack + aggregate (= get_gradients(i, n-f) of
        # the fastest peers, LEARN/trainer.py:249; per-node subsets). The
        # carried center (stateful rules) is each node's previous final
        # aggregate; at step 0 the lax.cond takes the robust-median-init
        # branch instead — the ONLY coordinate-median pass in the whole
        # step program, executed exactly once per run.
        with core.phase("exchange"):
            gathered = jax.tree.map(
                lambda l: jax.lax.all_gather(l, axis, tiled=True),
                grads_local,
            )

        stale_w2 = row_w_for(0)

        def phase2(centers_tree, centers_rows):
            if grad_tree_ok:
                return tree_exchange(
                    gathered, fold_plan, atk_key, sub_key, attack,
                    attack_params, center_tree=centers_tree,
                    row_weights=stale_w2,
                )
            with core.phase("rule"):
                stack0 = core.flatten_rows(gathered)  # (n, d)
            with core.phase("attack"):
                stack0 = apply_gradient_attack(
                    attack, stack0, byz_mask, key=atk_key, **attack_params
                )
            with core.phase("rule"):
                stack0 = weight_rows(stack0, stale_w2)
                return local_aggregates(
                    stack0, sub_key, centers=centers_rows
                )

        if gar.stateful_center:
            carried = state.gar_state  # (per_n, ...) local shard
            carried_rows = (
                None if grad_tree_ok else core.flatten_rows(carried)
            )
            aggr_local = jax.lax.cond(
                state.step == 0,
                lambda: phase2(None, None),
                lambda: phase2(
                    carried if grad_tree_ok else None, carried_rows
                ),
            )
        else:
            aggr_local = phase2(None, None)

        metrics_extra = {}
        grad_bundle = None
        if telemetry or defense is not None:
            # Phase-2 audit tap: the poisoned gathered stack rebuilt with
            # the SAME atk_key the exchange used (CSE'd on the flat path;
            # the enabled-only extra pass on the tree/fold paths). cclip
            # taps here use the rule's median-init center — the per-node
            # carried centers differ across observers (taps.py caveats).
            # With the defense on, this bundle is ALSO the feedback that
            # updates the carried exclusion EMA below.
            with core.phase("rule"):  # a tap recomputes the rule's view
                stack0p = apply_gradient_attack(
                    attack, core.flatten_rows(gathered), byz_mask, key=atk_key,
                    **attack_params,
                )
                # The tap audits the rows the rule consumed — staleness- and
                # suspicion-weighted included (the aggregathor convention).
                stack0p = weight_rows(stack0p, stale_w2)
                if waiting:
                    def one_tap(nid):
                        # SAME (sel, key) derivation as node_aggregate /
                        # node_subset_keys, so the tap audits exactly the
                        # quorum node ``nid`` aggregated.
                        sel_key, gkey = jax.random.split(
                            jax.random.fold_in(sub_key, nid)
                        )
                        sel = core.subset_indices(sel_key, num_nodes, subset)
                        bundle = taps_lib.compute_flat(
                            gar.name, stack0p[sel], f, key=gkey,
                            params=gar_params,
                        )
                        return taps_lib.scatter(bundle, sel, num_nodes)

                    local_mean = taps_lib.mean_bundles(
                        jax.vmap(one_tap)(node_ids)
                    )
                    grad_bundle = jax.tree.map(
                        lambda l: jax.lax.pmean(l, axis), local_mean
                    )
                else:
                    grad_bundle = taps_lib.compute_flat(
                        gar.name, stack0p, f, key=sub_key, params=gar_params,
                    )
            if telemetry:
                metrics_extra["tap"] = grad_bundle
        if track_spread:
            metrics_extra["aggr_spread_pre"] = honest_spread(
                aggr_rows_of(aggr_local)
            )

        # Phase 3: avg_agree rounds (ceil(log2 t), LEARN/trainer.py:208-222).
        # Each round every node PUBLISHES its own current aggregate (they
        # differ under wait-n-f), Byzantine rows are poisoned, and each node
        # re-aggregates its own num_wait_ps = q subset of the gathered stack
        # (get_aggr_grads polling, server.py:202-233). Stateful rules
        # re-center each round on the node's CURRENT aggregate (the natural
        # v_0: the previous round's output).
        if non_iid:
            t = jnp.maximum(state.step, 1).astype(jnp.float32)
            rounds = jnp.ceil(jnp.log2(jnp.maximum(t, 2.0))).astype(jnp.int32)
            rounds = jnp.minimum(rounds, max_rounds)

            if grad_tree_ok:
                def round_body(r, aggr):
                    with core.phase("exchange"):
                        served = jax.tree.map(
                            lambda l: jax.lax.all_gather(
                                l, axis, tiled=True
                            ),
                            aggr,
                        )  # (n, ...) leaves: every node's own aggregate
                    akey, skey = jax.random.split(
                        jax.random.fold_in(gossip_key, r)
                    )
                    new = tree_exchange(
                        served, fold_plan, akey, skey, attack, attack_params,
                        center_tree=aggr if gar.stateful_center else None,
                        row_weights=row_w_for(1 + r),
                    )
                    with core.phase("rule"):
                        return jax.tree.map(
                            lambda a, b: jnp.where(r < rounds, a, b),
                            new, aggr,
                        )
            else:
                def round_body(r, aggr):
                    with core.phase("exchange"):
                        served = jax.lax.all_gather(aggr, axis, tiled=True)
                    akey, skey = jax.random.split(
                        jax.random.fold_in(gossip_key, r)
                    )
                    with core.phase("attack"):
                        served = apply_gradient_attack(
                            attack, served, byz_mask, key=akey,
                            **attack_params
                        )
                    with core.phase("rule"):
                        served = weight_rows(served, row_w_for(1 + r))
                        new = local_aggregates(
                            served, skey,
                            centers=aggr if gar.stateful_center else None,
                        )
                        return jnp.where(r < rounds, new, aggr)

            aggr_local = jax.lax.fori_loop(
                0, max_rounds, round_body, aggr_local
            )

        if track_spread:
            metrics_extra["aggr_spread_post"] = honest_spread(
                aggr_rows_of(aggr_local)
            )

        # Phase 4: per-node optimizer step on that node's own aggregate.
        with core.phase("update"):
            new_params_list, new_opt_list, aggr_trees = [], [], []
            for k in range(per_n):
                p_k = jax.tree.map(lambda l: l[k], state.params)
                o_k = jax.tree.map(lambda l: l[k], state.opt_state)
                if grad_tree_ok:
                    aggr_tree = jax.tree.map(lambda l: l[k], aggr_local)
                else:
                    aggr_tree = core.unflatten_like(p_k, aggr_local[k])
                aggr_trees.append(aggr_tree)
                aggr_tree = core.cast_like(aggr_tree, p_k)  # no-op at f32
                updates, o_k = optimizer.update(aggr_tree, o_k, p_k)
                new_params_list.append(optax.apply_updates(p_k, updates))
                new_opt_list.append(o_k)
            new_params = jax.tree.map(
                lambda *ls: jnp.stack(ls), *new_params_list
            )
            new_opt = jax.tree.map(lambda *ls: jnp.stack(ls), *new_opt_list)

            new_gar_state = state.gar_state
            if gar.stateful_center:
                # Next step's per-node v_0 = this step's final aggregate
                # (f32 — the carried center should not round through the
                # bf16 pipeline).
                new_gar_state = jax.tree.map(
                    lambda *ls: jnp.stack([l.astype(jnp.float32) for l in ls]),
                    *aggr_trees,
                )

        # Phase 5: model gossip (LEARN/trainer.py:255-257, get_models(n-f) —
        # each node GAR-aggregates its own subset of the gossiped models).
        # Deterministic model attacks (reverse/crash) fold like the
        # gradient plane; stateful rules center each node's clip on its OWN
        # model (the ClippedGossip recipe) instead of a per-call median.
        new_attack_state = state.attack_state
        if model_gossip:
            stale_wg = row_w_for(0x5009)
            if gossip_tree_ok:
                with core.phase("model_exchange"):
                    models_tree = jax.tree.map(
                        lambda l: jax.lax.all_gather(l, axis, tiled=True),
                        new_params,
                    )
                # The outermost scope names the phase: the exchange's own
                # "rule" inside is the model plane's here.
                with core.phase("model_rule"):
                    new_params = tree_exchange(
                        models_tree, model_fold_plan, matk_key, msub_key,
                        None, {},
                        center_tree=(
                            new_params if gar.stateful_center else None
                        ),
                        row_weights=stale_wg,
                    )
            else:
                with core.phase("model_exchange"):
                    flat_models = core.flatten_rows(new_params)  # (per_n, d)
                    models = jax.lax.all_gather(
                        flat_models, axis, tiled=True
                    )
                with core.phase("attack"):
                    models = apply_model_attack_rows(
                        model_attack, models, act_mask_m, key=matk_key,
                        **eff_m_params,
                    )
                # Gossip-plane staleness: a stale model's row is
                # discounted like a stale gradient's — the robust rule
                # then treats the down-scaled row as the outlier it is,
                # and the fresh honest majority keeps its influence
                # (DESIGN.md §15; the same composition as the PS plane;
                # the defense's suspicion weight rides the same multiply).
                with core.phase("model_rule"):
                    models = weight_rows(models, stale_wg)
                if model_adaptive_cfg is not None:
                    # Gossip-plane selection feedback (DESIGN.md §17):
                    # the rule's verdict over the SAME poisoned, weighted
                    # stack the gossip aggregates — majority-excluded
                    # among the observed active nodes means detected; a
                    # round that observed none holds the bracket.
                    with core.phase("model_rule"):
                        if waiting:
                            def one_mtap(nid):
                                # SAME (sel, key) derivation as
                                # node_aggregate over msub_key.
                                sel_key, gkey = jax.random.split(
                                    jax.random.fold_in(msub_key, nid)
                                )
                                sel = core.subset_indices(
                                    sel_key, num_nodes, subset
                                )
                                bundle = taps_lib.compute_flat(
                                    gar.name, models[sel], f, key=gkey,
                                    params=gar_params,
                                )
                                return taps_lib.scatter(bundle, sel, num_nodes)

                            gb = taps_lib.mean_bundles(
                                jax.vmap(one_mtap)(node_ids)
                            )
                            gossip_bundle = jax.tree.map(
                                lambda l: jax.lax.pmean(l, axis), gb
                            )
                        else:
                            gossip_bundle = taps_lib.compute_flat(
                                gar.name, models, f, key=msub_key,
                                params=gar_params,
                            )
                    with core.phase("attack"):
                        act_f = act_mask_m.astype(jnp.float32) * gossip_bundle[
                            "observed"
                        ]
                        cnt = jnp.sum(act_f)
                        admitted = jnp.sum(
                            (gossip_bundle["selected"] > 0).astype(jnp.float32)
                            * act_f
                        )
                        m_detected = admitted * 2.0 < cnt
                        upd_lo, upd_hi = adaptive_lib.update_bracket(
                            m_lo, m_hi, m_detected,
                            mag_min=model_adaptive_cfg.mag_min,
                            mag_max=model_adaptive_cfg.mag_max,
                            regrow=model_adaptive_cfg.regrow,
                        )
                        hold = cnt == 0.0
                        new_attack_state = {
                            "lo": jnp.where(hold, m_lo, upd_lo),
                            "hi": jnp.where(hold, m_hi, upd_hi),
                        }
                        metrics_extra["model_attack_mag"] = jnp.asarray(
                            m_mag, jnp.float32
                        )
                        metrics_extra["model_attack_detected"] = (
                            m_detected.astype(jnp.float32)
                        )
                with core.phase("model_rule"):
                    aggr_models = local_aggregates(
                        models, msub_key,
                        centers=flat_models if gar.stateful_center else None,
                    )  # (per_n, d)
                    template = jax.tree.map(lambda l: l[0], new_params)
                    new_params = jax.tree.map(
                        lambda *ls: jnp.stack(ls),
                        *[
                            core.unflatten_like(template, aggr_models[k])
                            for k in range(per_n)
                        ],
                    )

        new_defense_state = state.defense_state
        if defense is not None:
            # The hub's exclusion law (observed minus admitted) carried
            # as a decayed EMA — the in-graph twin of the node hub's
            # windowed suspicion, fed by the phase-2 observer mean.
            dec = jnp.float32(d_decay)
            obs_v = grad_bundle["observed"]
            ind_v = (grad_bundle["selected"] > 0).astype(jnp.float32) * obs_v
            new_defense_state = {
                "obs": state.defense_state["obs"] * dec + obs_v,
                "exc": state.defense_state["exc"] * dec + (obs_v - ind_v),
            }
            metrics_extra["defense_w"] = def_w

        honest = (~byz_mask).astype(losses.dtype)[node_ids]
        loss_num = jax.lax.psum(jnp.sum(losses * honest), axis)
        loss_den = jax.lax.psum(jnp.sum(honest), axis)
        mean_loss = loss_num / jnp.maximum(loss_den, 1.0)
        # Per-node losses for observers (the reference demo renders per-node
        # progress, LEARN/demo.py:401-441 + templates/index.html); a tiny
        # replicated (n,) vector, node-id ordered.
        with core.phase("exchange"):
            metrics_extra["node_losses"] = jax.lax.all_gather(
                losses, axis, tiled=True
            )

        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                model_state=new_ms,
                opt_state=new_opt,
                worker_mom=new_mom,
                gar_state=new_gar_state,
                attack_state=new_attack_state,
                defense_state=new_defense_state,
            ),
            {"loss": mean_loss, **metrics_extra},
        )

    state_specs = core.TrainState(
        step=P(), params=P(axis), model_state=P(), opt_state=P(axis), rng=P(),
        worker_mom=(P(axis) if worker_momentum is not None else None),
        gar_state=(P(axis) if gar.stateful_center else None),
        attack_state=(P() if model_adaptive_cfg is not None else None),
        defense_state=(P() if defense is not None else None),
    )
    sharded_step = jax.shard_map(
        _local_step,
        mesh=mesh,
        in_specs=(state_specs, P(axis), P(axis)),
        out_specs=(state_specs, P()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=core.step_donation())
    def step_fn(state, x, y):
        return sharded_step(state, x, y)

    @jax.jit
    def eval_fn(state, x):
        params0 = jax.tree.map(lambda l: l[0], state.params)
        return eval_apply(params0, state.model_state, x)

    step_fn.mesh = mesh
    step_fn.batch_sharding = node_sharding
    # Chunking hook (core.make_chunked_step): scan the shard_map body
    # directly; shardings propagate as in the per-step jit (none pinned).
    step_fn.inner = sharded_step
    return init_fn, step_fn, eval_fn
