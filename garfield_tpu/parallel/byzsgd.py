"""ByzSGD / GuanYu topology: replicated Byzantine parameter servers.

TPU-native re-design of ``pytorch_impl/applications/ByzSGD/trainer.py``:
each of ``num_ps`` servers runs the AggregaThor step on the shared worker
gradients, then a model-space "gather step" (trainer.py:240-244) pulls every
peer server's model, GAR-aggregates them, and writes the result back —
defending against Byzantine servers (byzServer.py) exactly as the gradient
GAR defends against Byzantine workers.

SPMD mapping (SURVEY §2.3 "Replicated-PS" row): a 2-D mesh ("ps", axis);
server state is stacked over the "ps" axis, worker batches are sharded over
``axis``. Per step, on the device at (i, j):

    grads[j]    = vmap(worker_grad)(params[i], batch[j])   # each PS pushes its
                                                           # own model, server.py:112
    stack       = all_gather(grads, axis)                  # (n_w, d) per ps slot
    stack       = attack(stack, byz_workers)               # byzWorker.py
    aggr[i]     = gar(stack[subset_i], f_w)                # per-PS wait n-f subset
    params[i]   = opt(params[i], aggr[i])                  # update_model
    models      = all_gather(flat(params), "ps")           # get_models, :161-184
    models      = model_attack(models, byz_ps)             # byzServer.py:86-108
    params[i]   = unflat(gar(models[msubset_i], f_ps))     # write_model, :289-297

Honest-PS divergence (the reason model aggregation exists at all) arises here
from per-PS wait-n-f subsets — each PS samples its *own* q of n gradients,
mirroring different arrival orders at different servers in the async
reference. ``model_subset`` extends the same emulation to the model gather:
the reference's gather step pulls only the fastest ``num_ps - fps`` peer
models (``get_models(num_ps - fps)``, trainer.py:240-242), so each PS
aggregates its own seeded model subset — composed onto the model Gram for
Gram-form rules, with deterministic PS attacks folded into the Gram remap
(fold.plan_for_model).

``worker_momentum`` (aggregathor/learn) is deliberately NOT offered here:
in this topology every PS slot evaluates the workers' batches against its
OWN model replica, so a per-worker gradient EMA would need one momentum per
(ps, worker) pair — semantics no deployed worker has (a real worker holds
one momentum for the one model it pulls). Run the momentum defense on the
SSMW or LEARN topologies, which match the paper's setting.
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
    apply_model_attack_rows,
    model_attacks,
    model_collusion_attacks,
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
    num_workers,
    num_ps,
    fw=0,
    fps=0,
    attack=None,
    attack_params=None,
    ps_attack=None,
    ps_attack_params=None,
    byz_worker_mask=None,
    byz_ps_mask=None,
    mesh=None,
    axis="workers",
    ps_axis="ps",
    subset=None,
    model_subset=None,
    model_gar=None,
    granularity="model",
    tree_path=True,
    gar_dtype=None,
    gar_params=None,
    model_gar_params=None,
    num_iter=None,
    telemetry=False,
    defense=None,
):
    """Build ``(init_fn, step_fn, eval_fn)`` for the MSMW topology.

    ``telemetry`` adds ``metrics["tap"]`` — the WORKER-gradient plane's
    ``TapBundle`` (telemetry/taps.py), averaged across the num_ps server
    views (each PS evaluates the workers against its own replica and,
    under ``subset``, its own quorum): ``observed`` is the fraction of
    servers whose quorum contained the worker, ``selected`` the mean
    influence its gradient earned. The model gather plane is not tapped
    (PS models are few and the per-worker audit is the signal). Off by
    default — nothing tap-shaped is traced, taps never enter TrainState.

    ``gar`` aggregates gradients with tolerance ``fw``; ``model_gar``
    (default: same rule) aggregates server models with tolerance ``fps`` —
    the reference uses one GAR for both (ByzSGD/trainer.py:34 note).
    ``subset=q`` gives each PS its own sampled wait-for-q gradient subset.
    ``model_subset=q_m`` gives each PS its own sampled wait-for-q_m subset
    of the MODEL gather too — the reference-faithful semantics
    (``get_models(num_ps - fps)``, ByzSGD/trainer.py:240-242 /
    server.py:161-184: a server aggregates the fastest ``num_ps - fps``
    peer models, never all of them — pass ``q_m = num_ps - fps`` for exact
    protocol parity). With it, honest PS replicas hold genuinely DIFFERENT
    post-gather models (the async reality the broadcast-one-aggregate
    default hides); the contraction of the model GAR is what keeps them
    from drifting apart. The subset composes onto the model Gram for
    Gram-form rules (one (n_ps, n_ps) Gram build, per-PS (q_m, q_m)
    sub-Gram selections — the same fast-path composition as the gradient
    plane; ``tree_path=False`` forces the flat per-PS gathers), and the
    deterministic model attacks (reverse/crash) fold into the Gram remap
    (``fold.plan_for_model``). None (default) keeps the aggregate-all
    behavior.
    ``granularity="layer"`` applies both GARs independently per parameter
    tensor — the Garfield_CC GuanYu semantics (its reduce_gradients loops
    over model layers, Garfield_CC/trainer.py:55-204) — by segmenting the
    flat stacks at the (static) parameter boundaries; attacks still act on
    the whole flat vector.

    ``tree_path`` (default on): rules with tree-mode aggregation (average,
    krum, cclip, and the per-leaf coordinate-wise twins of median/tmean)
    run the gradient phase on the stacked gradient TREE — no
    (n_w, d) flat stack per PS slot (same win as aggregathor's tree path,
    PERF.md); the model gather phase always works on flat model vectors.

    ``gar_dtype`` narrows the gradient-phase pipeline (cast at the backward
    epilogue, attack + gather + GAR at the narrow width, cast back at the
    optimizer boundary) exactly like aggregathor's flag; the model-space
    phase stays full width (models are parameters, not gradients).

    ``gar_params`` passes rule hyperparameters (cclip tau/iters, condense
    p) to the gradient rule; ``model_gar_params`` to the model-space rule
    (default: same as ``gar_params``, matching the shared-rule default).

    ``ps_attack`` additionally accepts the model-plane COLLUSION attacks
    (``lie``/``empire`` — mu + z*sigma / -eps*mu over the gathered replica
    stack, DESIGN.md §17) and their ADAPTIVE controllers (``adaptive-lie``
    / ``adaptive-empire``, attacks/adaptive.py): the lie/empire magnitude
    becomes a bisection bracket carried in ``TrainState.attack_state``
    (the same carry slot aggregathor's gradient-plane bracket uses —
    this topology's adaptive adversary lives on the MODEL plane), fed
    back each step by whether the Byzantine PS rows entered the model
    gather's selection; ``ps_attack_params`` carries the controller knobs
    (``f_pool``/``rotation``/``mag_min``/``mag_max``). The model plane is
    the attack surface ByzSGD exists for — a Byzantine PS bisecting
    against the fastest-subset model gather (``model_subset``) is the
    gather step's worst case.

    ``defense`` (aggregators/defense.py) deploys suspicion weighting on
    BOTH planes: a dict with ``power``/``floor``/``halflife`` enables a
    per-rank exclusion EMA for the n_w workers AND one for the n_ps
    replicas, carried in ``TrainState.defense_state``, mapped through
    ``defense.suspicion_weights`` and composed as row scales into the
    gradient stacks (before the gradient rule) and the gathered model
    stack (before the model rule) — the MSMW twin of the SSMW PS's
    per-quorum weighting, covering the gradient plane *and* the model
    plane the adaptive PS attacker targets. ``defense=None`` (default)
    traces nothing: trajectories are bitwise the undefended ones. Rule
    ESCALATION lives above the trainer (apps/common.py rebuilds the step
    at level changes; the ladder swaps the GRADIENT rule only — the
    model rule is pinned so the two planes' ladders stay independent).

    ``step_fn(state, x, y)``: ``x``/``y`` lead with ``num_workers`` sharded
    over ``axis``; state params/opt_state lead with ``num_ps`` sharded over
    ``ps_axis``.
    """
    gar = _resolve_gar(gar)
    same_rule = model_gar is None
    model_gar = gar if same_rule else _resolve_gar(model_gar)
    attack_params = dict(attack_params or {})
    gar_params = dict(gar_params or {})
    # The model-space rule defaults to the gradient rule, and only then do
    # its params follow gar_params too. When model_gar is an explicitly
    # DIFFERENT rule, inheriting gradient-rule hyperparameters would be
    # silent misconfiguration (e.g. a cclip tau scaled to gradient radii
    # applied to model vectors, orders of magnitude larger — and unknown
    # keys vanish into the rules' **kwargs), so they default to {} there
    # (ADVICE r3).
    if model_gar_params is None:
        model_gar_params = dict(gar_params) if same_rule else {}
    else:
        model_gar_params = dict(model_gar_params)
    ps_attack_params = dict(ps_attack_params or {})
    if mesh is None:
        mesh = mesh_lib.make_mesh({ps_axis: 1, axis: -1})
    if subset is not None and not (1 <= subset <= num_workers):
        raise ValueError(
            f"subset (wait-for-q) must be in [1, num_workers], got {subset}"
        )
    n_eff = subset if subset is not None else num_workers
    _check_gar(gar, n_eff, fw)
    if telemetry and granularity == "layer":
        raise ValueError(
            "telemetry taps report one whole-model selection per rank; "
            'granularity="layer" has no single per-rank mask — run taps '
            "at model granularity"
        )
    per_w = mesh_lib.fold(num_workers, mesh.shape[axis], "workers")
    per_ps = mesh_lib.fold(num_ps, mesh.shape[ps_axis], "servers")
    if model_subset is not None and not (1 <= model_subset <= num_ps):
        raise ValueError(
            f"model_subset (wait-for-q models) must be in [1, {num_ps}], "
            f"got {model_subset}"
        )
    # The model GAR sees model_subset rows when waiting (the reference
    # passes the num_ps - fps received models straight to the rule,
    # ByzSGD/trainer.py:240-242).
    m_eff = model_subset if model_subset is not None else num_ps
    if num_ps > 1 or fps:
        _check_gar(model_gar, m_eff, fps)
    from ..attacks import targeted as targeted_lib

    if targeted_lib.is_targeted(attack):
        raise ValueError(
            f"targeted attack {attack!r} poisons worker BATCHES and is "
            "deployed on the aggregathor topology in-graph (and on real "
            "cluster workers via apps/cluster.py); the MSMW in-graph "
            "twin does not support it"
        )
    # Adaptive MODEL-plane attacker (DESIGN.md §17): resolve the
    # controller and strip it down to the base collusion attack; the
    # magnitude is supplied per step from the carried bracket.
    ps_adaptive_cfg = None
    if adaptive_lib.is_adaptive(ps_attack):
        if byz_ps_mask is not None:
            raise ValueError(
                "adaptive PS attacks derive their own Byzantine pool from "
                'ps_attack_params ("f_pool"/"pool"); an explicit '
                "byz_ps_mask would silently fight the rotation schedule"
            )
        ps_adaptive_cfg = adaptive_lib.configure(
            ps_attack, ps_attack_params, num_workers=num_ps, f=fps
        )
        ps_attack = ps_adaptive_cfg.base
        ps_attack_params = adaptive_lib.base_params(ps_attack_params)
        byz_ps_mask = ps_adaptive_cfg.pool_mask()
    if (ps_attack is not None and ps_attack != "none"
            and ps_attack not in model_attacks
            and ps_attack not in model_collusion_attacks):
        raise ValueError(f"unknown model attack {ps_attack!r}")
    if byz_worker_mask is None:
        byz_worker_mask = core.default_byz_mask(num_workers, fw if attack else 0)
    if byz_ps_mask is None:
        byz_ps_mask = core.default_byz_mask(num_ps, fps if ps_attack else 0)
    # Folded attack plan for the gradient phase: static for deterministic
    # attacks on fold-capable rules (see fold.plan_for); None -> where-path.
    fold_plan = fold.plan_for(gar, attack, byz_worker_mask, attack_params)
    # Model-plane twin: byzServer's reverse/crash are pure row scalings, so
    # under per-PS model subsets the poisoned model Gram is a static outer
    # scaling of the raw one (fold.plan_for_model); None -> where-path.
    model_fold_plan = fold.plan_for_model(
        model_gar, ps_attack, byz_ps_mask, ps_attack_params
    )
    byz_worker_mask = jnp.asarray(byz_worker_mask, bool)
    byz_ps_mask = jnp.asarray(byz_ps_mask, bool)
    # Closed-loop defense (see docstring): normalized EMA/weighting knobs,
    # the aggregathor convention. Defense routes the gradient plane
    # through the flat path (the weighted rows are what the host-plane
    # MSMW replicas aggregate; the sub-Gram weighted composition is
    # aggregathor's specialty) — a defense-only cost.
    d_power = d_floor = d_decay = None
    if defense is not None:
        from ..aggregators import defense as defense_lib

        if granularity == "layer":
            raise ValueError(
                "the suspicion-weighted defense needs whole-model "
                'selection evidence; granularity="layer" has no per-rank '
                "verdict"
            )
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
    model_waiting = model_subset is not None and model_subset < num_ps
    # Per-PS model subsets compose onto the model Gram for Gram-form rules
    # (the gradient plane's sub-Gram fast path applied to the (n_ps, d)
    # model stack); other rules gather per-PS rows on the flat path.
    model_gram_ok = (
        tree_path and model_gar.gram_select is not None
        and granularity != "layer"
    )

    init_worker, grad_fn, eval_apply = core.make_worker_fns(module, loss_fn)
    # Slot-fused gradient twin (models/slotfused.py) — worker slots share
    # one model here, so the fused fwd/dx + per-slot dw formulation applies
    # exactly as in aggregathor (LEARN cannot use it: per-NODE params).
    slot_fused_fn, force_unroll = core.select_slot_path(
        module, loss_fn, per_w, num_iter, log_tag="byzsgd"
    )
    repl = NamedSharding(mesh, P())
    ps_sharding = NamedSharding(mesh, P(ps_axis))
    # True subsets force the flat path (dynamic per-leaf gathers measured
    # 3.5x slower); without them tree == flat on one chip and tree avoids
    # the per-PS flatten on real multi-chip meshes. See _tree_path_ok.
    # The suspicion-weighted defense also routes flat: its row weights
    # (and the selection feedback they need) are explicit there.
    tree_ok = (
        _tree_path_ok(tree_path, subset, num_workers, granularity, gar)
        and defense is None
    )

    def init_fn(key, example_x, seed_rng=None):
        params, model_state = init_worker(key, example_x)
        opt_state = optimizer.init(params)
        # Stack server-resident state over the ps axis (identical replicas at
        # t=0, like every server loading the same seeded model).
        stack = lambda tree: jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (num_ps,) + l.shape), tree
        )
        attack_state = None
        if ps_adaptive_cfg is not None:
            # The model-plane bisection bracket starts wide open; the
            # first gathers ARE the controller's probes.
            attack_state = jax.device_put(
                adaptive_lib.init_state(ps_adaptive_cfg), repl
            )
        defense_state = None
        if defense is not None:
            # One carried exclusion EMA PER PLANE: the workers' gradient
            # audit and the replicas' model-gather audit are independent
            # suspicion histories (independent planes, DESIGN.md §17).
            defense_state = jax.device_put({
                "obs": jnp.zeros((num_workers,), jnp.float32),
                "exc": jnp.zeros((num_workers,), jnp.float32),
                "ps_obs": jnp.zeros((num_ps,), jnp.float32),
                "ps_exc": jnp.zeros((num_ps,), jnp.float32),
            }, repl)
        state = core.TrainState(
            step=jnp.zeros((), jnp.int32),
            params=jax.device_put(stack(params), ps_sharding),
            model_state=jax.device_put(model_state, repl),
            opt_state=jax.device_put(stack(opt_state), ps_sharding),
            rng=jax.device_put(key if seed_rng is None else seed_rng, repl),
            attack_state=attack_state,
            defense_state=defense_state,
        )
        return state.replace(step=jax.device_put(state.step, repl))

    def _ps_slot_step(ps_id, params, opt_state, grads_stack, keys,
                      row_weights=None):
        """One server's gradient phase: attack is already applied; sample this
        PS's own arrival subset, aggregate, update (server.py:112-159 +
        update_model :277-287). ``row_weights`` is the defense's suspicion
        discount — composed after the subset, like the SSMW PS's quorum
        weighting (DESIGN.md §16)."""
        sub_key, gar_key = keys
        gkey = jax.random.fold_in(gar_key, ps_id)
        stack = grads_stack
        n = stack.shape[0]
        with core.phase("rule"):
            if subset is not None and subset < n:
                sel = core.subset_indices(
                    jax.random.fold_in(sub_key, ps_id), n, subset
                )
                stack = stack[sel]
                if row_weights is not None:
                    row_weights = row_weights[sel]
            if row_weights is not None:
                stack = (stack * row_weights[:, None]).astype(stack.dtype)
            if granularity == "layer":
                aggr = core.segmented_aggregate(
                    lambda s, i: gar.unchecked(
                        s, f=fw, key=jax.random.fold_in(gkey, i),
                        **gar_params
                    ),
                    stack,
                    core.leaf_segments(params),
                )
            else:
                aggr = gar.unchecked(stack, f=fw, key=gkey, **gar_params)
            aggr_tree = core.unflatten_like(params, aggr)
        with core.phase("update"):
            aggr_tree = core.cast_like(aggr_tree, params)  # no-op at f32
            updates, new_opt = optimizer.update(aggr_tree, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

    def _local_step(state, x_local, y_local):
        base = jax.random.fold_in(state.rng, state.step)
        (atk_key, sub_key, psatk_key, drop_base,
         gar_key, mgar_key, msub_key) = jax.random.split(base, 7)
        ps_shard = jax.lax.axis_index(ps_axis)
        w_shard = jax.lax.axis_index(axis)
        ps_ids = ps_shard * per_ps + jnp.arange(per_ps)
        slot_ids = w_shard * per_w + jnp.arange(per_w)

        # Closed-loop defense weights (DESIGN.md §16/§17): per-PLANE
        # suspicion from the carried exclusion EMAs — one history for the
        # n_w workers, an independent one for the n_ps replicas. Exactly
        # 1.0 on clean histories (the weighted identity contract).
        def_w = ps_def_w = None
        if defense is not None:
            susp_w = state.defense_state["exc"] / jnp.maximum(
                state.defense_state["obs"], 1e-6
            )
            def_w = defense_lib.suspicion_weights(
                susp_w, power=d_power, floor=d_floor
            )
            susp_ps = state.defense_state["ps_exc"] / jnp.maximum(
                state.defense_state["ps_obs"], 1e-6
            )
            ps_def_w = defense_lib.suspicion_weights(
                susp_ps, power=d_power, floor=d_floor
            )

        # Adaptive MODEL-plane controller (DESIGN.md §17): play the
        # carried bracket's midpoint as the collusion magnitude, rotate
        # the active replica cohort. Nothing here is traced when the PS
        # attack is oblivious.
        act_ps_mask = byz_ps_mask
        eff_ps_params = ps_attack_params
        ps_mag = None
        p_lo = p_hi = None
        if ps_adaptive_cfg is not None:
            p_lo = state.attack_state["lo"]
            p_hi = state.attack_state["hi"]
            ps_mag = adaptive_lib.played_magnitude(p_lo, p_hi)
            act_ps_mask = adaptive_lib.active_mask_traced(
                ps_adaptive_cfg, state.step
            )
            eff_ps_params = dict(ps_attack_params)
            eff_ps_params[
                adaptive_lib.magnitude_key(ps_adaptive_cfg.base)
            ] = ps_mag

        # --- gradient phase, vmapped over this shard's local PS slots -----
        def grads_for_ps(ps_local_idx, params, ms):
            keys = jax.vmap(
                lambda i: jax.random.fold_in(
                    jax.random.fold_in(drop_base, ps_local_idx), i
                )
            )(slot_ids)
            g, (loss, ms_out) = core.per_slot_grads(
                grad_fn, params, ms, x_local, y_local, keys,
                fused_fn=slot_fused_fn, force_unroll=force_unroll,
            )
            with core.phase("grads"):
                g = core.cast_leaves(g, gar_dtype)
            with core.phase("exchange"):
                if tree_ok:
                    gathered = jax.tree.map(
                        lambda l: jax.lax.all_gather(l, axis, tiled=True), g
                    )  # tree with (n_w, ...) leaves
                    return gathered, loss, ms_out
                flat = core.flatten_rows(g)  # (per_w, d)
                stack = jax.lax.all_gather(flat, axis, tiled=True)  # (n_w, d)
                return stack, loss, ms_out

        # Unrolled over the (small, static) local PS slots: a vmap here would
        # batch conv kernels over the ps axis, which XLA's conv batching
        # rules handle poorly; per_ps is O(1) so unrolling is free.
        ms = state.model_state
        outs = [
            grads_for_ps(
                ps_ids[k],
                jax.tree.map(lambda l: l[k], state.params),
                ms,
            )
            for k in range(per_ps)
        ]
        losses = jnp.stack([o[1] for o in outs])  # (per_ps, per_w)
        ms_all = jax.tree.map(
            lambda *ls: jnp.stack(ls), *[o[2] for o in outs]
        )

        tap = None
        if tree_ok:
            # Tree-mode gradient phase: per-PS attack + GAR + update, all
            # on the stacked TREE (unrolled over the O(1) local PS slots;
            # no flat stack is built). subset is None here (see tree_ok).
            new_params_list, new_opt_list = [], []
            for k in range(per_ps):
                slot_gar_key = jax.random.fold_in(gar_key, ps_ids[k])
                if fold_plan is not None:
                    # Folded attack: Gram remap instead of row rewrite
                    # (parallel/fold.py) — same eligibility as aggregathor.
                    aggr_tree = fold.folded_tree_aggregate(
                        gar, fold_plan, outs[k][0], f=fw, key=slot_gar_key,
                        gar_params=gar_params,
                    )
                else:
                    with core.phase("attack"):
                        poisoned = apply_gradient_attack_tree(
                            attack, outs[k][0], byz_worker_mask, key=atk_key,
                            **attack_params,
                        )
                    with core.phase("rule"):
                        aggr_tree = gar.tree_aggregate(
                            poisoned, f=fw, key=slot_gar_key, **gar_params,
                        )
                with core.phase("update"):
                    p_k = jax.tree.map(lambda l: l[k], state.params)
                    o_k = jax.tree.map(lambda l: l[k], state.opt_state)
                    aggr_tree = core.cast_like(aggr_tree, p_k)  # no-op at f32
                    updates, o_k = optimizer.update(aggr_tree, o_k, p_k)
                    new_params_list.append(optax.apply_updates(p_k, updates))
                    new_opt_list.append(o_k)
            with core.phase("update"):
                new_params = jax.tree.map(
                    lambda *ls: jnp.stack(ls), *new_params_list
                )
                new_opt = jax.tree.map(
                    lambda *ls: jnp.stack(ls), *new_opt_list
                )
            if telemetry:
                # Per-PS audit taps on the gradient plane (no subsets on
                # this branch — see tree_ok): each slot's gathered tree
                # differs (its own replica's gradients), so tap each and
                # average; pmean folds in the other PS shards.
                with core.phase("rule"):  # a tap recomputes the rule's view
                    bundles = [
                        taps_lib.compute_flat(
                            gar.name,
                            apply_gradient_attack(
                                attack, core.flatten_rows(outs[k][0]),
                                byz_worker_mask, key=atk_key, **attack_params,
                            ),
                            fw, key=jax.random.fold_in(gar_key, ps_ids[k]),
                            params=gar_params,
                        )
                        for k in range(per_ps)
                    ]
                    tap = taps_lib.mean_bundles(
                        jax.tree.map(lambda *ls: jnp.stack(ls), *bundles)
                    )
        else:
            with core.phase("attack"):
                stacks = jnp.stack([o[0] for o in outs])  # (per_ps, n_w, d)
                stacks = jax.vmap(
                    lambda s: apply_gradient_attack(
                        attack, s, byz_worker_mask, key=atk_key,
                        **attack_params
                    )
                )(stacks)

            new_params, new_opt = jax.vmap(
                _ps_slot_step, in_axes=(0, 0, 0, 0, None, None)
            )(ps_ids, state.params, state.opt_state, stacks,
              (sub_key, gar_key), def_w)
            if telemetry or defense is not None:
                with core.phase("rule"):  # a tap recomputes the rule's view
                    def one_tap(ps_id, stack):
                        # SAME (sel, key, weight) derivation as _ps_slot_step,
                        # so the tap audits exactly the (suspicion-weighted)
                        # quorum this PS aggregated — the defense's feedback.
                        gkey = jax.random.fold_in(gar_key, ps_id)
                        if subset is not None and subset < num_workers:
                            sel = core.subset_indices(
                                jax.random.fold_in(sub_key, ps_id),
                                num_workers, subset,
                            )
                            sub = stack[sel]
                            if def_w is not None:
                                sub = (sub * def_w[sel][:, None]).astype(
                                    sub.dtype
                                )
                            bundle = taps_lib.compute_flat(
                                gar.name, sub, fw, key=gkey,
                                params=gar_params,
                            )
                            return taps_lib.scatter(bundle, sel, num_workers)
                        sub = stack
                        if def_w is not None:
                            sub = (sub * def_w[:, None]).astype(sub.dtype)
                        return taps_lib.compute_flat(
                            gar.name, sub, fw, key=gkey, params=gar_params,
                        )

                    tap = taps_lib.mean_bundles(
                        jax.vmap(one_tap)(ps_ids, stacks)
                    )

        # --- model gather phase (ByzSGD/trainer.py:240-244) ----------------
        with core.phase("model_exchange"):
            flat_models = core.flatten_rows(new_params)  # (per_ps, d)
            # (n_ps, d)
            models = jax.lax.all_gather(flat_models, ps_axis, tiled=True)
        params0 = jax.tree.map(lambda l: l[0], new_params)
        # Model-plane selection feedback (DESIGN.md §17): the rule's
        # verdict over the SAME poisoned, weighted replica stack the
        # gather consumes — what the adaptive PS controller bisects
        # against and what feeds the replica-plane suspicion EMA. Under
        # model_subset the bundle is the observer mean over every PS
        # view, pmean'd so the carried state stays replicated.
        ps_bundle = None
        if defense is not None or ps_adaptive_cfg is not None:
            with core.phase("model_rule"):  # a tap recomputes the rule's view
                poisoned_m = apply_model_attack_rows(
                    ps_attack, models, act_ps_mask, key=psatk_key,
                    **eff_ps_params,
                )
                if ps_def_w is not None:
                    poisoned_m = (poisoned_m * ps_def_w[:, None]).astype(
                        poisoned_m.dtype
                    )
                if model_waiting:
                    def one_mtap(ps_id):
                        # SAME (sel, key) derivation as the gather below.
                        sel = core.subset_indices(
                            jax.random.fold_in(msub_key, ps_id), num_ps,
                            model_subset,
                        )
                        mkey = jax.random.fold_in(mgar_key, ps_id)
                        bundle = taps_lib.compute_flat(
                            model_gar.name, poisoned_m[sel], fps, key=mkey,
                            params=model_gar_params,
                        )
                        return taps_lib.scatter(bundle, sel, num_ps)

                    ps_bundle = taps_lib.mean_bundles(
                        jax.vmap(one_mtap)(ps_ids)
                    )
                    ps_bundle = jax.tree.map(
                        lambda l: jax.lax.pmean(l, ps_axis), ps_bundle
                    )
                else:
                    ps_bundle = taps_lib.compute_flat(
                        model_gar.name, poisoned_m, fps, key=mgar_key,
                        params=model_gar_params,
                    )
        if model_waiting:
            # Reference-faithful wait-n-f on the model plane: each PS
            # aggregates only its own seeded fastest q_m peer models
            # (get_models(num_ps - fps), trainer.py:240-242 /
            # server.py:161-184) — honest replicas genuinely DIVERGE here;
            # the model GAR's contraction, not a broadcast, holds them
            # together. Same per-observer composition as the gradient
            # plane: for Gram-form rules ONE model Gram serves every local
            # PS slot via (q_m, q_m) sub-Gram selections, with
            # deterministic PS attacks (reverse/crash) folded into the
            # Gram remap instead of poisoning the rows.
            with core.phase("model_rule"):
                sels = jax.vmap(
                    lambda i: core.subset_indices(
                        jax.random.fold_in(msub_key, i), num_ps, model_subset
                    )
                )(ps_ids)
                mkeys = jax.vmap(
                    lambda i: jax.random.fold_in(mgar_key, i)
                )(ps_ids)
            if model_gram_ok:
                base_models = models
                if model_fold_plan is None:
                    with core.phase("attack"):
                        base_models = apply_model_attack_rows(
                            ps_attack, models, act_ps_mask, key=psatk_key,
                            **eff_ps_params,
                        )
                with core.phase("model_rule"):  # claims the fold's own "rule"
                    aggr_models = fold.folded_tree_aggregate_multi(
                        model_gar, model_fold_plan, base_models, f=fps,
                        keys=mkeys, gar_params=model_gar_params,
                        subset_sels=sels, row_weights=ps_def_w,
                    )  # (per_ps, d)
            else:
                with core.phase("attack"):
                    poisoned = apply_model_attack_rows(
                        ps_attack, models, act_ps_mask, key=psatk_key,
                        **eff_ps_params,
                    )

                def one_ps(sel, mkey):
                    sub = poisoned[sel]
                    if ps_def_w is not None:
                        # Replica-plane suspicion discount composed after
                        # the subset — the gather's rows enter the rule
                        # weighted, like the gradient plane's quorum.
                        sub = (sub * ps_def_w[sel][:, None]).astype(
                            sub.dtype
                        )
                    if granularity == "layer":
                        return core.segmented_aggregate(
                            lambda s, i: model_gar.unchecked(
                                s, f=fps, key=jax.random.fold_in(mkey, i),
                                **model_gar_params,
                            ),
                            sub,
                            core.leaf_segments(params0),
                        )
                    return model_gar.unchecked(
                        sub, f=fps, key=mkey, **model_gar_params
                    )

                with core.phase("model_rule"):
                    aggr_models = jax.vmap(one_ps)(sels, mkeys)  # (per_ps, d)
            with core.phase("model_rule"):
                new_params = jax.tree.map(
                    lambda *ls: jnp.stack(ls),
                    *[
                        core.unflatten_like(params0, aggr_models[k])
                        for k in range(per_ps)
                    ],
                )
        else:
            with core.phase("attack"):
                models = apply_model_attack_rows(
                    ps_attack, models, act_ps_mask, key=psatk_key,
                    **eff_ps_params,
                )
            with core.phase("model_rule"):
                if ps_def_w is not None:
                    models = (models * ps_def_w[:, None]).astype(
                        models.dtype
                    )
                if granularity == "layer":
                    aggr_model = core.segmented_aggregate(
                        lambda s, i: model_gar.unchecked(
                            s, f=fps, key=jax.random.fold_in(mgar_key, i),
                            **model_gar_params,
                        ),
                        models,
                        core.leaf_segments(params0),
                    )
                else:
                    aggr_model = model_gar.unchecked(
                        models, f=fps, key=mgar_key, **model_gar_params
                    )
                written = core.unflatten_like(params0, aggr_model)
                new_params = jax.tree.map(
                    lambda l: jnp.broadcast_to(l[None], (per_ps,) + l.shape),
                    written,
                )

        # losses: (per_ps, per_w) — honest-worker mean, then over the mesh.
        honest = (~byz_worker_mask).astype(losses.dtype)
        local_honest = honest[slot_ids]
        loss_num = jnp.sum(jnp.mean(losses, axis=0) * local_honest)
        loss_den = jnp.sum(local_honest)
        mean_loss = jax.lax.psum(loss_num, axis) / jnp.maximum(
            jax.lax.psum(loss_den, axis), 1.0
        )
        mean_loss = jax.lax.pmean(mean_loss, ps_axis)

        new_ms = core.mean_model_state(
            jax.tree.map(lambda l: l.reshape((-1,) + l.shape[2:]), ms_all), axis
        )
        new_ms = jax.tree.map(lambda l: jax.lax.pmean(l, ps_axis), new_ms)

        tap_full = None
        if tap is not None:
            # Observer mean over ALL num_ps server views (the local slots
            # were averaged where `tap` was built). pmean'd ONCE here so
            # the defense's carried state — updated from it below — stays
            # replicated across shards.
            tap_full = jax.tree.map(
                lambda l: jax.lax.pmean(l, ps_axis), tap
            )

        # Adaptive feedback: was the active replica cohort admitted by
        # the model gather? Majority-excluded among the OBSERVED
        # colluders counts as detected; a round that observed none
        # (cohort outside every model subset) holds the bracket.
        new_attack_state = state.attack_state
        ps_detected = None
        if ps_adaptive_cfg is not None:
            with core.phase("attack"):
                act_f = act_ps_mask.astype(jnp.float32) * ps_bundle["observed"]
                cnt = jnp.sum(act_f)
                admitted = jnp.sum(
                    (ps_bundle["selected"] > 0).astype(jnp.float32) * act_f
                )
                ps_detected = admitted * 2.0 < cnt
                upd_lo, upd_hi = adaptive_lib.update_bracket(
                    p_lo, p_hi, ps_detected,
                    mag_min=ps_adaptive_cfg.mag_min,
                    mag_max=ps_adaptive_cfg.mag_max,
                    regrow=ps_adaptive_cfg.regrow,
                )
                hold = cnt == 0.0
                new_attack_state = {
                    "lo": jnp.where(hold, p_lo, upd_lo),
                    "hi": jnp.where(hold, p_hi, upd_hi),
                }

        new_defense_state = state.defense_state
        if defense is not None:
            # The hub's exclusion law (observed minus admitted) carried
            # as decayed EMAs, one pair PER PLANE — the in-graph twin of
            # the two MetricsHub histories the cluster roles keep.
            dec = jnp.float32(d_decay)
            w_obs = tap_full["observed"]
            w_ind = (tap_full["selected"] > 0).astype(jnp.float32) * w_obs
            m_obs = ps_bundle["observed"]
            m_ind = (ps_bundle["selected"] > 0).astype(jnp.float32) * m_obs
            new_defense_state = {
                "obs": state.defense_state["obs"] * dec + w_obs,
                "exc": state.defense_state["exc"] * dec + (w_obs - w_ind),
                "ps_obs": state.defense_state["ps_obs"] * dec + m_obs,
                "ps_exc": state.defense_state["ps_exc"] * dec
                + (m_obs - m_ind),
            }

        metrics = {"loss": mean_loss}
        if telemetry and tap_full is not None:
            metrics["tap"] = tap_full
        if ps_adaptive_cfg is not None:
            # Controller observability (schema v8 ``ps_attack_adapt``
            # events via the app loop): the magnitude played on the model
            # plane and whether the gather caught it this round.
            metrics["ps_attack_mag"] = jnp.asarray(ps_mag, jnp.float32)
            metrics["ps_attack_detected"] = ps_detected.astype(jnp.float32)
        if defense is not None:
            metrics["defense_w"] = def_w
            metrics["ps_defense_w"] = ps_def_w
        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                model_state=new_ms,
                opt_state=new_opt,
                attack_state=new_attack_state,
                defense_state=new_defense_state,
            ),
            metrics,
        )

    # Replicated carries for the model-plane controller bracket and the
    # per-plane defense EMAs (None fields stay structurally absent, so
    # oblivious/undefended programs are byte-identical to the pre-§17
    # ones).
    state_specs = core.TrainState(
        step=P(), params=P(ps_axis), model_state=P(),
        opt_state=P(ps_axis), rng=P(),
        attack_state=(P() if ps_adaptive_cfg is not None else None),
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
    step_fn.batch_sharding = NamedSharding(mesh, P(axis))
    # Chunking hook (core.make_chunked_step): scan the shard_map body
    # directly; shardings propagate as in the per-step jit (none pinned).
    step_fn.inner = sharded_step
    return init_fn, step_fn, eval_fn
