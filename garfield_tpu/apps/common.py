"""Shared CLI scaffolding and training-loop driver for all applications.

Flag surface mirrors the reference trainers (Aggregathor/trainer.py:62-135):
--dataset/--batch/--num_workers/--num_ps/--fw/--fps/--model/--loss/
--optimizer/--opt_args (JSON)/--num_iter/--gar/--acc_freq/--bench/--log, plus
the knobs that were hard-coded or implicit there: --attack (byzWorker.py
attack table), --subset (the wait-n-f async path, server.py:134-155),
--granularity (Garfield_CC per-layer mode), --seed (torch.manual_seed(1234),
trainer.py:210), --lr_decay*/--lr_decay_epochs (the x0.2/30-epoch hack,
trainer.py:227-229), and new-capability flags: --checkpoint_dir/--resume
(SURVEY §5: checkpointing is our deliberate upgrade), --profile_dir
(jax.profiler), --mesh (device-axis layout).
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import data as data_lib, models as models_lib, parallel
from ..utils import checkpoint as ckpt_lib, profiling, selectors, tools

__all__ = ["base_parser", "build_ingredients", "chunk_length", "train"]


def base_parser(description, *, default_model="convnet", default_loss="nll"):
    p = argparse.ArgumentParser(
        description=description, formatter_class=argparse.RawTextHelpFormatter
    )
    a = p.add_argument
    a("--dataset", type=str, default="mnist",
      help="Dataset to be used, e.g., mnist, cifar10, cifar100, pima.")
    a("--batch", type=int, default=32,
      help="Minibatch size employed by each worker.")
    a("--num_workers", type=int, default=1, help="Number of workers.")
    a("--num_ps", type=int, default=1, help="Number of parameter servers.")
    a("--fw", type=int, default=0, help="Declared Byzantine workers.")
    a("--fps", type=int, default=0, help="Declared Byzantine servers.")
    a("--model", type=str, default=default_model,
      help="Model name, e.g., convnet, cifarnet, resnet18, vgg16, ...")
    a("--loss", type=str, default=default_loss,
      help="Loss: nll, cross-entropy, bce.")
    a("--optimizer", type=str, default="sgd",
      help="Optimizer: sgd, adam, adamw, rmsprop, adagrad.")
    a("--opt_args", type=json.loads, default={"lr": "0.1"},
      help='Optimizer args as JSON, e.g., \'{"lr":"0.1","momentum":"0.9"}\'')
    a("--num_iter", type=int, default=5000, help="Training iterations.")
    a("--gar", type=str, default="average", help="Gradient aggregation rule.")
    a("--acc_freq", type=int, default=100,
      help="Iterations between accuracy evaluations.")
    a("--bench", action="store_true",
      help="Print per-step time and derived collective bandwidth.")
    a("--log", action="store_true", help="Print loss every iteration.")
    # --- knobs hard-coded in the reference ---
    a("--attack", type=str, default=None,
      help="Byzantine gradient attack: random, reverse, drop, lie, empire, "
           "crash — or an ADAPTIVE controller (DESIGN.md §16): "
           "adaptive-lie, adaptive-empire (magnitude bisected against the "
           "rule's selection feedback, cohort rotation over an f_pool > fw "
           "colluder pool, full-magnitude bursts in quorum-degradation "
           "windows) — or a TARGETED data poisoner (DESIGN.md §17): "
           "labelflip (the cohort relabels source-class samples as the "
           "target class), backdoor (pixel-trigger stamp + target label); "
           "targeted success is measured per class (ASR, schema v8), not "
           "as divergence.")
    a("--attack_params", type=json.loads, default={},
      help="Attack parameters as JSON (e.g. lie z, empire eps; adaptive "
           'controller knobs: {"f_pool": 4, "rotation": 8, "mag_max": 6.0, '
           '"burst": 6.0}; targeted knobs: {"source": 0, "target": 1, '
           '"poison_frac": 1.0, "trigger_size": 2, "trigger_value": 2.5}).')
    a("--defense", type=str, default=None,
      choices=["none", "weighted", "escalate", "data", "weighted+data",
               "escalate+data"],
      help="Closed-loop defense (aggregators/defense.py, DESIGN.md §16/"
           "§18): 'weighted' scales each rank's rows by its (decayed) "
           "suspicion before the GAR; 'escalate' adds the rule ladder "
           "(krum -> multi-krum -> bulyan) driven by suspicion "
           "concentration, with hysteresis; 'data' adds the DATA-plane "
           "detectors (aggregators/dataplane.py: per-class classifier-"
           "head gradient fingerprints, spectral filtering + 2-means "
           "cohort clustering — the only plane that sees a backdoor/"
           "labelflip cohort, whose gradients are divergence-invisible); "
           "'weighted+data'/'escalate+data' run the GAR-side and "
           "data-plane defenses simultaneously. Off (default): the "
           "vanilla rule — trajectories bitwise unchanged.")
    a("--defense_params", type=json.loads, default={},
      help="Defense knobs as JSON: power/floor (the suspicion-weight "
           "law), halflife (suspicion EMA, steps), theta_up/theta_down/"
           "patience/clean_window/levels (the escalation hysteresis), "
           "dp_tau/dp_power/dp_floor/dp_halflife (the data-plane "
           "spectral tail threshold + its own weight law and EMA).")
    a("--suspicion_halflife", type=float, default=None,
      help="Exponential halflife (in observed steps) of the telemetry "
           "hub's WINDOWED suspicion score (schema v7): the decayed "
           "score a rotated Byzantine cohort cannot launder by sitting "
           "honest while its cumulative denominator grows. Default: env "
           "GARFIELD_SUSPICION_HALFLIFE, else cumulative-only.")
    a("--subset", type=int, default=None,
      help="Async wait-for-q emulation: aggregate a random q-subset "
           "of worker gradients each step (server.py:134-155).")
    a("--async", dest="async_agg", action="store_true",
      help="Bounded-staleness asynchronous aggregation (DESIGN.md §14): "
           "the PS aggregates the freshest n-fw arrivals with staleness-"
           "discounted weights and reuses admissible stale frames instead "
           "of blocking on stragglers; workers publish-and-continue. In "
           "--cluster mode this is the real host-plane protocol (SSMW/"
           "MSMW); on-mesh it is the seeded in-graph emulation "
           "(aggregathor topology). Off: round-synchronous (default).")
    a("--max_staleness", type=int, default=None,
      help="Hard staleness cutoff for --async, in rounds: a gradient "
           "tagged more than this many rounds behind the PS is excluded "
           "(weight 0). 0 = synchronous semantics (exact-round frames "
           "only, bitwise-equal trajectory). Default: env "
           "GARFIELD_MAX_STALENESS, else 4.")
    a("--staleness_decay", type=float, default=None,
      help="Per-round geometric discount for --async: a gradient tau "
           "rounds stale enters the GAR scaled by decay**tau. Default: "
           "env GARFIELD_STALENESS_DECAY, else 0.5.")
    a("--autoscale", action="store_true",
      help="Load-driven worker autoscaling (DESIGN.md §15; --cluster PS "
           "role, requires --async): the PS watches its round rate and "
           "quorum margin and SPAWNS worker processes (reserve ranks "
           "from the cluster config's worker list, launched with this "
           "process's own CLI re-targeted at worker:K) or RETIRES them "
           "(clean stop sentinel + watcher teardown; a later spawn "
           "rejoins through read_latest and re-reads its shard) so the "
           "deployment tracks --target_rate instead of a fixed n. With "
           "--autoscale the PS launches its own initial workers — do "
           "not start worker processes externally.")
    a("--target_rate", type=float, default=0.0,
      help="Autoscale throughput target in rounds/s; <= 0 (default) "
           "auto-calibrates to the first measurement window's rate, so "
           "the initial deployment's service level is held through load "
           "spikes.")
    a("--autoscale_min", type=int, default=1,
      help="Fewest active workers the autoscaler may retire down to "
           "(must keep the GAR feasible at q = min - fw).")
    a("--autoscale_max", type=int, default=0,
      help="Most workers the autoscaler may spawn; 0 (default) = every "
           "worker slot in the cluster config.")
    a("--autoscale_window", type=int, default=8,
      help="Rounds per autoscale measurement window.")
    a("--autoscale_cooldown", type=int, default=8,
      help="Rounds between consecutive autoscale actions (the new "
           "membership's steady state is measured, not the transient).")
    a("--straggler_ms", type=int, default=0,
      help="Scenario-injection knob (the straggler half of the async "
           "plane's tests): in cluster mode THIS "
           "worker sleeps the given milliseconds after each gradient "
           "compute before publishing — a reproducible 'slow rank'. "
           "0 (default) disables; ignored on-mesh and on PS roles.")
    a("--granularity", type=str, default="model", choices=["model", "layer"],
      help="GAR over the whole flat gradient or per parameter tensor "
           "(Garfield_CC semantics).")
    a("--seed", type=int, default=1234, help="Base PRNG seed.")
    a("--lr_decay", type=float, default=0.2,
      help="LR decay factor applied every --lr_decay_epochs epochs.")
    a("--lr_decay_epochs", type=int, default=0,
      help="Epoch interval for LR step decay (reference uses 30 for "
           "CIFAR-10; 0 disables).")
    a("--train_size", type=int, default=None,
      help="Optional cap on training-set size (debug/smoke).")
    a("--dtype", type=str, default="float32",
      choices=["float32", "bfloat16"],
      help="Model compute dtype (bfloat16 routes matmuls to the MXU).")
    a("--gar_dtype", type=str, default=None,
      choices=["float32", "bfloat16"],
      help="Aggregation-pipeline dtype: bfloat16 halves the HBM traffic of "
           "the attack+gather+GAR phase (Gram still accumulates in f32); "
           "default: full width.")
    a("--gar_params", type=json.loads, default={},
      help='Rule hyperparameters as JSON passed through to the GAR, e.g. '
           '\'{"tau": 10.0}\' (cclip) or \'{"p": 0.5}\' (condense).')
    a("--worker_momentum", type=float, default=None,
      help="Worker-momentum beta in [0, 1): workers submit EMA momenta "
           "instead of raw gradients (Karimireddy et al. 2021) — pairs "
           "with --gar cclip to survive the lie attack that defeats "
           "krum/bulyan (BASELINE.md TTA grid). Use a PLAIN-SGD server "
           "with it (omit momentum from --opt_args and raise lr ~x1/"
           "(1-beta)): the worker EMA is the momentum; stacking it on a "
           "momentum server destabilizes training. Default: off.")
    a("--fault_crashes", type=json.loads, default=None,
      help='Host crash schedule as JSON {"host": step, ...}: from the given '
           "step on, that simulated host's worker slots feed zero gradients "
           "(crash attack) and count against the Byzantine budget — the "
           "host-level fault simulation of utils/multihost.FaultSchedule "
           "(the reference's mar='crash', Garfield_CC/trainer.py:97,137).")
    a("--fault_hosts", type=int, default=None,
      help="Number of simulated hosts the worker slots fold onto for "
           "--fault_crashes (default: one host per worker slot).")
    # --- new capabilities (absent in the reference) ---
    a("--chunk_steps", type=int, default=None,
      help="On-device step chunking (docs/DESIGN.md §12): lax.scan K "
           "training steps inside ONE jitted dispatch "
           "(parallel/core.make_chunked_step), so K-1 of every K host "
           "dispatches disappear and XLA overlaps step i's optimizer/GAR "
           "tail with step i+1's forward. Chunks auto-clip at every loop "
           "boundary (eval points, checkpoint saves, crash-schedule "
           "re-jits, the profiled step, end of run), and trajectories are "
           "bitwise equal to per-step execution. Default: env "
           "GARFIELD_CHUNK_STEPS, else 1 (per-step).")
    a("--telemetry", type=str, nargs="?", const="telemetry", default=None,
      metavar="DIR",
      help="Enable the telemetry plane (docs/TELEMETRY.md): in-graph GAR "
           "audit taps (per-rank selection masks/scores; cclip tau + clip "
           "fraction), host-side aggregation with per-rank SUSPICION "
           "scores (cumulative exclusion frequency under the active GAR), "
           "and exporters — schema-versioned JSONL (DIR/telemetry.jsonl) "
           "plus a Prometheus text snapshot (DIR/metrics.prom). DIR "
           "defaults to ./telemetry. Costs one host sync + one extra "
           "selection pass per step; disabled (the default) it traces "
           "nothing and the trajectory is bitwise identical.")
    a("--trace", action="store_true",
      help="Distributed round tracing (docs/TELEMETRY.md §4): record "
           "host-side SPANS for every phase of a round (broadcast, "
           "quorum wait, waiter-thread wire decode + H2D, GAR compute, "
           "apply, eval, checkpoint, ...) as schema-v5 records in the "
           "telemetry JSONL. Implies --telemetry (spans need the sink); "
           "host-only, so trajectories stay bitwise identical. Merge a "
           "cluster run's per-role streams into a Chrome trace + run "
           "report with `python -m garfield_tpu.telemetry.report DIR`. "
           "Env twin: GARFIELD_TRACE=1.")
    a("--checkpoint_dir", type=str, default=None,
      help="Directory for orbax checkpoints (reference has none).")
    a("--checkpoint_freq", type=int, default=1000,
      help="Iterations between checkpoints.")
    a("--resume", action="store_true",
      help="Resume from the latest checkpoint in --checkpoint_dir.")
    a("--profile_dir", type=str, default=None,
      help="Write one jax.profiler trace of a few whole steady-state "
           "steps here (from the 6th step on, ended by a device sync). "
           "Turns the host spans of --trace on, as profiler annotations: "
           "the trace holds them on /host:CPU beside the device's "
           "operations, which the step's phase scopes name.")
    a("--sync_eval", action="store_true",
      help="Run periodic accuracy inline (blocking) instead of overlapped "
           "with training in a side thread (the reference's accuracy "
           "thread, Aggregathor/trainer.py:251-264, is the default).")
    a("--mesh", type=str, default=None,
      help='Mesh axis layout, e.g. "workers=8" or "ps=2,workers=4"; '
           "default: all devices on the topology's main axis.")
    return p


def resolve_suspicion_halflife(args):
    """--suspicion_halflife with its GARFIELD_SUSPICION_HALFLIFE env twin
    (the fleet-wide switch convention of utils/rounds.resolve)."""
    hl = getattr(args, "suspicion_halflife", None)
    if hl is None:
        env = os.environ.get("GARFIELD_SUSPICION_HALFLIFE", "").strip()
        hl = float(env) if env else None
    return hl


def parse_mesh(spec):
    """'ps=2,workers=-1' -> Mesh. Fixed-size specs smaller than the device
    count use the first prod(sizes) devices (a run may occupy a sub-slice of
    the chips, like the reference running fewer ranks than hosts)."""
    if not spec:
        return None
    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        axes[name.strip()] = int(size)
    devices = None
    sizes = list(axes.values())
    if -1 not in sizes:
        import math

        total = math.prod(sizes)
        devices = jax.devices()[:total]
    return parallel.mesh.make_mesh(axes, devices=devices)


def _coerce_opt_args(opt_args):
    """Reference CLIs pass numbers as strings ('{"lr":"0.2"}'); coerce."""
    out = {}
    for k, v in opt_args.items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            out[k] = v
    return out


def build_ingredients(args, iters_per_epoch=None):
    """(module, loss_fn, optimizer) from the CLI flags — the selector layer
    (garfieldpp/tools.py:47-123) applied exactly as the trainers do."""
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.loss == "bce" and models_lib.num_classes_dict.get(args.dataset) != 1:
        raise SystemExit(
            f"--loss bce expects a binary dataset (pima), got "
            f"{args.dataset!r}; use --loss nll or cross-entropy."
        )
    module = models_lib.select_model(args.model, args.dataset, dtype=dtype)
    loss_fn = selectors.select_loss(args.loss)
    opt_args = _coerce_opt_args(dict(args.opt_args))
    lr = opt_args.pop("lr", 0.1)
    if args.lr_decay_epochs and iters_per_epoch:
        lr = selectors.adjust_learning_rate(
            lr, decay=args.lr_decay,
            every_epochs=args.lr_decay_epochs,
            iters_per_epoch=iters_per_epoch,
        )
    optimizer = selectors.select_optimizer(
        args.optimizer, lr=lr,
        momentum=opt_args.pop("momentum", 0.0),
        weight_decay=opt_args.pop("weight_decay", 0.0),
        **opt_args,
    )
    return module, loss_fn, optimizer


def load_data(args, num_slots):
    """Stacked per-slot batch streams + test set.

    ``num_slots`` is the leading axis the topology shards (workers for
    aggregathor/byzsgd, nodes for learn). Returns
    (xs, ys, test_batches, iters_per_epoch) with xs: (S, B, bsz, ...).
    """
    manager = data_lib.DatasetManager(
        args.dataset, args.batch, num_slots, num_slots, 0,
        train_size=args.train_size,
    )
    manager.num_ps = 0  # slots are pure data partitions here
    xs, ys = manager.sharded_train_batches()
    test = manager.get_test_set()
    return xs, ys, test, xs.shape[1]


def _crash_schedule(args, num_slots, declared_f):
    """Validated FaultSchedule from --fault_crashes, or None.

    Fails fast (before any data/model work) on: combination with --attack,
    host layouts that leave slots unattached or hosts empty, out-of-range
    host ids, and crash counts exceeding the declared Byzantine budget —
    each of which would otherwise make the experiment silently wrong.
    """
    crashes = getattr(args, "fault_crashes", None)
    if not crashes:
        return None
    if getattr(args, "attack", None) or getattr(args, "model_attack", None):
        raise SystemExit(
            "--fault_crashes simulates crashed slots as zero-gradient "
            "(crash-attack) rows and cannot be combined with "
            "--attack/--model_attack; run the attack and the crash scenario "
            "separately."
        )
    num_hosts = getattr(args, "fault_hosts", None)
    num_hosts = num_slots if num_hosts is None else num_hosts
    if not (1 <= num_hosts <= num_slots) or num_slots % num_hosts:
        raise SystemExit(
            f"--fault_hosts {num_hosts} must evenly divide the "
            f"{num_slots} worker slots (1 <= hosts <= slots)."
        )
    crashes = {int(k): int(v) for k, v in crashes.items()}
    bad = [h for h in crashes if not 0 <= h < num_hosts]
    if bad:
        raise SystemExit(
            f"--fault_crashes host ids {bad} out of range [0, {num_hosts})."
        )
    dead_slots = len(
        [h for h, at in crashes.items() if at < args.num_iter]
    ) * (num_slots // num_hosts)
    if dead_slots > declared_f:
        raise SystemExit(
            f"--fault_crashes kills {dead_slots} worker slots by step "
            f"{args.num_iter} but the declared Byzantine budget is "
            f"{declared_f}; raise --fw (crashed slots count against it)."
        )
    from ..utils import multihost

    return multihost.FaultSchedule(num_hosts, crashes=crashes)


def chunk_length(i, *, chunk, num_iter, acc_freq=0, checkpoint_freq=0,
                 crash_steps=(), profile_step=None):
    """Steps the chunk starting at step ``i`` may cover (>= 1, <= chunk).

    A chunked dispatch (``--chunk_steps``) is opaque to the host until it
    returns, so every host-side action the per-step loop interleaves must
    land exactly on a chunk boundary. The clip rules (one per boundary
    kind, each pinned by a test in tests/test_chunked.py):

      - **eval**: accuracy runs after step j when ``j % acc_freq == 0``,
        so the chunk must end at ``j + 1`` for the first such j >= i;
      - **checkpoint**: a save fires after step j when ``(j + 1) %
        checkpoint_freq == 0``, so the chunk must end on the next multiple
        of ``checkpoint_freq`` above i;
      - **crash**: a ``--fault_crashes`` event at step s re-jits the step
        with the new Byzantine mask, so no chunk may span s;
      - **profile**: the ``--profile_dir`` trace starts at a chunk's
        first step, and that chunk is one step long (the steps traced
        after it chunk as usual);
      - **end of run**: never past ``num_iter``.
    """
    end = min(i + chunk, num_iter)
    if acc_freq:
        # First eval point j >= i (j % acc_freq == 0); eval needs the
        # post-step-j state, so the chunk may include j but nothing after.
        end = min(end, i + (-i % acc_freq) + 1)
    if checkpoint_freq:
        end = min(end, i + checkpoint_freq - i % checkpoint_freq)
    for at in crash_steps:
        if at > i:
            end = min(end, at)
    if profile_step is not None:
        if i < profile_step:
            end = min(end, profile_step)
        elif i == profile_step:
            end = min(end, i + 1)
    return max(1, end - i)


def train(args, *, topology, make_trainer_kwargs, num_slots, tag):
    """The reference training loop (Aggregathor/trainer.py:226-264), SPMD:
    batch selection by step index (batch i = train_set[i % len],
    worker.py:87), jit'd step, periodic accuracy, optional bench/profile
    instrumentation, optional checkpointing. With --fault_crashes, the jit'd
    step is rebuilt at each (rare) crash event so the dead hosts' slots turn
    into zero-gradient Byzantine rows from that step on."""
    import inspect

    t_start = time.time()
    # Every CLI run shares one persistent compile cache (the directory the
    # launcher named, else <checkout>/.jax_cache): a second run of the same
    # config does not recompile ResNet-18.
    profiling.enable_compile_cache()
    declared_f = make_trainer_kwargs.get("f", make_trainer_kwargs.get("fw", 0))
    sched = _crash_schedule(args, num_slots, declared_f)
    xs_np, ys_np, test_batches, iters_per_epoch = load_data(args, num_slots)
    binary = args.dataset == "pima"
    # One scanned eval program over the device-stacked test set instead of
    # one dispatch per batch (parallel.EvalSet docstring).
    test_batches = parallel.EvalSet(test_batches, binary=binary)
    tools.info(
        f"[{tag}] One EPOCH consists of {iters_per_epoch} iterations"
    )
    module, loss_fn, optimizer = build_ingredients(args, iters_per_epoch)
    mesh = parse_mesh(args.mesh)
    trainer_params = inspect.signature(topology.make_trainer).parameters
    mask_key = (
        "byz_mask" if "byz_mask" in trainer_params
        else "byz_worker_mask"  # byzsgd naming
    )
    for flag in ("worker_momentum", "gar_params"):
        set_ = getattr(args, flag, None)
        if set_ is not None and set_ != {} and flag not in trainer_params:
            tools.warning(
                f"[{tag}] --{flag} is not supported by this topology; ignored"
            )

    # Telemetry plane (docs/TELEMETRY.md): hub + JSONL exporter, installed
    # as the process-global event sink so exchange/liveness events land in
    # the same stream as the per-step taps.
    from ..telemetry import trace as trace_lib

    # Closed-loop defense (DESIGN.md §16): resolve the CLI intent early —
    # escalation consumes the hub's suspicion, so it implies --telemetry
    # the same way --trace does.
    from ..aggregators import defense as defense_lib

    defense_plan = defense_lib.resolve(args)
    esc_policy = None
    if defense_plan is not None and defense_plan.escalate:
        if getattr(args, "gar", None) not in defense_lib.LEVEL_RULES:
            raise SystemExit(
                f"--defense escalate needs --gar to name an escalation-"
                f"ladder rule ({sorted(defense_lib.LEVEL_RULES)}), got "
                f"{args.gar!r}"
            )
        esc_policy = defense_plan.policy()
        levels = esc_policy.config.levels
        # Start the ladder AT the configured rule's SEMANTICS — the
        # default krum is the multi-krum level; --defense must never
        # downgrade the rule it defends (defense.start_level).
        esc_policy.level = defense_lib.start_level(
            levels, args.gar, getattr(args, "gar_params", None)
        )
        if not getattr(args, "telemetry", None):
            args.telemetry = "telemetry"  # suspicion needs the hub
    if trace_lib.requested(args) and not getattr(args, "telemetry", None):
        # Spans stream through the hub's JSONL sink; --trace without an
        # explicit --telemetry gets the default directory.
        args.telemetry = "telemetry"
    tele_hub = tele_exp = None
    if getattr(args, "telemetry", None):
        from ..telemetry import exporters as tele_fmt, hub as tele_hub_lib

        taps_supported = "telemetry" in trainer_params
        if not taps_supported:
            tools.warning(
                f"[{tag}] --telemetry: this topology exposes no in-graph "
                "taps; recording loss/timing/events only"
            )
        os.makedirs(args.telemetry, exist_ok=True)
        tele_hub = tele_hub_lib.MetricsHub(
            num_ranks=num_slots,
            suspicion_halflife=resolve_suspicion_halflife(args),
            meta={
                "tag": tag,
                "gar": args.gar,
                "attack": getattr(args, "attack", None),
                "f": declared_f,
                "num_slots": num_slots,
                "dataset": args.dataset,
                "model": args.model,
                "seed": args.seed,
            },
        )
        tele_hub_lib.install(tele_hub)
        tele_exp = tele_fmt.JsonlExporter(
            os.path.join(args.telemetry, "telemetry.jsonl")
        )
        tele_exp.write(tele_fmt.make_record("run", meta=tele_hub.meta))
        # Streaming sink (crash-safe): every record — per-step taps AND
        # the trace spans below — drains to the JSONL as it is recorded.
        tele_hub._sink = tele_exp
    if trace_lib.requested(args) or args.profile_dir:
        # Without a hub (--profile_dir alone) the spans are profiler
        # annotations only.
        trace_lib.enable(who=tag)

    # Targeted attacks (DESIGN.md §17): resolve the config once — the
    # trainer poisons the cohort's batches with it, and the eval loop
    # below measures the per-class/ASR success the suspicion plane is
    # blind to (telemetry schema v8). Resolved AFTER the hub install so
    # the one-time binary-surrogate fallback event reaches the stream.
    from ..attacks import targeted as targeted_lib

    targeted_cfg = None
    if targeted_lib.is_targeted(getattr(args, "attack", None)):
        targeted_cfg = targeted_lib.configure(
            args.attack, getattr(args, "attack_params", None),
            num_classes=models_lib.num_classes_dict.get(args.dataset, 2),
        )

    def build(step):
        kwargs = dict(make_trainer_kwargs)
        gar_name = args.gar
        gar_params = dict(getattr(args, "gar_params", None) or {})
        if esc_policy is not None:
            # The escalation ladder owns the rule (aggregators/defense.py):
            # level changes rebuild the step here, exactly like the
            # crash-schedule re-jit below.
            gar_name, lvl_params = esc_policy.current()
            gar_params.update(lvl_params)
            if "model_gar" in kwargs and kwargs.get("model_gar") is None:
                # Per-plane ladder independence (DESIGN.md §17): the
                # ladder owns the GRADIENT rule only — a model rule that
                # defaulted to --gar must stay pinned at the configured
                # rule, not silently ride the gradient plane's
                # escalations.
                kwargs["model_gar"] = args.gar
        if defense_plan is not None and "defense" in trainer_params:
            dkw = {}
            if defense_plan.weighted:
                dkw.update(
                    power=defense_plan.power,
                    floor=defense_plan.floor,
                    halflife=defense_plan.halflife,
                )
            else:
                dkw["weighted"] = False
            if defense_plan.data:
                if getattr(topology, "SUPPORTS_DATAPLANE", False):
                    # Data-plane detectors (DESIGN.md §18): SSMW only —
                    # its gather holds the full per-rank stack the
                    # fingerprints need; the host-plane twins live on
                    # the cluster PS quorums (apps/cluster.py).
                    dkw["data"] = {
                        "tau": defense_plan.dp_tau,
                        "power": defense_plan.dp_power,
                        "floor": defense_plan.dp_floor,
                        "halflife": defense_plan.dp_halflife,
                    }
                elif step == start_iter:
                    tools.warning(
                        f"[{tag}] --defense data: this topology has no "
                        "in-graph data-plane detectors; the GAR-side "
                        "defense (if any) still applies"
                    )
            if defense_plan.weighted or "data" in dkw:
                kwargs["defense"] = dkw
        elif defense_plan is not None and step == start_iter:
            tools.warning(
                f"[{tag}] --defense: this topology has no in-graph "
                "suspicion weighting; applying rule escalation only"
            )
        if getattr(args, "gar_dtype", None):
            kwargs["gar_dtype"] = (
                jnp.bfloat16 if args.gar_dtype == "bfloat16"
                else jnp.float32
            )
        if (getattr(args, "worker_momentum", None) is not None
                and "worker_momentum" in trainer_params):
            kwargs["worker_momentum"] = args.worker_momentum
        if gar_params and "gar_params" in trainer_params:
            kwargs["gar_params"] = gar_params
        if tele_hub is not None and "telemetry" in trainer_params:
            kwargs["telemetry"] = True
        if "num_iter" in trainer_params:
            # Run-length hint for the unroll-vs-vmap amortization choice
            # (core.slot_path_decision): REMAINING steps from this build
            # point — crash-schedule events and resumes re-jit mid-run, and
            # a compile premium only amortizes over the steps the rebuilt
            # program will actually serve.
            kwargs["num_iter"] = max(0, args.num_iter - step)
        if sched is not None:
            kwargs["attack"] = "crash"
            kwargs[mask_key] = sched.byz_mask(step, num_slots)
            if "model_attack" in trainer_params:
                # LEARN phase-5 model gossip: a crashed node cannot serve its
                # model either — zero it with the model-space crash attack.
                kwargs["model_attack"] = "crash"
        return topology.make_trainer(
            module, loss_fn, optimizer, gar_name, mesh=mesh, **kwargs
        )

    chunk = args.chunk_steps
    if chunk is None:
        chunk = int(os.environ.get("GARFIELD_CHUNK_STEPS") or 1)
    if chunk < 1:
        raise SystemExit(f"--chunk_steps must be >= 1, got {chunk}")

    # Resume target BEFORE the first build: the rebuilt program's num_iter
    # hint (the unroll-amortization decision, core.slot_path_decision) must
    # see the REMAINING steps, not the original total — a resumed run only
    # serves num_iter - start_iter steps from here.
    ckpt = None
    start_iter = 0
    if args.checkpoint_dir:
        ckpt = ckpt_lib.Checkpointer(args.checkpoint_dir)
        if args.resume and ckpt.latest_step() is not None:
            start_iter = int(ckpt.latest_step())

    init_fn, step_fn, eval_fn = build(start_iter)

    # Straight from host memory to each device's own shard — staging through
    # jnp.asarray would land the whole training set on device 0 first.
    xs = jax.device_put(xs_np, step_fn.batch_sharding)
    ys = jax.device_put(ys_np, step_fn.batch_sharding)
    key = jax.random.PRNGKey(args.seed)
    state = init_fn(key, xs_np[0, 0])
    counter_keys = parallel.core.counter_names(state.model_state)

    if ckpt is not None and start_iter:
        state = jax.device_put(
            ckpt.restore(jax.tree.map(np.asarray, state)),
            jax.tree.map(lambda l: l.sharding, state),
        )
        start_iter = int(np.asarray(state.step))
        tools.info(f"[{tag}] resumed from step {start_iter}")

    timer = profiling.StepTimer()
    d = int(sum(np.prod(l.shape) for l in jax.tree.leaves(state.params)))
    num_batches = xs.shape[1]
    metrics = {}

    cur_mask = sched.byz_mask(start_iter, num_slots) if sched else None
    eval_threads = []

    # Chunked dispatch programs, one per distinct (clipped) chunk length —
    # boundary clipping produces a handful of lengths at most. Invalidated
    # whenever the step itself is rebuilt (crash-schedule re-jit).
    crash_steps = sorted(set(sched.crashes.values())) if sched else []
    steps_trace = profiling.StepsTrace(args.profile_dir, start_iter + 5)
    profile_step = steps_trace.first if args.profile_dir else None
    chunk_fns = {}

    def chunked_for(k):
        fn = chunk_fns.get(k)
        if fn is None:
            fn = chunk_fns[k] = parallel.core.make_chunked_step(
                step_fn, k, num_batches
            )
        return fn

    t_train = time.time()
    i = start_iter
    while i < args.num_iter:
        if sched is not None:
            mask = sched.byz_mask(i, num_slots)
            if (mask != cur_mask).any():
                cur_mask = mask
                tools.info(
                    f"[{tag}] crash event at step {i}: dead slots "
                    f"{np.flatnonzero(mask).tolist()}; re-jitting step"
                )
                # Only the step depends on the mask — keep eval_fn's (and
                # init_fn's) compiled programs. Chunk programs scan the
                # step body, so they are rebuilt from the new step too.
                _, step_fn, _ = build(i)
                chunk_fns.clear()
        k = chunk_length(
            i, chunk=chunk, num_iter=args.num_iter, acc_freq=args.acc_freq,
            checkpoint_freq=(args.checkpoint_freq if ckpt else 0),
            crash_steps=crash_steps, profile_step=profile_step,
        )
        steps_trace.before(i, metrics)
        # Span semantics without --bench: dispatch is asynchronous, so
        # the span covers ENQUEUE time only (tag blocked=False); with
        # --bench the block_until_ready makes it the honest device time.
        with trace_lib.span("dispatch", step=i, chunk=k,
                            blocked=bool(args.bench)):
            if k == 1:
                b = i % num_batches
                if args.bench:
                    # Honest per-step numbers require a device sync;
                    # without --bench we leave dispatch asynchronous
                    # (faster) and report only whole-run throughput below.
                    with timer.step(block_on=None):
                        state, metrics = step_fn(state, xs[:, b], ys[:, b])
                        jax.block_until_ready(metrics["loss"])
                else:
                    state, metrics = step_fn(state, xs[:, b], ys[:, b])
            else:
                # One dispatch for k on-device steps; metrics leaves carry
                # a leading k axis. A per-step sync here would serialize
                # the chunk back into per-step dispatches and defeat it —
                # bench mode syncs ONCE per chunk and reports the honest
                # per-step time chunk_time / k (PERF.md methodology).
                cfn = chunked_for(k)
                if args.bench:
                    t0 = time.perf_counter()
                    state, metrics = cfn(state, xs, ys, np.int32(i))
                    jax.block_until_ready(metrics["loss"])
                    timer.record_chunk(time.perf_counter() - t0, k)
                else:
                    state, metrics = cfn(state, xs, ys, np.int32(i))
        end = i + k
        if args.bench:
            byz_bytes = profiling.collective_bytes(
                tag, num_workers=num_slots, d=d,
                num_ps=getattr(args, "num_ps", 1),
                axis_size=step_fn.mesh.shape[
                    step_fn.mesh.axis_names[-1]
                ],
            )
            if k == 1:
                print(
                    f"Training step {i} takes {timer.last():.4f} seconds",
                    flush=True,
                )
            else:
                print(
                    f"Training steps {i}-{end - 1} take "
                    f"{timer.last() * k:.4f} seconds "
                    f"({timer.last():.4f} s/step, chunked x{k})",
                    flush=True,
                )
            print(
                "Consumed bandwidth in this iteration: "
                f"{profiling.convert_to_gbit(byz_bytes):.4f} Gbits",
                flush=True,
            )
        if tele_hub is not None:
            # One host readback per CHUNK (the documented telemetry sync
            # cost), fanned back out into k per-step records — the hub
            # ingests the same stream as the per-step loop.
            host_metrics = jax.device_get(metrics)
            for j in range(k):
                m_j = (
                    host_metrics if k == 1
                    else jax.tree.map(lambda l: l[j], host_metrics)
                )
                # record_step drains to the JSONL via the hub's sink.
                tele_hub.record_step(
                    i + j,
                    loss=float(m_j["loss"]),
                    tap=m_j.get("tap"),
                    step_time_s=timer.last() if args.bench else None,
                    # The model's counters (core.step_counters), one
                    # number per module that writes one, beside the loss.
                    extra={
                        key: np.asarray(m_j[key], np.float64).tolist()
                        for key in counter_keys if key in m_j
                    },
                )
                if "attack_mag" in m_j:
                    # Adaptive-controller observability (schema v7): the
                    # magnitude the attacker played and the verdict it
                    # read back, one event per step.
                    tele_hub.record_event(
                        "attack_adapt",
                        step=int(i + j),
                        magnitude=float(m_j["attack_mag"]),
                        detected=bool(m_j["attack_detected"] > 0.5),
                    )
                for mag_key, det_key, plane in (
                    ("ps_attack_mag", "ps_attack_detected", "model"),
                    ("model_attack_mag", "model_attack_detected",
                     "gossip"),
                ):
                    if mag_key in m_j:
                        # Model-plane adaptive controller (schema v8):
                        # a Byzantine PS vs the replica gather, or a
                        # LEARN node vs the model gossip.
                        tele_hub.record_event(
                            "ps_attack_adapt",
                            step=int(i + j),
                            magnitude=float(m_j[mag_key]),
                            detected=bool(m_j[det_key] > 0.5),
                            plane=plane,
                        )
                if "defense_w" in m_j:
                    # Suspicion weights the step composed (schema v7) —
                    # the hub digests them into summary.defense.
                    tele_hub.record_event(
                        "defense_weights",
                        step=int(i + j),
                        weights=np.round(
                            np.asarray(m_j["defense_w"], np.float64), 6
                        ).tolist(),
                    )
                if "dataplane_score" in m_j:
                    # Data-plane defense observability (schema v9): the
                    # per-rank spectral outlier scores, detector flags,
                    # and composed weights of the in-graph detectors —
                    # the hub digests them into summary.data_defense and
                    # the garfield_dataplane_outlier_score gauge.
                    tele_hub.record_event(
                        "data_defense",
                        step=int(i + j),
                        scores=np.round(
                            np.asarray(m_j["dataplane_score"],
                                       np.float64), 6
                        ).tolist(),
                        flags=[int(x) for x in np.asarray(
                            m_j["dataplane_flags"]
                        )],
                        weights=np.round(
                            np.asarray(m_j["dataplane_w"], np.float64), 6
                        ).tolist(),
                    )
                if "ps_defense_w" in m_j:
                    # Replica-plane suspicion weights (schema v8): the
                    # MSMW twin's second, independent defense history.
                    tele_hub.record_event(
                        "defense_weights",
                        step=int(i + j),
                        plane="model",
                        weights=np.round(
                            np.asarray(m_j["ps_defense_w"], np.float64), 6
                        ).tolist(),
                    )
        if esc_policy is not None and tele_hub is not None:
            # Closed-loop escalation (DESIGN.md §16): fold the windowed
            # suspicion's concentration into the hysteresis policy once
            # per dispatch; a level change rebuilds the step exactly
            # like a crash-schedule event (same TrainState structure —
            # the ladder is stateful-homogeneous by construction).
            susp = tele_hub.suspicion_decayed()
            if susp is not None:
                conc = defense_lib.suspicion_concentration(
                    susp, max(1, declared_f)
                )
                act = esc_policy.observe(float(conc))
                if act:
                    # Feasibility at this deployment's quorum geometry
                    # (the cluster PS convention): a ladder level whose
                    # rule contract fails at n_eff (bulyan needs
                    # n >= 4f + 3) is refused loudly and reverted —
                    # rebuilding with it would assert mid-run.
                    from ..aggregators import gars as gars_reg

                    lvl_gar, _ = esc_policy.current()
                    n_eff = getattr(args, "subset", None) or num_slots
                    msg = gars_reg[lvl_gar].check(
                        np.zeros((n_eff, 4), np.float32),
                        f=max(1, declared_f),
                    )
                    if msg is not None:
                        tools.warning(
                            f"[{tag}] defense cannot move to "
                            f"{esc_policy.level_name!r} at n={n_eff}: "
                            f"{msg}"
                        )
                        esc_policy.level -= act
                        act = 0
                if act:
                    tools.info(
                        f"[{tag}] defense "
                        f"{'escalates' if act > 0 else 'de-escalates'} to "
                        f"{esc_policy.level_name!r} at step {end - 1} "
                        f"(suspicion concentration {float(conc):.3f})"
                    )
                    tele_hub.record_event(
                        "defense_escalate",
                        step=int(end - 1),
                        level=int(esc_policy.level),
                        rule=str(esc_policy.level_name),
                        direction=(
                            "escalate" if act > 0 else "deescalate"
                        ),
                        concentration=round(float(conc), 6),
                    )
                    _, step_fn, _ = build(end)
                    chunk_fns.clear()
        if args.log:
            losses = np.asarray(metrics["loss"]).reshape(-1)
            for j in range(k):
                print(
                    f"Loss {i + j}: {float(losses[j if k > 1 else -1]):.6f}",
                    flush=True,
                )
        last = end - 1
        if args.acc_freq and last % args.acc_freq == 0:
            # Boundary clipping guarantees an eval point is always the
            # chunk's LAST step, so the state here is the post-step-`last`
            # state the per-step loop evaluated. Stamp Time at the eval
            # REQUEST, not at the (possibly much later) async readback,
            # so accuracy-vs-time stays meaningful.
            t_req = time.time() - t_start

            def _report(acc, i=last, t_req=t_req):
                print(
                    f"Epoch: {i / max(iters_per_epoch, 1):.2f} "
                    f"Accuracy: {acc:.4f} Time: {t_req:.1f}",
                    flush=True,
                )

            if args.sync_eval or args.bench:
                # --bench promises honest per-step numbers; overlapped eval
                # device work would execute inside the next timed window,
                # so bench mode keeps eval inline.
                with trace_lib.span("eval", step=last):
                    _report(parallel.compute_accuracy(
                        state, eval_fn, test_batches, binary=binary
                    ))
            else:
                # Overlapped eval (reference's accuracy side thread): device
                # work is enqueued here, the blocking readback happens off
                # the training thread, so the step stream does not stall.
                eval_threads.append(parallel.compute_accuracy_async(
                    state, eval_fn, test_batches, binary=binary,
                    on_done=_report,
                    after=eval_threads[-1] if eval_threads else None,
                ))
            if targeted_cfg is not None and tele_hub is not None:
                # Per-class eval digest (schema v8): the targeted
                # attack's success metric, measured at every eval point
                # — global accuracy alone cannot see a labelflip/
                # backdoor (DESIGN.md §17). Inline (blocking): this is a
                # measurement run by construction.
                rep = parallel.targeted_eval(
                    state, eval_fn, test_batches,
                    source=targeted_cfg.source,
                    target=targeted_cfg.target,
                    trigger_cfg=(
                        targeted_cfg
                        if targeted_cfg.attack == "backdoor" else None
                    ),
                )
                tele_hub.record_event(
                    "targeted_eval", step=int(last),
                    source=rep["source"], target=rep["target"],
                    accuracy=round(rep["accuracy"], 6),
                    confusion=(
                        None if rep["confusion"] is None
                        else round(rep["confusion"], 6)
                    ),
                    asr=(
                        None if rep["asr"] is None
                        else round(rep["asr"], 6)
                    ),
                    asr_baseline=(
                        None if rep["asr_baseline"] is None
                        else round(rep["asr_baseline"], 6)
                    ),
                    per_class={
                        str(k): round(v, 6)
                        for k, v in rep["per_class"].items()
                    },
                )
        if ckpt and args.checkpoint_freq and end % args.checkpoint_freq == 0:
            with trace_lib.span("checkpoint", step=end - 1):
                ckpt.save(end, jax.tree.map(np.asarray, state))
        steps_trace.after(end, metrics)
        i = end

    steps_trace.after(None, metrics)  # a run shorter than the trace
    jax.block_until_ready(state.step)  # drain async dispatch for honest wall
    train_wall = time.time() - t_train
    for t in eval_threads:  # flush overlapped accuracy reports
        t.join()
        if t.exc is not None:
            raise t.exc
    steps_done = args.num_iter - start_iter
    acc = parallel.compute_accuracy(state, eval_fn, test_batches, binary=binary)
    targeted_rep = None
    if targeted_cfg is not None:
        # Run-closing targeted digest: confusion/ASR into the printed
        # summary (and one last v8 event), so a targeted run's success
        # metric is never only in the JSONL stream.
        targeted_rep = parallel.targeted_eval(
            state, eval_fn, test_batches,
            source=targeted_cfg.source, target=targeted_cfg.target,
            trigger_cfg=(
                targeted_cfg if targeted_cfg.attack == "backdoor" else None
            ),
        )
        if tele_hub is not None:
            tele_hub.record_event(
                "targeted_eval", step=int(args.num_iter),
                source=targeted_rep["source"],
                target=targeted_rep["target"],
                accuracy=round(targeted_rep["accuracy"], 6),
                confusion=(
                    None if targeted_rep["confusion"] is None
                    else round(targeted_rep["confusion"], 6)
                ),
                asr=(
                    None if targeted_rep["asr"] is None
                    else round(targeted_rep["asr"], 6)
                ),
                asr_baseline=(
                    None if targeted_rep["asr_baseline"] is None
                    else round(targeted_rep["asr_baseline"], 6)
                ),
                per_class={
                    str(k): round(v, 6)
                    for k, v in targeted_rep["per_class"].items()
                },
            )
    summary = {
        "final_accuracy": acc,
        # The last dispatch may have been a chunk: its loss carries a
        # leading chunk axis; the final loss is the last scan step's.
        "final_loss": (
            float(np.asarray(metrics["loss"]).reshape(-1)[-1])
            if metrics else None
        ),
        "wall_s": time.time() - t_start,
        "train_wall_s": train_wall,
        "steps_per_sec": steps_done / train_wall if train_wall > 0 else None,
        **({"targeted": {
            "confusion": targeted_rep["confusion"],
            "asr": targeted_rep["asr"],
            "asr_baseline": targeted_rep["asr_baseline"],
            "per_class": targeted_rep["per_class"],
        }} if targeted_rep is not None else {}),
        **{f"step_{k}": v for k, v in timer.summary().items()},
    }
    print(json.dumps({"tag": tag, **summary}), flush=True)
    trace_lib.disable()
    if tele_hub is not None:
        from ..telemetry import exporters as tele_fmt, hub as tele_hub_lib

        tele_hub._sink = None  # summary is written once, explicitly
        tele_exp.write(tele_hub.summary())
        with open(os.path.join(args.telemetry, "metrics.prom"), "w") as fp:
            fp.write(tele_fmt.prometheus_text(tele_hub))
        tele_exp.close()
        tele_hub_lib.uninstall()
    if ckpt:
        if args.checkpoint_freq:
            ckpt.save(args.num_iter, jax.tree.map(np.asarray, state))
        ckpt.close()
    return state, summary
