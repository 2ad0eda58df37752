"""Cross-process AggregaThor/ByzSGD: one OS process per node, PeerExchange.

This is the host-driver deployment shape of the reference — one process per
node pulling models/gradients through the message exchange
(tensorflow_impl/applications/AggregaThor/trainer.py:55-95 and
ByzSGD/trainer.py:76-95, fanned out by the per-app run_exp.sh) — with the
gRPC servicer replaced by ``utils.exchange.PeerExchange`` (TCP frames + the
native MRMW register). Unlike the on-mesh SPMD topologies, synchronization
here is REAL wait-n-f: the PS proceeds with the q = n_w - f *fastest*
worker gradients per step (server.py:134-155), so crashed or straggling
workers are simply absent from the quorum — no seeded-subset emulation.

Roles (ClusterConfig task):
  - ``ps`` (ranks 0..n_ps-1): publishes its flat model each step, collects
    the q fastest worker gradients, aggregates with the GAR, applies the
    optimizer update. With ONE PS this is AggregaThor SSMW (trusted
    server). With num_ps > 1 it is the ByzSGD MSMW deployment
    (tensorflow_impl/applications/ByzSGD/trainer.py:76-95): each step every
    node first collects ALL PS models and GAR-aggregates them with
    tolerance fps (the "gather step", pytorch ByzSGD/trainer.py:240-244),
    so a Byzantine PS process — launched with ``--ps_attack``, publishing
    poisoned models host-side exactly like ``byzServer.py:86-108`` — is
    outvoted in model space by the honest replicas. Straggler tolerance on
    the model plane is NOT subsetted: the fps budget covers VALUE faults
    (a live lying PS); a crashed PS stalls the deployment, as in the
    reference's bounded-retry-then-exit pull loops (server.py:138-141).
  - ``worker`` (ranks 1..n_w): collects the step's model from the PS slot,
    computes its data shard's gradient, publishes the flat gradient back to
    the PS. A worker started with ``--attack`` is a REAL Byzantine process
    (byzWorker.py:50-143): it poisons its own published gradient host-side.
    The self-contained attacks (reverse, random, crash) transform its own
    gradient; the colluding-statistics attacks (lie, empire) use the
    reference's local-cohort trick (byzWorker.py:114-125): the attacker
    computes the cohort's honest gradients ITSELF from its own extra
    batches, derives mu/sigma, and publishes mu + z*sigma / -eps*mu — no
    visibility into honest peers' gradients is needed, exactly as in the
    real deployment.

Both planes share one exchange: the PS slot only ever carries models, the
worker slots only gradients, and ``collect(..., peers=...)`` waits on
exactly the relevant slots.

Model state (BatchNorm statistics) travels in every deployment shape
(SSMW r4; MSMW/LEARN r5, VERDICT r4 #4), robust-aggregated with the
coordinate-wise f-trimmed ``_robust_stats`` at its plane's budget — so
all three shapes converge on BN architectures instead of the reference's
silent local-BN drift (its RPC path ships gradients only). Frame
layouts: SSMW and MSMW gradient frames carry ``[grad || batch_stats]``
and model frames ``[params || stats]``; LEARN syncs stats once per round
on its GOSSIP frames only (``[params || stats]`` at phase 2i+3 — its
gradient plane ships bare gradients, so BN adoption lags the gradient
phase by half a round, matching the on-mesh twin's once-per-step
``mean_model_state`` cadence).
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree

from ..aggregators import (
    dataplane as dataplane_lib,
    defense as defense_lib,
    gars,
)
from ..parallel import core
from ..telemetry import hub as tele_hooks, trace as tele_trace
from ..utils import multihost, rounds, tools, wire
from ..utils.exchange import PeerExchange
from . import common

__all__ = ["run"]

# Logical exchange planes (DESIGN.md §15). Every typed data frame stamps
# its plane into the wire codec header's spare bits (wire.encode plane=)
# so bytes attribute per plane in telemetry; the LEARN async deployment
# ADDITIONALLY uses them as real per-peer register slots
# (PeerExchange(planes=3)) — the slot separation that removes the old
# one-register-per-peer gossip multiplexing. The PS topologies keep a
# single-plane transport (their planes are already separated by peer
# role) and use the tags for accounting only.
PLANE_CTRL = 0
PLANE_GRAD = 1
PLANE_MODEL = 2


def _host_attack(name, params, fw):
    """Byzantine gradient attacks for a REAL attacker process.

    Returns ``(kind, fn, cohort)`` (``cohort`` only set for "cohort"):
      - ``("post", fn)``: self-contained transforms of the attacker's own
        gradient (byzWorker.py: 'random' :78-85, 'reverse' :87-94; 'crash'
        = the process simply dies, covered by killing it);
      - ``("cohort", fn)``: the colluding attacks. The reference's attacker
        simulates its fw colluders by computing fw honest gradients locally
        from its own batches (byzWorker.py:114-117) and publishing one
        statistic of that stack: lie = mu + z*sigma (:108-125, z=1.035),
        empire = -eps*mu (:127-143, eps=10). ``fn`` maps the (cohort, d)
        stack of locally-computed honest gradients to the published vector;
        the worker loop supplies the stack. Cohort size defaults to fw
        (byzWorker semantics — at fw=1 the Bessel sigma is NaN exactly like
        torch.std of one sample, and the published NaN vector is the
        reference's emergent behavior); ``attack_params["cohort"]``
        overrides it (the attacker controls its own simulation budget).
    """
    from .. import attacks as attacks_lib

    if name is None:
        return None, None, None
    if name in ("adaptive-lie", "adaptive-empire"):
        # Suspicion-aware attacker (attacks/adaptive.py, DESIGN.md §16):
        # the worker loop builds the HostController itself (it needs the
        # cluster's worker count and its own rank for the rotation
        # schedule); the cohort size rides the same local-simulation
        # budget as the oblivious colluding attacks. adaptive-lie floors
        # it at TWO: the Bessel sigma of one sample is NaN (the
        # reference's emergent fw=1 behavior), and a NaN fake is
        # self-defeating for a controller whose whole point is staying
        # admitted — it reads back "excluded" forever.
        floor = 2 if name == "adaptive-lie" else 1
        cohort = int(params.get("cohort", max(fw, floor)))
        if cohort < floor:
            raise SystemExit(
                f"--attack {name!r} needs a cohort of at least {floor} "
                f"honest gradients to simulate (got {cohort})"
            )
        return "adaptive", None, cohort
    if name in ("labelflip", "backdoor"):
        # Targeted data poisoner (attacks/targeted.py, DESIGN.md §17):
        # the worker rewrites its OWN batches (label flips / trigger
        # stamps) and publishes the honest gradient of the poisoned task
        # — nothing divergence-shaped for the suspicion plane to see.
        # The role builds the TargetedConfig itself AFTER its telemetry
        # hub is installed, so the one-time binary-surrogate fallback
        # event reaches the stream.
        return "targeted", None, None
    scale = float(params.get("scale", 100.0))
    rng = np.random.default_rng(int(params.get("seed", 666)))
    if name == "random":
        return "post", (
            lambda g: rng.standard_normal(g.shape).astype(g.dtype) * scale
        ), None
    if name == "reverse":
        return "post", (lambda g: g * (-scale)), None
    if name in ("lie", "empire"):
        cohort = int(params.get("cohort", fw))
        if cohort < 1:
            raise SystemExit(
                f"--attack {name!r} needs a cohort of at least 1 honest "
                f"gradient to simulate (got {cohort}; set --fw or "
                'attack_params {"cohort": k})'
            )
        z = float(params.get("z", attacks_lib.LIE_Z))
        eps = float(params.get("eps", attacks_lib.EMPIRE_EPS))

        def fn(stack):
            mu = stack.mean(axis=0)
            if name == "empire":
                return (-eps * mu).astype(np.float32)
            sigma = stack.std(axis=0, ddof=1)  # NaN at cohort=1, like torch
            return (mu + z * sigma).astype(np.float32)

        return "cohort", fn, cohort
    raise SystemExit(
        f"unknown cluster attack {name!r}; workers support random/reverse/"
        "lie/empire, the adaptive controllers (adaptive-lie/"
        "adaptive-empire), the targeted poisoners (labelflip/backdoor) — "
        "or kill the process for a crash."
    )


def _targeted_config(args, who):
    """``TargetedConfig`` for a cluster role running a targeted attack —
    built AFTER the role's telemetry hub is installed (the one-time
    binary-surrogate fallback event must reach the stream)."""
    from .. import models as models_lib
    from ..attacks import targeted as targeted_lib

    try:
        return targeted_lib.configure(
            args.attack, args.attack_params,
            num_classes=models_lib.num_classes_dict.get(args.dataset, 2),
        )
    except ValueError as e:
        raise SystemExit(f"[{who}] --attack {args.attack}: {e}") from e


def _host_model_attack(name, params):
    """Model attacks for a REAL Byzantine PS process (byzServer.py:86-108):
    the poisoned vector is what this PS publishes on the model plane.
    Self-contained by construction — a Byzantine server needs nothing from
    its peers to lie about its own model."""
    if name is None:
        return None
    scale = float(params.get("scale", 100.0))
    p = float(params.get("p", 0.3))
    rng = np.random.default_rng(int(params.get("seed", 777)))
    if name == "random":
        return lambda m: rng.standard_normal(m.shape).astype(m.dtype) * scale
    if name == "reverse":
        return lambda m: m * (-scale)
    if name == "drop":
        return lambda m: np.where(
            rng.random(m.shape) > (1.0 - p), 0.0, m
        ).astype(m.dtype)
    raise SystemExit(
        f"unknown PS model attack {name!r}; supported: random, reverse, "
        "drop (byzServer.py:74-78), the collusion statistics lie/empire "
        "and their adaptive controllers adaptive-lie/adaptive-empire "
        "(DESIGN.md §17)."
    )


class _ModelPoisoner:
    """Host-side Byzantine MODEL publisher: one object per attacking role
    (an MSMW replica under ``--ps_attack``, a LEARN node under
    ``--model_attack``) covering three attack shapes (DESIGN.md §17):

      - **simple**: byzServer's self-contained random/reverse/drop —
        the pre-§17 behavior, byte-identical (the whole published frame,
        stats segment included, goes through the same transform).
      - **collusion** (``lie``/``empire`` at a fixed z/eps): the
        publisher hides inside the spread of the model-plane rows it
        GATHERED last round — unlike the gradient plane it simulates
        nothing, the protocol hands it every row it wants statistics
        over (``attacks.adaptive.model_fake``). Until the first gather
        it publishes honestly (no cohort to collude against yet).
      - **adaptive** (``adaptive-lie``/``adaptive-empire``): the
        collusion magnitude is a ``HostController`` bisection bracket.
        Feedback is the MODEL-plane delta probe: if the poisoned model
        entered the peers' aggregation at round r, the mean of the
        honest peers' models moves toward the fake excess between the
        round-r and round-(r+1) gathers (``model_delta_probe``; the
        honest-drift estimate is the PREVIOUS round's observed peer
        delta). Rotation and gap-triggered bursts ride the same
        controller as the gradient-plane worker.

    The caller feeds every model-plane gather through ``note_gather``
    (rows + their ranks) and routes every model publication through
    ``publish_frame``.
    """

    def __init__(self, name, params, *, n_ranks, f, my_rank, who,
                 plane="model"):
        from ..attacks import adaptive as adaptive_lib, LIE_Z, EMPIRE_EPS

        params = dict(params or {})
        self.kind = None
        self.who = who
        self.plane = plane
        self.my_rank = int(my_rank)
        self.base = None
        self.controller = None
        self._fn = None
        self._mag = None
        self._last_stack = None
        self._prev_peer_mean = None
        self._prev_delta = None
        self._pending = None  # (round, excess u, magnitude)
        if name is None:
            return
        if adaptive_lib.is_adaptive(name):
            if f < 1:
                raise SystemExit(
                    f"--ps_attack/--model_attack {name!r} needs a declared "
                    f"Byzantine budget >= 1 on its plane (got {f})"
                )
            cfg = adaptive_lib.configure(
                name, params, num_workers=n_ranks, f=f
            )
            self.controller = adaptive_lib.HostController(
                cfg, my_rank,
                burst_factor=float(params.get("burst_factor", 3.0)),
                burst_rounds=int(params.get("burst_rounds", 3)),
            )
            self.base = cfg.base
            self.kind = "adaptive"
        elif name in ("lie", "empire"):
            self.base = name
            self._mag = float(params.get(
                "z" if name == "lie" else "eps",
                LIE_Z if name == "lie" else EMPIRE_EPS,
            ))
            self.kind = "collusion"
        else:
            self._fn = _host_model_attack(name, params)
            self.kind = "simple"

    def note_gather(self, stack, ranks, rnd):
        """One gathered model-plane stack (params rows, host numpy) with
        its per-row rank ids: refresh the collusion statistics, feed the
        burst trigger, and close the pending adaptive probe."""
        if self.kind in (None, "simple"):
            return
        from ..attacks import adaptive as adaptive_lib

        stack = np.asarray(stack, np.float32)
        ranks = list(ranks)
        self._last_stack = stack
        if self.kind != "adaptive":
            return
        self.controller.observe_round(time.time())
        peer_rows = [
            stack[j] for j, r in enumerate(ranks) if r != self.my_rank
        ]
        if not peer_rows:
            return
        peer_mean = np.mean(np.stack(peer_rows), axis=0)
        if self._pending is not None and self._prev_peer_mean is not None:
            pr, u, mag = self._pending
            detected, score = adaptive_lib.model_delta_probe(
                self._prev_peer_mean, peer_mean, u,
                honest_delta=self._prev_delta,
            )
            self.controller.feedback(detected)
            tele_hooks.emit_event(
                "ps_attack_adapt", step=int(pr), plane=self.plane,
                magnitude=round(float(mag), 6), detected=bool(detected),
                lo=round(self.controller.lo, 6),
                hi=round(self.controller.hi, 6),
                score=round(float(score), 6),
            )
            self._pending = None
        if self._prev_peer_mean is not None:
            # The NEXT probe's honest-drift estimate: what the peers'
            # mean moved this round (smooth across rounds; the previous
            # poison's contribution is second-order at probe scale).
            self._prev_delta = peer_mean - self._prev_peer_mean
        self._prev_peer_mean = peer_mean

    def publish_frame(self, params_vec, bn_vec, rnd):
        """The full ``[params || stats]`` frame this role publishes at
        round ``rnd``, poisoned per the attack shape. The collusion
        shapes poison the PARAMS segment (their statistics are over the
        gathered params rows) and keep the honest stats segment; the
        simple shapes transform the whole frame (pre-§17 byte parity)."""
        params_vec = np.asarray(params_vec, np.float32)
        has_bn = bn_vec is not None and np.asarray(bn_vec).size
        full = (
            np.concatenate([params_vec, np.asarray(bn_vec, np.float32)])
            if has_bn else params_vec
        )
        if self.kind is None:
            return full
        if self.kind == "simple":
            return self._fn(full).astype(np.float32)
        if self._last_stack is None:
            return full  # no gathered cohort to collude against yet
        from ..attacks import adaptive as adaptive_lib

        if self.kind == "collusion":
            fake = adaptive_lib.model_fake(
                self.base, self._last_stack, self._mag
            )
        else:
            if not self.controller.is_active(rnd):
                return full  # rotation: this round the role plays honest
            mag = self.controller.magnitude()
            fake = adaptive_lib.model_fake(self.base, self._last_stack, mag)
            if not self.controller.bursting():
                self._pending = (
                    int(rnd), fake - self._last_stack.mean(axis=0), mag
                )
        return (
            np.concatenate([fake, np.asarray(bn_vec, np.float32)])
            if has_bn else fake
        )

    def stats(self):
        if self.controller is None:
            return None
        return self.controller.stats()


def _startup_ms(args):
    """Startup ceiling: how long a peer may lawfully take to appear (python
    + jax import + data/model init + first compiles — minutes on a shared
    host). Used as the first-connect grace AND the startup-barrier budget;
    it costs nothing when everyone arrives promptly."""
    import os

    return max(
        args.cluster_timeout_ms,
        int(os.environ.get("GARFIELD_STARTUP_TIMEOUT_MS", 1_800_000)),
    )


def _telemetry_open(args, who, num_ranks=None, meta=None):
    """Per-role telemetry plane for cluster deployments: one MetricsHub
    streaming into ``<dir>/<who>.telemetry.jsonl`` (each process writes
    its own file — roles are separate OS processes), installed as the
    process-global sink so exchange wait latencies and the liveness
    events below land in the stream. Returns (hub, exporter) or
    (None, None) when --telemetry is off. With --trace/GARFIELD_TRACE
    the round-tracing spans (telemetry/trace.py, schema v5) are enabled
    into the same per-role stream — the raw material of
    ``python -m garfield_tpu.telemetry.report``."""
    if tele_trace.requested(args) and not getattr(args, "telemetry", None):
        args.telemetry = "telemetry"  # spans need the JSONL sink
    if not getattr(args, "telemetry", None):
        return None, None
    import os

    from ..telemetry import exporters as tele_fmt

    os.makedirs(args.telemetry, exist_ok=True)
    exp = tele_fmt.JsonlExporter(
        os.path.join(args.telemetry, f"{who}.telemetry.jsonl")
    )
    hub = tele_hooks.MetricsHub(
        num_ranks=num_ranks,
        suspicion_halflife=common.resolve_suspicion_halflife(args),
        meta={"tag": who, "gar": args.gar, "fw": args.fw, **(meta or {})},
        sink=exp,
    )
    exp.write(tele_fmt.make_record("run", meta=hub.meta))
    tele_hooks.install(hub)
    if tele_trace.requested(args):
        tele_trace.enable(who=who)
    return hub, exp


def _telemetry_close(hub, exp):
    if hub is None:
        return
    tele_trace.disable()
    try:
        exp.write(hub.summary())
    finally:
        exp.close()
        tele_hooks.uninstall()


# Public aliases (DESIGN.md §19): the federated shard/fleet roles
# (apps/benchmarks/fed_bench.py) are cluster-style OS processes and
# reuse the per-role telemetry plane and wire accounting verbatim —
# aliased rather than duplicated so the stream/summary format cannot
# drift between the cluster and federated deployments.
telemetry_open = _telemetry_open
telemetry_close = _telemetry_close


def _robust_stats(rows, f):
    """Coordinate-wise trimmed mean of worker-supplied BatchNorm-statistic
    rows under the deployment's f budget (ADVICE r4 medium).

    A Byzantine PROCESS controls its wire bytes, so the BN segment of its
    gradient frame is attacker-chosen regardless of the gradient GAR; a
    plain mean would hand it an unbounded write path into every honest
    worker's normalizer — a poisoning channel the reference never opens
    (its RPC plane ships gradients only; BN stays local). Trimming the f
    smallest and f largest values per coordinate bounds the influence of up
    to f Byzantine rows PROVIDED q >= 2f + 1 (the stats analog of tmean);
    at f=0 this IS the plain mean the on-mesh path computes
    (core.mean_model_state — where stats are honestly computed by
    construction, so no trim is needed). When q < 2f + 1 the trim clamps
    to the coordinate-wise median — the best available estimator, but a
    quorum whose Byzantine members can be the majority (n_w <= 3f) is
    indefensible for stats and _run_ps warns about it once at startup.
    """
    q = rows.shape[0]
    t = min(int(f), (q - 1) // 2)
    if t == 0:
        return np.mean(rows, axis=0).astype(np.float32)
    s = np.sort(rows, axis=0)
    return np.mean(s[t:q - t], axis=0).astype(np.float32)


class WireStats:
    """Per-role wire-plane accounting for the telemetry plane
    (docs/TELEMETRY.md): bytes and codec seconds, both directions,
    broken down PER PLANE (schema v6 — the ``planes`` sub-object of the
    per-step ``wire`` event feeds the plane-labelled Prometheus byte
    counters) and PER SCHEME (schema v11 — the ``schemes`` sub-object
    plus the ``compression_ratio`` / ``ef_residual_norm`` fields behind
    the round-18 compressed wire). Receive-side appends happen on
    exchange waiter threads — ``list.append`` is GIL-atomic; the sums
    happen at the per-step ``flush`` on the role's main thread."""

    def __init__(self, who):
        self.who = who
        self._out = []
        self._in = []
        # Set by roles that run error feedback (the gradient-plane
        # senders) so flush can surface the residual norm per step.
        self.ef = None

    def sent(self, nbytes, encode_s, fanout, plane=0, scheme="f32",
             elems=0):
        # f32-equivalent bytes ride along so flush can report the
        # compression ratio without re-deriving frame geometry.
        f32_eq = (wire.HEADER_NBYTES + 4 * int(elems)) * int(fanout)
        self._out.append(
            (int(nbytes) * int(fanout), float(encode_s), int(plane),
             str(scheme), f32_eq)
        )

    def received(self, nbytes, decode_s, plane=0, scheme="f32"):
        self._in.append(
            (int(nbytes), float(decode_s), int(plane), str(scheme))
        )

    def flush(self, step):
        out, self._out = self._out, []
        rin, self._in = self._in, []
        if tele_hooks.current() is None:
            return
        planes = {}
        schemes = {}
        for b, _, p, s, _ in out:
            planes.setdefault(p, [0, 0])[0] += b
            schemes.setdefault(s, [0, 0])[0] += b
        for b, _, p, s in rin:
            planes.setdefault(p, [0, 0])[1] += b
            schemes.setdefault(s, [0, 0])[1] += b
        bytes_out = sum(b for b, _, _, _, _ in out)
        f32_eq_out = sum(e for _, _, _, _, e in out)
        extra = {}
        if bytes_out and f32_eq_out != bytes_out:
            # The per-step send-side ratio vs an f32 wire — the ≥8x
            # claim's live counterpart (schema v11).
            extra["compression_ratio"] = round(f32_eq_out / bytes_out, 3)
        if self.ef is not None:
            extra["ef_residual_norm"] = round(self.ef.total_norm(), 6)
        tele_hooks.emit_event(
            "wire", who=self.who, step=int(step),
            bytes_out=bytes_out,
            bytes_in=sum(b for b, _, _, _ in rin),
            frames_in=len(rin),
            encode_s=round(sum(t for _, t, _, _, _ in out), 6),
            decode_s=round(sum(t for _, t, _, _ in rin), 6),
            planes={
                str(p): {"bytes_out": bo, "bytes_in": bi}
                for p, (bo, bi) in sorted(planes.items())
            },
            schemes={
                s: {"bytes_out": bo, "bytes_in": bi}
                for s, (bo, bi) in sorted(schemes.items())
            },
            **extra,
        )


# The schemes whose compression error is biased (and therefore needs the
# error-feedback accumulator): everything lossy except bf16, which stays
# EF-free like the PR 4 wire so its frames remain byte-identical.
_EF_SCHEMES = ("int8", "int4", "topk")


def _wire_scheme(plane):
    """Resolve the send scheme for ``plane`` (round 18, DESIGN.md §20).

    The ``GARFIELD_WIRE_TOPK`` sparsification overlay applies to the
    GRADIENT plane only: model/gossip broadcasts are absolute state —
    a sparse model frame read by a catching-up peer (read_latest,
    last-writer-wins) would zero every coordinate outside this round's
    top-k — so they keep the dense ``GARFIELD_WIRE_DTYPE`` width. The
    control plane (plane 0 sentinels) is dense for the same reason."""
    if plane == PLANE_GRAD and wire.wire_topk() > 0:
        return "topk"
    return wire.wire_dtype()


def _maybe_error_feedback(who, wire_stats):
    """This role's gradient-plane error-feedback accumulator, when the
    resolved gradient scheme is biased-lossy (``_EF_SCHEMES``); None
    otherwise. HOST RESTART SEMANTICS (the documented contract —
    tests/test_compress.py pins the in-graph half): the accumulator is
    rebuilt AT ZERO here, because the residual is a bounded one-step
    correction (||e|| <= one step's compression error) — a restart
    costs one step of compensation, not convergence — and the rebuild
    is ANNOUNCED so a restarted run's log shows the reset instead of a
    silent zeroing. Bitwise-reproducible resume is the in-graph twin's
    job (TrainState.wire_state rides the checkpoint tree)."""
    scheme = _wire_scheme(PLANE_GRAD)
    if scheme not in _EF_SCHEMES:
        return None
    ef = wire.ErrorFeedback()
    wire_stats.ef = ef
    tools.info(
        f"[{who}] wire scheme {scheme!r}: error-feedback accumulator "
        "rebuilt at zero (a host restart drops at most one step of "
        "compensation; bitwise resume lives on the in-graph twin)"
    )
    return ef


def _encode_frame(parts, stats=None, fanout=1, plane=0, ef=None):
    """The wire codec's single PRODUCER for the cluster driver: encode
    the concatenation of f32 segments (``[grad || stats]`` /
    ``[params || stats]``) as one typed frame at the plane's resolved
    scheme (``_wire_scheme``), accounting bytes x fan-out and encode
    time for the telemetry plane. ``plane`` stamps the codec header's
    plane tag (PLANE_GRAD/PLANE_MODEL) — the self-describing half of
    the per-plane accounting.

    With multiple parts the FIRST part is the additive head (gradient /
    params) and the rest the BatchNorm-stats tail: top-k keeps the tail
    dense (``keep_from`` — robust-stats input, not a sparse signal) and
    error feedback compensates the head only. ``ef`` (a
    ``wire.ErrorFeedback``, keyed per plane — frames broadcast
    byte-identical to all peers, so per sender x plane is full
    resolution) makes this sender transmit C(g + e) and carry
    e' = (g + e) - decode(C(g + e)); the residual uses the receiver's
    OWN decode of the frame actually shipped, so it is exactly the
    error every peer saw."""
    t0 = time.perf_counter()
    parts = [np.asarray(p, np.float32).reshape(-1) for p in parts]
    vec = parts[0] if len(parts) == 1 else np.concatenate(parts)
    scheme = _wire_scheme(plane)
    keep_from = parts[0].size if len(parts) > 1 else None
    if ef is not None and scheme in _EF_SCHEMES:
        upto = vec.size if keep_from is None else keep_from
        vec = ef.compensate(plane, vec, upto=upto)
        frame = wire.encode(vec, scheme, plane=plane, keep_from=keep_from)
        ef.update(plane, vec, wire.decode(frame), upto=upto)
    else:
        frame = wire.encode(vec, scheme, plane=plane, keep_from=keep_from)
    if stats is not None:
        stats.sent(len(frame), time.perf_counter() - t0, fanout, plane,
                   scheme=scheme, elems=vec.size)
    return frame


def _frame_transform(split, stats=None, pass_empty=False, plane=0):
    """The wire codec's single CONSUMER: the eager per-frame decode hook
    every cluster role hands to ``collect_begin``/``read_latest_begin``
    (the four roles used to hand-roll paired ``np.frombuffer`` splits
    after the quorum closed). Runs on the exchange waiter thread the
    moment a frame lands: wire-decode (crc + dtype restore), split into
    ``(primary, stats_segment)``, and stage the primary segment onto the
    device — overlapping decode + H2D with the other peers' receives and
    the local device step. A codec reject raises ``wire.WireError``
    (stored by the exchange as the peer's result — ban/exclusion
    evidence, with ``.nbytes`` carrying the observed frame length).
    ``pass_empty`` lets the SSMW stop sentinel (an empty frame) through
    undecoded."""
    d0, d1 = split

    def transform(idx, payload):
        if pass_empty and not payload:
            return payload
        t0 = time.perf_counter()
        try:
            # expect_elems pins the header's dense size BEFORE the
            # scatter allocation: a sparse frame's elems is otherwise a
            # bare claim (see wire.decode) — the consumer's d is the
            # ground truth here.
            vec = wire.decode(payload, expect_elems=d0 + d1)
            if vec.size != d0 + d1:
                raise wire.WireError(
                    f"frame has {vec.size} elements, expected {d0 + d1}"
                )
        except wire.WireError as exc:
            exc.nbytes = len(payload)
            raise
        head, tail = vec[:d0], vec[d0:]
        # From the waiter thread (jax dispatch is thread-safe on the
        # pinned jax/jaxlib): H2D staging overlaps the still-open quorum.
        try:
            head = jax.device_put(head)
        except Exception:  # noqa: BLE001 — host row still works
            pass  # jnp.stack uploads at harvest instead
        if stats is not None:
            stats.received(
                len(payload), time.perf_counter() - t0, plane,
                scheme=wire.frame_scheme(payload),
            )
        return head, tail

    return transform


def _cancel_wait(wait_fn):
    """Retire a pre-registered exchange harvest a role will never consume
    (shutdown, catch-up jump, membership change): without the cancel its
    waiter threads linger until the deadline or ``close()`` — the
    lifecycle leak tests/test_exchange.py pins."""
    if wait_fn is not None and hasattr(wait_fn, "cancel"):
        wait_fn.cancel()


def _async_gradient_quorum(collector, i, q, policy, republish, timeout_ms,
                           who):
    """The bounded-staleness twin of ``_gradient_quorum`` (DESIGN.md §14):
    admissible frames for round ``i`` from the persistent round-tagged
    collector — stale frames within ``policy.max_staleness`` are REUSED,
    so the round rate decouples from the slowest rank; the collector's
    freshness floor (at least one new arrival per harvest) stops the PS
    from free-running on cached data. Codec rejects are Byzantine ban
    evidence exactly as on the synchronous path: the rank's watcher is
    retired (``remove_peer`` — the membership-change form of the waiter
    lifecycle) and the gather retries over the survivors. Returns
    ``{rank: (tag, (grad_row, stats_row))}``.
    """
    attempts = 0
    while True:
        try:
            got = collector.gather(
                i, q, max_staleness=policy.max_staleness,
                timeout_ms=timeout_ms,
            )
        except TimeoutError:
            attempts += 1
            if attempts >= 3:
                raise
            tools.warning(
                f"[{who}] round {i} admissible quorum timed out; "
                f"re-publishing the model (attempt {attempts})"
            )
            tele_hooks.emit_event(
                "quorum_retry", who=who, step=int(i), attempt=attempts
            )
            republish()
            continue
        bad = [k for k in got if isinstance(got[k][1], Exception)]
        if not bad:
            return got
        for k in bad:
            exc = got[k][1]
            tools.warning(
                f"[{who}] worker rank {k} sent a gradient frame that "
                f"failed the wire codec ({exc}); excluding it from "
                "all future quorums"
            )
            tele_hooks.emit_event(
                "quorum_exclusion", who=who, step=int(i), rank=int(k),
                got_bytes=int(getattr(exc, "nbytes", -1)),
                why=str(exc),
            )
            collector.remove_peer(k)
        if len(collector.peers()) < q:
            raise SystemExit(
                f"only {len(collector.peers())} well-formed workers "
                f"remain but the quorum needs q={q}; aborting"
            )


def _staleness_quorum(got, i, q, policy, worker_ranks, who):
    """Deterministic freshest-q composition + weights: sort the
    admissible frames by (staleness, rank) — at ``max_staleness 0``
    every tag equals ``i`` and this is exactly the synchronous path's
    lowest-q-ranks composition — and derive the discount weights via the
    shared policy (utils/rounds.py). Emits the per-round ``staleness``
    telemetry event (schema v4: per-rank staleness + weights, folded
    into suspicion alongside exclusions). Returns
    ``(quorum_ranks, taus, weights)``."""
    quorum = sorted(got, key=lambda k: (i - got[k][0], k))[:q]
    taus = np.array([max(0, i - got[k][0]) for k in quorum], np.int64)
    w = np.asarray(policy.weights(taus), np.float32)
    if tele_hooks.current() is not None:
        base = worker_ranks[0]
        tele_hooks.emit_event(
            "staleness", who=who, step=int(i),
            ranks=[int(k - base) for k in quorum],
            staleness=[int(t) for t in taus],
            weights=[round(float(x), 6) for x in w],
            reused=int((taus > 0).sum()),
        )
    return quorum, taus, w


class _AutoscalePlane:
    """PS-side elastic worker pool (DESIGN.md §15): the autoscale
    controller (``utils/autoscale.py``) plus the mechanics of acting on
    its decisions against a live async deployment.

    Membership is three nested sets over the config's worker ranks:
    the POOL (every worker slot in the cluster config — the reserve),
    the ACTIVE set (processes this PS has spawned and not retired), and
    the READY set (active ranks whose frames have actually reached a
    quorum — a spawning worker pays tens of seconds of jax boot, and
    counting it toward q before its first frame would stall every round
    on its cold start). The effective quorum is
    ``q = max(1, |ready ∩ active| - f)``.

    SPAWN: launch the lowest reserve rank as a real OS process running
    this PS's own CLI re-targeted at ``worker:K``
    (``autoscale.worker_command``); it joins through the existing
    ``read_latest`` catch-up path and re-reads its own shard. RETIRE: a
    CLEAN teardown of the highest active rank — drop it from the
    broadcast fan-out, send it the stop sentinel (it exits rc 0 through
    its normal end-of-run path), retire its exchange watchers
    (``PeerExchange.remove_peer`` — the symmetric-teardown contract) and
    its collector membership. Every action emits the schema-v6
    ``autoscale`` telemetry event; the hub folds the running
    active-worker count into ``garfield_active_workers``.
    """

    def __init__(self, args, worker_ranks, f, gar, who):
        from ..utils import autoscale as autoscale_lib

        n_w = len(worker_ranks)
        max_w = int(getattr(args, "autoscale_max", 0) or 0) or n_w
        cfg = autoscale_lib.AutoscaleConfig(
            target_rate=float(getattr(args, "target_rate", 0.0) or 0.0),
            min_workers=int(getattr(args, "autoscale_min", 1) or 1),
            max_workers=min(max_w, n_w),
            window=int(getattr(args, "autoscale_window", 8) or 8),
            cooldown=int(getattr(args, "autoscale_cooldown", 8) or 8),
        )
        q_min = max(1, cfg.min_workers - f)
        if f:
            msg = gar.check(np.zeros((q_min, 4), np.float32), f=f)
            if msg is not None:
                raise SystemExit(
                    f"--autoscale_min {cfg.min_workers} is infeasible: "
                    f"GAR {gar.name!r} cannot aggregate q = min - fw = "
                    f"{q_min} rows: {msg}"
                )
        self.cfg = cfg
        self.controller = autoscale_lib.AutoscaleController(cfg)
        self.f = f
        self.who = who
        self.worker_ranks = list(worker_ranks)
        self.base = worker_ranks[0]
        self.active = list(worker_ranks[:cfg.min_workers])
        self.ready = set()
        self.ex = None
        self.collector = None
        self._procs = []
        self._log_dir = getattr(args, "telemetry", None)

    def bind(self, ex, collector):
        self.ex = ex
        self.collector = collector

    def q(self):
        live = len(self.ready & set(self.active)) or len(self.active)
        return max(1, live - self.f)

    def note_arrivals(self, ranks):
        self.ready.update(r for r in ranks if r in self.active)

    def _spawn_proc(self, windex):
        import os
        import subprocess
        import sys

        from ..utils import autoscale as autoscale_lib

        cmd = autoscale_lib.worker_command(windex)
        out = subprocess.DEVNULL
        if self._log_dir:
            os.makedirs(self._log_dir, exist_ok=True)
            out = open(
                os.path.join(self._log_dir, f"worker_{windex}.log"), "ab"
            )
        # A list, not a dict keyed by rank: a retire-then-respawn of the
        # same rank must not drop the first process's handle unreaped.
        self._procs.append(subprocess.Popen(
            cmd, stdout=out, stderr=subprocess.STDOUT,
            env=dict(os.environ),
        ))

    def spawn_initial(self):
        """Launch the initial active set (with --autoscale the PS owns
        its worker processes; external launches would double-bind the
        configured ports)."""
        for r in self.active:
            self._spawn_proc(r - self.base)
            self.collector.add_peer(r)

    def observe(self, i, round_s, admissible):
        """Fold one round into the controller and act on its decision."""
        action = self.controller.observe(
            round_s, active=len(self.active),
            quorum_margin=admissible - self.q(),
        )
        if action == 0:
            return
        if action > 0:
            reserve = [
                r for r in self.worker_ranks if r not in self.active
            ]
            rank = reserve[0]
            self.active = sorted(self.active + [rank])
            self._spawn_proc(rank - self.base)
            self.collector.add_peer(rank)
            verb = "spawn"
        else:
            rank = self.active[-1]
            self.active = [r for r in self.active if r != rank]
            self.ready.discard(rank)
            # Clean retire: stop sentinel first (the worker exits rc 0
            # through its end-of-run path the moment its model watcher
            # latches the empty frame), THEN the symmetric watcher
            # teardown — collector membership and any exchange-level
            # latches on the rank (read_latest probes) go together.
            self.ex.publish(i + 1, b"", to=[rank])
            self.collector.remove_peer(rank)
            self.ex.remove_peer(rank)
            verb = "retire"
        rate = self.controller.rate()
        tools.warning(
            f"[{self.who}] autoscale {verb}: worker rank {rank} "
            f"(active {len(self.active)}, target "
            f"{self.controller.target:.2f} r/s)"
        )
        tele_hooks.emit_event(
            "autoscale", who=self.who, step=int(i), action=verb,
            rank=int(rank - self.base), active=len(self.active),
            rate=None if rate is None else round(float(rate), 4),
            target=round(float(self.controller.target), 4),
        )

    def reap(self, timeout=120):
        """Join every process this PS spawned (the run's stop sentinel
        has been published); kill stragglers after ``timeout``."""
        import subprocess

        for p in self._procs:
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()


def _setup(args):
    """Shared ingredients for both roles."""
    cfg = multihost.ClusterConfig(args.cluster)
    if args.task:
        ttype, _, tidx = args.task.partition(":")
        cfg.task_type = ttype
        cfg.task_index = int(tidx or 0)
    n_ps = len(cfg.ps)
    if n_ps < 1:
        raise SystemExit("cluster config needs at least one PS host")
    if n_ps > 1:
        # MSMW (ByzSGD): only the byzsgd app can parameterize the
        # fps-tolerant model plane (--model_gar/--ps_attack are its flags;
        # --fps alone lives in the shared base parser, so its presence
        # distinguishes nothing) — an aggregathor config with several PS
        # hosts must fail loudly, not silently enter the MSMW path.
        if not hasattr(args, "model_gar"):
            raise SystemExit(
                f"the cluster config has {n_ps} PS hosts but this app has "
                "no --model_gar/--ps_attack support; launch MSMW "
                "deployments through the byzsgd app (or use a single-PS "
                "config)"
            )
        model_gar_name = args.model_gar or args.gar
        model_gar = gars[model_gar_name]
        fps = args.fps
        if fps:
            msg = model_gar.check(np.zeros((n_ps, 4), np.float32), f=fps)
            if msg is not None:
                raise SystemExit(
                    f"model GAR {model_gar_name!r} cannot aggregate the "
                    f"{n_ps} PS models at fps={fps}: {msg}"
                )
        else:
            # fps=0: most rules' check() rejects f=0 outright even though
            # unchecked() is well-defined there (krum at f=0 still selects
            # m = n - 2), so checking would break valid fps=0 deployments.
            # Instead probe the EXACT runtime call on a dummy stack — an
            # infeasible (rule, n_ps) pair (ADVICE r4: krum over n_ps=2
            # gives m = 0) fails loudly here instead of as an opaque
            # ZeroDivisionError at trace time.
            try:
                model_gar.unchecked(np.zeros((n_ps, 4), np.float32), f=0)
            except Exception as e:  # noqa: BLE001 — any trace failure
                raise SystemExit(
                    f"model GAR {model_gar_name!r} cannot aggregate the "
                    f"{n_ps} PS models at fps=0: {type(e).__name__}: {e}"
                ) from e
    n_w = len(cfg.workers)
    f = args.fw
    q = n_w - f
    wm = getattr(args, "worker_momentum", None)
    if wm is not None and not (0.0 <= wm < 1.0):
        raise SystemExit(f"worker_momentum must be in [0, 1), got {wm}")
    if not f * 2 < n_w:
        # The majority-honest invariant the reference asserts
        # (Aggregathor/trainer.py:150-152) — enforced against the CONFIG's
        # worker count (the --cluster path bypasses the on-mesh assert).
        raise SystemExit(
            f"the number of Byzantine workers should be less than half the "
            f"number of workers (fw={f}, config has {n_w} workers)"
        )
    # Fail fast with the GAR's own contract before any process waits on
    # another (e.g. krum needs q >= 2f+3).
    if f:
        msg = gars[args.gar].check(np.zeros((q, 4), np.float32), f=f)
        if msg is not None:
            raise SystemExit(
                f"GAR {args.gar!r} cannot run on the q = n_w - fw = {q} "
                f"collected gradients: {msg}"
            )
    xs, ys, test_batches, iters_per_epoch = common.load_data(args, n_w)
    module, loss_fn, optimizer = common.build_ingredients(
        args, iters_per_epoch
    )
    init_fn, grad_fn, eval_fn = core.make_worker_fns(module, loss_fn)
    params0, ms0 = init_fn(jax.random.PRNGKey(args.seed), xs[0, 0])
    # Role-aware retention: the PS never trains (drop the shards), a worker
    # only reads its own shard (drop the rest and the test set) — no point
    # keeping n_w + 1 copies of the dataset across the deployment's hosts.
    if cfg.task_type == "ps":
        xs = ys = None
    else:
        xs, ys = xs[cfg.task_index], ys[cfg.task_index]
        test_batches = None
    flat0, unravel = ravel_pytree(params0)
    # First connects get the startup-scale grace: a peer that is still
    # importing/compiling must not cost the cluster its hello/model frames
    # (the sender holds the frame while retrying — see exchange._sock_for).
    ex = PeerExchange(
        cfg.process_id, cfg.hosts, connect_retry_ms=_startup_ms(args)
    )
    return (cfg, n_w, f, q, xs, ys, test_batches, optimizer, grad_fn,
            eval_fn, params0, ms0, flat0, unravel, ex)


def run(args):
    """Entry: dispatch on the configured role (and PS count: one PS is
    AggregaThor SSMW, several are the ByzSGD MSMW deployment; a "node"
    config is the decentralized LEARN deployment)."""
    # NOTE on the persistent compile cache: deliberately NOT enabled here.
    # On hosts where the XLA:CPU AOT loader rejects its own cache entries
    # (machine-feature validation mismatch — observed on the dev image),
    # every jit pays a failed load per executable and the error spam +
    # retries starved worker startup past the PS's quorum budget. TPU
    # entry points (apps/common.train, __graft_entry__) keep the cache, where it
    # works and matters.
    cfg_probe = multihost.ClusterConfig(args.cluster)
    if cfg_probe.nodes or (args.task or "").startswith("node"):
        return _run_learn(args)
    (cfg, n_w, f, q, xs, ys, test_batches, optimizer, grad_fn, eval_fn,
     params0, ms0, flat0, unravel, ex) = _setup(args)
    n_ps = len(cfg.ps)
    ps_ranks = list(range(n_ps))
    worker_ranks = list(range(n_ps, n_ps + n_w))
    timeout_ms = args.cluster_timeout_ms
    try:
        if cfg.task_type == "ps":
            if n_ps > 1:
                return _run_ps_multi(
                    args, cfg.task_index, ps_ranks, q, worker_ranks,
                    test_batches, optimizer, eval_fn, params0, ms0, flat0,
                    unravel, ex, timeout_ms,
                )
            return _run_ps(
                args, q, worker_ranks, test_batches, optimizer, eval_fn,
                params0, ms0, flat0, unravel, ex, timeout_ms,
            )
        return _run_worker(
            args, cfg.task_index, ps_ranks, xs, ys, grad_fn, ms0, flat0,
            unravel, ex, timeout_ms,
        )
    finally:
        ex.close()


def _gradient_quorum(ex, step, q, good_ranks, split, republish,
                     timeout_ms, who, stats=None, wait_fn=None):
    """The PS-side gradient quorum, shared by SSMW and MSMW.

    A Byzantine PROCESS controls its wire bytes, not just its values: a
    frame the wire codec rejects (bad magic/dtype tag/element count/crc
    or a truncation — ``_frame_transform`` stores the ``WireError`` as
    that rank's result) cannot enter the GAR and proves its sender
    Byzantine — exclude the rank from all future quorums and re-collect
    from the rest (the frames already received return instantly). A
    quorum TIMEOUT triggers ``republish`` before the final attempt: the
    model plane is fire-and-forget, so workers whose listener bound
    after this step's publish (cold start) would otherwise never see a
    frame to catch up to and the healthy cluster would deadlock.
    ``wait_fn`` is the caller's pre-registered ``collect_begin`` harvest
    for the overlap fast path (consumed on the first attempt only —
    retries re-collect over the surviving ranks). Returns
    ``(got, good_ranks)`` with every ``got`` value a decoded
    ``(grad_row, stats_row)`` pair.
    """
    transform = _frame_transform(split, stats, plane=PLANE_GRAD)
    attempts = 0
    while True:
        try:
            if wait_fn is not None:
                # Clear BEFORE harvesting: a timed-out registration must
                # not be re-harvested on the retry path (its waiter
                # threads have already expired).
                w, wait_fn = wait_fn, None
                got = w()
            else:
                got = ex.collect(
                    step, q, peers=good_ranks, timeout_ms=timeout_ms,
                    transform=transform,
                )
        except TimeoutError:
            attempts += 1
            if attempts >= 3:
                raise
            tools.warning(
                f"[{who}] step {step} quorum timed out; re-publishing "
                f"the model (attempt {attempts})"
            )
            tele_hooks.emit_event(
                "quorum_retry", who=who, step=int(step), attempt=attempts
            )
            republish()
            continue
        bad = [k for k in got if isinstance(got[k], Exception)]
        if not bad:
            return got, good_ranks
        for k in bad:
            tools.warning(
                f"[{who}] worker rank {k} sent a gradient frame that "
                f"failed the wire codec ({got[k]}); excluding it from "
                "all future quorums"
            )
            tele_hooks.emit_event(
                "quorum_exclusion", who=who, step=int(step), rank=int(k),
                got_bytes=int(getattr(got[k], "nbytes", -1)),
                why=str(got[k]),
            )
        good_ranks = [k for k in good_ranks if k not in bad]
        if len(good_ranks) < q:
            raise SystemExit(
                f"only {len(good_ranks)} well-formed workers remain "
                f"but the quorum needs q={q}; aborting"
            )


def _run_ps(args, q, worker_ranks, test_batches, optimizer, eval_fn,
            params0, ms0, flat0, unravel, ex, timeout_ms):
    """The trusted server: model out, q fastest gradients in, GAR, update.

    BatchNorm statistics travel too (VERDICT r3 weak #5): each worker's
    gradient frame carries its updated flat ``batch_stats`` appended after
    the gradient, the PS aggregates the quorum's stats with a coordinate-
    wise f-trimmed mean (``_robust_stats`` — a real Byzantine process
    controls the BN segment of its frame, so the aggregation must carry
    the same f budget as the gradients; at f=0 it reduces to the plain
    mean of the on-mesh core.mean_model_state) and appends the result to
    the published model frame — so the two deployment shapes of the SSMW
    topology converge to the same model on BN architectures instead of
    the reference's silent local-BN drift (at f>0 the trim makes the two
    shapes agree only statistically, the price of robustness). Stat-less
    models (d_bn = 0) keep byte-identical frames.
    """
    from .. import parallel

    f = args.fw
    gar = gars[args.gar]
    gar_params = dict(getattr(args, "gar_params", None) or {})
    base_gar_params = dict(gar_params)
    # Closed-loop defense (DESIGN.md §16): suspicion weighting + rule
    # escalation on the host plane. The suspicion source is this PS's
    # own MetricsHub (it sees the real arrival-order quorums), so
    # --defense implies --telemetry like --trace does.
    defense_plan = defense_lib.resolve(args)
    esc_policy = None
    if defense_plan is not None:
        if not getattr(args, "telemetry", None):
            args.telemetry = "telemetry"
        if defense_plan.escalate:
            allowed = sorted(
                k for k in defense_lib.LEVEL_RULES if k in gars
            )
            if args.gar not in allowed:
                raise SystemExit(
                    f"--defense escalate needs --gar to name a REGISTERED "
                    f"escalation-ladder rule ({allowed}), got {args.gar!r}"
                )
            esc_policy = defense_plan.policy()
            esc_policy.level = defense_lib.start_level(
                esc_policy.config.levels, args.gar,
                getattr(args, "gar_params", None),
            )
            lvl_gar, lvl_params = esc_policy.current()
            gar = gars[lvl_gar]
            gar_params = {**base_gar_params, **lvl_params}
    opt_state0 = optimizer.init(params0)
    bn0_flat, bn_unravel = ravel_pytree(ms0)
    bn_elems = int(np.asarray(bn0_flat).size)
    bn_mean = np.asarray(bn0_flat, np.float32)
    if bn_elems and f and q < 2 * f + 1:
        tools.warning(
            f"BN-stat aggregation: the quorum q={q} is below 2*fw+1="
            f"{2 * f + 1}, so the f-trimmed mean clamps to the coordinate-"
            "wise median — if all fw Byzantine workers land in one quorum "
            "they are its majority and can steer the BN statistics "
            "(n_w <= 3*fw is indefensible for stats; see _robust_stats)"
        )
    test_batches = parallel.EvalSet(
        test_batches, binary=args.dataset == "pima"
    )

    gar_base_key = jax.random.PRNGKey(args.seed)

    # Telemetry plane (docs/TELEMETRY.md): this PS is the deployment's
    # natural audit point — it sees the REAL arrival order, so
    # ``observed`` marks the q fastest workers (true wait-n-f, not the
    # on-mesh seeded emulation) and the tap audits the rule's selection
    # inside that quorum. Exchange waits and quorum exclusions stream in
    # through the global hook.
    n_w = len(worker_ranks)
    tele_hub, tele_exp = _telemetry_open(
        args, "cluster-ps", num_ranks=n_w,
        meta={"attack": getattr(args, "attack", None), "q": q},
    )
    # Data-plane defense (aggregators/dataplane.py, DESIGN.md §18): the
    # host twin of the on-mesh detectors — fingerprints the wire frames
    # this PS already decodes, carries its own decayed flag EMA, and
    # composes per-quorum weights into the same row-scale slot as the
    # staleness/suspicion discounts.
    dp_def = None
    if defense_plan is not None and defense_plan.data:
        dp_def = dataplane_lib.DataPlaneDefense(
            n_w, dataplane_lib.head_spec(params0),
            f=max(1, f), plane="gradient",
            tau=defense_plan.dp_tau, power=defense_plan.dp_power,
            floor=defense_plan.dp_floor,
            halflife=defense_plan.dp_halflife,
        )

    def _build_tap(g, gp):
        from ..telemetry import taps as taps_lib

        @jax.jit
        def tap_fn(stack, sel):
            bundle = taps_lib.compute_flat(g.name, stack, f, params=gp)
            return taps_lib.scatter(bundle, sel, n_w)

        return tap_fn

    tap_fn = _build_tap(gar, gar_params) if tele_hub is not None else None

    def _build_updates(g, gp):
        """(ps_update, ps_update_weighted) jits for one rule — rebuilt on
        a defense-escalation level change (same shape, new selection)."""

        def _update_body(flat_params, opt_state, grads_stack, step):
            # f=0 with the default rule short-circuits to the mean, but
            # an explicitly requested rule (e.g. cclip, valid at f=0)
            # must run — silently averaging would fake the defense.
            # Randomized rules (condense) need a fresh per-step key:
            # without it the fixed keyless fallback would apply the SAME
            # coordinate mask every iteration under jit.
            if f or g.name != "average":
                agg = g.unchecked(
                    grads_stack, f=f,
                    key=jax.random.fold_in(gar_base_key, step), **gp,
                )
            else:
                agg = jnp.mean(grads_stack, axis=0)
            params = unravel(flat_params)
            updates, opt_state2 = optimizer.update(
                unravel(agg), opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return ravel_pytree(params)[0], opt_state2

        # Bounded-staleness / suspicion-weighted update (DESIGN.md §14,
        # §16): the weights are composed into the stack BEFORE the GAR —
        # Kardam's dampening and the defense's suspicion discount share
        # one row-scale multiply — so any registered rule aggregates the
        # weighted rows. A fully-fresh, fully-trusted quorum (all
        # weights exactly 1.0) dispatches the unweighted jit instead:
        # same program as the synchronous path, which is the
        # --max_staleness 0 bitwise-equality contract.
        return jax.jit(_update_body), jax.jit(
            lambda fp, ost, stack, w, step: _update_body(
                fp, ost, stack * w[:, None], step
            )
        )

    ps_update, ps_update_weighted = _build_updates(gar, gar_params)

    def acc_eval(state_flat):
        return parallel.compute_accuracy(
            (unravel(state_flat), bn_unravel(jnp.asarray(bn_mean))),
            lambda s, x: eval_fn(s[0], s[1], x),
            test_batches,
            binary=args.dataset == "pima",
        )

    t0 = time.time()
    flat = np.asarray(flat0, np.float32)
    flat_dev, opt_state = jnp.asarray(flat), opt_state0
    good_ranks = list(worker_ranks)
    losses_seen = 0
    # Wire plane (DESIGN.md §11): every data frame goes through the typed
    # codec — encode once per step here, decode eagerly per arriving frame
    # in the exchange waiter threads (``_frame_transform``).
    wire_stats = WireStats("cluster-ps")
    split = (flat.size, bn_elems)
    grad_tf = _frame_transform(split, wire_stats, plane=PLANE_GRAD)
    # Bounded-staleness async mode (--async; DESIGN.md §14): ONE
    # persistent round-tagged collector replaces the per-round
    # collect_begin registrations — its multi-round watchers latch every
    # worker frame (eagerly decoded + device-staged, same transform) and
    # ``gather`` reuses admissible stale frames instead of blocking
    # re-collects.
    policy = rounds.resolve(args)
    collector = None
    scaler = None
    if getattr(args, "autoscale", False):
        # Elastic worker pool (DESIGN.md §15): only composes with the
        # async plane — a synchronous quorum's rate is pinned to its
        # slowest member no matter how many workers exist, so scaling
        # it is meaningless (and the membership mechanics live on the
        # round collector).
        if policy is None:
            raise SystemExit(
                "--autoscale requires --async: the synchronous quorum's "
                "round rate does not scale with the worker count "
                "(DESIGN.md §15)"
            )
        scaler = _AutoscalePlane(args, worker_ranks, f, gar, "cluster-ps")
    if policy is not None:
        collector = ex.round_collector(
            scaler.active if scaler else worker_ranks, transform=grad_tf
        )
        if scaler is not None:
            scaler.bind(ex, collector)
            scaler.spawn_initial()
    # PS-side checkpoint/resume (utils/checkpoint.py — the deliberate
    # upgrade over the reference, which has none; the on-mesh analog with
    # sharded TrainState + bit-exact rng replay lives in common.train).
    # Only the PS holds TRAINING state: resumed workers request model
    # round 0 and read_latest's catch-up semantics jump them straight to
    # the PS's resumed round. Exception: with --worker_momentum the workers
    # hold the EMA, which is NOT persisted — it re-warms over ~1/(1-beta)
    # steps after a resume (the worker warns; see _run_worker).
    ckpt = None
    start_iter = last_saved = 0
    if args.checkpoint_dir:
        from ..utils import checkpoint as ckpt_lib

        ckpt = ckpt_lib.Checkpointer(args.checkpoint_dir)
        step = ckpt.latest_step()
        if args.resume and step is not None:
            restored = ckpt.restore(
                {"flat": flat, "opt_state": jax.tree.map(
                    np.asarray, opt_state),
                 **({"bn": bn_mean} if bn_elems else {})},
                step=step,
            )
            flat = np.asarray(restored["flat"], np.float32)
            flat_dev = jnp.asarray(flat)
            opt_state = jax.tree.map(jnp.asarray, restored["opt_state"])
            if bn_elems:
                bn_mean = np.asarray(restored["bn"], np.float32)
            start_iter = last_saved = int(step)
            print(f"[cluster-ps] resumed from step {start_iter}", flush=True)
    grad_wait = None
    try:
        if collector is None and start_iter < args.num_iter:
            grad_wait = ex.collect_begin(
                start_iter, q, timeout_ms=timeout_ms, peers=good_ranks,
                transform=grad_tf,
            )
        for i in range(start_iter, args.num_iter):
            t_step = time.time()
            # Elastic membership (--autoscale): the broadcast fans out to
            # the ACTIVE set and the quorum tracks the READY subset —
            # both are just ``worker_ranks`` without a scaler.
            targets = scaler.active if scaler else worker_ranks
            q_round = scaler.q() if scaler else q
            with tele_trace.span("broadcast", step=i):
                frame = _encode_frame(
                    [flat] + ([bn_mean] if bn_elems else []),
                    wire_stats, fanout=len(targets), plane=PLANE_MODEL,
                )
                ex.publish(i, frame, to=targets)
            w = None
            if collector is not None:
                # Bounded staleness (DESIGN.md §14): admissible frames —
                # freshest per worker, reused across rounds within the
                # cutoff — instead of an exact-round quorum; the freshest
                # q compose the aggregate with decayed weights.
                with tele_trace.span("quorum", step=i):
                    got = _async_gradient_quorum(
                        collector, i, q_round, policy,
                        lambda: ex.publish(i, frame, to=targets),
                        timeout_ms, "cluster-ps",
                    )
                if scaler is not None:
                    scaler.note_arrivals(got)
                quorum, taus, w = _staleness_quorum(
                    got, i, q_round, policy, worker_ranks, "cluster-ps"
                )
                rows = {k: got[k][1] for k in quorum}
            else:
                with tele_trace.span("quorum", step=i):
                    got, good_ranks = _gradient_quorum(
                        ex, i, q, good_ranks, split,
                        lambda: ex.publish(i, frame, to=worker_ranks),
                        timeout_ms, "cluster-ps", stats=wire_stats,
                        wait_fn=grad_wait,
                    )
                # Overlap (DESIGN.md §11): the NEXT round's collect is
                # registered before this round's device update/eval, so
                # fast workers' next-round gradients are latched +
                # decoded + device-staged by the waiter threads while the
                # PS is still updating/evaluating.
                grad_wait = None
                if i + 1 < args.num_iter:
                    grad_wait = ex.collect_begin(
                        i + 1, q, timeout_ms=timeout_ms, peers=good_ranks,
                        transform=grad_tf,
                    )
                # Deterministic composition: of the >= q arrivals,
                # aggregate the q lowest ranks (the GAR's n is static
                # under jit). Rows arrive pre-decoded (and device-staged)
                # from the waiter threads.
                quorum = sorted(got)[:q]
                rows = {k: got[k] for k in quorum}
            with tele_trace.span("gar_apply", step=i):
                stack = jnp.stack([rows[k][0] for k in quorum])
                if bn_elems:
                    # Robust coordinate-wise aggregation of the quorum's
                    # BatchNorm stats (trim f per side; plain mean at
                    # f=0 == the on-mesh core.mean_model_state) — see
                    # _robust_stats. Async mode reuses the same quorum
                    # rows (stats staleness rides the same cutoff; the
                    # trim bounds a stale row like any other outlier).
                    with tele_trace.span("bn_stats", step=i):
                        bn_mean = _robust_stats(
                            np.stack([rows[k][1] for k in quorum]), f
                        )
                if dp_def is not None:
                    # Data-plane detectors (DESIGN.md §18): fingerprint
                    # this quorum's decoded rows, fold the flags into
                    # the dp EMA, and compose by CENTER-PULL — suspect
                    # rows collapse onto the quorum's trusted-mean center
                    # (toward-zero scaling would hand the cohort krum
                    # centrality; dataplane.center_pull_rows). The host
                    # twin of the in-graph dataplane block.
                    qidx = [k - worker_ranks[0] for k in quorum]
                    rep = dp_def.observe(
                        qidx, np.asarray(stack, np.float32)
                    )
                    tele_hooks.emit_event(
                        "data_defense", who="cluster-ps", step=int(i),
                        plane="gradient",
                        ranks=[int(x) for x in qidx],
                        scores=[round(float(s), 6)
                                for s in rep["scores"]],
                        flags=[int(x) for x in rep["flags"]],
                        weights=[round(float(x), 6) for x in
                                 dp_def.weights_full()[qidx]],
                    )
                    w_dp = dp_def.weights_for(qidx)
                    if w_dp is not None:
                        stack = dataplane_lib.center_pull_rows(
                            stack, jnp.asarray(w_dp)
                        )
                if defense_plan is not None and defense_plan.weighted \
                        and tele_hub is not None:
                    # Suspicion weighting (DESIGN.md §16): the quorum's
                    # rows enter the GAR scaled by their ranks' decayed,
                    # median-relative suspicion — composed with the
                    # staleness discount through the same row-scale
                    # multiply. A clean history is all-exactly-1.0 and
                    # keeps the unweighted program.
                    susp = tele_hub.suspicion_decayed()
                    if susp is not None:
                        qidx = [k - worker_ranks[0] for k in quorum]
                        w_def = np.asarray(defense_lib.suspicion_weights(
                            susp, power=defense_plan.power,
                            floor=defense_plan.floor,
                        ))[qidx].astype(np.float32)
                        tele_hooks.emit_event(
                            "defense_weights", who="cluster-ps",
                            step=int(i),
                            ranks=[int(x) for x in qidx],
                            weights=[round(float(x), 6) for x in w_def],
                        )
                        if not np.all(w_def == 1.0):
                            w = w_def if w is None else (
                                np.asarray(w) * w_def
                            ).astype(np.float32)
                if w is not None and not np.all(w == 1.0):
                    stack_gar = stack * jnp.asarray(w)[:, None]
                    flat_dev, opt_state = ps_update_weighted(
                        flat_dev, opt_state, stack, jnp.asarray(w),
                        jnp.asarray(i, jnp.int32),
                    )
                else:
                    # Fully-fresh quorum (or synchronous mode): the
                    # unweighted program — at --max_staleness 0 this is
                    # the bitwise synchronous trajectory.
                    stack_gar = stack
                    flat_dev, opt_state = ps_update(
                        flat_dev, opt_state, stack,
                        jnp.asarray(i, jnp.int32),
                    )
                flat = np.asarray(flat_dev, np.float32)  # next publication
            wire_stats.flush(i)
            if tele_hub is not None:
                # Worker index = exchange rank - first worker rank; the q
                # quorum members are the observed ranks this step. The
                # tap audits the rows the rule consumed — staleness-
                # weighted included. Its own span: the audit pass is
                # telemetry cost, not round cost, and the report should
                # say so.
                with tele_trace.span("audit", step=i):
                    sel = jnp.asarray(
                        [k - worker_ranks[0] for k in quorum], jnp.int32
                    )
                    tele_hub.record_step(
                        i, tap=tap_fn(stack_gar, sel),
                        step_time_s=time.time() - t_step,
                    )
            if esc_policy is not None and tele_hub is not None:
                # Rule escalation (DESIGN.md §16): fold this round's
                # suspicion concentration into the hysteresis ladder; a
                # level change swaps the jitted update + audit programs
                # (the host-plane twin of the on-mesh re-jit). A level
                # infeasible at this quorum size (bulyan needs
                # q >= 4f+3) is refused loudly and reverted.
                susp = tele_hub.suspicion_decayed()
                if susp is not None:
                    conc = float(defense_lib.suspicion_concentration(
                        susp, max(1, f)
                    ))
                    act = esc_policy.observe(conc)
                    if act:
                        name, lvl_params = esc_policy.current()
                        new_gar = gars[name]
                        msg = new_gar.check(
                            np.zeros((q, 4), np.float32), f=f
                        ) if f else None
                        if msg is not None:
                            tools.warning(
                                f"[cluster-ps] defense cannot escalate "
                                f"to {name!r} at q={q}: {msg}"
                            )
                            esc_policy.level -= act
                        else:
                            gar = new_gar
                            gar_params = {**base_gar_params, **lvl_params}
                            ps_update, ps_update_weighted = _build_updates(
                                gar, gar_params
                            )
                            tap_fn = _build_tap(gar, gar_params)
                            tools.warning(
                                f"[cluster-ps] defense "
                                f"{'escalates' if act > 0 else 'de-escalates'}"
                                f" to {esc_policy.level_name!r} at step {i} "
                                f"(suspicion concentration {conc:.3f})"
                            )
                            tele_hooks.emit_event(
                                "defense_escalate", who="cluster-ps",
                                step=int(i),
                                level=int(esc_policy.level),
                                rule=str(esc_policy.level_name),
                                direction=(
                                    "escalate" if act > 0 else "deescalate"
                                ),
                                gar=name,
                                concentration=round(conc, 6),
                            )
            if scaler is not None:
                # Load control (DESIGN.md §15): fold this round's wall
                # time + admissibility margin into the controller; spawn/
                # retire side effects happen here, between rounds.
                scaler.observe(i, time.time() - t_step, len(got))
            losses_seen = i + 1
            if (ckpt and args.checkpoint_freq
                    and (i + 1) % args.checkpoint_freq == 0):
                with tele_trace.span("checkpoint", step=i):
                    ckpt.save(i + 1, {
                        "flat": flat,
                        "opt_state": jax.tree.map(np.asarray, opt_state),
                        **({"bn": bn_mean} if bn_elems else {}),
                    })
                last_saved = i + 1
            if args.acc_freq and i % args.acc_freq == 0:
                with tele_trace.span("eval", step=i):
                    acc = acc_eval(flat_dev)
                print(
                    f"Step: {i} Accuracy: {acc:.4f} "
                    f"Time: {time.time() - t0:.1f}",
                    flush=True,
                )
    finally:
        # Waiter lifecycle (tests/test_exchange.py): a registration left
        # pending by an abort must not leak its threads until close().
        _cancel_wait(grad_wait)
        if collector is not None:
            collector.close()
    # Stop sentinel: an empty frame at step num_iter tells every worker
    # (including stragglers that skipped rounds) training is over. The
    # full pool is addressed — retired autoscale ranks already exited and
    # a dead rank costs one bounded sender queue.
    ex.publish(args.num_iter, b"", to=worker_ranks)
    if scaler is not None:
        scaler.reap()
    acc = acc_eval(flat_dev)
    if ckpt:
        if args.checkpoint_freq and last_saved != args.num_iter:
            # Final save, skipped when the in-loop save already wrote this
            # exact step (orbax writes are synchronous; workers idle
            # meanwhile).
            ckpt.save(args.num_iter, {
                "flat": flat,
                "opt_state": jax.tree.map(np.asarray, opt_state),
                **({"bn": bn_mean} if bn_elems else {}),
            })
        ckpt.close()
    summary = {
        "final_accuracy": acc,
        "steps": losses_seen,
        "wall_s": time.time() - t0,
    }
    _telemetry_close(tele_hub, tele_exp)
    print(json.dumps({"tag": "cluster-ps", **summary}), flush=True)
    return summary


class _ModelPlane:
    """Shared MSMW model-plane state for PS replicas and workers: the live
    rank list, the (possibly degraded) model GAR + fps, and per-peer
    PROGRESS tracking for crash detection.

    Liveness policy (review-hardened, r5): a peer is declared dead only
    when its newest observed round stops ADVANCING across two consecutive
    timeout cycles — "has no frame at the round I want" is NOT death (an
    alive-but-behind replica, e.g. one paying a minutes-long eval compile
    or resuming from a checkpoint, would be misclassified, and a
    permanent drop is self-fulfilling). Publishing always fans out to the
    FULL original rank list — sends to a dead rank cost one bounded queue
    (exchange per-peer senders), while excluding a merely-slow rank from
    the fan-out would starve it into a real partition.

    Drops are NOT permanent (r6, ADVICE r5 #1): every timeout probe also
    reads the dropped ranks' newest rounds, and a dropped rank whose round
    ADVANCES again is re-admitted (``readmit``) with the tolerance
    restored by the same ``_shrink_fps`` feasibility walk. A healthy
    replica falsely dropped during a multi-minute eval/compile pause
    rejoins the plane the first time any observer next times out, instead
    of fragmenting the deployment into asymmetric plane compositions
    forever. (Re-admission is per-observer, like the drop — each process
    converges on the set of peers IT observes making progress.)
    """

    def __init__(self, ps_ranks, model_gar_name, fps, who):
        self.all_ranks = list(ps_ranks)
        self.ranks = list(ps_ranks)
        self.base_gar = model_gar_name
        self.base_fps = fps
        self.gar_name = model_gar_name
        self.fps = fps
        self.who = who
        self._last_step = {}
        self._stalls = {}

    def aggregate(self, models_stack):
        return _jit_model_agg(self.gar_name, self.fps)(
            jnp.asarray(models_stack)
        )

    def note_progress(self, rank, step):
        if step > self._last_step.get(rank, -1):
            self._last_step[rank] = step
            self._stalls[rank] = 0
            return True
        self._stalls[rank] = self._stalls.get(rank, 0) + 1
        return False

    def stalled_out(self, rank):
        return self._stalls.get(rank, 0) >= 2

    def drop(self, dead):
        self.ranks = [r for r in self.ranks if r not in dead]
        self.gar_name, self.fps = _shrink_fps(
            self.base_gar, len(self.ranks), self.base_fps
        )
        tools.warning(
            f"[{self.who}] model plane degraded: ranks {dead} declared "
            f"crashed (no round progress across two timeout cycles); "
            f"{len(self.ranks)} replicas remain, model GAR "
            f"{self.gar_name!r} at fps={self.fps}"
        )
        tele_hooks.emit_event(
            "plane_drop", who=self.who, ranks=[int(r) for r in dead],
            survivors=len(self.ranks), model_gar=self.gar_name,
            fps=int(self.fps),
        )

    def dropped(self):
        return [r for r in self.all_ranks if r not in self.ranks]

    def readmit(self, rank):
        """Restore a previously dropped rank whose round advanced again
        (it was paused, not dead); tolerance re-grows by the same
        feasibility walk the drop shrank it with."""
        if rank in self.ranks:
            return
        self.ranks = sorted(self.ranks + [rank])
        self.gar_name, self.fps = _shrink_fps(
            self.base_gar, len(self.ranks), self.base_fps
        )
        self._stalls[rank] = 0
        tools.warning(
            f"[{self.who}] model plane re-admitted rank {rank} (round "
            f"progress observed after a drop); {len(self.ranks)} replicas, "
            f"model GAR {self.gar_name!r} at fps={self.fps}"
        )
        tele_hooks.emit_event(
            "plane_readmit", who=self.who, rank=int(rank),
            replicas=len(self.ranks), model_gar=self.gar_name,
            fps=int(self.fps),
        )


@functools.lru_cache(maxsize=16)
def _jit_model_agg(name, f2):
    return jax.jit(lambda m: gars[name].unchecked(m, f=f2))


def _shrink_fps(model_gar_name, n_ps, fps):
    """Largest feasible tolerance for the model GAR over n_ps models, and
    the rule to use. Crash degradation (VERDICT r4 #7): after dropping a
    dead replica the configured rule may be infeasible at the surviving
    count (krum needs n >= 2f+3); prefer shrinking fps, and when no fps
    works at all fall back to the coordinate-wise median — feasible at
    any n and still value-robust to a minority — ALWAYS loudly."""
    gar = gars[model_gar_name]
    for f2 in range(min(fps, n_ps - 1), -1, -1):
        try:
            if f2:
                if gar.check(np.zeros((n_ps, 4), np.float32), f=f2) is None:
                    return model_gar_name, f2
            else:
                gar.unchecked(np.zeros((n_ps, 4), np.float32), f=0)
                return model_gar_name, 0
        except Exception:
            continue
    return "median", 0


def _collect_models(ex, step, plane, timeout_ms, split, stats=None,
                    wait_fn=None):
    """The MSMW model plane: the live PS models for ``step``, stacked by
    rank (``plane.ranks`` after any degradation).

    A frame that fails the wire codec (a Byzantine PROCESS controls its
    wire bytes; ``_frame_transform`` stores the ``WireError``) is
    replaced by a ZERO row — a crash-like value fault inside the fps
    budget — with a warning. On repeated timeout the plane DEGRADES
    instead of raising (VERDICT r4 #7), under ``_ModelPlane``'s
    progress-based liveness: each silent slot is probed for its newest
    round at ANY step (``read_latest(r, 0)``); a peer whose newest round
    advanced is alive (merely slow/behind — keep waiting), a peer with
    no advance across two timeout cycles is dropped (and RE-ADMITTED by a
    later probe that sees its round advancing — _ModelPlane.readmit), and
    a probe that
    reveals the plane has MOVED AHEAD of ``step`` (this caller resumed
    or straggled behind its peers) raises ``_Lapped`` so the caller can
    jump. Raises TimeoutError only when every peer slot is silent.
    ``wait_fn`` is the caller's pre-registered harvest (overlap fast
    path; first attempt only). Returns ``(params_rows, bn_rows)``: the
    device-staged params stack and the host stats stack (None when the
    model carries no stats).
    """
    who = plane.who
    transform = _frame_transform(split, stats, plane=PLANE_MODEL)
    attempts = 0
    while True:
        try:
            if wait_fn is not None:
                # Clear BEFORE harvesting (retries must re-collect, not
                # re-harvest an expired registration).
                w, wait_fn = wait_fn, None
                got = w()
            else:
                got = ex.collect(
                    step, len(plane.ranks), peers=plane.ranks,
                    timeout_ms=timeout_ms, transform=transform,
                )
            break
        except TimeoutError:
            attempts += 1
            if attempts < 3:
                tools.warning(
                    f"[{who}] step {step} model plane timed out; waiting "
                    f"again (attempt {attempts})"
                )
                continue
            newest = step
            heard = []
            for r in plane.ranks:
                try:
                    s, _ = ex.read_latest(r, 0, timeout_ms=2_000)
                    heard.append(r)
                    plane.note_progress(r, s)
                    newest = max(newest, s)
                except TimeoutError:
                    plane.note_progress(r, -1)
            # Dropped ranks are probed too (ADVICE r5 #1): a drop is a
            # liveness HYPOTHESIS, and a dropped rank whose newest round
            # advanced has refuted it — re-admit it so a falsely-dropped
            # replica (multi-minute eval/compile pause) rejoins instead of
            # fragmenting the plane permanently. Publishing never stopped
            # fanning out to it, so it kept receiving frames all along.
            readmitted = False
            for r in plane.dropped():
                try:
                    s, _ = ex.read_latest(r, 0, timeout_ms=2_000)
                except TimeoutError:
                    continue
                if plane.note_progress(r, s):
                    plane.readmit(r)
                    newest = max(newest, s)
                    readmitted = True
            if newest > step:
                raise _Lapped(newest)
            if readmitted:
                attempts = 0
                continue  # retry the collect over the restored plane
            dead = [
                r for r in plane.ranks
                if r != ex.my_index and plane.stalled_out(r)
            ]
            survivors = [r for r in plane.ranks if r not in dead]
            if dead and survivors:
                plane.drop(dead)
                attempts = 0
                continue
            if not heard:
                raise
            attempts = 0  # someone is alive and moving; keep waiting
    d0, d1 = split
    rows, bn_rows = [], []
    for r in sorted(plane.ranks):
        v = got.get(r)
        if v is None or isinstance(v, Exception):
            tools.warning(
                f"[{who}] PS rank {r} sent a model frame at step {step} "
                f"that failed the wire codec ({v}); substituting zeros "
                "(a value fault inside the fps budget)"
            )
            rows.append(np.zeros(d0, np.float32))
            bn_rows.append(np.zeros(d1, np.float32))
        else:
            rows.append(v[0])
            bn_rows.append(v[1])
    return jnp.stack(rows), (np.stack(bn_rows) if d1 else None)


class _Lapped(Exception):
    """Model plane has moved past the expected round (resume/straggle):
    carries the newest observed round so the caller can jump forward."""

    def __init__(self, newest):
        super().__init__(f"model plane is at round {newest}")
        self.newest = newest


def _run_ps_multi(args, pindex, ps_ranks, q, worker_ranks, test_batches,
                  optimizer, eval_fn, params0, ms0, flat0, unravel, ex,
                  timeout_ms):
    """One ByzSGD server replica (MSMW, tensorflow_impl ByzSGD/trainer.py
    :76-95 loop shape): per step — publish own model; gather the live PS
    models and GAR-aggregate with tolerance fps (the pytorch "gather
    step", ByzSGD/trainer.py:240-244); collect the q fastest worker
    gradients; gradient-GAR; optimizer update on the aggregated model. A
    PS launched with --ps_attack publishes its model POISONED
    (byzServer.py:86-108) but otherwise runs the honest loop — a live
    lying replica, the exact fault ByzSGD exists to survive.

    r5 (VERDICT r4 #4/#7):
      - BatchNorm statistics travel on BOTH planes like SSMW: gradient
        frames are [grad || stats], model frames [params || stats]; each
        replica blends the model-plane stats aggregate (fps budget) with
        its own worker quorum's stats (f budget) at equal weight — the
        same reconcile-then-refresh shape as the params — so MSMW
        deployments stop silently drifting on BN architectures
        (ByzSGD/trainer.py:240-244 never ships buffers; workers still
        robust-aggregate the PS stats on their side).
      - Checkpoint/resume: each replica saves under
        checkpoint_dir/ps_{pindex}; a replica that resumes behind its
        peers catches up via the model plane (_Lapped: jump to the
        newest round, where the gather step re-synchronizes its model).
        The catch-up publish necessarily carries the RESTORED model into
        the live round once (the gather's stack shape is static) — a
        value fault the fps budget absorbs; at fps=0 resume is a
        full-deployment-restart operation, not a hot-rejoin.
      - Crash degradation: a PS slot with no frame and no newer round is
        dropped from the plane (loudly), fps shrinks to the largest
        feasible tolerance for the survivors (_shrink_fps; the rule
        degrades to the always-feasible coordinate median as a last
        resort) — one SIGKILLed replica no longer halts the deployment,
        unlike the reference's bounded-retry-then-exit (server.py:138-141).
    """
    from .. import parallel

    f = args.fw
    fps = getattr(args, "fps", 0)
    gar = gars[args.gar]
    gar_params = dict(getattr(args, "gar_params", None) or {})
    base_gar_params = dict(gar_params)
    # Closed-loop defense on the MSMW GRADIENT plane (DESIGN.md §17):
    # the SSMW PS's deployment verbatim — suspicion weighting from this
    # replica's own MetricsHub plus the per-replica escalation ladder.
    # The model plane's rule stays PINNED at the configured model GAR
    # (per-plane ladder independence: the fps gather's contract is not
    # this ladder's to change).
    defense_plan = defense_lib.resolve(args)
    esc_policy = None
    if defense_plan is not None:
        if not getattr(args, "telemetry", None):
            args.telemetry = "telemetry"
        if defense_plan.escalate:
            allowed = sorted(
                k for k in defense_lib.LEVEL_RULES if k in gars
            )
            if args.gar not in allowed:
                raise SystemExit(
                    f"--defense escalate needs --gar to name a REGISTERED "
                    f"escalation-ladder rule ({allowed}), got {args.gar!r}"
                )
            esc_policy = defense_plan.policy()
            esc_policy.level = defense_lib.start_level(
                esc_policy.config.levels, args.gar,
                getattr(args, "gar_params", None),
            )
            lvl_gar, lvl_params = esc_policy.current()
            gar = gars[lvl_gar]
            gar_params = {**base_gar_params, **lvl_params}
    model_gar_name = getattr(args, "model_gar", None) or args.gar
    # Byzantine replica (--ps_attack): byzServer's simple attacks, the
    # model-plane collusion statistics, or the ADAPTIVE controller
    # bisecting against the replica gather (DESIGN.md §17).
    poisoner = _ModelPoisoner(
        getattr(args, "ps_attack", None),
        dict(getattr(args, "ps_attack_params", None) or {}),
        n_ranks=len(ps_ranks), f=fps, my_rank=pindex,
        who=f"cluster-ps-{pindex}", plane="model",
    )
    opt_state = optimizer.init(params0)
    bn0_flat, bn_unravel = ravel_pytree(ms0)
    bn_elems = int(np.asarray(bn0_flat).size)
    bn = np.asarray(bn0_flat, np.float32)
    test_batches = parallel.EvalSet(
        test_batches, binary=args.dataset == "pima"
    )
    gar_base_key = jax.random.PRNGKey(args.seed)
    who = f"cluster-ps-{pindex}"
    plane = _ModelPlane(ps_ranks, model_gar_name, fps, who)

    # Telemetry (docs/TELEMETRY.md): same gradient-plane audit tap as the
    # SSMW PS, plus the model-plane liveness events (plane_drop/readmit)
    # and exchange waits through the global hook.
    n_w = len(worker_ranks)
    tele_hub, tele_exp = _telemetry_open(
        args, who, num_ranks=n_w,
        meta={"attack": getattr(args, "attack", None), "q": q,
              "fps": int(fps), "model_gar": model_gar_name},
    )
    # Data-plane defense on the MSMW GRADIENT quorums (DESIGN.md §18):
    # each replica runs its own detector history over the worker frames
    # it decodes — the per-plane independence convention (the model
    # gather is an agreement over replica MODELS; fingerprinting applies
    # to the worker gradient plane only).
    dp_def = None
    if defense_plan is not None and defense_plan.data:
        dp_def = dataplane_lib.DataPlaneDefense(
            n_w, dataplane_lib.head_spec(params0),
            f=max(1, f), plane="gradient",
            tau=defense_plan.dp_tau, power=defense_plan.dp_power,
            floor=defense_plan.dp_floor,
            halflife=defense_plan.dp_halflife,
        )

    def _build_tap(g, gp):
        if tele_hub is None:
            return None
        from ..telemetry import taps as taps_lib

        @jax.jit
        def tap_fn(stack, sel):
            bundle = taps_lib.compute_flat(g.name, stack, f, params=gp)
            return taps_lib.scatter(bundle, sel, n_w)

        return tap_fn

    tap_fn = _build_tap(gar, gar_params)

    def _build_updates(g, gp):
        """(ps_update, ps_update_weighted) jits for one rule — rebuilt on
        a defense-escalation level change (the SSMW PS convention)."""

        def _update_body(flat_params, opt_state, grads_stack, step):
            if f or g.name != "average":
                agg = g.unchecked(
                    grads_stack, f=f,
                    key=jax.random.fold_in(gar_base_key, step), **gp,
                )
            else:
                agg = jnp.mean(grads_stack, axis=0)
            params = unravel(flat_params)
            updates, opt_state2 = optimizer.update(
                unravel(agg), opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return ravel_pytree(params)[0], opt_state2

        # Staleness/suspicion-weighted twin (DESIGN.md §14/§16) — see
        # _run_ps: weights compose into the stack before the GAR;
        # all-fresh fully-trusted quorums dispatch the unweighted program
        # (the --max_staleness 0 bitwise contract).
        return jax.jit(_update_body), jax.jit(
            lambda fp, ost, stack, w, step: _update_body(
                fp, ost, stack * w[:, None], step
            )
        )

    ps_update, ps_update_weighted = _build_updates(gar, gar_params)

    t0 = time.time()
    flat = np.asarray(flat0, np.float32)
    flat_dev = jnp.asarray(flat)  # --num_iter 0: eval the init model
    good_ranks = list(worker_ranks)
    wire_stats = WireStats(who)
    split = (flat.size, bn_elems)
    model_tf = _frame_transform(split, wire_stats, plane=PLANE_MODEL)
    grad_tf = _frame_transform(split, wire_stats, plane=PLANE_GRAD)
    # --async (DESIGN.md §14): bounded staleness applies to the WORKER
    # gradient plane only — the PS-replica model gather stays exact-round
    # (the ByzSGD fps contract is an agreement over one round's models;
    # mixing rounds there would let a lagging replica's stale model count
    # as a live vote).
    policy = rounds.resolve(args)
    collector = None
    if policy is not None:
        collector = ex.round_collector(worker_ranks, transform=grad_tf)
    ckpt = None
    start_iter = last_saved = 0
    if args.checkpoint_dir:
        import os

        from ..utils import checkpoint as ckpt_lib

        ckpt = ckpt_lib.Checkpointer(
            os.path.join(args.checkpoint_dir, f"ps_{pindex}")
        )
        step = ckpt.latest_step()
        if args.resume and step is not None:
            restored = ckpt.restore(
                {"flat": flat, "opt_state": jax.tree.map(
                    np.asarray, opt_state),
                 **({"bn": bn} if bn_elems else {})},
                step=step,
            )
            flat = np.asarray(restored["flat"], np.float32)
            flat_dev = jnp.asarray(flat)
            opt_state = jax.tree.map(jnp.asarray, restored["opt_state"])
            if bn_elems:
                bn = np.asarray(restored["bn"], np.float32)
            start_iter = last_saved = int(step)
            print(f"[{who}] resumed from step {start_iter}", flush=True)
    losses_seen = start_iter
    i = start_iter
    model_wait = grad_wait = None
    while i < args.num_iter:
        # Byzantine replica publication (byzServer semantics; the
        # collusion/adaptive shapes poison the params segment against
        # the LAST gathered replica stack — _ModelPoisoner).
        vec = poisoner.publish_frame(flat, bn if bn_elems else None, i)
        # Fan out to the FULL original plane (a dead rank costs one
        # bounded sender queue; excluding a merely-slow rank would starve
        # it into a real partition — _ModelPlane docstring). NOTE: after a
        # _Lapped catch-up this publish carries the restored (stale)
        # model into the live round once — a value fault the fps budget
        # absorbs (at fps=0, resume is a full-restart operation; the
        # docstring says so).
        everyone = [
            r for r in plane.all_ranks if r != ex.my_index
        ] + list(worker_ranks)
        with tele_trace.span("broadcast", step=i):
            frame = _encode_frame([vec], wire_stats, fanout=len(everyone),
                                  plane=PLANE_MODEL)
            ex.publish(i, frame, to=everyone)
        try:
            with tele_trace.span("model_gather", step=i):
                models_p, models_bn = _collect_models(
                    ex, i, plane, timeout_ms, split,
                    stats=wire_stats, wait_fn=model_wait,
                )
        except _Lapped as lap:
            # Resumed/straggled behind the peers: jump to their round; the
            # gather step there re-synchronizes the model (docstring). Any
            # pre-registered waiters for the abandoned round self-expire
            # when the plane's slots advance past it.
            tools.warning(
                f"[{who}] behind the model plane at round {i}; jumping "
                f"to round {lap.newest}"
            )
            i = lap.newest
            # Abandoned-round registrations must not leak their waiter
            # threads until the slots happen to advance past them.
            _cancel_wait(model_wait)
            _cancel_wait(grad_wait)
            model_wait = grad_wait = None
            continue
        model_wait = None  # consumed
        if poisoner.kind is not None:
            # Collusion statistics + adaptive probe feed (the gathered
            # rows are this round's replica plane, ranks in sorted
            # order — _collect_models' stacking contract).
            poisoner.note_gather(
                np.asarray(models_p), sorted(plane.ranks), i
            )
        flat_dev = plane.aggregate(models_p)
        if bn_elems:
            # Model-plane BN aggregate (fps budget) — BLENDED with the
            # worker quorum's stats below, not overwritten (ADVICE r5 #2:
            # the old assignment here was dead, so replicas never actually
            # reconciled BN state).
            bn_plane = _robust_stats(models_bn, plane.fps)
        w = None
        if collector is not None:
            with tele_trace.span("quorum", step=i):
                got = _async_gradient_quorum(
                    collector, i, q, policy,
                    lambda: ex.publish(i, frame, to=everyone),
                    timeout_ms, who,
                )
            quorum, taus, w = _staleness_quorum(
                got, i, q, policy, worker_ranks, who
            )
            rows = {k: got[k][1] for k in quorum}
        else:
            with tele_trace.span("quorum", step=i):
                got, good_ranks = _gradient_quorum(
                    ex, i, q, good_ranks, split,
                    lambda: ex.publish(i, frame, to=everyone),
                    timeout_ms, who, stats=wire_stats, wait_fn=grad_wait,
                )
            grad_wait = None
            quorum = sorted(got)[:q]
            rows = {k: got[k] for k in quorum}
        # Overlap (DESIGN.md §11): next round's planes registered before
        # the device update/eval — peer models and fast workers' gradients
        # decode + stage while this replica still computes. (The async
        # gradient plane needs no registration: its collector watches
        # every round persistently.)
        if i + 1 < args.num_iter:
            model_wait = ex.collect_begin(
                i + 1, len(plane.ranks), timeout_ms=timeout_ms,
                peers=plane.ranks, transform=model_tf,
            )
            if collector is None:
                grad_wait = ex.collect_begin(
                    i + 1, q, timeout_ms=timeout_ms, peers=good_ranks,
                    transform=grad_tf,
                )
        with tele_trace.span("gar_apply", step=i):
            stack = jnp.stack([rows[k][0] for k in quorum])
            if bn_elems:
                # BN reconciliation mirrors the params: equal-weight
                # blend of the peer replicas' robust-aggregated stats
                # (published next round) with this quorum's fresh worker
                # stats. Replicas see overlapping-but-different worker
                # quorums, so without the plane term their BN states
                # drift apart unboundedly; the 1/2 contraction bounds
                # the spread at O(one quorum's dispersion) while still
                # tracking the live statistics (the on-mesh twin's pmean
                # over the ps axis, parallel/byzsgd.py, is the
                # limit-case of this blend).
                with tele_trace.span("bn_stats", step=i):
                    bn = 0.5 * (bn_plane + _robust_stats(
                        np.stack([rows[k][1] for k in quorum]), f
                    ))
            if dp_def is not None:
                # Data-plane detectors (DESIGN.md §18): the SSMW PS's
                # per-quorum composition verbatim — detect, fold the
                # EMA, center-pull suspect rows onto the trusted mean —
                # against this replica's own detector history.
                qidx = [k - worker_ranks[0] for k in quorum]
                rep = dp_def.observe(qidx, np.asarray(stack, np.float32))
                tele_hooks.emit_event(
                    "data_defense", who=who, step=int(i),
                    plane="gradient",
                    ranks=[int(x) for x in qidx],
                    scores=[round(float(s), 6) for s in rep["scores"]],
                    flags=[int(x) for x in rep["flags"]],
                    weights=[round(float(x), 6) for x in
                             dp_def.weights_full()[qidx]],
                )
                w_dp = dp_def.weights_for(qidx)
                if w_dp is not None:
                    stack = dataplane_lib.center_pull_rows(
                        stack, jnp.asarray(w_dp)
                    )
            if defense_plan is not None and defense_plan.weighted \
                    and tele_hub is not None:
                # Suspicion weighting on the MSMW gradient plane
                # (DESIGN.md §17): the SSMW PS's per-quorum composition
                # verbatim — decayed median-relative suspicion from this
                # replica's own hub, multiplied into the same row-scale
                # slot as the staleness discount.
                susp = tele_hub.suspicion_decayed()
                if susp is not None:
                    qidx = [k - worker_ranks[0] for k in quorum]
                    w_def = np.asarray(defense_lib.suspicion_weights(
                        susp, power=defense_plan.power,
                        floor=defense_plan.floor,
                    ))[qidx].astype(np.float32)
                    tele_hooks.emit_event(
                        "defense_weights", who=who, step=int(i),
                        ranks=[int(x) for x in qidx],
                        weights=[round(float(x), 6) for x in w_def],
                    )
                    if not np.all(w_def == 1.0):
                        w = w_def if w is None else (
                            np.asarray(w) * w_def
                        ).astype(np.float32)
            if w is not None and not np.all(w == 1.0):
                stack_gar = stack * jnp.asarray(w)[:, None]
                flat_dev, opt_state = ps_update_weighted(
                    flat_dev, opt_state, stack, jnp.asarray(w),
                    jnp.asarray(i, jnp.int32),
                )
            else:
                stack_gar = stack
                flat_dev, opt_state = ps_update(
                    flat_dev, opt_state, stack,
                    jnp.asarray(i, jnp.int32),
                )
            flat = np.asarray(flat_dev, np.float32)
        wire_stats.flush(i)
        if tele_hub is not None:
            with tele_trace.span("audit", step=i):
                sel = jnp.asarray(
                    [k - worker_ranks[0] for k in quorum], jnp.int32
                )
                tele_hub.record_step(
                    i, tap=tap_fn(stack_gar, sel),
                )
        if esc_policy is not None and tele_hub is not None:
            # Per-replica escalation ladder on the gradient plane
            # (DESIGN.md §17) — the SSMW PS's hysteresis loop: a level
            # infeasible at this quorum size is refused loudly and
            # reverted; the model plane's rule never moves.
            susp = tele_hub.suspicion_decayed()
            if susp is not None:
                conc = float(defense_lib.suspicion_concentration(
                    susp, max(1, f)
                ))
                act = esc_policy.observe(conc)
                if act:
                    name, lvl_params = esc_policy.current()
                    new_gar = gars[name]
                    msg = new_gar.check(
                        np.zeros((q, 4), np.float32), f=f
                    ) if f else None
                    if msg is not None:
                        tools.warning(
                            f"[{who}] defense cannot escalate to "
                            f"{name!r} at q={q}: {msg}"
                        )
                        esc_policy.level -= act
                    else:
                        gar = new_gar
                        gar_params = {**base_gar_params, **lvl_params}
                        ps_update, ps_update_weighted = _build_updates(
                            gar, gar_params
                        )
                        tap_fn = _build_tap(gar, gar_params)
                        tools.warning(
                            f"[{who}] defense "
                            f"{'escalates' if act > 0 else 'de-escalates'}"
                            f" to {esc_policy.level_name!r} at step {i} "
                            f"(suspicion concentration {conc:.3f})"
                        )
                        tele_hooks.emit_event(
                            "defense_escalate", who=who, step=int(i),
                            plane="gradient",
                            level=int(esc_policy.level),
                            rule=str(esc_policy.level_name),
                            direction=(
                                "escalate" if act > 0 else "deescalate"
                            ),
                            gar=name,
                            concentration=round(conc, 6),
                        )
        losses_seen = i + 1
        if ckpt and args.checkpoint_freq and (i + 1) % args.checkpoint_freq == 0:
            with tele_trace.span("checkpoint", step=i):
                ckpt.save(i + 1, {
                    "flat": flat,
                    "opt_state": jax.tree.map(np.asarray, opt_state),
                    **({"bn": bn} if bn_elems else {}),
                })
            last_saved = i + 1
        if args.acc_freq and i % args.acc_freq == 0:
            with tele_trace.span("eval", step=i):
                acc = parallel.compute_accuracy(
                    (unravel(flat_dev), bn_unravel(jnp.asarray(bn))),
                    lambda s, x: eval_fn(s[0], s[1], x),
                    test_batches, binary=args.dataset == "pima",
                )
            print(
                f"Step: {i} Accuracy: {acc:.4f} "
                f"Time: {time.time() - t0:.1f}",
                flush=True,
            )
        i += 1
    # Waiter lifecycle: retire anything the loop left registered (the
    # exception paths fall through to run()'s ex.close(), whose close
    # sentinel wakes and joins every watcher before the register frees).
    _cancel_wait(model_wait)
    _cancel_wait(grad_wait)
    if collector is not None:
        collector.close()
    acc = parallel.compute_accuracy(
        (unravel(flat_dev), bn_unravel(jnp.asarray(bn))),
        lambda s, x: eval_fn(s[0], s[1], x),
        test_batches, binary=args.dataset == "pima",
    )
    if ckpt:
        if args.checkpoint_freq and last_saved != args.num_iter:
            ckpt.save(args.num_iter, {
                "flat": flat,
                "opt_state": jax.tree.map(np.asarray, opt_state),
                **({"bn": bn} if bn_elems else {}),
            })
        ckpt.close()
    summary = {
        "final_accuracy": acc,
        "steps": losses_seen,
        "wall_s": time.time() - t0,
        **({"ps_attack_adapt": poisoner.stats()}
           if poisoner.stats() else {}),
    }
    _telemetry_close(tele_hub, tele_exp)
    print(json.dumps({"tag": who, **summary}), flush=True)
    return summary


def _run_learn(args):
    """One LEARN peer: worker AND server in the same process
    (LEARN/trainer.py:224-231), gossiping over PeerExchange.

    Per iteration (LEARN/trainer.py:251-257, both planes at per-node
    wait-n-f): compute the local gradient on the own model; publish it;
    collect the q = n - f FASTEST peer gradients (self included) and
    GAR-aggregate; apply the local optimizer; publish the updated model;
    collect the q fastest peer models and model-GAR-aggregate (the gossip
    that keeps honest models from drifting apart). The two planes share
    one exchange slot per node via step multiplexing (barrier at 0,
    gradients at 2i+2, models at 2i+3 — the last-writer-wins register then
    ages out a round's gradient exactly when its publisher moves on, which
    is the wait-n-f contract). The non-iid ⌈log2 t⌉ agreement rounds
    (avg_agree, :208-222) remain the on-mesh topology's domain
    (parallel/learn.py).

    Liveness: the loop is preceded by a jit WARMUP and an all-nodes
    BARRIER — without them, compile skew lets the fast majority form
    quorums among themselves and age a slow node's rounds out of the
    register before it ever sees them. A node that still loses a round's
    quorum in steady state retries, then exits GRACEFULLY as a dropout —
    the reference's bounded-retry-then-exit(0) semantics
    (server.py:138-141, ps.py:84-88): the survivors' wait-n-f quorums flow
    around it exactly as around a crash.

    A node with --attack is a real Byzantine peer poisoning its published
    gradient (cohort attacks compute their own local statistics); with
    --model_attack it also poisons its gossiped model (the LEARN-side
    byzServer analog). A SIGKILLed node simply stops publishing and every
    survivor's wait-n-f quorum flows around it.

    ``--async`` (DESIGN.md §15): bounded-staleness gossip over PER-PLANE
    register slots. The old single-slot multiplexing (which is what made
    LEARN reject --async through r11 — a round-tagged watcher could not
    hold a stale gradient once its publisher gossiped the model over it)
    is replaced by a 3-plane exchange: control beacons on plane 0,
    gradients on PLANE_GRAD, models on PLANE_MODEL, each with its own
    persistent ``RoundCollector``. Per round each node PUBLISHES-AND-
    CONTINUES on both planes: it gathers the freshest q = n - f
    admissible plane-tagged frames per phase (stale frames within
    ``--max_staleness`` are REUSED with ``utils/rounds.py`` discount
    weights composed into the stack before the rule — the same
    Kardam-style law the PS plane applies, so one slow node stops
    setting every honest node's pace), and ``--max_staleness 0`` is
    bitwise the synchronous trajectory (exact-round admission, all
    weights exactly 1.0, the unweighted jit programs).
    """
    cfg = multihost.ClusterConfig(args.cluster)
    if args.task:
        ttype, _, tidx = args.task.partition(":")
        cfg.task_type = ttype
        cfg.task_index = int(tidx or 0)
    n = len(cfg.nodes)
    f = args.fw
    q = n - f
    if not f * 2 < n:
        raise SystemExit(
            f"the number of Byzantine nodes should be less than half the "
            f"number of nodes (fw={f}, config has {n} nodes)"
        )
    if f:
        msg = gars[args.gar].check(np.zeros((q, 4), np.float32), f=f)
        if msg is not None:
            raise SystemExit(
                f"GAR {args.gar!r} cannot run on the q = n - fw = {q} "
                f"collected rows: {msg}"
            )
    # Bounded-staleness async gossip (--async, DESIGN.md §15): the
    # exchange grows per-plane register slots — control beacons keep
    # plane 0, gradients and models each get their own slot per peer, so
    # the planes stop overwriting each other in the last-writer-wins
    # register (the multiplexing limitation that made LEARN reject
    # --async through r11).
    policy = rounds.resolve(args)
    # The exchange (and the stage-1 liveness hello, below) must exist
    # BEFORE any heavy local work: model init + data staging compile for
    # minutes on a loaded host, and a peer's barrier read cannot see that
    # (r5 — observed 4 co-located ResNet-class inits blowing the fixed
    # barrier budget when the hello waited for them).
    ex = PeerExchange(
        cfg.process_id, cfg.hosts, connect_retry_ms=_startup_ms(args),
        planes=3 if policy is not None else 1,
    )
    ex.publish(0, b"up")
    xs, ys, test_batches, iters_per_epoch = common.load_data(args, n)
    module, loss_fn, optimizer = common.build_ingredients(
        args, iters_per_epoch
    )
    init_fn, grad_fn, eval_fn = core.make_worker_fns(module, loss_fn)
    params0, ms0 = init_fn(jax.random.PRNGKey(args.seed), xs[0, 0])
    my_xs, my_ys = xs[cfg.task_index], ys[cfg.task_index]
    flat0, unravel = ravel_pytree(params0)

    from .. import parallel

    me = cfg.task_index
    gar = gars[args.gar]
    model_gar = gars[getattr(args, "model_gar", None) or args.gar]
    gar_params = dict(getattr(args, "gar_params", None) or {})
    atk_kind, attack, atk_cohort = _host_attack(
        args.attack, args.attack_params, f
    )
    if atk_kind == "adaptive":
        # The LEARN gossip plane has no single broadcast-model feedback
        # channel (every node aggregates its own view), so the adaptive
        # controller's probe is undefined here — reject loudly instead
        # of silently running an oblivious loop.
        raise SystemExit(
            f"--attack {args.attack!r} drives the PS-topology worker "
            "role; LEARN nodes support the oblivious gradient attacks "
            "(random/reverse/lie/empire), the targeted poisoners "
            "(labelflip/backdoor), and the ADAPTIVE gossip attacks via "
            "--model_attack adaptive-* (the model plane is where a "
            "LEARN node has a probe)"
        )
    # Closed-loop defense on LEARN's gossip phases (DESIGN.md §17): one
    # ``PlaneDefense`` PER PLANE — the gradient gather and the model
    # gossip keep INDEPENDENT decayed exclusion histories and independent
    # escalation ladders (the gradient ladder moving must not drag the
    # gossip rule along, and vice versa). Suspicion weights compose into
    # ``node_update_weighted``/``model_aggregate_weighted`` through the
    # same row-scale slot as the async staleness discount; the per-level
    # jits are cached per rule like the SSMW PS's.
    defense_plan = defense_lib.resolve(args)
    grad_def = gossip_def = None
    if defense_plan is not None and defense_plan.data:
        # The data-plane detectors deploy on the PS gradient quorums
        # (SSMW/MSMW) and the on-mesh SSMW step (DESIGN.md §18); a LEARN
        # node's per-phase quorums keep the GAR-side ladder only.
        tools.warning(
            f"[cluster-node-{cfg.task_index}] --defense data: the "
            "data-plane detectors are a PS-quorum deployment; LEARN "
            "nodes apply the GAR-side defense components only"
        )
        if not defense_plan.weighted and not defense_plan.escalate:
            defense_plan = None
    if defense_plan is not None:
        if not getattr(args, "telemetry", None):
            args.telemetry = "telemetry"
        if defense_plan.escalate:
            allowed = sorted(
                k for k in defense_lib.LEVEL_RULES if k in gars
            )
            for plane_name, rule in (
                ("gradient", args.gar),
                ("gossip", getattr(args, "model_gar", None) or args.gar),
            ):
                if rule not in allowed:
                    raise SystemExit(
                        f"--defense escalate on the LEARN {plane_name} "
                        f"plane needs its rule to name a REGISTERED "
                        f"escalation-ladder level ({allowed}), got "
                        f"{rule!r}"
                    )
        grad_def = defense_lib.PlaneDefense(
            defense_plan, n, f=f, plane="gradient",
            base_gar=args.gar, base_params=gar_params,
        )
        gossip_def = defense_lib.PlaneDefense(
            defense_plan, n, f=f, plane="gossip",
            base_gar=getattr(args, "model_gar", None) or args.gar,
        )
    # Byzantine gossip publisher (--model_attack): byzServer's simple
    # attacks, the model-plane collusion statistics, or the ADAPTIVE
    # controller bisecting against the gossip quorum (DESIGN.md §17).
    poisoner = _ModelPoisoner(
        getattr(args, "model_attack", None),
        dict(getattr(args, "model_attack_params", None) or {}),
        n_ranks=n, f=f, my_rank=me, who=f"cluster-node-{me}",
        plane="gossip",
    )
    beta = getattr(args, "worker_momentum", None)
    mom = None
    eval_set = parallel.EvalSet(test_batches, binary=args.dataset == "pima")
    gar_base_key = jax.random.PRNGKey(args.seed)
    opt_state = optimizer.init(params0)

    @jax.jit
    def worker_grad(flat_params, ms, x, y, rng):
        grads, (loss, new_ms) = grad_fn(unravel(flat_params), ms, x, y, rng)
        return ravel_pytree(grads)[0], loss, new_ms

    def _build_node_updates(g, gp):
        """(node_update, node_update_weighted) jits for one gradient-
        plane rule — rebuilt on a defense-escalation level change."""

        def _node_update_body(flat_params, opt_state, grads_stack, step):
            agg = g.unchecked(
                grads_stack, f=f,
                key=jax.random.fold_in(gar_base_key, step), **gp,
            )
            params = unravel(flat_params)
            updates, opt_state2 = optimizer.update(
                unravel(agg), opt_state, params
            )
            return (
                ravel_pytree(optax.apply_updates(params, updates))[0],
                opt_state2,
            )

        # Staleness/suspicion-weighted twin (DESIGN.md §15/§17) — the PS
        # plane's composition verbatim: weights scale the rows BEFORE
        # the rule; an all-fresh fully-trusted quorum dispatches the
        # unweighted program, which is the --max_staleness 0 (and
        # defense-off) bitwise contract.
        return jax.jit(_node_update_body), jax.jit(
            lambda fp, ost, stack, w, step: _node_update_body(
                fp, ost, stack * w[:, None], step
            )
        )

    node_update, node_update_weighted = _build_node_updates(
        gar, gar_params
    )

    def _build_model_aggs(g, gp):
        """(model_aggregate, model_aggregate_weighted) jits for one
        gossip-plane rule — the gossip ladder's per-level programs."""

        def _model_aggregate_body(models_stack, step):
            return g.unchecked(
                models_stack, f=f,
                key=jax.random.fold_in(
                    jax.random.fold_in(gar_base_key, step), 1
                ), **gp,
            )

        # Gossip-plane staleness/suspicion composition (DESIGN.md
        # §15/§17): a discounted model row is treated as the outlier it
        # is; all-fresh trusted quorums dispatch the unweighted program
        # (the ms=0 bitwise contract).
        return jax.jit(_model_aggregate_body), jax.jit(
            lambda stack, w, step: _model_aggregate_body(
                stack * w[:, None], step
            )
        )

    model_aggregate, model_aggregate_weighted = _build_model_aggs(
        model_gar, {}
    )

    def _rebuild_grad(new_g, gp):
        nonlocal gar, gar_params, node_update, node_update_weighted
        nonlocal grad_tap
        gar = new_g
        gar_params = gp
        node_update, node_update_weighted = _build_node_updates(new_g, gp)
        grad_tap = _plane_tap(new_g, gp)

    def _rebuild_gossip(new_g, gp):
        nonlocal model_gar, model_aggregate, model_aggregate_weighted
        nonlocal gossip_tap
        model_gar = new_g
        model_aggregate, model_aggregate_weighted = _build_model_aggs(
            new_g, gp
        )
        gossip_tap = _plane_tap(new_g, gp)

    def _compose_w(w, gw):
        """Compose a quorum's staleness weights (length q, or None) with
        the defense's per-row weights (length <= q, or None; pad rows
        are fully trusted) — one row-scale multiply, like the PS."""
        if gw is None:
            return w
        full = np.ones(q, np.float32)
        full[:len(gw)] = gw
        if w is None:
            return jnp.asarray(full)
        return jnp.asarray(
            (np.asarray(w, np.float32) * full).astype(np.float32)
        )

    def _plane_tap(g, gp):
        """Jitted per-quorum audit for one plane's rule: the rule's
        selection weights over exactly the quorum stack it consumed —
        what feeds the plane's ``PlaneDefense`` history."""
        from ..telemetry import taps as taps_lib

        @jax.jit
        def tap(stack, key):
            return taps_lib.compute_flat(
                g.name, stack, f, key=key, params=gp
            )["selected"]

        return tap

    grad_tap = gossip_tap = None
    if defense_plan is not None:
        grad_tap = _plane_tap(gar, gar_params)
        gossip_tap = _plane_tap(model_gar, {})

    def _plane_escalate(pdef, i, rebuild):
        """One round of a plane's escalation ladder: fold concentration,
        validate feasibility at q, rebuild the plane's jits on a level
        change (or revert loudly)."""
        act = pdef.observe()
        if not act:
            return
        name, lvl_params = pdef.current()
        new_g = gars[name]
        msg = new_g.check(np.zeros((q, 4), np.float32), f=f) if f else None
        if msg is not None:
            tools.warning(
                f"[{who}] defense cannot escalate the {pdef.plane} plane "
                f"to {name!r} at q={q}: {msg}"
            )
            pdef.revert(act)
            return
        rebuild(new_g, lvl_params)
        tools.warning(
            f"[{who}] defense {'escalates' if act > 0 else 'de-escalates'}"
            f" the {pdef.plane} plane to {pdef.policy.level_name!r} at "
            f"round {i} (concentration {pdef.concentration():.3f})"
        )
        tele_hooks.emit_event(
            "defense_escalate", who=who, step=int(i), plane=pdef.plane,
            level=int(pdef.policy.level),
            rule=str(pdef.policy.level_name),
            direction="escalate" if act > 0 else "deescalate",
            gar=name,
            concentration=round(pdef.concentration(), 6),
        )

    def _audit_plane(pdef, tap, stack, ranks, i, key, plane):
        """Fold one quorum's selection verdict into the plane's defense
        history (+ the per-round defense_weights event) and return the
        composed per-row weights for THIS quorum (None = all-1.0)."""
        if pdef is None or not ranks:
            return None
        sel = np.asarray(tap(stack, key))[:len(ranks)]
        pdef.fold(ranks, sel)
        w = pdef.weights_for(ranks)
        if w is not None:
            tele_hooks.emit_event(
                "defense_weights", who=who, step=int(i), plane=plane,
                ranks=[int(r) for r in ranks],
                weights=[round(float(x), 6) for x in w],
            )
        return w

    def harvest(wait_fn, split):
        """Drain a pre-registered quorum, stack the q lowest-rank
        WELL-FORMED rows (frames arrive pre-decoded and device-staged by
        ``_frame_transform`` on the waiter threads). Frames the wire
        codec rejected (Byzantine wire bytes — the stored ``WireError``)
        are filtered FIRST, so an extra well-formed frame from a higher
        rank replaces a malformed lower one (ADVICE r4: discarding honest
        data while feeding the GAR substitute zeros would hand the
        attacker a second fault for free); zero rows — a crash-like value
        fault inside the f budget — pad only when fewer than q
        well-formed frames exist. Returns ``(rows, bn_rows, ranks)``:
        the stacks (``bn_rows`` None when the plane carries no stats
        segment) plus the contributing peers' rank ids in row order
        (pad rows carry no rank) — the attribution the per-plane
        defense audit keys on (DESIGN.md §17)."""
        got = wait_fn()
        d0, d1 = split
        well_formed = []
        for k in sorted(got):
            v = got[k]
            if not isinstance(v, Exception):
                well_formed.append((k, v))
            elif k not in warned_malformed:  # once per peer, not per round
                warned_malformed.add(k)
                tools.warning(
                    f"[{who}] peer rank {k} sent a frame that failed the "
                    f"wire codec ({v}); dropping its malformed frames "
                    "from every quorum (warned once)"
                )
        ranks = [k for k, _ in well_formed[:q]]
        rows = [v[0] for _, v in well_formed[:q]]
        bn_rows = [v[1] for _, v in well_formed[:q]]
        while len(rows) < q:
            rows.append(np.zeros(d0, np.float32))
            bn_rows.append(np.zeros(d1, np.float32))
        return (
            jnp.stack(rows), (np.stack(bn_rows) if d1 else None), ranks
        )

    who = f"cluster-node-{me}"
    warned_malformed = set()

    def gather_rows(collector, i, split, phase):
        """The bounded-staleness twin of ``harvest`` (one per-plane
        ``RoundCollector``): admissible frames for round ``i`` — stale
        within ``--max_staleness`` REUSED — composed as the freshest q
        rows (ties on rank: at ms=0 this is exactly ``harvest``'s
        lowest-rank composition), with ``utils/rounds.py`` discount
        weights. Malformed frames (stored ``WireError``) retire the
        peer's watcher (the PS plane's ban semantics, softened to
        drop-and-flow like ``harvest``); zero rows pad below q. Emits the
        per-round plane-tagged ``staleness`` telemetry event (schema v6)
        whose discount deficits feed this node's suspicion ranking.
        Returns ``(stack, bn_stack|None, weights|None, ranks)`` —
        weights None when every admitted row is fresh, so the caller
        dispatches the UNWEIGHTED jit program (the ms=0 bitwise
        contract); ``ranks`` are the quorum's peer ids in row order
        (pad rows carry no rank), the defense audit's attribution."""
        got = collector.gather(
            i, q, max_staleness=policy.max_staleness,
            timeout_ms=args.cluster_timeout_ms,
        )
        d0, d1 = split
        well = {}
        for k, (tag, v) in got.items():
            if isinstance(v, Exception):
                if k not in warned_malformed:
                    warned_malformed.add(k)
                    tools.warning(
                        f"[{who}] peer rank {k} sent a frame that failed "
                        f"the wire codec ({v}); retiring its watcher "
                        "(warned once)"
                    )
                    collector.remove_peer(k)
            else:
                well[k] = (tag, v)
        quorum = sorted(well, key=lambda k: (i - well[k][0], k))[:q]
        taus = [max(0, i - well[k][0]) for k in quorum]
        rows = [well[k][1][0] for k in quorum]
        bn_rows = [well[k][1][1] for k in quorum]
        while len(rows) < q:
            rows.append(np.zeros(d0, np.float32))
            bn_rows.append(np.zeros(d1, np.float32))
            taus.append(0)
        w = np.asarray(
            policy.weights(np.asarray(taus, np.int64)), np.float32
        )
        if tele_hooks.current() is not None:
            # The audit covers EVERY admissible frame, not just the
            # composed freshest-q quorum: a badly lagging peer rarely
            # makes the quorum at all, and auditing only the quorum
            # would hide exactly the rank the discount deficit exists
            # to expose (its observed stale frames must keep feeding
            # its suspicion even when fresher peers out-compose it).
            adm = sorted(well)
            adm_taus = np.asarray(
                [max(0, i - well[k][0]) for k in adm], np.int64
            )
            adm_w = np.asarray(policy.weights(adm_taus), np.float32)
            tele_hooks.emit_event(
                "staleness", who=who, step=int(i), plane=phase,
                ranks=[int(k) for k in adm],
                staleness=[int(t) for t in adm_taus],
                weights=[round(float(x), 6) for x in adm_w],
                reused=int((adm_taus > 0).sum()),
            )
        return (
            jnp.stack(rows),
            (np.stack(bn_rows) if d1 else None),
            (jnp.asarray(w) if not np.all(w == 1.0) else None),
            list(quorum),
        )

    # LEARN-peer telemetry: exchange wait latencies + liveness events
    # stream here; async mode adds per-plane staleness events whose
    # discount deficits rank a straggling peer in this node's suspicion.
    # With --defense the per-plane quorum audits (``_audit_plane``) feed
    # the node's OWN rank-attributed defense histories — the plane
    # deployment DESIGN.md §17 describes.
    tele_hub, tele_exp = _telemetry_open(args, who, num_ranks=n)
    # Targeted poisoner (labelflip/backdoor): config built after the hub
    # install so the one-time binary-surrogate event reaches the stream.
    targeted_cfg = None
    if atk_kind == "targeted":
        targeted_cfg = _targeted_config(args, who)
    t0 = time.time()
    base_key = jax.random.PRNGKey(args.seed + 1 + me)
    flat = np.asarray(flat0, np.float32)
    flat_dev = jnp.asarray(flat)
    ms = ms0
    bn0_flat, bn_unravel = ravel_pytree(ms0)
    bn_elems = int(np.asarray(bn0_flat).size)
    num_batches = my_xs.shape[0]
    dropped_at = None
    # Wire plane (DESIGN.md §11): LEARN's gradient plane ships bare
    # gradients, the gossip plane [params || stats] — both through the
    # typed codec, decoded eagerly by the pre-registered waiters.
    wire_stats = WireStats(who)
    grad_ef = _maybe_error_feedback(who, wire_stats)
    grad_split = (flat.size, 0)
    gossip_split = (flat.size, bn_elems)
    grad_tf = _frame_transform(grad_split, wire_stats, plane=PLANE_GRAD)
    gossip_tf = _frame_transform(gossip_split, wire_stats,
                                 plane=PLANE_MODEL)
    # Per-node checkpoint/resume (r5): each peer persists its OWN model +
    # optimizer + BN stats under checkpoint_dir/node_{me}. Resume expects
    # the whole deployment to restart from a common step (the round-
    # indexed gossip planes give a lone restarted node no quorum for its
    # old rounds — it would exit as a dropout, the documented semantics).
    ckpt = None
    start_iter = 0
    if args.checkpoint_dir:
        import os

        from ..utils import checkpoint as ckpt_lib

        ckpt = ckpt_lib.Checkpointer(
            os.path.join(args.checkpoint_dir, f"node_{me}")
        )
        step0 = ckpt.latest_step()
        if getattr(args, "resume", False) and step0 is not None:
            restored = ckpt.restore(
                {"flat": flat,
                 "opt_state": jax.tree.map(np.asarray, opt_state),
                 **({"bn": np.asarray(bn0_flat, np.float32)}
                    if bn_elems else {})},
                step=step0,
            )
            flat = np.asarray(restored["flat"], np.float32)
            flat_dev = jnp.asarray(flat)
            opt_state = jax.tree.map(jnp.asarray, restored["opt_state"])
            if bn_elems:
                ms = bn_unravel(jnp.asarray(restored["bn"]))
            start_iter = int(step0)
            print(f"[{who}] resumed from step {start_iter}", flush=True)
    try:
        # Startup rendezvous (r5 redesign; comment corrected r6, ADVICE r5
        # #4): the hello at step 0 (published the moment the exchange
        # exists, before data/model init) is a cheap config-error barrier.
        # Safety against compile skew comes from the READY barrier below —
        # no node starts round ``start_iter`` before every peer has
        # finished its jit warmup — plus the waiter ordering: round
        # ``start_iter``'s waiters are registered BEFORE this node
        # publishes its own ready beacon, so by the time any peer can see
        # the full barrier (our beacon included) and publish its first
        # frame, our ``collect_begin`` readers are already latched and no
        # round frame can age out of the last-writer-wins register. The
        # barrier's read budget is a generous startup ceiling (env
        # GARFIELD_STARTUP_TIMEOUT_MS, default 30 min): co-located nodes
        # compile ResNet-class programs nearly serially on a small host,
        # and the timeout only bounds how long a genuinely dead peer can
        # stall startup — it costs nothing when everyone arrives. (An
        # earlier warmup-then-barrier design gated round 0 on a fixed
        # post-warmup budget; asymmetric compile/cache skew blew it
        # reproducibly.)
        startup_ms = _startup_ms(args)
        deadline = time.monotonic() + startup_ms / 1e3

        def await_beacon(r, min_step, beacon, what):
            """Poll for peer r's startup beacon, RE-PUBLISHING our own on
            every retry: a beacon published once can be dropped for any
            peer whose listener had not bound inside the sender's
            first-connect grace (tens of seconds of python/jax import),
            and a node that stops beaconing after passing its own wait
            deadlocks the peers that missed it — both observed."""
            waited = 0
            while True:
                try:
                    ex.read_latest(r, min_step, timeout_ms=10_000)
                    return
                except TimeoutError:
                    if time.monotonic() > deadline:
                        raise
                    waited += 10
                    if waited % 60 == 0:
                        tools.warning(
                            f"[{who}] still waiting for node {r}'s {what} "
                            f"({waited}s); re-beaconing"
                        )
                    ex.publish(min_step, beacon)

        for r in range(n):
            if r != me:
                await_beacon(r, 0, b"up", "hello")

        # Post-warmup READY stage: rounds must not start until EVERY node
        # has compiled — without this lockstep gate, fast nodes race
        # rounds ahead while slow peers are still compiling, and their
        # round frames age out of the last-writer-wins register before
        # the slow peers register waiters (observed: healthy 4-node
        # convnet runs dropping two nodes). The read budget is the same
        # startup ceiling: post-hello, a missing "ready" means a peer is
        # compiling (minutes on a shared host) or dead — the generous
        # wait costs nothing when everyone arrives.
        _, _, _ = worker_grad(
            flat_dev, ms, my_xs[0], my_ys[0], jax.random.fold_in(base_key, 0)
        )
        dummy = jnp.zeros((q, flat.size), jnp.float32)
        node_update(flat_dev, opt_state, dummy, jnp.asarray(0, jnp.int32))
        model_aggregate(dummy, jnp.asarray(0, jnp.int32))

        def register_round(i):
            """Pre-register BOTH phases' waiters before any local work —
            frames arriving while this node computes (or evaluates) are
            latched by the blocked readers and cannot be overwritten away
            (exchange.collect_begin docstring; its timeout clock starts at
            wait(), so registering before the ready barrier below cannot
            eat the round budget)."""
            return (
                ex.collect_begin(
                    2 * i + 2, q, timeout_ms=args.cluster_timeout_ms,
                    transform=grad_tf,
                ),
                ex.collect_begin(
                    2 * i + 3, q, timeout_ms=args.cluster_timeout_ms,
                    transform=gossip_tf,
                ),
            )

        straggle_s = max(
            0, int(getattr(args, "straggler_ms", 0) or 0)
        ) / 1e3

        def compute_grad(i):
            """One local gradient for round ``i`` — the SAME derivation
            on the sync and async paths (batch ``i % num_batches``, key
            ``fold_in(base_key, i)``), which is what makes the two
            trajectories comparable at all and bitwise-equal at ms=0.
            Cohort attackers simulate their colluders from their own
            extra batches; ``--straggler_ms`` injects the scenario
            harness's reproducible slow node before the publish."""
            nonlocal ms, mom
            with tele_trace.span("grad_compute", step=i):
                if atk_kind == "cohort":
                    rows = []
                    for j in range(atk_cohort):
                        b = (i * atk_cohort + j) % num_batches
                        gj, _, ms = worker_grad(
                            flat_dev, ms, my_xs[b], my_ys[b],
                            jax.random.fold_in(
                                base_key, i * atk_cohort + j
                            ),
                        )
                        rows.append(np.asarray(gj, np.float32))
                    rows = np.stack(rows)
                    if beta is not None:
                        mom = (1.0 - beta) * rows + beta * (
                            0.0 if mom is None else mom
                        )
                        rows = mom.astype(np.float32)
                    g = attack(rows)
                else:
                    b = i % num_batches
                    xb, yb = my_xs[b], my_ys[b]
                    if targeted_cfg is not None:
                        # Targeted poisoning (DESIGN.md §17): rewrite the
                        # node's OWN batch (label flips / trigger stamps)
                        # and publish the honest gradient of the
                        # poisoned task — suspicion-invisible.
                        from ..attacks import targeted as targeted_lib

                        xb, yb = targeted_lib.poison_batch(
                            targeted_cfg, np.asarray(xb), np.asarray(yb),
                            seed=me, step=i,
                        )
                    g, _, ms = worker_grad(
                        flat_dev, ms, xb, yb,
                        jax.random.fold_in(base_key, i),
                    )
                    g = np.asarray(g, np.float32)
                    if beta is not None:
                        mom = (1.0 - beta) * g + beta * (
                            0.0 if mom is None else mom
                        )
                        g = mom.astype(np.float32)
                    if attack is not None:
                        g = attack(g)
            if straggle_s:
                # Injected slow node (scenario knob) — its own span so the
                # trace report attributes the delay (see _run_worker).
                with tele_trace.span("straggle", step=i):
                    time.sleep(straggle_s)
            return g

        def async_rounds():
            """The bounded-staleness round loop (--async, DESIGN.md §15):
            publish-and-continue on BOTH per-plane collectors. A lost
            gradient quorum still exits as a dropout (the sync
            semantics); a lost gossip quorum keeps the local model for
            one round. Returns ``dropped_at`` (None = completed).

            CATCH-UP JUMP: unlike a PS worker (whose frame tags track
            the PS broadcast through read_latest), a decentralized node
            advances its round counter only by computing — a 10x
            straggler would fall UNBOUNDEDLY behind the swarm in tag
            space and leave every peer's admissible window permanently.
            So a node whose counter lags the swarm clock (the newest tag
            its gradient collector has seen) by more than the staleness
            cutoff JUMPS to the swarm's round, skipping the rounds
            nobody could consume: its contribution RATE stays what its
            hardware allows, but its tags stay admissible and each fresh
            frame it lands unlocks up to ``max_staleness`` rounds of
            swarm progress — which is precisely where the fw=0 async
            speedup over the synchronous wait-everyone pace comes from.
            """
            nonlocal flat, flat_dev, opt_state, ms, rounds_skipped
            # Jump once the lag exceeds HALF the admissible window (>= 1
            # so healthy in-phase pipelining — a peer can lawfully run
            # one round ahead — never triggers it): the swarm throttles
            # at exactly max_staleness behind its slowest required
            # member, so a threshold AT the cutoff would never fire for
            # the one node that needs it, and the straggler would grind
            # every fw=0 quorum to its own pace — measured 1.25x instead
            # of ~ms x. DISABLED at ms=0: the synchronous contract
            # processes every round (there is no unbounded lag to escape
            # — the exact-round quorum waits — and a jump would skip
            # checkpoint rounds and break the bitwise equality).
            jump_lag = (
                max(1, policy.max_staleness // 2)
                if policy.max_staleness > 0 else None
            )
            i = start_iter
            while i < args.num_iter:
                newest = grad_col.newest() if jump_lag is not None else None
                if newest is not None and newest - i > jump_lag:
                    jump = min(int(newest), args.num_iter - 1)
                    rounds_skipped += jump - i
                    tools.warning(
                        f"[{who}] {jump - i} rounds behind the swarm "
                        f"clock; jumping from round {i} to {jump} "
                        f"(total skipped: {rounds_skipped})"
                    )
                    i = jump
                g = compute_grad(i)
                ex.publish(
                    i,
                    _encode_frame([g], wire_stats, fanout=n - 1,
                                  plane=PLANE_GRAD, ef=grad_ef),
                    plane=PLANE_GRAD,
                )
                try:
                    with tele_trace.span("quorum", step=i, plane="grad"):
                        grads, _, w, granks = gather_rows(
                            grad_col, i, grad_split, "grad"
                        )
                except TimeoutError:
                    tools.warning(
                        f"[{who}] no admissible round-{i} gradient quorum "
                        f"within the staleness cutoff; exiting as a "
                        "dropout (reference bounded-retry semantics)"
                    )
                    return i
                # Per-plane defense (DESIGN.md §17): audit the quorum,
                # compose the suspicion weights with the staleness
                # discount, escalate the plane's ladder independently.
                w = _compose_w(w, _audit_plane(
                    grad_def, grad_tap, grads, granks, i,
                    jax.random.fold_in(gar_base_key, i), "gradient",
                ))
                with tele_trace.span("update", step=i):
                    if w is not None:
                        flat_dev, opt_state = node_update_weighted(
                            flat_dev, opt_state, grads, w,
                            jnp.asarray(i, jnp.int32),
                        )
                    else:
                        flat_dev, opt_state = node_update(
                            flat_dev, opt_state, grads,
                            jnp.asarray(i, jnp.int32),
                        )
                    flat = np.asarray(flat_dev, np.float32)
                if grad_def is not None:
                    _plane_escalate(grad_def, i, _rebuild_grad)
                pub = poisoner.publish_frame(
                    flat,
                    (np.asarray(ravel_pytree(ms)[0], np.float32)
                     if bn_elems else None),
                    i,
                )
                with tele_trace.span("gossip", step=i):
                    ex.publish(
                        i,
                        _encode_frame([pub], wire_stats, fanout=n - 1,
                                      plane=PLANE_MODEL),
                        plane=PLANE_MODEL,
                    )
                    try:
                        models_p, models_bn, wm, mranks = gather_rows(
                            model_col, i, gossip_split, "model"
                        )
                    except TimeoutError:
                        tools.warning(
                            f"[{who}] no admissible round-{i} gossip "
                            "quorum; keeping the locally updated model "
                            "this round"
                        )
                        models_p = None
                    if models_p is not None:
                        if poisoner.kind is not None:
                            poisoner.note_gather(
                                np.asarray(models_p)[:len(mranks)],
                                mranks, i,
                            )
                        wm = _compose_w(wm, _audit_plane(
                            gossip_def, gossip_tap, models_p, mranks, i,
                            jax.random.fold_in(
                                jax.random.fold_in(gar_base_key, i), 1
                            ), "gossip",
                        ))
                        if wm is not None:
                            flat_dev = model_aggregate_weighted(
                                models_p, wm, jnp.asarray(i, jnp.int32),
                            )
                        else:
                            flat_dev = model_aggregate(
                                models_p, jnp.asarray(i, jnp.int32),
                            )
                        flat = np.asarray(flat_dev, np.float32)
                        if bn_elems:
                            ms = bn_unravel(jnp.asarray(
                                _robust_stats(models_bn, f)
                            ))
                        if gossip_def is not None:
                            _plane_escalate(gossip_def, i, _rebuild_gossip)
                wire_stats.flush(i)
                if (ckpt and args.checkpoint_freq
                        and (i + 1) % args.checkpoint_freq == 0):
                    with tele_trace.span("checkpoint", step=i):
                        ckpt.save(i + 1, {
                            "flat": flat,
                            "opt_state": jax.tree.map(
                                np.asarray, opt_state),
                            **({"bn": np.asarray(
                                ravel_pytree(ms)[0], np.float32)}
                               if bn_elems else {}),
                        })
                if args.acc_freq and i % args.acc_freq == 0:
                    with tele_trace.span("eval", step=i):
                        acc = parallel.compute_accuracy(
                            (unravel(flat_dev), ms),
                            lambda s, x: eval_fn(s[0], s[1], x),
                            eval_set, binary=args.dataset == "pima",
                        )
                    print(
                        f"Step: {i} Accuracy: {acc:.4f} "
                        f"Time: {time.time() - t0:.1f}",
                        flush=True,
                    )
                i += 1
            return None

        # First round's waiters BEFORE our ready beacon (see the startup
        # comment above): a peer can only start publishing rounds after it
        # has seen this beacon, at which point our readers already latch.
        # The async collectors are PERSISTENT multi-round watchers on
        # their own planes — registered here for the same reason, and
        # never re-registered again.
        grad_col = model_col = None
        grad_wait = model_wait = None
        rounds_skipped = 0
        if policy is not None:
            grad_col = ex.round_collector(
                range(n), transform=grad_tf, plane=PLANE_GRAD
            )
            model_col = ex.round_collector(
                range(n), transform=gossip_tf, plane=PLANE_MODEL
            )
        else:
            grad_wait, model_wait = register_round(start_iter)
        ex.publish(1, b"ready")
        deadline = time.monotonic() + startup_ms / 1e3  # re-arm for stage 2
        for r in range(n):
            if r != me:
                await_beacon(r, 1, b"ready", "ready beacon")
        if policy is not None:
            try:
                dropped_at = async_rounds()
            finally:
                grad_col.close()
                model_col.close()
        # Synchronous round loop (the async path returned its rounds
        # above; an empty iterable keeps the shared summary tail below).
        sync_iters = (
            range(start_iter, args.num_iter) if policy is None else ()
        )
        for i in sync_iters:
            # --- gradient plane (phase 2i+2) -----------------------------
            g = compute_grad(i)
            ex.publish(
                2 * i + 2,
                _encode_frame([g], wire_stats, fanout=n - 1,
                              plane=PLANE_GRAD, ef=grad_ef),
            )
            try:
                with tele_trace.span("quorum", step=i, plane="grad"):
                    grads, _, granks = harvest(grad_wait, grad_split)
            except TimeoutError:
                # Dropped out of the quorum flow: the reference's pull
                # loops retry a bounded number of times then exit
                # gracefully (server.py:138-141, ps.py:84-88); survivors'
                # wait-n-f treats this node as crashed from here on. The
                # round's model-plane registration is never harvested —
                # cancel it so its waiter threads retire now, not at
                # close() (the waiter-lifecycle contract).
                dropped_at = i
                _cancel_wait(model_wait)
                tools.warning(
                    f"[{who}] lost the round-{i} gradient quorum; exiting "
                    "as a dropout (reference bounded-retry semantics)"
                )
                break
            # Per-plane defense (DESIGN.md §17): audit the quorum, weight
            # its rows by suspicion, escalate the gradient ladder — all
            # independent of the gossip plane's history below.
            gw = _audit_plane(
                grad_def, grad_tap, grads, granks, i,
                jax.random.fold_in(gar_base_key, i), "gradient",
            )
            gw = _compose_w(None, gw)
            with tele_trace.span("update", step=i):
                if gw is not None:
                    flat_dev, opt_state = node_update_weighted(
                        flat_dev, opt_state, grads, gw,
                        jnp.asarray(i, jnp.int32),
                    )
                else:
                    flat_dev, opt_state = node_update(
                        flat_dev, opt_state, grads,
                        jnp.asarray(i, jnp.int32),
                    )
                flat = np.asarray(flat_dev, np.float32)
            if grad_def is not None:
                _plane_escalate(grad_def, i, _rebuild_grad)
            # --- model gossip plane (phase 2i+3) -------------------------
            # Gossip frames are [params || stats] (r5, VERDICT r4 #4): the
            # model GAR aggregates the params, the stats segment goes
            # through the same f-trimmed robust mean as SSMW — the on-mesh
            # twin syncs BN state with core.mean_model_state every step
            # (parallel/learn.py), so local-BN drift here would diverge
            # the deployment shapes on BN architectures.
            pub = poisoner.publish_frame(
                flat,
                (np.asarray(ravel_pytree(ms)[0], np.float32)
                 if bn_elems else None),
                i,
            )
            with tele_trace.span("gossip", step=i):
                ex.publish(
                    2 * i + 3,
                    _encode_frame([pub], wire_stats, fanout=n - 1,
                                  plane=PLANE_MODEL),
                )
                try:
                    models_p, models_bn, mranks = harvest(
                        model_wait, gossip_split
                    )
                except TimeoutError:
                    tools.warning(
                        f"[{who}] lost the round-{i} model-gossip quorum; "
                        "keeping the locally updated model this round"
                    )
                    models_p = None
                if models_p is not None:
                    if poisoner.kind is not None:
                        poisoner.note_gather(
                            np.asarray(models_p)[:len(mranks)], mranks, i
                        )
                    mw = _compose_w(None, _audit_plane(
                        gossip_def, gossip_tap, models_p, mranks, i,
                        jax.random.fold_in(
                            jax.random.fold_in(gar_base_key, i), 1
                        ), "gossip",
                    ))
                    if mw is not None:
                        flat_dev = model_aggregate_weighted(
                            models_p, mw, jnp.asarray(i, jnp.int32),
                        )
                    else:
                        flat_dev = model_aggregate(
                            models_p, jnp.asarray(i, jnp.int32),
                        )
                    flat = np.asarray(flat_dev, np.float32)
                    if bn_elems:
                        ms = bn_unravel(jnp.asarray(
                            _robust_stats(models_bn, f)
                        ))
                    if gossip_def is not None:
                        _plane_escalate(gossip_def, i, _rebuild_gossip)
            wire_stats.flush(i)
            if (ckpt and args.checkpoint_freq
                    and (i + 1) % args.checkpoint_freq == 0):
                with tele_trace.span("checkpoint", step=i):
                    ckpt.save(i + 1, {
                        "flat": flat,
                        "opt_state": jax.tree.map(np.asarray, opt_state),
                        **({"bn": np.asarray(
                            ravel_pytree(ms)[0], np.float32)}
                           if bn_elems else {}),
                    })
            # Register the NEXT round's waiters before the (potentially
            # slow — first-eval compile) accuracy pass: with no waiters
            # pending, the q fastest peers can run a whole round ahead and
            # age this node's next quorum out of the register (observed
            # dropping the slowest evaluator at round 1 on the 1-core box).
            if i + 1 < args.num_iter:
                next_waits = register_round(i + 1)
            if args.acc_freq and i % args.acc_freq == 0:
                with tele_trace.span("eval", step=i):
                    acc = parallel.compute_accuracy(
                        (unravel(flat_dev), ms),
                        lambda s, x: eval_fn(s[0], s[1], x),
                        eval_set, binary=args.dataset == "pima",
                    )
                print(
                    f"Step: {i} Accuracy: {acc:.4f} "
                    f"Time: {time.time() - t0:.1f}",
                    flush=True,
                )
            if i + 1 < args.num_iter:
                grad_wait, model_wait = next_waits
        acc = parallel.compute_accuracy(
            (unravel(flat_dev), ms), lambda s, x: eval_fn(s[0], s[1], x),
            eval_set, binary=args.dataset == "pima",
        )
        if ckpt is not None:
            ckpt.close()
        summary = {
            "final_accuracy": acc,
            "steps": dropped_at if dropped_at is not None else args.num_iter,
            "dropped_at": dropped_at,
            # Async catch-up jumps (a straggler contributes at its own
            # rate but tracks the swarm clock): rounds it never computed.
            **({"skipped": rounds_skipped} if policy is not None else {}),
            **({"model_attack_adapt": poisoner.stats()}
               if poisoner.stats() else {}),
            "wall_s": time.time() - t0,
        }
        _telemetry_close(tele_hub, tele_exp)
        print(json.dumps({"tag": who, **summary}), flush=True)
        return summary
    finally:
        ex.close()


def _run_worker(args, windex, ps_ranks, my_xs, my_ys, grad_fn, ms0, flat0,
                unravel, ex, timeout_ms):
    """One worker process: model(s) in, shard gradient out. ``windex`` is
    the worker's data shard; its exchange rank is n_ps + windex.

    SSMW (one PS): the model read is ``read_latest`` (newest round >= the
    expected one), NOT an exact-step collect — a straggler whose expected
    model was already overwritten in the last-writer-wins slot must catch
    up to the PS's current round, not crash (turning a tolerated straggler
    into a permanent casualty would silently consume the f budget).

    MSMW (ByzSGD, n_ps > 1): collect ALL PS models for the exact step and
    GAR-aggregate them with tolerance fps before computing the gradient —
    the worker-side half of the gather step (tensorflow_impl ByzSGD
    trainer.py:55-75: pull models -> aggregate -> compute -> commit). The
    gradient goes to EVERY PS. Round skipping is not available here (an
    exact-step quorum over several independent publishers has no single
    newest round to jump to); the PSes' re-publish-on-timeout covers the
    cold-start skew instead.
    """
    atk_kind, attack, atk_cohort = _host_attack(
        args.attack, args.attack_params, args.fw
    )
    # Adaptive attacker (attacks/adaptive.py, DESIGN.md §16): this process
    # is a REAL suspicion-aware Byzantine worker — bisection magnitude fed
    # by its own published-frame fate (the broadcast model delta, or a
    # leaked PS audit stream via attack_params {"feedback_taps": path}),
    # deterministic cohort rotation over the f_pool colluders, and
    # full-magnitude bursts when the model-broadcast cadence blows out (a
    # quorum-degradation window: straggler / soft timeout / partition).
    controller = None
    adaptive_base = None
    feedback_taps = None
    pending_probe = None  # (round, excess u, mu estimate, magnitude)
    last_model = None  # (round, flat np model) for the delta probe
    if atk_kind == "adaptive":
        from ..attacks import adaptive as adaptive_lib

        if args.fw < 1:
            raise SystemExit(
                f"--attack {args.attack!r} needs --fw >= 1 (the declared "
                "active-cohort size)"
            )
        cfg_all = multihost.ClusterConfig(args.cluster)
        acfg = adaptive_lib.configure(
            args.attack, args.attack_params,
            num_workers=len(cfg_all.workers), f=args.fw,
        )
        controller = adaptive_lib.HostController(
            acfg, windex,
            burst_factor=float(args.attack_params.get("burst_factor", 3.0)),
            burst_rounds=int(args.attack_params.get("burst_rounds", 3)),
        )
        adaptive_base = acfg.base
        feedback_taps = args.attack_params.get("feedback_taps")

    def _note_model(step, flat_params):
        """Adaptive feedback hook, called at every model arrival: close
        the pending probe (delta probe against the previous round's
        model, or the leaked audit stream when configured) and feed the
        broadcast cadence to the burst trigger."""
        nonlocal pending_probe, last_model
        if controller is None:
            return
        from ..attacks import adaptive as adaptive_lib

        controller.observe_round(time.time())
        flat_np = np.asarray(flat_params, np.float32)
        if pending_probe is not None:
            pr_round, u, mu, mag = pending_probe
            detected = score = None
            if feedback_taps:
                got = adaptive_lib.read_selected(feedback_taps, windex)
                if got is not None and got[0] >= pr_round:
                    detected, score = got[1] <= 0.0, got[1]
            if (detected is None and last_model is not None
                    and last_model[0] == pr_round
                    and step == pr_round + 1):
                detected, score = adaptive_lib.delta_probe(
                    last_model[1], flat_np, u, mu_est=mu,
                )
            if detected is not None:
                controller.feedback(detected)
                tele_hooks.emit_event(
                    "attack_adapt", step=int(pr_round),
                    magnitude=round(float(mag), 6),
                    detected=bool(detected),
                    lo=round(controller.lo, 6), hi=round(controller.hi, 6),
                    score=None if score is None else round(float(score), 6),
                )
            pending_probe = None
        last_model = (int(step), flat_np)

    # Worker momentum (Karimireddy et al. 2021; same EMA + zeros init as the
    # on-mesh trainers, core.worker_mom_update): this process publishes its
    # EMA instead of the raw gradient. A Byzantine worker poisons whatever
    # it publishes (attack applied after), and a straggler that skips steps
    # via read_latest only folds in gradients it actually computed — the
    # real deployment semantics.
    beta = getattr(args, "worker_momentum", None)
    mom = None
    # The worker EMA is training state too (ADVICE r3): without it a resume
    # re-warms the momenta from zero over ~1/(1-beta) steps, weakening the
    # variance-reduction premise of the cclip+momentum defense while an
    # attacker keeps full strength. Persist it next to the PS checkpoint
    # (shared checkpoint_dir, one small npz per worker) and restore on
    # --resume.
    mom_path = None
    if beta is not None and args.checkpoint_dir:
        import os

        os.makedirs(args.checkpoint_dir, exist_ok=True)
        mom_path = os.path.join(
            args.checkpoint_dir, f"worker_{windex}_mom.npz"
        )
    if beta is not None and getattr(args, "resume", False):
        if mom_path is not None and __import__("os").path.exists(mom_path):
            with np.load(mom_path) as z:
                mom = z["mom"].astype(np.float32)
                saved_step = int(z["step"])
            print(
                f"[cluster-worker-{windex}] restored momentum EMA from "
                f"step {saved_step}",
                flush=True,
            )
        else:
            tools.warning(
                f"worker {windex}: no saved momentum EMA found — it "
                f"restarts from zero and re-warms over "
                f"~{1.0 / (1.0 - beta):.0f} steps after this resume"
            )

    @jax.jit
    def worker_grad(flat_params, ms, x, y, rng):
        grads, (loss, new_ms) = grad_fn(unravel(flat_params), ms, x, y, rng)
        return ravel_pytree(grads)[0], loss, new_ms

    base_key = jax.random.PRNGKey(args.seed + 1 + windex)
    flat_np = np.asarray(flat0, np.float32)
    # SSMW BN-stat exchange (see _run_ps docstring): model frames arrive as
    # [params || mean batch_stats] and gradient frames ship
    # [grad || this worker's updated batch_stats]; d_bn = 0 models keep the
    # plain layout.
    bn0_flat, bn_unravel = ravel_pytree(ms0)
    bn_elems = int(np.asarray(bn0_flat).size)
    who = f"cluster-worker-{windex}"
    # Events-only telemetry for workers (no GAR runs here, so no taps):
    # exchange waits, wire accounting and — with --trace — the
    # model_wait/grad_compute/publish spans land in this role's own
    # <who>.telemetry.jsonl, which is what lets telemetry.report
    # reconstruct the cross-process round timeline (a PS-only stream
    # cannot attribute a slow quorum to the worker that caused it).
    tele_hub, tele_exp = _telemetry_open(args, who)
    # Targeted poisoner (labelflip/backdoor, DESIGN.md §17): config built
    # after the hub install so the one-time binary-surrogate fallback
    # event reaches the stream.
    targeted_cfg = None
    if atk_kind == "targeted":
        targeted_cfg = _targeted_config(args, who)
    wire_stats = WireStats(who)
    grad_ef = _maybe_error_feedback(who, wire_stats)
    split = (flat_np.size, bn_elems)
    # pass_empty: the PS's stop sentinel is an empty frame, not a codec
    # frame — it must reach the loop's sentinel check undecoded.
    model_tf = _frame_transform(split, wire_stats, pass_empty=True,
                                plane=PLANE_MODEL)
    num_batches = my_xs.shape[0]
    multi_ps = len(ps_ranks) > 1
    if multi_ps:
        fps = getattr(args, "fps", 0)
        model_gar_name = getattr(args, "model_gar", None) or args.gar
        plane = _ModelPlane(ps_ranks, model_gar_name, fps, who)

    ms = ms0
    loss = None
    steps_done = 0
    refreshes = 0
    i = 0
    # Bounded-staleness async mode (--async, DESIGN.md §14): the worker
    # side is publish-and-continue — it never barriers on its gradient
    # entering a quorum, and while the next model broadcast is pending it
    # REFRESHES its published frame (same round tag — staleness is set by
    # the model round used — fresh batch/key), so the PS's stale-frame
    # reuse sees this rank's newest data instead of its oldest.
    policy = rounds.resolve(args)
    async_mode = policy is not None and not multi_ps
    straggle_s = max(0, int(getattr(args, "straggler_ms", 0) or 0)) / 1e3
    refresh_ms = min(timeout_ms, 2_000)
    prev = None  # (step, flat_params) of the newest model seen
    refresh_r = 0

    def compute_and_publish(step, flat_params, r=0):
        """One gradient compute + publish for model round ``step``.

        ``r > 0`` marks an async REFRESH: the batch index and RNG fold in
        the refresh counter so the republished frame carries NEW data
        (the register is last-writer-wins — it replaces this rank's older
        frame at the same tag). ``r == 0`` derivations are EXACTLY the
        synchronous ones, so non-refresh trajectories are untouched (the
        --max_staleness 0 bitwise contract). ``--straggler_ms`` injects
        the scenario harness's reproducible slow-rank delay just before
        the publish."""
        nonlocal ms, mom, loss, pending_probe
        attacking = atk_kind == "cohort" or (
            atk_kind == "adaptive" and controller.is_active(step)
        )
        with tele_trace.span("grad_compute", step=int(step), refresh=int(r)):
            if attacking:
                # Colluding attacker (byzWorker.py:114-125): compute the
                # cohort's honest gradients locally on DISTINCT batches
                # of the attacker's own shard, publish the collusion
                # statistic. In a --worker_momentum deployment the
                # honest workers publish EMA momenta, so the attacker
                # simulates its cohort's MOMENTA and hides inside their
                # (shrunken) variance — the on-mesh semantics and the
                # strongest form of the attack the cclip defense is
                # built for.
                rows = []
                for j in range(atk_cohort):
                    o = step * atk_cohort + j
                    key = jax.random.fold_in(base_key, o)
                    if r:
                        key = jax.random.fold_in(key, 1_000_003 + r)
                    gj, loss_, ms_new = worker_grad(
                        flat_params, ms, my_xs[(o + r) % num_batches],
                        my_ys[(o + r) % num_batches], key,
                    )
                    loss, ms = loss_, ms_new
                    rows.append(np.asarray(gj, np.float32))
                rows = np.stack(rows)
                if beta is not None:
                    mom = (1.0 - beta) * rows + beta * (
                        0.0 if mom is None else mom
                    )
                    rows = mom.astype(np.float32)
                if atk_kind == "adaptive":
                    # Publish the base attack's collusion statistic at the
                    # controller's CURRENT magnitude (burst-aware), and
                    # arm the probe: the next model delta tells this rank
                    # whether the fake entered the selection.
                    mag = controller.magnitude()
                    mu = rows.mean(axis=0)
                    if adaptive_base == "empire":
                        g = (-mag * mu).astype(np.float32)
                    else:
                        sigma = rows.std(axis=0, ddof=1)
                        g = (mu + mag * sigma).astype(np.float32)
                    pending_probe = (int(step), g - mu, mu, mag)
                else:
                    g = attack(rows)
            else:
                key = jax.random.fold_in(base_key, step)
                if r:
                    key = jax.random.fold_in(key, 1_000_003 + r)
                b = (step + r) % num_batches
                xb, yb = my_xs[b], my_ys[b]
                if targeted_cfg is not None:
                    # Targeted poisoning (DESIGN.md §17): rewrite this
                    # worker's OWN batch and publish the honest gradient
                    # of the poisoned task — nothing divergence-shaped
                    # for the PS's suspicion plane to see.
                    from ..attacks import targeted as targeted_lib

                    xb, yb = targeted_lib.poison_batch(
                        targeted_cfg, np.asarray(xb), np.asarray(yb),
                        seed=windex, step=step,
                    )
                g, loss_, ms_new = worker_grad(
                    flat_params, ms, xb, yb, key,
                )
                loss, ms = loss_, ms_new
                g = np.asarray(g, np.float32)
                if beta is not None:
                    mom = (1.0 - beta) * g + beta * (
                        0.0 if mom is None else mom
                    )
                    g = mom.astype(np.float32)
                if attack is not None:
                    g = attack(g)
            out_parts = [g]
            if bn_elems:
                # Both deployment shapes ship [grad || stats] (MSMW BN
                # plane, r5); the PS robust-aggregates the stats segment.
                out_parts.append(
                    np.asarray(ravel_pytree(ms)[0], np.float32)
                )
        if straggle_s:
            # Injected slow rank (scenario knob) — its own span so the
            # report attributes the delay instead of hiding it in the
            # compute phase.
            with tele_trace.span("straggle", step=int(step)):
                time.sleep(straggle_s)
        targets = plane.all_ranks if multi_ps else ps_ranks
        ex.publish(
            step,
            _encode_frame(out_parts, wire_stats, fanout=len(targets),
                          plane=PLANE_GRAD, ef=grad_ef),
            to=targets,
        )

    # Overlap (DESIGN.md §11): the model read is registered BEFORE the
    # local gradient compute each round, so the next model frame is
    # latched + decoded + device-staged by the watcher thread while this
    # worker is still inside its own device step.
    model_wait = None
    if not multi_ps:
        model_wait = ex.read_latest_begin(0, 0, transform=model_tf)
    while i < args.num_iter:
        if multi_ps:
            step = i
            try:
                with tele_trace.span("model_gather", step=i):
                    models_p, models_bn = _collect_models(
                        ex, i, plane, timeout_ms, split,
                        stats=wire_stats, wait_fn=model_wait,
                    )
            except _Lapped as lap:
                # MSMW catch-up: a worker outside the PSes' q-fastest
                # quorum is lapped — jump to the plane's newest round
                # (the MSMW twin of the SSMW read_latest jump).
                model_wait = None
                if lap.newest >= args.num_iter:
                    break
                tools.warning(
                    f"[{who}] lapped at round {i}; "
                    f"jumping to the PSes' round {lap.newest}"
                )
                i = lap.newest
                continue
            model_wait = None  # consumed
            if i + 1 < args.num_iter:
                model_wait = ex.collect_begin(
                    i + 1, len(plane.ranks), timeout_ms=timeout_ms,
                    peers=plane.ranks, transform=model_tf,
                )
            flat_params = plane.aggregate(models_p)
            _note_model(i, flat_params)
            if bn_elems:
                # Adopt the robust-aggregated PS statistics (fps budget),
                # the MSMW twin of the SSMW mean-stats adoption.
                ms = bn_unravel(jnp.asarray(
                    _robust_stats(models_bn, plane.fps)
                ))
        else:
            if async_mode and prev is not None:
                # Publish-and-continue (DESIGN.md §14): poll for the next
                # broadcast in short chunks; while none arrives, refresh
                # the published frame from the stale model on a new batch
                # — the PS's bounded-staleness reuse then aggregates this
                # rank's NEWEST data, and a straggling PS cannot idle the
                # worker. The full timeout budget still bounds the wait.
                waited = 0.0
                while True:
                    try:
                        step, payload = model_wait(timeout_ms=refresh_ms)
                        break
                    except TimeoutError:
                        waited += refresh_ms
                        if waited >= timeout_ms:
                            raise
                        if policy.max_staleness > 0:
                            refresh_r += 1
                            refreshes += 1
                            compute_and_publish(
                                prev[0], prev[1], r=refresh_r
                            )
                            wire_stats.flush(prev[0])
                        # The timed-out harvest retired its watcher;
                        # re-register before the next poll.
                        model_wait = ex.read_latest_begin(
                            0, prev[0] + 1, transform=model_tf
                        )
            else:
                step, payload = model_wait(timeout_ms=timeout_ms)
            if step >= args.num_iter or payload == b"":
                break  # PS's stop sentinel (empty frame at num_iter)
            if isinstance(payload, Exception):
                # NOT the sentinel: the trusted PS's model frame failing
                # the wire codec means the PS runs a different model/dtype
                # config — a deployment error that must fail loudly, not
                # exit rc 0.
                raise SystemExit(
                    f"model frame failed the wire codec ({payload}); PS "
                    "and worker configs disagree (--model/--dtype/"
                    "--dataset)"
                )
            # Next round's read registered before the compute; the
            # watcher keeps latching newer rounds, so the straggler
            # catch-up semantics survive the pre-registration.
            model_wait = ex.read_latest_begin(
                0, step + 1, transform=model_tf
            )
            flat_params, bn_seg = payload
            _note_model(step, flat_params)
            if bn_elems:
                # Adopt the PS's mean BatchNorm statistics — the cluster
                # twin of the on-mesh core.mean_model_state sync.
                ms = bn_unravel(jnp.asarray(bn_seg))
            prev = (step, flat_params)
            refresh_r = 0
        compute_and_publish(step, flat_params)
        wire_stats.flush(step)
        if (mom_path is not None and mom is not None
                and args.checkpoint_freq
                and (step + 1) % args.checkpoint_freq == 0):
            # Atomic replace: a crash mid-save must not leave a torn npz.
            import os

            np.savez(mom_path + ".tmp.npz", mom=mom, step=step + 1)
            os.replace(mom_path + ".tmp.npz", mom_path)
        steps_done += 1
        if args.log:
            print(
                f"Worker {windex} loss {step}: {float(loss):.6f}", flush=True
            )
        i = step + 1
    # Waiter lifecycle: the loop's last registration (the round past the
    # final one, or the sentinel path's re-read) is never harvested —
    # retire it now instead of at close() (tests/test_exchange.py).
    _cancel_wait(model_wait)
    summary = {
        "steps": steps_done,
        **({"refreshes": refreshes} if async_mode else {}),
        **({"attack_adapt": controller.stats()} if controller else {}),
        "final_loss": float(loss) if loss is not None else None,
    }
    _telemetry_close(tele_hub, tele_exp)
    print(json.dumps({"tag": f"cluster-worker-{windex}", **summary}),
          flush=True)
    return summary
