"""Host-side metrics aggregation: ring buffer, suspicion scores, events.

``MetricsHub`` is the single host-side sink of the telemetry plane: the
training loops feed it per-step taps (``record_step``), the cluster
driver and ``utils.exchange`` feed it liveness / wait-n-f events through
the process-global hook (``install`` + ``emit_event`` — a no-op when no
hub is installed, so instrumented code paths cost nothing un-telemetered).

The derived audit signal is the per-rank **suspicion score**: the
cumulative exclusion frequency under the active GAR,

    suspicion[i] = sum_steps (observed[i] - selected[i]) /
                   sum_steps  observed[i]

i.e. "of the quorums that contained rank i, what fraction of influence
did the rule refuse it". Byzantine ranks that a robust rule keeps
rejecting converge to suspicion ~1 while honest ranks stay near 0 — the
audit that makes Byzantine ranks visible without ground truth (asserted
end-to-end in tests/test_telemetry.py under the lie attack).
"""

import collections
import threading
import time

import numpy as np

from .exporters import make_record

__all__ = ["MetricsHub", "install", "uninstall", "current", "emit_event",
           "emit_span"]

# Span-duration histogram buckets (seconds) for the Prometheus
# ``garfield_phase_seconds`` exposition — log-spaced from wire-decode
# scale (0.1 ms) to a straggler-dominated quorum wait (10 s).
PHASE_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
)


class MetricsHub:
    """Ring-buffered aggregation of taps, timings and liveness events.

    Thread-safe: the cluster driver's exchange threads emit events
    concurrently with the training loop's ``record_step``.
    """

    def __init__(self, num_ranks=None, capacity=2048, meta=None, sink=None,
                 suspicion_halflife=None):
        self.num_ranks = num_ranks
        self.meta = dict(meta or {})
        # Windowed suspicion (schema v7, DESIGN.md §16): the cumulative
        # exclusion frequency never decays, so a ROTATED Byzantine cohort
        # launders it for free — each member attacks briefly, then sits
        # honest while its denominator grows. With ``suspicion_halflife``
        # (in observed steps) the hub additionally keeps exponentially
        # decayed observed/excluded twins: suspicion_decayed() weights
        # the recent window, so a rank that attacked 50 rounds ago and a
        # rank attacking NOW stop looking identical. None keeps only the
        # cumulative score (v1 behavior).
        self._halflife = (
            float(suspicion_halflife) if suspicion_halflife else None
        )
        if self._halflife is not None and self._halflife <= 0.0:
            raise ValueError(
                f"suspicion_halflife must be > 0, got {suspicion_halflife}"
            )
        self._susp_decay = (
            0.5 ** (1.0 / self._halflife) if self._halflife else 1.0
        )
        self._observed_d = None
        self._excluded_d = None
        # Closed-loop defense accounting (schema v7): per-round
        # suspicion-weight digests + escalation state, folded from the
        # PS's ``defense_weights``/``defense_escalate`` events and the
        # attacker-side ``attack_adapt`` stream.
        self._defense = {
            "rounds": 0, "w_sum": 0.0, "w_min": None,
            "escalations": 0, "deescalations": 0, "level": None,
            "rule": None,
        }
        self._attack_adapt = {"events": 0, "last_mag": None}
        # Data-plane defense accounting (schema v9, DESIGN.md §18):
        # folded from ``data_defense`` events — per-rank spectral outlier
        # scores (the garfield_dataplane_outlier_score gauge), flag and
        # weight extremes for the summary digest.
        self._dataplane = {
            "rounds": 0, "flagged": 0, "max_score": None, "min_w": None,
            "scores": {},
        }
        # Federated round accounting (schema v10, DESIGN.md §19): folded
        # from the round engine's ``fed_round``/``cohort`` events.
        # Client suspicion is keyed by the STABLE GLOBAL client id, not
        # the per-round cohort index: under partial participation a
        # cohort index means a different client every round, so indexing
        # suspicion by it hands every resampled Byzantine client a fresh
        # ledger — the sampling-scale twin of the rotation laundering
        # the halflife window closes (pinned by the rotating-attacker
        # regression in tests/test_federated.py). The map is sparse
        # (only sampled-and-audited clients appear) with lazily applied
        # decay per cohort event, so a million-client population costs
        # only its audited cohorts.
        self._clients = {}  # cid -> [obs_d, exc_d, last_cohort_event]
        self._cohort_events = 0
        self._fed = {
            "rounds": 0, "shards": None, "last_cohort": None,
            "budget_exceeded": 0, "round_s_sum": 0.0, "f_budget": None,
        }
        # Targeted-attack eval accounting (schema v8, DESIGN.md §17):
        # folded from ``targeted_eval`` events — the per-class digest the
        # divergence-blind suspicion plane cannot produce.
        self._targeted = {
            "events": 0, "last_confusion": None, "last_asr": None,
        }
        # Optional streaming sink (a JsonlExporter): every record is
        # written as it is recorded — crash-safe for the cluster roles,
        # whose exchange threads emit events the training loop never sees.
        self._sink = sink
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=capacity)
        self._steps = 0
        self._events = 0
        self._last_loss = None
        self._last_tau = 0.0
        self._last_clip_frac = 0.0
        self._step_times = []
        self._observed = None
        self._excluded = None
        self._selected_hist = collections.deque(maxlen=120)
        # Wire-plane accounting (DESIGN.md §11): folded from the cluster
        # roles' per-step "wire" events and the exchange's publisher-side
        # "send_queue_drop" events, exposed by both exporters. Schema v6
        # adds the per-PLANE byte breakdown (wire events' ``planes``
        # sub-object) behind the plane-labelled Prometheus counters.
        self._wire = {
            "bytes_out": 0, "bytes_in": 0, "frames_in": 0,
            "encode_s": 0.0, "decode_s": 0.0, "send_queue_drops": 0,
        }
        self._wire_planes = {}  # plane -> {"bytes_out": n, "bytes_in": n}
        # Schema v11 (round 18, the compressed wire): per-SCHEME byte
        # breakdown (wire events' ``schemes`` sub-object) behind the
        # garfield_wire_bytes_total{scheme=} Prometheus counters.
        self._wire_schemes = {}  # scheme -> {"bytes_out": n, "bytes_in": n}
        # Schema v15 (round 22, batched wire ingest — DESIGN.md §24):
        # folded from ``ingest_batch`` events — bulk push_frames calls,
        # frames/rejects/seconds split by whether the vectorized decode
        # path ran (garfield_ingest_batch_seconds{batched=}).
        self._ingest_batch = {
            "calls": 0, "frames": 0, "rejected": 0,
            "batched_s": 0.0, "fallback_s": 0.0,
        }
        # Elastic-membership accounting (schema v6, DESIGN.md §15):
        # folded from the PS autoscaler's "autoscale" events — running
        # active-worker count (the garfield_active_workers gauge) and
        # spawn/retire totals for the run summary.
        self._autoscale = {"spawns": 0, "retires": 0, "active": None}
        # Bounded-staleness accounting (schema v4, DESIGN.md §14): the
        # async PS emits one "staleness" event per round with the
        # quorum's per-rank staleness + discount weights; folded into a
        # rounds histogram (garfield_staleness_rounds) and — alongside
        # the exclusion taps — into the per-rank suspicion score (a rank
        # whose influence the discount keeps refusing is suspect the
        # same way a rank the rule keeps excluding is).
        self._staleness = {
            "count": 0, "sum": 0, "max": 0,
            "hist": collections.Counter(),
        }
        # Span accounting (schema v5, trace.py): per-phase duration
        # digests for the exporters (Prometheus histogram, summary
        # ``phases``) and a small per-round phase breakdown for the
        # demo's /status panel. The raw spans stream to the sink like
        # every other record; the hub keeps only bounded aggregates.
        self._spans = 0
        self._phase = {}            # phase -> {count,sum,buckets,durs}
        self._round_phases = collections.OrderedDict()  # step -> {phase: s}

    # --- feeding -----------------------------------------------------------

    def _ensure_ranks(self, n):
        if self._observed is None:
            self.num_ranks = n
            self._observed = np.zeros(n, np.float64)
            self._excluded = np.zeros(n, np.float64)
            self._observed_d = np.zeros(n, np.float64)
            self._excluded_d = np.zeros(n, np.float64)

    def _fold_exclusion(self, obs_inc, exc_inc):
        """One exclusion observation into BOTH suspicion accumulators:
        the cumulative arrays, and — with ``suspicion_halflife`` — the
        exponentially decayed window twins (every feeder: taps, async
        staleness deficits, hierarchical per-client audits)."""
        self._observed += obs_inc
        self._excluded += exc_inc
        if self._halflife is not None:
            self._observed_d *= self._susp_decay
            self._excluded_d *= self._susp_decay
            self._observed_d += obs_inc
            self._excluded_d += exc_inc

    def record_step(self, step, *, loss=None, tap=None, step_time_s=None,
                    extra=None):
        """Fold one training step into the hub; returns the JSONL record."""
        tap_host = None
        if tap is not None:
            tap_host = {
                "observed": np.asarray(tap["observed"], np.float64),
                "selected": np.asarray(tap["selected"], np.float64),
                "score": np.asarray(tap["score"], np.float64),
                "tau": float(np.asarray(tap["tau"])),
                "clip_frac": float(np.asarray(tap["clip_frac"])),
            }
        with self._lock:
            self._steps += 1
            if loss is not None:
                self._last_loss = float(loss)
            if step_time_s is not None:
                self._step_times.append(float(step_time_s))
            if tap_host is not None:
                obs, sel = tap_host["observed"], tap_host["selected"]
                self._ensure_ranks(obs.size)
                # A rank's per-step exclusion is the influence the rule
                # refused it, bounded by how much of it was observed at
                # all (multi-observer bundles report fractions of both).
                self._fold_exclusion(
                    obs, np.maximum(obs - np.minimum(sel, obs), 0.0)
                )
                self._last_tau = tap_host["tau"]
                self._last_clip_frac = tap_host["clip_frac"]
                self._selected_hist.append(
                    (int(step), np.round(sel, 5).tolist())
                )
            rec = make_record(
                "step",
                step=int(step),
                loss=None if loss is None else float(loss),
                step_time_s=(
                    None if step_time_s is None else float(step_time_s)
                ),
                tap=None if tap_host is None else {
                    "observed": np.round(tap_host["observed"], 6).tolist(),
                    "selected": np.round(tap_host["selected"], 6).tolist(),
                    "score": np.round(tap_host["score"], 6).tolist(),
                    "tau": tap_host["tau"],
                    "clip_frac": tap_host["clip_frac"],
                },
                **(extra or {}),
            )
            self._ring.append(rec)
            self._drain(rec)
            return rec

    def record_event(self, kind, **fields):
        """Fold one liveness/exchange event (e.g. ``exchange_wait``,
        ``quorum_exclusion``, ``plane_drop``); returns the record."""
        rec = make_record("event", event=str(kind), t=time.time(), **fields)
        with self._lock:
            self._events += 1
            if kind == "wire":
                for key in ("bytes_out", "bytes_in", "frames_in"):
                    self._wire[key] += int(fields.get(key, 0) or 0)
                for key in ("encode_s", "decode_s"):
                    self._wire[key] += float(fields.get(key, 0.0) or 0.0)
                for p, d in (fields.get("planes") or {}).items():
                    acc = self._wire_planes.setdefault(
                        str(p), {"bytes_out": 0, "bytes_in": 0}
                    )
                    acc["bytes_out"] += int(d.get("bytes_out", 0) or 0)
                    acc["bytes_in"] += int(d.get("bytes_in", 0) or 0)
                for s, d in (fields.get("schemes") or {}).items():
                    acc = self._wire_schemes.setdefault(
                        str(s), {"bytes_out": 0, "bytes_in": 0}
                    )
                    acc["bytes_out"] += int(d.get("bytes_out", 0) or 0)
                    acc["bytes_in"] += int(d.get("bytes_in", 0) or 0)
            elif kind == "send_queue_drop":
                self._wire["send_queue_drops"] += 1
            elif kind == "ingest_batch":
                ib = self._ingest_batch
                ib["calls"] += 1
                ib["frames"] += int(fields.get("frames", 0) or 0)
                ib["rejected"] += int(fields.get("rejected", 0) or 0)
                key = "batched_s" if fields.get("batched") else "fallback_s"
                ib[key] += float(fields.get("dur_s", 0.0) or 0.0)
            elif kind == "autoscale":
                a = self._autoscale
                if fields.get("action") == "spawn":
                    a["spawns"] += 1
                elif fields.get("action") == "retire":
                    a["retires"] += 1
                if fields.get("active") is not None:
                    a["active"] = int(fields["active"])
            elif kind == "staleness":
                # Per-round async-quorum audit (apps/cluster.py): fold
                # the discount deficit (1 - w) into the same exclusion-
                # frequency suspicion the taps feed — each quorum rank
                # was observed once and had (1 - w) of its influence
                # refused by the staleness discount.
                ranks = np.asarray(fields.get("ranks", ()), np.int64)
                taus = np.asarray(fields.get("staleness", ()), np.int64)
                ws = np.asarray(fields.get("weights", ()), np.float64)
                if ranks.size and taus.size == ranks.size:
                    st = self._staleness
                    st["count"] += int(ranks.size)
                    st["sum"] += int(taus.sum())
                    st["max"] = max(st["max"], int(taus.max()))
                    for t in taus.tolist():
                        st["hist"][int(t)] += 1
                    if self.num_ranks and ranks.max() < self.num_ranks:
                        self._ensure_ranks(self.num_ranks)
                        if ws.size == ranks.size:
                            obs_inc = np.zeros_like(self._observed)
                            exc_inc = np.zeros_like(self._excluded)
                            np.add.at(obs_inc, ranks, 1.0)
                            np.add.at(
                                exc_inc, ranks,
                                np.clip(1.0 - ws, 0.0, 1.0),
                            )
                            self._fold_exclusion(obs_inc, exc_inc)
            elif kind == "defense_weights":
                # Closed-loop defense (schema v7): one per-round
                # suspicion-weight vector over the quorum — digested to
                # rounds/min/mean for the summary (the raw event streams
                # to the sink like everything else).
                ws = np.asarray(fields.get("weights", ()), np.float64)
                if ws.size:
                    d = self._defense
                    d["rounds"] += 1
                    d["w_sum"] += float(ws.mean())
                    wmin = float(ws.min())
                    d["w_min"] = (
                        wmin if d["w_min"] is None
                        else min(d["w_min"], wmin)
                    )
            elif kind == "defense_escalate":
                d = self._defense
                if fields.get("direction") == "deescalate":
                    d["deescalations"] += 1
                else:
                    d["escalations"] += 1
                if fields.get("level") is not None:
                    d["level"] = int(fields["level"])
                if fields.get("rule") is not None:
                    d["rule"] = str(fields["rule"])
            elif kind == "data_defense":
                # v9: one round of the data-plane detectors (aggregators/
                # dataplane.py) — digest extremes + the last per-rank
                # scores for the Prometheus gauge; raw events stream to
                # the sink like everything else.
                d = self._dataplane
                d["rounds"] += 1
                sc = list(fields.get("scores") or ())
                fl = list(fields.get("flags") or ())
                ws = list(fields.get("weights") or ())
                d["flagged"] += int(sum(1 for x in fl if x))
                if sc:
                    m = float(max(sc))
                    d["max_score"] = (
                        m if d["max_score"] is None
                        else max(d["max_score"], m)
                    )
                    ranks = fields.get("ranks")
                    if ranks is None:
                        ranks = range(len(sc))
                    for r, s in zip(ranks, sc):
                        d["scores"][int(r)] = float(s)
                if ws:
                    wmin = float(min(ws))
                    d["min_w"] = (
                        wmin if d["min_w"] is None
                        else min(d["min_w"], wmin)
                    )
            elif kind in ("attack_adapt", "ps_attack_adapt"):
                # v8: the model-plane twin folds into the same digest —
                # one adaptive adversary per run is the deployed shape,
                # and the raw plane-tagged events stream to the sink.
                a = self._attack_adapt
                a["events"] += 1
                if fields.get("magnitude") is not None:
                    a["last_mag"] = float(fields["magnitude"])
            elif kind == "targeted_eval":
                t = self._targeted
                t["events"] += 1
                if fields.get("confusion") is not None:
                    t["last_confusion"] = float(fields["confusion"])
                if fields.get("asr") is not None:
                    t["last_asr"] = float(fields["asr"])
            elif kind == "fed_round":
                # v10: one federated round (federated/engine.py) —
                # digest counters for the summary + Prometheus.
                fd = self._fed
                fd["rounds"] += 1
                if fields.get("shards") is not None:
                    fd["shards"] = int(fields["shards"])
                if fields.get("cohort") is not None:
                    fd["last_cohort"] = int(fields["cohort"])
                if fields.get("f_budget") is not None:
                    fd["f_budget"] = int(fields["f_budget"])
                if fields.get("budget_exceeded"):
                    fd["budget_exceeded"] += 1
                if fields.get("round_s") is not None:
                    fd["round_s_sum"] += float(fields["round_s"])
            elif kind == "cohort":
                # v10: one audited cohort — per-CLIENT observed/selected
                # keyed by stable global ids (see __init__'s comment on
                # why NOT cohort index). Lazy decay: a client's twins
                # decay by decay**(events since it was last sampled)
                # before the new observation folds in, so untouched
                # entries cost nothing per event.
                ids = fields.get("client_ids") or ()
                sel = fields.get("selected")
                if ids:
                    self._cohort_events += 1
                    now = self._cohort_events
                    if sel is None or len(sel) != len(ids):
                        sel = [1.0] * len(ids)
                    for cid, s in zip(ids, sel):
                        ent = self._clients.get(int(cid))
                        if ent is None:
                            ent = self._clients[int(cid)] = [0.0, 0.0, now]
                        elif self._halflife is not None:
                            k = now - ent[2]
                            if k:
                                dk = self._susp_decay ** k
                                ent[0] *= dk
                                ent[1] *= dk
                            ent[2] = now
                        else:
                            ent[2] = now
                        ent[0] += 1.0
                        ent[1] += max(0.0, 1.0 - float(s))
            elif kind == "hier_exclusion":
                # The hierarchical reducer's per-client audit (aggregators/
                # hierarchy.py): observed/selected weight vectors over the
                # n CLIENTS, folded into the same exclusion-frequency
                # suspicion the in-graph taps feed — bucket-level
                # exclusions (and whole excluded bucket summaries) surface
                # per client without ground truth.
                obs = np.asarray(fields.get("observed", ()), np.float64)
                sel = np.asarray(fields.get("selected", ()), np.float64)
                if obs.size and sel.size == obs.size:
                    self._ensure_ranks(obs.size)
                    if obs.size == self._observed.size:
                        self._fold_exclusion(
                            obs, np.maximum(obs - np.minimum(sel, obs), 0.0)
                        )
            self._ring.append(rec)
            self._drain(rec)
            return rec

    def record_span(self, phase, *, t_wall, dur_s, **tags):
        """Fold one trace span (schema v5, trace.py) into the hub: the
        record streams to the sink, the duration lands in the per-phase
        digest (Prometheus ``garfield_phase_seconds``), and — when the
        span carries a ``step`` tag — in the per-round phase breakdown
        behind ``last_round_phases`` (the demo's /status panel)."""
        phase = str(phase)
        dur = float(dur_s)
        rec = make_record(
            "span", phase=phase, t_wall=round(float(t_wall), 6),
            dur_s=round(dur, 9), **tags,
        )
        with self._lock:
            self._spans += 1
            ph = self._phase.get(phase)
            if ph is None:
                ph = self._phase[phase] = {
                    "count": 0, "sum": 0.0,
                    "buckets": collections.Counter(),
                    "durs": collections.deque(maxlen=2048),
                }
            ph["count"] += 1
            ph["sum"] += dur
            ph["durs"].append(dur)
            for le in PHASE_BUCKETS:
                if dur <= le:
                    ph["buckets"][le] += 1
                    break
            step = tags.get("step")
            if isinstance(step, int) and not isinstance(step, bool):
                rp = self._round_phases.setdefault(step, {})
                rp[phase] = rp.get(phase, 0.0) + dur
                while len(self._round_phases) > 32:
                    self._round_phases.popitem(last=False)
            self._ring.append(rec)
            self._drain(rec)
            return rec

    def _drain(self, rec):
        if self._sink is not None:
            try:
                self._sink.write(rec)
            except Exception:
                pass  # a full disk must not take down the data path

    # --- reading -----------------------------------------------------------

    def suspicion(self):
        """Per-rank cumulative exclusion frequency, or None before any tap."""
        with self._lock:
            if self._observed is None:
                return None
            return self._excluded / np.maximum(self._observed, 1e-9)

    def suspicion_decayed(self):
        """Per-rank exclusion frequency over the exponentially decayed
        window (``suspicion_halflife``), falling back to the cumulative
        score when no halflife was configured — what the closed-loop
        defense and the report tool's straggler cross-check consume: a
        rotation attack cannot launder THIS score by sitting honest
        while its cumulative denominator grows. None before any tap."""
        with self._lock:
            if self._observed is None:
                return None
            if self._halflife is None:
                return self._excluded / np.maximum(self._observed, 1e-9)
            return self._excluded_d / np.maximum(self._observed_d, 1e-9)

    def client_suspicion_decayed(self, k=None):
        """Per-CLIENT decayed exclusion frequency over the sampled
        cohorts, keyed by stable GLOBAL client id ({cid: score}), or
        None before any cohort event. Entries not sampled recently are
        decayed to 'now' on read (numerator and denominator by the same
        factor — the RATIO is sampling-gap-invariant, so a Byzantine
        client cannot shrink its score by being resampled later; what
        the halflife does change is how fast old exclusions stop
        counting, same law as ``suspicion_decayed``). ``k`` returns only
        the top-k by score."""
        with self._lock:
            if not self._clients:
                return None
            out = {
                cid: (exc / max(obs, 1e-9))
                for cid, (obs, exc, _) in self._clients.items()
            }
        if k is not None:
            top = sorted(out.items(), key=lambda kv: -kv[1])[:int(k)]
            return dict(top)
        return out

    def client_suspicion_snapshot(self):
        """The raw per-client suspicion accumulators
        ({cid: (obs, exc)}), decayed to 'now' — what a shard failover
        checkpoints so a handoff carries suspicion FORWARD
        (controlplane/failover.py, DESIGN.md §22): an adaptive attacker
        who times a crash must not get its exclusion history reset by
        the standby's fresh hub. Empty dict before any cohort event."""
        with self._lock:
            now = self._cohort_events
            out = {}
            for cid, (obs, exc, last) in self._clients.items():
                if self._halflife is not None and now > last:
                    dk = self._susp_decay ** (now - last)
                    obs, exc = obs * dk, exc * dk
                out[int(cid)] = (float(obs), float(exc))
            return out

    def absorb_client_suspicion(self, snapshot):
        """Fold a checkpointed ``client_suspicion_snapshot`` into this
        hub — the restore half of the failover handoff. Merge is
        element-wise MAX against any live accumulator: absorbing a
        snapshot can only ever RAISE a client's recorded history, so a
        replayed (older) snapshot cannot launder suspicion accumulated
        since it was taken."""
        with self._lock:
            now = self._cohort_events
            for cid, (obs, exc) in dict(snapshot).items():
                ent = self._clients.get(int(cid))
                if ent is None:
                    self._clients[int(cid)] = [
                        float(obs), float(exc), now
                    ]
                else:
                    if self._halflife is not None and now > ent[2]:
                        dk = self._susp_decay ** (now - ent[2])
                        ent[0] *= dk
                        ent[1] *= dk
                        ent[2] = now
                    ent[0] = max(ent[0], float(obs))
                    ent[1] = max(ent[1], float(exc))

    def federated_stats(self):
        """Federated-round digest (schema v10), or None when no
        ``fed_round`` event was folded (non-federated runs)."""
        with self._lock:
            fd = self._fed
            if not fd["rounds"]:
                return None
            return {
                "rounds": int(fd["rounds"]),
                "shards": fd["shards"],
                "last_cohort": fd["last_cohort"],
                "f_budget": fd["f_budget"],
                "budget_exceeded": int(fd["budget_exceeded"]),
                "mean_round_s": round(
                    fd["round_s_sum"] / fd["rounds"], 6
                ),
            }

    def defense_stats(self):
        """Suspicion-weight digest + escalation state of the closed-loop
        defense (schema v7), or None when no defense event was folded."""
        with self._lock:
            d = self._defense
            if (not d["rounds"] and not d["escalations"]
                    and not d["deescalations"] and d["level"] is None):
                return None
            return {
                "rounds": int(d["rounds"]),
                "mean_w": (
                    None if not d["rounds"]
                    else round(d["w_sum"] / d["rounds"], 6)
                ),
                "min_w": (
                    None if d["w_min"] is None else round(d["w_min"], 6)
                ),
                "escalations": int(d["escalations"]),
                "deescalations": int(d["deescalations"]),
                "level": d["level"],
                "rule": d["rule"],
            }

    def data_defense_stats(self):
        """Data-plane defense digest (schema v9), or None when no
        ``data_defense`` event was folded. ``scores`` is the last
        per-rank outlier-score map (the Prometheus gauge's samples);
        the summary digest drops it (rounds/flagged/max_score/min_w)."""
        with self._lock:
            d = self._dataplane
            if not d["rounds"]:
                return None
            return {
                "rounds": int(d["rounds"]),
                "flagged": int(d["flagged"]),
                "max_score": (
                    None if d["max_score"] is None
                    else round(d["max_score"], 6)
                ),
                "min_w": (
                    None if d["min_w"] is None else round(d["min_w"], 6)
                ),
                "scores": dict(d["scores"]),
            }

    def targeted_stats(self):
        """Targeted-eval digest (schema v8), or None when no
        ``targeted_eval`` event was folded (untargeted runs)."""
        with self._lock:
            t = self._targeted
            if not t["events"]:
                return None
            return {
                "events": int(t["events"]),
                "last_confusion": (
                    None if t["last_confusion"] is None
                    else round(t["last_confusion"], 6)
                ),
                "last_asr": (
                    None if t["last_asr"] is None
                    else round(t["last_asr"], 6)
                ),
            }

    def attack_adapt_stats(self):
        """Adaptive-attacker digest (schema v7), or None when no
        ``attack_adapt`` event was folded (oblivious-attack runs)."""
        with self._lock:
            a = self._attack_adapt
            if not a["events"]:
                return None
            return {
                "events": int(a["events"]),
                "last_magnitude": (
                    None if a["last_mag"] is None
                    else round(a["last_mag"], 6)
                ),
            }

    def selection_history(self, k=60):
        """Last k (step, selected-list) pairs — the demo's history panel."""
        with self._lock:
            return list(self._selected_hist)[-k:]

    def records(self):
        with self._lock:
            return list(self._ring)

    def counters(self):
        with self._lock:
            return {
                "steps": self._steps,
                "events": self._events,
                "spans": self._spans,
                "loss": self._last_loss,
                "tau": self._last_tau,
                "clip_frac": self._last_clip_frac,
            }

    def wire_counters(self):
        """Cumulative wire-plane totals (bytes/codec-seconds/drops)."""
        with self._lock:
            return dict(self._wire)

    def wire_plane_counters(self):
        """Per-plane wire byte totals ({plane: {bytes_out, bytes_in}}),
        or {} when no plane-tagged wire event was folded (schema v6)."""
        with self._lock:
            return {p: dict(d) for p, d in sorted(
                self._wire_planes.items()
            )}

    def wire_scheme_counters(self):
        """Per-scheme wire byte totals ({scheme: {bytes_out, bytes_in}}),
        or {} when no scheme-tagged wire event was folded (schema v11,
        the round-18 compressed wire)."""
        with self._lock:
            return {s: dict(d) for s, d in sorted(
                self._wire_schemes.items()
            )}

    def ingest_batch_stats(self):
        """Bulk-ingest digest (schema v15), or None when no
        ``ingest_batch`` event was folded (per-frame-only runs)."""
        with self._lock:
            ib = self._ingest_batch
            if not ib["calls"]:
                return None
            return {
                "calls": int(ib["calls"]),
                "frames": int(ib["frames"]),
                "rejected": int(ib["rejected"]),
                "batched_s": float(ib["batched_s"]),
                "fallback_s": float(ib["fallback_s"]),
            }

    def autoscale_stats(self):
        """spawns/retires/active_workers over the run, or None when no
        autoscale event was folded (fixed-membership runs)."""
        with self._lock:
            a = self._autoscale
            if not a["spawns"] and not a["retires"] and a["active"] is None:
                return None
            return {
                "spawns": int(a["spawns"]),
                "retires": int(a["retires"]),
                "active_workers": int(a["active"] or 0),
            }

    def active_workers(self):
        """Current active-worker count (last autoscale event), or None."""
        with self._lock:
            return self._autoscale["active"]

    def staleness_stats(self):
        """count/mean/max + rounds histogram over every quorum member of
        every async round, or None when no staleness event was folded
        (synchronous runs). The histogram keys are staleness-in-rounds —
        the ``garfield_staleness_rounds`` exposition."""
        with self._lock:
            st = self._staleness
            if not st["count"]:
                return None
            return {
                "count": int(st["count"]),
                "mean": float(st["sum"] / st["count"]),
                "max": int(st["max"]),
                "hist": {int(k): int(v) for k, v in sorted(
                    st["hist"].items()
                )},
            }

    def phase_stats(self):
        """Per-phase duration percentiles over the recorded spans
        ({phase: {count, mean_s, p50_s, p95_s, p99_s}}), or None before
        any span — the per-phase twin of ``step_time_stats``."""
        with self._lock:
            if not self._phase:
                return None
            out = {}
            for phase in sorted(self._phase):
                ph = self._phase[phase]
                a = np.asarray(ph["durs"])
                out[phase] = {
                    "count": int(ph["count"]),
                    "mean_s": float(ph["sum"] / ph["count"]),
                    "p50_s": float(np.percentile(a, 50)),
                    "p95_s": float(np.percentile(a, 95)),
                    "p99_s": float(np.percentile(a, 99)),
                }
            return out

    def phase_histograms(self):
        """Per-phase {buckets: {le: count}, sum, count} — raw (non-
        cumulative) bucket counts over PHASE_BUCKETS; the Prometheus
        exporter renders the cumulative form."""
        with self._lock:
            return {
                phase: {
                    "buckets": dict(ph["buckets"]),
                    "sum": float(ph["sum"]),
                    "count": int(ph["count"]),
                }
                for phase, ph in sorted(self._phase.items())
            }

    def last_round_phases(self):
        """(step, {phase: seconds}) for the last COMPLETED round — the
        second-newest step seen in span tags (the newest may still be
        mid-round) — or None before two rounds of spans. The demo's
        /status phase-breakdown panel."""
        with self._lock:
            if not self._round_phases:
                return None
            steps = list(self._round_phases)
            step = steps[-2] if len(steps) >= 2 else steps[-1]
            return step, {
                k: round(v, 6)
                for k, v in sorted(self._round_phases[step].items())
            }

    def step_time_stats(self):
        """count/mean/min/max plus p50/p95/p99 over the recorded step
        times (the chunking win — fewer, fatter dispatches — shows up in
        the tail percentiles, not the mean)."""
        with self._lock:
            if not self._step_times:
                return None
            a = np.asarray(self._step_times)
            return {
                "count": int(a.size),
                "mean_s": float(a.mean()),
                "min_s": float(a.min()),
                "max_s": float(a.max()),
                "p50_s": float(np.percentile(a, 50)),
                "p95_s": float(np.percentile(a, 95)),
                "p99_s": float(np.percentile(a, 99)),
            }

    def summary(self):
        """The run-closing JSONL record: suspicion, counters, timings."""
        susp = self.suspicion()
        susp_d = (
            self.suspicion_decayed() if self._halflife is not None else None
        )
        defense = self.defense_stats()
        adapt = self.attack_adapt_stats()
        targeted = self.targeted_stats()
        data_defense = self.data_defense_stats()
        if data_defense is not None:
            # The per-rank score map serves the Prometheus gauge only;
            # the summary digest keeps the bounded extremes.
            data_defense = {
                k: v for k, v in data_defense.items() if k != "scores"
            }
        stale = self.staleness_stats()
        autos = self.autoscale_stats()
        fed = self.federated_stats()
        if fed is not None:
            # v10: top sampled-client suspects ride the digest (the full
            # sparse map serves the Prometheus gauge only — a summary
            # must stay bounded at million-client populations).
            top = self.client_suspicion_decayed(k=8) or {}
            fed = {
                **fed,
                "top_clients": {
                    str(cid): round(s, 6) for cid, s in top.items()
                },
            }
        wire_planes = self.wire_plane_counters()
        wire_schemes = self.wire_scheme_counters()
        phases = self.phase_stats()
        if phases is not None:
            phases = {
                k: {kk: round(vv, 6) for kk, vv in v.items()}
                for k, v in phases.items()
            }
        with self._lock:
            return make_record(
                "summary",
                steps=self._steps,
                events=self._events,
                # schema v5: per-phase span digest (None when no spans
                # were recorded — tracing-off runs are unchanged).
                spans=self._spans,
                phases=phases,
                loss=self._last_loss,
                num_ranks=self.num_ranks,
                suspicion=(
                    None if susp is None else np.round(susp, 6).tolist()
                ),
                # schema v7: the windowed score (None without a
                # configured suspicion_halflife — v6 consumers see
                # nothing new).
                suspicion_decayed=(
                    None if susp_d is None
                    else np.round(susp_d, 6).tolist()
                ),
                suspicion_halflife=self._halflife,
                # schema v7: closed-loop defense + adaptive-attacker
                # digests (None on runs without those events).
                defense=defense,
                attack_adapt=adapt,
                # schema v8: targeted-eval digest (None on untargeted
                # runs — v7 consumers see nothing new).
                targeted=targeted,
                # schema v9: data-plane defense digest (None on runs
                # without the data detectors).
                data_defense=data_defense,
                observed=(
                    None if self._observed is None
                    else np.round(self._observed, 3).tolist()
                ),
                excluded=(
                    None if self._excluded is None
                    else np.round(self._excluded, 3).tolist()
                ),
                step_time=(
                    None if not self._step_times else {
                        "count": len(self._step_times),
                        "mean_s": float(np.mean(self._step_times)),
                        # schema v2: tail percentiles from the ring of
                        # recorded step times (see step_time_stats).
                        "p50_s": float(
                            np.percentile(self._step_times, 50)
                        ),
                        "p95_s": float(
                            np.percentile(self._step_times, 95)
                        ),
                        "p99_s": float(
                            np.percentile(self._step_times, 99)
                        ),
                    }
                ),
                wire=(
                    None if not any(self._wire.values())
                    else {k: (round(v, 6) if isinstance(v, float) else v)
                          for k, v in self._wire.items()}
                ),
                # schema v6: per-plane wire byte breakdown (None when no
                # plane-tagged wire event was folded).
                wire_planes=wire_planes or None,
                # schema v11: per-scheme wire byte breakdown (None when
                # no scheme-tagged wire event was folded — pre-round-18
                # streams and compression-off runs).
                wire_schemes=wire_schemes or None,
                # schema v4: the async plane's staleness digest (None on
                # synchronous runs — v3 consumers are unaffected).
                staleness=stale,
                # schema v6: elastic-membership digest (None on
                # fixed-membership runs).
                autoscale=autos,
                # schema v10: federated-round digest + top sampled-client
                # suspects (None on non-federated runs).
                federated=fed,
                meta=self.meta,
            )


# --- process-global hook ----------------------------------------------------
#
# The exchange layer and the cluster driver sit far from the training loop
# that owns the hub; they report through this module-level slot instead of
# threading a handle through every call. ``emit_event`` is a cheap no-op
# when nothing is installed, so the instrumented paths stay free in
# un-telemetered runs.

_GLOBAL = None


def install(hub):
    """Make ``hub`` the process-global event sink (returns the previous)."""
    global _GLOBAL
    prev, _GLOBAL = _GLOBAL, hub
    return prev


def uninstall():
    global _GLOBAL
    _GLOBAL = None


def current():
    return _GLOBAL


def emit_event(kind, **fields):
    hub = _GLOBAL
    if hub is not None:
        try:
            hub.record_event(kind, **fields)
        except Exception:
            pass  # telemetry must never take down the data path


def emit_span(phase, *, t_wall, dur_s, **tags):
    """Span twin of ``emit_event`` (trace.py's emission path): a no-op
    when no hub is installed, and never raises into the traced phase."""
    hub = _GLOBAL
    if hub is not None:
        try:
            hub.record_span(phase, t_wall=t_wall, dur_s=dur_s, **tags)
        except Exception:
            pass  # tracing must never take down the data path
