"""Telemetry exporters: schema-versioned JSONL, Prometheus text, validation.

One record schema serves every producer (training loops, the cluster
driver, the scenario harnesses under ``apps/benchmarks``) so consumers —
dashboards, ``telemetry.report``, the tier-1 schema check — parse one
format:

    {"schema": "garfield-telemetry", "v": 1, "kind": <kind>, ...}

Kinds: ``run`` (header: config/meta), ``step`` (per-step tap + loss +
timing), ``event`` (liveness / exchange waits / wire accounting),
``summary`` (run-closing suspicion + counters + wire totals), ``span``
(one timed phase of a round), and one kind per scenario harness that
stays: ``defense_bench``, ``fed_bench``, ``soak_bench``.
``validate_record`` / ``validate_jsonl`` are stdlib-only and run in the
tier-1 suite, so a malformed record fails loudly instead of going dark.
"""

import json
import numbers

__all__ = [
    "SCHEMA",
    "SCHEMA_VERSION",
    "JsonlExporter",
    "make_record",
    "prometheus_text",
    "validate_record",
    "validate_jsonl",
]

SCHEMA = "garfield-telemetry"
# v2 (round 9): summary.step_time gained p50_s/p95_s/p99_s tail
# percentiles (the chunked-dispatch win lives in the tail, not the mean).
# v4 (round 11, the bounded-staleness async plane — DESIGN.md §14): the
# per-round ``staleness`` EVENT (per-rank staleness + discount weights,
# validated below) and the ``summary.staleness`` digest
# (count/mean/max/hist). v5 (round 12, distributed round
# tracing — telemetry/trace.py): the ``span`` kind (one timed phase of
# a round: ``phase``, wall-clock start ``t_wall``, monotonic ``dur_s``,
# optional ``step``/``who``/``tid`` tags — the raw material of
# ``telemetry.report``'s causal timeline) and ``summary`` gained the
# optional ``spans`` count + per-phase ``phases`` digest. v6 (round 13,
# elastic asynchrony — DESIGN.md §15): exchange events are PLANE-TAGGED
# (``exchange_wait``/``staleness`` may carry ``plane``; per-step
# ``wire`` events may carry a per-plane byte breakdown under
# ``planes``) and the new ``autoscale`` EVENT (action/rank/active/rate/
# target — validated below) with its ``summary.autoscale`` digest
# (spawns/retires/active_workers) and the ``garfield_active_workers``
# Prometheus gauge. v7 (round 14, adaptive adversaries and the
# closed-loop defense — DESIGN.md §16): the ``attack_adapt`` EVENT (one
# adaptive-controller observation: magnitude played, detected verdict,
# bracket), the ``defense_weights`` EVENT (the PS's per-round
# suspicion-weight vector), the ``defense_escalate`` EVENT (one rule-
# ladder transition), the ``attack_fallback`` EVENT (a randomized/
# rotated attack keeping the where-path, emitted once), ``summary``
# gained ``suspicion_decayed``/``suspicion_halflife`` (the windowed
# score a rotated cohort cannot launder) plus the ``defense``/
# ``attack_adapt`` digests, and the new ``defense_bench`` kind
# (defense_bench's accuracy-cell rows). Older records still validate —
# consumers key on field presence, not version. v8 (round 15, the full
# threat-model matrix — DESIGN.md §17): the ``ps_attack_adapt`` EVENT
# (one MODEL-plane adaptive-controller observation — a Byzantine PS
# bisecting against the replica gather, or a LEARN node against the
# gossip; same fields as ``attack_adapt`` plus an optional ``plane``
# tag), the ``targeted_eval`` EVENT (the per-class eval digest:
# per-class accuracy, source→target confusion, backdoor
# attack-success-rate — what makes a suspicion-blind targeted attack
# measurable), ``summary`` gained the optional ``targeted`` digest
# (events/last_confusion/last_asr), ``defense_weights`` events and
# ``defense_escalate`` events may carry a ``plane`` tag (gradient/
# model/gossip — the per-plane ladder deployment), and
# ``defense_bench`` rows may carry ``plane``/``confusion``/``asr``/
# ``clean_confusion`` (the plane column and the targeted rows' success
# metric). v9 (round 16, the data-plane defense — DESIGN.md §18): the
# ``data_defense`` EVENT (one round of the fingerprint detectors:
# per-rank spectral outlier ``scores``, the tau-sigma/2-means
# ``flags``, the composed ``weights``, optional ``ranks``/``plane``
# attribution — validated below), ``summary`` gained the optional
# ``data_defense`` digest (rounds/flagged/max_score/min_w) and the
# ``garfield_dataplane_outlier_score`` Prometheus gauge,
# ``targeted_eval`` events and ``defense_bench`` rows may carry
# ``asr_baseline`` (the clean-model trigger-rate floor — ASR cells
# report attributable lift, not raw rate), and ``defense_bench``
# ``defense`` strings may name the composed modes (``data``/
# ``escalate+data``).
# v10 (round 17, the federated round engine — DESIGN.md §19): the
# ``fed_round`` EVENT (one sharded federated round: shard count, active
# cohort size, the cohort's priced ``f_budget``, the simulation-side
# ``realized_byz``/``budget_exceeded`` audit, round wall and a
# ``per_shard`` digest of per-shard fold latencies and wire bytes), the
# ``cohort`` EVENT (the audited cohort's stable GLOBAL ``client_ids``
# with their composed ``selected`` weights — what the hub's
# client-id-keyed decayed suspicion folds, the score resampling cannot
# launder), ``summary`` gained the optional ``federated`` digest
# (rounds/shards/last_cohort/f_budget/budget_exceeded/mean_round_s +
# ``top_clients``), the ``garfield_fed_*`` /
# ``garfield_client_suspicion_decayed`` Prometheus series, and the new
# ``fed_bench`` kind (fed_bench's rows: the 1/S shard-scaling cells,
# the S=1 bitwise anchor, the autoscaled fleet-rate cells).
# v11 (round 18, the compressed wire — DESIGN.md §20): the ``wire``
# EVENT gained the per-SCHEME byte breakdown (``schemes`` sub-object:
# f32/bf16/int8/int4/topk, each {bytes_out, bytes_in}) plus the
# optional ``compression_ratio`` (send-side f32-equivalent bytes /
# actual bytes this step) and ``ef_residual_norm`` (the gradient-plane
# error-feedback accumulator's L2 norm) fields — all validated below —
# ``summary`` gained the optional ``wire_schemes`` digest, and the
# ``garfield_wire_bytes_total{scheme=}`` Prometheus counters landed
# beside the direction-only totals.
# v12 (round 19, kernel-grade robust selection — DESIGN.md §21):
# ``fed_bench`` rows may carry a ``phases`` sub-object (phase name ->
# numeric stat object, here the per-phase ingest/h2d/fold p50/p95 from
# the trace plane — a scaling row attributes WHERE its round time went,
# not just how much), validated below.
# v13 (round 20, the control plane — DESIGN.md §22): the ``membership``
# EVENT (one membership change: the new ``epoch`` — or null on a
# pre-epoch deployment — the ``action`` that caused it
# (failover/split/merge), the affected ``shard`` when there is one, the
# resulting ``num_shards``, and the round as ``step``), and the new
# ``soak_bench`` kind (soak_bench's rows: one sustained-load scenario
# each — steady / rolling_restart / partition / churn — with round
# counts, p50/p95/p99 round latency from the trace plane, the
# failover/partition/epoch accounting, and the measured
# ``kill_cost_rounds`` for the mid-round-kill SLO).
# v15 (round 22, batched wire ingest — DESIGN.md §24): the new
# ``ingest_batch`` EVENT (one bulk ``push_frames`` call on a shard
# server: the ``shard``, how many ``frames`` arrived, how many were
# ``rejected`` with ban attribution, the accepted ``bytes``, whether
# the vectorized ``batched`` decode path ran or the call fell back to
# per-frame decode, the wall ``dur_s``, and the round as ``step``),
# the ``garfield_ingest_batch_seconds`` Prometheus series beside the
# wire codec counters, and the ``fed_bench`` check="ingest_micro" row
# family (batch-vs-per-frame decode isolation — extra numeric columns
# like ``per_frame_s``/``batch_s``/``batch`` and a ``scheme`` string
# ride the kind's open extra-field policy; the required
# check/n/d/shards/gar envelope still applies).
# v16 (PR 30): the kinds whose only reader was their own validator went
# with the programs that wrote them; a line of one of them is refused as
# any unknown kind is. No field of a kind that stays changed.
SCHEMA_VERSION = 16

KINDS = ("run", "step", "event", "summary", "span",
         "defense_bench", "fed_bench", "soak_bench")


def make_record(kind, **fields):
    """Stamp ``fields`` with the schema envelope."""
    if kind not in KINDS:
        raise ValueError(f"unknown telemetry record kind {kind!r}")
    return {"schema": SCHEMA, "v": SCHEMA_VERSION, "kind": kind, **fields}


class JsonlExporter:
    """Line-buffered JSONL writer (one record per line, flushed — a
    crashed run keeps every record written before the crash)."""

    def __init__(self, path, append=False):
        self.path = str(path)
        self._fp = open(self.path, "a" if append else "w")

    def write(self, record):
        validate_record(record)
        self._fp.write(json.dumps(record) + "\n")
        self._fp.flush()
        return record

    def close(self):
        if self._fp is not None:
            self._fp.close()
            self._fp = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# --- validation (stdlib only) ----------------------------------------------


def _fail(msg):
    raise ValueError(f"telemetry schema violation: {msg}")


def _is_num(v):
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


def _check_float_list(rec_kind, name, val, length=None):
    if not isinstance(val, list) or not all(_is_num(x) for x in val):
        _fail(f"{rec_kind}.{name} must be a list of numbers, got {val!r}")
    if length is not None and len(val) != length:
        _fail(
            f"{rec_kind}.{name} has {len(val)} entries, expected {length}"
        )


def validate_record(rec):
    """Raise ValueError unless ``rec`` is a well-formed telemetry record."""
    if not isinstance(rec, dict):
        _fail(f"record must be an object, got {type(rec).__name__}")
    if rec.get("schema") != SCHEMA:
        _fail(f"schema must be {SCHEMA!r}, got {rec.get('schema')!r}")
    v = rec.get("v")
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        _fail(f"v must be a positive int, got {v!r}")
    kind = rec.get("kind")
    if kind not in KINDS:
        _fail(f"kind must be one of {KINDS}, got {kind!r}")
    if kind == "step":
        step = rec.get("step")
        if not isinstance(step, int) or isinstance(step, bool) or step < 0:
            _fail(f"step.step must be a non-negative int, got {step!r}")
        for key in ("loss", "step_time_s"):
            val = rec.get(key)
            if val is not None and not _is_num(val):
                _fail(f"step.{key} must be a number or null, got {val!r}")
        tap = rec.get("tap")
        if tap is not None:
            if not isinstance(tap, dict):
                _fail(f"step.tap must be an object, got {tap!r}")
            obs = tap.get("observed")
            _check_float_list("tap", "observed", obs)
            for key in ("selected", "score"):
                _check_float_list("tap", key, tap.get(key), len(obs))
            for key in ("tau", "clip_frac"):
                if not _is_num(tap.get(key)):
                    _fail(f"tap.{key} must be a number, got {tap.get(key)!r}")
    elif kind == "event":
        if not isinstance(rec.get("event"), str):
            _fail(f"event.event must be a string, got {rec.get('event')!r}")
        if rec.get("event") == "staleness":
            # v4: the async quorum audit — parallel per-rank lists.
            ranks = rec.get("ranks")
            _check_float_list("staleness", "ranks", ranks)
            for key in ("staleness", "weights"):
                _check_float_list(
                    "staleness", key, rec.get(key), len(ranks)
                )
            step = rec.get("step")
            if not isinstance(step, int) or isinstance(step, bool) or step < 0:
                _fail(
                    f"staleness.step must be a non-negative int, "
                    f"got {step!r}"
                )
        elif rec.get("event") in ("attack_adapt", "ps_attack_adapt"):
            # v7: one adaptive-controller observation (DESIGN.md §16);
            # v8 adds the MODEL-plane twin ``ps_attack_adapt`` (a
            # Byzantine PS vs the replica gather / a LEARN node vs the
            # gossip) with an optional plane tag.
            ev = rec["event"]
            if not _is_num(rec.get("magnitude")):
                _fail(
                    f"{ev}.magnitude must be a number, got "
                    f"{rec.get('magnitude')!r}"
                )
            for key in ("lo", "hi"):
                val = rec.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"{ev}.{key} must be a number or null, "
                        f"got {val!r}"
                    )
            det = rec.get("detected")
            if det is not None and not isinstance(det, bool) \
                    and not _is_num(det):
                _fail(
                    f"{ev}.detected must be a bool/number or "
                    f"null, got {det!r}"
                )
            plane = rec.get("plane")
            if plane is not None and not isinstance(plane, str):
                _fail(f"{ev}.plane must be a string or null, got {plane!r}")
        elif rec.get("event") == "targeted_eval":
            # v8: the per-class eval digest of a targeted-attack run —
            # what the suspicion plane cannot see, made measurable.
            for key in ("source", "target"):
                val = rec.get(key)
                if not isinstance(val, int) or isinstance(val, bool):
                    _fail(
                        f"targeted_eval.{key} must be an int, got {val!r}"
                    )
            for key in ("confusion", "asr", "accuracy",
                        # v9: the clean-model trigger-rate floor.
                        "asr_baseline"):
                val = rec.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"targeted_eval.{key} must be a number or null, "
                        f"got {val!r}"
                    )
            pc = rec.get("per_class")
            if pc is not None:
                if not isinstance(pc, dict) or not all(
                    _is_num(v) for v in pc.values()
                ):
                    _fail(
                        f"targeted_eval.per_class must map classes to "
                        f"numbers, got {pc!r}"
                    )
        elif rec.get("event") == "defense_weights":
            # v7: the PS's per-round suspicion-weight vector.
            ws = rec.get("weights")
            _check_float_list("defense_weights", "weights", ws)
            ranks = rec.get("ranks")
            if ranks is not None:
                _check_float_list(
                    "defense_weights", "ranks", ranks, len(ws)
                )
        elif rec.get("event") == "data_defense":
            # v9: one round of the data-plane detectors (aggregators/
            # dataplane.py): per-rank spectral outlier scores, the
            # tau-sigma/2-means flags, the weights composed into the
            # quorum, optional rank attribution + plane tag.
            sc = rec.get("scores")
            _check_float_list("data_defense", "scores", sc)
            for key in ("flags", "weights", "ranks"):
                val = rec.get(key)
                if val is not None:
                    _check_float_list("data_defense", key, val, len(sc))
            plane = rec.get("plane")
            if plane is not None and not isinstance(plane, str):
                _fail(
                    f"data_defense.plane must be a string or null, "
                    f"got {plane!r}"
                )
            step = rec.get("step")
            if step is not None and (
                not isinstance(step, int) or isinstance(step, bool)
                or step < 0
            ):
                _fail(
                    f"data_defense.step must be a non-negative int or "
                    f"null, got {step!r}"
                )
        elif rec.get("event") == "defense_escalate":
            # v7: one rule-ladder transition of the closed-loop defense.
            lvl = rec.get("level")
            if not isinstance(lvl, int) or isinstance(lvl, bool) or lvl < 0:
                _fail(
                    f"defense_escalate.level must be a non-negative int, "
                    f"got {lvl!r}"
                )
            if not isinstance(rec.get("rule"), str):
                _fail(
                    f"defense_escalate.rule must be a string, got "
                    f"{rec.get('rule')!r}"
                )
            if rec.get("direction") not in ("escalate", "deescalate"):
                _fail(
                    f"defense_escalate.direction must be 'escalate' or "
                    f"'deescalate', got {rec.get('direction')!r}"
                )
        elif rec.get("event") == "attack_fallback":
            # v7: a fold-ineligible attack keeping the where-path, made
            # loud (one-time per process).
            for key in ("attack", "path", "why"):
                if not isinstance(rec.get(key), str):
                    _fail(
                        f"attack_fallback.{key} must be a string, got "
                        f"{rec.get(key)!r}"
                    )
        elif rec.get("event") == "fed_round":
            # v10: one sharded federated round (federated/engine.py).
            for key in ("shards", "cohort"):
                val = rec.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 1:
                    _fail(
                        f"fed_round.{key} must be a positive int, "
                        f"got {val!r}"
                    )
            for key in ("step", "f_budget", "realized_byz"):
                val = rec.get(key)
                if val is not None and (
                    not isinstance(val, int) or isinstance(val, bool)
                    or val < 0
                ):
                    _fail(
                        f"fed_round.{key} must be a non-negative int or "
                        f"null, got {val!r}"
                    )
            be = rec.get("budget_exceeded")
            if be is not None and not isinstance(be, bool):
                _fail(
                    f"fed_round.budget_exceeded must be a bool or null, "
                    f"got {be!r}"
                )
            rs = rec.get("round_s")
            if rs is not None and not _is_num(rs):
                _fail(
                    f"fed_round.round_s must be a number or null, "
                    f"got {rs!r}"
                )
            ps = rec.get("per_shard")
            if ps is not None:
                if not isinstance(ps, dict) or not all(
                    isinstance(v, dict) and all(
                        x is None or _is_num(x) for x in v.values()
                    )
                    for v in ps.values()
                ):
                    _fail(
                        f"fed_round.per_shard must map shard ids to "
                        f"numeric digests, got {ps!r}"
                    )
        elif rec.get("event") == "cohort":
            # v10: the audited cohort — stable global client ids with
            # their composed selected weights (parallel lists).
            ids = rec.get("client_ids")
            _check_float_list("cohort", "client_ids", ids)
            sel = rec.get("selected")
            if sel is not None:
                _check_float_list("cohort", "selected", sel, len(ids))
            fb = rec.get("f_budget")
            if fb is not None and (
                not isinstance(fb, int) or isinstance(fb, bool) or fb < 0
            ):
                _fail(
                    f"cohort.f_budget must be a non-negative int or "
                    f"null, got {fb!r}"
                )
        elif rec.get("event") == "wire":
            # v11: the per-step wire digest (apps/cluster.WireStats) —
            # byte totals, the per-plane/per-scheme breakdowns, and the
            # compressed-wire extras (DESIGN.md §20): the live
            # compression ratio vs an f32 wire and the error-feedback
            # residual norm.
            for key in ("bytes_out", "bytes_in", "frames_in"):
                val = rec.get(key)
                if val is not None and (
                    not isinstance(val, int) or isinstance(val, bool)
                    or val < 0
                ):
                    _fail(
                        f"wire.{key} must be a non-negative int or "
                        f"null, got {val!r}"
                    )
            for key in ("encode_s", "decode_s", "compression_ratio",
                        "ef_residual_norm"):
                val = rec.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"wire.{key} must be a number or null, got {val!r}"
                    )
            for key in ("planes", "schemes"):
                d = rec.get(key)
                if d is not None:
                    if not isinstance(d, dict) or not all(
                        isinstance(v, dict) and all(
                            _is_num(x) for x in v.values()
                        )
                        for v in d.values()
                    ):
                        _fail(
                            f"wire.{key} must map names to numeric byte "
                            f"objects, got {d!r}"
                        )
        elif rec.get("event") == "autoscale":
            # v6: one elastic-membership action (DESIGN.md §15).
            if rec.get("action") not in ("spawn", "retire"):
                _fail(
                    f"autoscale.action must be 'spawn' or 'retire', "
                    f"got {rec.get('action')!r}"
                )
            for key in ("rank", "active"):
                val = rec.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"autoscale.{key} must be a non-negative int, "
                        f"got {val!r}"
                    )
            for key in ("rate", "target"):
                val = rec.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"autoscale.{key} must be a number or null, "
                        f"got {val!r}"
                    )
        elif rec.get("event") == "membership":
            # v13: one membership change (controlplane — DESIGN.md §22):
            # every failover / split / merge is exactly one epoch bump,
            # and this event is its audit trail.
            if not isinstance(rec.get("action"), str) \
                    or not rec["action"]:
                _fail(
                    f"membership.action must be a non-empty string, "
                    f"got {rec.get('action')!r}"
                )
            ep = rec.get("epoch")
            if ep is not None and (
                not isinstance(ep, int) or isinstance(ep, bool) or ep < 0
            ):
                _fail(
                    f"membership.epoch must be a non-negative int or "
                    f"null (pre-epoch deployment), got {ep!r}"
                )
            ns = rec.get("num_shards")
            if not isinstance(ns, int) or isinstance(ns, bool) or ns < 1:
                _fail(
                    f"membership.num_shards must be a positive int, "
                    f"got {ns!r}"
                )
            for key in ("shard", "step"):
                val = rec.get(key)
                if val is not None and (
                    not isinstance(val, int) or isinstance(val, bool)
                    or val < 0
                ):
                    _fail(
                        f"membership.{key} must be a non-negative int "
                        f"or null, got {val!r}"
                    )
        elif rec.get("event") == "ingest_batch":
            # v15: one bulk push_frames call (batched wire ingest —
            # DESIGN.md §24): frames in, rejects attributed, bytes
            # accepted, and whether the vectorized path actually ran.
            for key in ("shard", "frames", "rejected", "bytes"):
                val = rec.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"ingest_batch.{key} must be a non-negative "
                        f"int, got {val!r}"
                    )
            if rec["rejected"] > rec["frames"]:
                _fail(
                    f"ingest_batch.rejected ({rec['rejected']}) exceeds "
                    f"frames ({rec['frames']})"
                )
            if not isinstance(rec.get("batched"), bool):
                _fail(
                    f"ingest_batch.batched must be a bool, "
                    f"got {rec.get('batched')!r}"
                )
            dur = rec.get("dur_s")
            if not _is_num(dur) or dur < 0:
                _fail(
                    f"ingest_batch.dur_s must be a non-negative "
                    f"number, got {dur!r}"
                )
            step = rec.get("step")
            if step is not None and (
                not isinstance(step, int) or isinstance(step, bool)
                or step < 0
            ):
                _fail(
                    f"ingest_batch.step must be a non-negative int "
                    f"or null, got {step!r}"
                )
    elif kind == "span":
        # v5: one timed phase of a round (telemetry/trace.py).
        if not isinstance(rec.get("phase"), str) or not rec["phase"]:
            _fail(f"span.phase must be a non-empty string, "
                  f"got {rec.get('phase')!r}")
        for key in ("t_wall", "dur_s"):
            if not _is_num(rec.get(key)):
                _fail(f"span.{key} must be a number, got {rec.get(key)!r}")
        if rec["dur_s"] < 0:
            _fail(f"span.dur_s must be non-negative, got {rec['dur_s']!r}")
        step = rec.get("step")
        if step is not None and (
            not isinstance(step, int) or isinstance(step, bool) or step < 0
        ):
            _fail(f"span.step must be a non-negative int or null, "
                  f"got {step!r}")
        who = rec.get("who")
        if who is not None and not isinstance(who, str):
            _fail(f"span.who must be a string or null, got {who!r}")
    elif kind == "summary":
        for key in ("steps", "events"):
            val = rec.get(key)
            if not isinstance(val, int) or isinstance(val, bool) or val < 0:
                _fail(f"summary.{key} must be a non-negative int, got {val!r}")
        spans = rec.get("spans")
        if spans is not None and (
            not isinstance(spans, int) or isinstance(spans, bool) or spans < 0
        ):
            _fail(f"summary.spans must be a non-negative int or null, "
                  f"got {spans!r}")
        phases = rec.get("phases")
        if phases is not None:
            # v5: per-phase span digest ({phase: {count/mean_s/...}}).
            if not isinstance(phases, dict):
                _fail(f"summary.phases must be an object, got {phases!r}")
            for pk, pv in phases.items():
                if not isinstance(pv, dict) or not all(
                    _is_num(x) for x in pv.values()
                ):
                    _fail(
                        f"summary.phases[{pk!r}] must map stat names to "
                        f"numbers, got {pv!r}"
                    )
        if rec.get("suspicion") is not None:
            _check_float_list("summary", "suspicion", rec["suspicion"])
        if rec.get("suspicion_decayed") is not None:
            # v7: the windowed (halflife-decayed) score.
            _check_float_list(
                "summary", "suspicion_decayed", rec["suspicion_decayed"]
            )
        dfd = rec.get("defense")
        if dfd is not None:
            # v7: the closed-loop defense digest (hub.defense_stats).
            if not isinstance(dfd, dict):
                _fail(f"summary.defense must be an object, got {dfd!r}")
            for key in ("rounds", "escalations", "deescalations"):
                val = dfd.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"summary.defense.{key} must be a non-negative "
                        f"int, got {val!r}"
                    )
            for key in ("mean_w", "min_w"):
                val = dfd.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"summary.defense.{key} must be a number or "
                        f"null, got {val!r}"
                    )
        dpd = rec.get("data_defense")
        if dpd is not None:
            # v9: the data-plane defense digest (hub.data_defense_stats).
            if not isinstance(dpd, dict):
                _fail(
                    f"summary.data_defense must be an object, got {dpd!r}"
                )
            for key in ("rounds", "flagged"):
                val = dpd.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"summary.data_defense.{key} must be a "
                        f"non-negative int, got {val!r}"
                    )
            for key in ("max_score", "min_w"):
                val = dpd.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"summary.data_defense.{key} must be a number "
                        f"or null, got {val!r}"
                    )
        tgt = rec.get("targeted")
        if tgt is not None:
            # v8: the targeted-eval digest (hub.targeted_stats).
            if not isinstance(tgt, dict):
                _fail(f"summary.targeted must be an object, got {tgt!r}")
            ev = tgt.get("events")
            if not isinstance(ev, int) or isinstance(ev, bool) or ev < 0:
                _fail(
                    f"summary.targeted.events must be a non-negative "
                    f"int, got {ev!r}"
                )
            for key in ("last_confusion", "last_asr"):
                val = tgt.get(key)
                if val is not None and not _is_num(val):
                    _fail(
                        f"summary.targeted.{key} must be a number or "
                        f"null, got {val!r}"
                    )
        for key in ("wire_planes", "wire_schemes"):
            # v6 planes / v11 schemes: the hub's cumulative wire byte
            # breakdowns ({name: {bytes_out, bytes_in}}).
            d = rec.get(key)
            if d is not None:
                if not isinstance(d, dict) or not all(
                    isinstance(v, dict) and all(
                        _is_num(x) for x in v.values()
                    )
                    for v in d.values()
                ):
                    _fail(
                        f"summary.{key} must map names to numeric byte "
                        f"objects, got {d!r}"
                    )
        st = rec.get("step_time")
        if st is not None:
            if not isinstance(st, dict):
                _fail(f"summary.step_time must be an object, got {st!r}")
            for key in ("mean_s", "p50_s", "p95_s", "p99_s"):
                val = st.get(key)
                # v1 summaries carry only mean_s; v2 adds the percentiles
                # — whichever are present must be numbers.
                if key in st and not _is_num(val):
                    _fail(
                        f"summary.step_time.{key} must be a number, "
                        f"got {val!r}"
                    )
        sd = rec.get("staleness")
        if sd is not None:
            # v4: the async plane's digest (hub.staleness_stats).
            if not isinstance(sd, dict):
                _fail(f"summary.staleness must be an object, got {sd!r}")
            for key in ("count", "mean", "max"):
                if not _is_num(sd.get(key)):
                    _fail(
                        f"summary.staleness.{key} must be a number, "
                        f"got {sd.get(key)!r}"
                    )
            hist = sd.get("hist")
            if not isinstance(hist, dict) or not all(
                _is_num(v) for v in hist.values()
            ):
                _fail(
                    f"summary.staleness.hist must map staleness to "
                    f"counts, got {hist!r}"
                )
        fed = rec.get("federated")
        if fed is not None:
            # v10: the federated-round digest (hub.federated_stats).
            if not isinstance(fed, dict):
                _fail(f"summary.federated must be an object, got {fed!r}")
            for key in ("rounds", "budget_exceeded"):
                val = fed.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"summary.federated.{key} must be a non-negative "
                        f"int, got {val!r}"
                    )
            tc = fed.get("top_clients")
            if tc is not None and (
                not isinstance(tc, dict)
                or not all(_is_num(v) for v in tc.values())
            ):
                _fail(
                    f"summary.federated.top_clients must map client ids "
                    f"to numbers, got {tc!r}"
                )
        asd = rec.get("autoscale")
        if asd is not None:
            # v6: the elastic-membership digest (hub.autoscale_stats).
            if not isinstance(asd, dict):
                _fail(f"summary.autoscale must be an object, got {asd!r}")
            for key in ("spawns", "retires", "active_workers"):
                val = asd.get(key)
                if not isinstance(val, int) or isinstance(val, bool) \
                        or val < 0:
                    _fail(
                        f"summary.autoscale.{key} must be a non-negative "
                        f"int, got {val!r}"
                    )
    elif kind == "defense_bench":
        # v7: one accuracy cell of the adaptive-attack / closed-loop-
        # defense record: which attack faced which rule
        # under which defense, and where the accuracy landed.
        if not isinstance(rec.get("cell"), str) or not rec["cell"]:
            _fail(
                f"defense_bench.cell must be a non-empty string, got "
                f"{rec.get('cell')!r}"
            )
        for key in ("gar",):
            if not isinstance(rec.get(key), str):
                _fail(
                    f"defense_bench.{key} must be a string, got "
                    f"{rec.get(key)!r}"
                )
        atk = rec.get("attack")
        if atk is not None and not isinstance(atk, str):
            _fail(
                f"defense_bench.attack must be a string or null, got {atk!r}"
            )
        dfs = rec.get("defense")
        if dfs is not None and not isinstance(dfs, str):
            _fail(
                f"defense_bench.defense must be a string or null, got {dfs!r}"
            )
        for key in ("n", "f", "steps", "seed"):
            val = rec.get(key)
            if val is not None and (
                not isinstance(val, int) or isinstance(val, bool)
            ):
                _fail(
                    f"defense_bench.{key} must be an int or null, got {val!r}"
                )
        plane = rec.get("plane")
        if plane is not None and not isinstance(plane, str):
            _fail(
                f"defense_bench.plane must be a string or null, got "
                f"{plane!r}"
            )
        for key in ("final_accuracy", "final_loss", "attack_magnitude",
                    "wall_s",
                    # v8: the targeted rows' success metrics; v9 adds
                    # the clean-model trigger-rate floor.
                    "confusion", "asr", "clean_confusion",
                    "asr_baseline"):
            val = rec.get(key)
            if val is not None and not _is_num(val):
                _fail(
                    f"defense_bench.{key} must be a number or null, "
                    f"got {val!r}"
                )
        for key in ("suspicion", "suspicion_decayed"):
            val = rec.get(key)
            if val is not None:
                _check_float_list("defense_bench", key, val)
        esc = rec.get("escalations")
        if esc is not None and (
            not isinstance(esc, int) or isinstance(esc, bool) or esc < 0
        ):
            _fail(
                f"defense_bench.escalations must be a non-negative int "
                f"or null, got {esc!r}"
            )
    elif kind == "fed_bench":
        # v10: one fed_bench row — a shard-scaling cell (check
        # "scaling"), the S=1 bitwise anchor ("s1_bitwise"), or an
        # autoscaled fleet-rate cell ("fleet").
        if not isinstance(rec.get("check"), str) or not rec["check"]:
            _fail(
                f"fed_bench.check must be a non-empty string, got "
                f"{rec.get('check')!r}"
            )
        for key in ("n", "d", "shards"):
            val = rec.get(key)
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 1:
                _fail(
                    f"fed_bench.{key} must be a positive int, got {val!r}"
                )
        if not isinstance(rec.get("gar"), str):
            _fail(f"fed_bench.gar must be a string, got {rec.get('gar')!r}")
        for key in ("population", "f", "rounds", "spawns", "retires",
                    "active_initial", "active_final"):
            val = rec.get(key)
            if val is not None and (
                not isinstance(val, int) or isinstance(val, bool)
                or val < 0
            ):
                _fail(
                    f"fed_bench.{key} must be a non-negative int or "
                    f"null, got {val!r}"
                )
        for key in ("round_s", "round_s_sum", "speedup", "per_client_s",
                    "target_rate", "achieved_rate", "pre_rate",
                    "recovered_rate"):
            val = rec.get(key)
            if val is not None and not _is_num(val):
                _fail(
                    f"fed_bench.{key} must be a number or null, got {val!r}"
                )
        for key in ("per_shard_s", "per_shard_rss"):
            val = rec.get(key)
            if val is not None:
                _check_float_list("fed_bench", key, val)
        phases = rec.get("phases")
        if phases is not None:
            # v12: per-phase p50/p95 attribution on scaling rows
            # (ingest/h2d/fold from the trace plane).
            if not isinstance(phases, dict) or not all(
                isinstance(v, dict) and all(_is_num(x) for x in v.values())
                for v in phases.values()
            ):
                _fail(
                    f"fed_bench.phases must map phases to numeric "
                    f"stat objects, got {phases!r}"
                )
        for key in ("s1_bitwise_equal", "budget_exceeded"):
            val = rec.get(key)
            if val is not None and not isinstance(val, bool):
                _fail(
                    f"fed_bench.{key} must be a bool or null, got {val!r}"
                )
        rss = rec.get("peak_rss_bytes")
        if rss is not None and (
            not isinstance(rss, int) or isinstance(rss, bool) or rss < 0
        ):
            _fail(
                f"fed_bench.peak_rss_bytes must be a non-negative int "
                f"or null, got {rss!r}"
            )
    elif kind == "soak_bench":
        # v13: one soak_bench scenario row — sustained rounds through
        # the federated engine under control-plane stress (steady /
        # rolling_restart / partition / churn), with the trace plane's
        # round-latency percentiles as the SLO columns.
        if not isinstance(rec.get("check"), str) or not rec["check"]:
            _fail(
                f"soak_bench.check must be a non-empty string, got "
                f"{rec.get('check')!r}"
            )
        for key in ("rounds", "d", "shards", "cohort"):
            val = rec.get(key)
            if not isinstance(val, int) or isinstance(val, bool) \
                    or val < 1:
                _fail(
                    f"soak_bench.{key} must be a positive int, got {val!r}"
                )
        for key in ("population", "failovers", "partitions", "resizes",
                    "stale_rejects", "epoch_final", "dropped_total"):
            val = rec.get(key)
            if val is not None and (
                not isinstance(val, int) or isinstance(val, bool)
                or val < 0
            ):
                _fail(
                    f"soak_bench.{key} must be a non-negative int or "
                    f"null, got {val!r}"
                )
        for key in ("p50_s", "p95_s", "p99_s", "mean_s", "wall_s",
                    "kill_cost_rounds"):
            val = rec.get(key)
            if val is not None and not _is_num(val):
                _fail(
                    f"soak_bench.{key} must be a number or null, "
                    f"got {val!r}"
                )
        bw = rec.get("bitwise_equal")
        if bw is not None and not isinstance(bw, bool):
            _fail(
                f"soak_bench.bitwise_equal must be a bool or null, "
                f"got {bw!r}"
            )
    # kind == "run": meta payload is free-form (validated as JSON above).
    return rec


def validate_jsonl(path):
    """Validate every line of a JSONL artifact; returns the record count."""
    count = 0
    with open(path) as fp:
        for lineno, line in enumerate(fp, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                _fail(f"{path}:{lineno} is not JSON: {e}")
            try:
                validate_record(rec)
            except ValueError as e:
                raise ValueError(f"{path}:{lineno}: {e}") from None
            count += 1
    return count


# --- Prometheus text exposition --------------------------------------------


def prometheus_text(hub):
    """Prometheus text-format snapshot of a ``MetricsHub`` (exposition
    format 0.0.4 — what ``GET /metrics`` on apps/demo.py serves)."""
    lines = []

    def metric(name, mtype, help_, samples):
        lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} {mtype}")
        for labels, value in samples:
            if value is None:
                continue
            label_s = (
                "{" + ",".join(f'{k}="{v}"' for k, v in labels.items()) + "}"
                if labels else ""
            )
            lines.append(f"{name}{label_s} {value:g}")

    c = hub.counters()
    metric("garfield_steps_total", "counter",
           "Training steps folded into the hub.", [({}, c["steps"])])
    metric("garfield_events_total", "counter",
           "Liveness/exchange events folded into the hub.",
           [({}, c["events"])])
    metric("garfield_loss", "gauge", "Last recorded training loss.",
           [({}, c["loss"])])
    metric("garfield_gar_tau", "gauge",
           "cclip clip threshold at the last tapped step (0 for other "
           "rules).", [({}, c["tau"])])
    metric("garfield_gar_clip_fraction", "gauge",
           "Fraction of ranks clipped at the last tapped step.",
           [({}, c["clip_frac"])])
    st = hub.step_time_stats()
    metric("garfield_step_time_seconds", "gauge",
           "Mean recorded step wall time.",
           [({}, None if st is None else st["mean_s"])])
    if st is not None:
        metric("garfield_step_time_seconds_quantile", "gauge",
               "Step wall-time percentiles from the hub's recorded step "
               "times (the dispatch-tail signal --chunk_steps targets).",
               [({"quantile": "0.5"}, st["p50_s"]),
                ({"quantile": "0.95"}, st["p95_s"]),
                ({"quantile": "0.99"}, st["p99_s"])])
    hists = hub.phase_histograms()
    if hists:
        # v5: per-phase round-time attribution (telemetry/trace.py) — a
        # real Prometheus histogram per phase over the span durations,
        # the per-phase twin of the step-time quantiles above (and the
        # latency control signal the autoscaling work needs).
        from .hub import PHASE_BUCKETS

        lines.append(
            "# HELP garfield_phase_seconds Wall time of each traced "
            "round phase (spans, schema v5)."
        )
        lines.append("# TYPE garfield_phase_seconds histogram")
        for phase, h in hists.items():
            cum = 0
            for le in PHASE_BUCKETS:
                cum += h["buckets"].get(le, 0)
                lines.append(
                    f'garfield_phase_seconds_bucket'
                    f'{{phase="{phase}",le="{le:g}"}} {cum}'
                )
            lines.append(
                f'garfield_phase_seconds_bucket'
                f'{{phase="{phase}",le="+Inf"}} {h["count"]}'
            )
            lines.append(
                f'garfield_phase_seconds_sum{{phase="{phase}"}} '
                f'{h["sum"]:g}'
            )
            lines.append(
                f'garfield_phase_seconds_count{{phase="{phase}"}} '
                f'{h["count"]}'
            )
    w = hub.wire_counters()
    if any(w.values()):
        # v11: the scheme-labelled samples (DESIGN.md §20) join the
        # direction-only totals under the same counter — the
        # compressed-wire claim (≥8x bytes/step) auditable live. Sum
        # over {direction=} alone; the {scheme=,direction=} series are
        # the breakdown, not additional traffic.
        wire_samples = [({"direction": "out"}, float(w["bytes_out"])),
                        ({"direction": "in"}, float(w["bytes_in"]))]
        wire_samples += [
            ({"scheme": s, "direction": d}, float(counts["bytes_" + d]))
            for s, counts in hub.wire_scheme_counters().items()
            for d in ("out", "in")
        ]
        metric("garfield_wire_bytes_total", "counter",
               "Wire bytes through the typed host-plane codec "
               "(direction-only totals, plus per-scheme breakdown "
               "series labelled scheme=).",
               wire_samples)
        planes = hub.wire_plane_counters()
        if planes:
            # v6: plane-labelled byte counters (DESIGN.md §15) — the
            # gradient/model/control planes' wire costs attribute
            # separately instead of blurring into the totals.
            metric("garfield_wire_plane_bytes_total", "counter",
                   "Wire bytes per exchange plane (0=control, "
                   "1=gradients, 2=models).",
                   [({"plane": p, "direction": d},
                     float(counts["bytes_" + d]))
                    for p, counts in planes.items()
                    for d in ("out", "in")])
        metric("garfield_wire_codec_seconds_total", "counter",
               "Host seconds spent in the wire codec.",
               [({"op": "encode"}, w["encode_s"]),
                ({"op": "decode"}, w["decode_s"])])
        metric("garfield_send_queue_drops_total", "counter",
               "Publisher-side frames shed to sender-queue overflow "
               "(backpressure; the send-side twin of plane_drop).",
               [({}, float(w["send_queue_drops"]))])
    ib = hub.ingest_batch_stats()
    if ib is not None:
        # v15: the bulk ingest plane (DESIGN.md §24) — host seconds in
        # push_frames split by path, plus the frame/reject totals that
        # say whether the vectorized decode is actually being hit.
        metric("garfield_ingest_batch_seconds", "counter",
               "Host seconds spent in bulk frame ingest (push_frames), "
               "split by whether the vectorized batch decode ran.",
               [({"path": "batched"}, ib["batched_s"]),
                ({"path": "fallback"}, ib["fallback_s"])])
        metric("garfield_ingest_batch_frames_total", "counter",
               "Frames offered to bulk ingest, and the subset rejected "
               "with sender attribution.",
               [({"outcome": "offered"}, float(ib["frames"])),
                ({"outcome": "rejected"}, float(ib["rejected"]))])
    stale = hub.staleness_stats()
    if stale is not None:
        # v4: bounded-staleness async plane (DESIGN.md §14) — a real
        # Prometheus histogram over per-quorum-member staleness in
        # rounds, plus the hard-cutoff tail visible in the +Inf bucket.
        buckets = [0, 1, 2, 4, 8, 16, 32]
        lines.append(
            "# HELP garfield_staleness_rounds Staleness (rounds behind "
            "the PS) of every async-quorum member."
        )
        lines.append("# TYPE garfield_staleness_rounds histogram")
        cum = 0
        for le in buckets:
            cum = sum(
                c for t, c in stale["hist"].items() if t <= le
            )
            lines.append(
                f'garfield_staleness_rounds_bucket{{le="{le}"}} {cum}'
            )
        lines.append(
            f'garfield_staleness_rounds_bucket{{le="+Inf"}} '
            f'{stale["count"]}'
        )
        lines.append(
            f'garfield_staleness_rounds_sum '
            f'{stale["mean"] * stale["count"]:g}'
        )
        lines.append(f'garfield_staleness_rounds_count {stale["count"]}')
        metric("garfield_staleness_rounds_max", "gauge",
               "Largest staleness admitted so far (bounded by "
               "--max_staleness).", [({}, float(stale["max"]))])
    autos = hub.autoscale_stats()
    if autos is not None:
        # v6: the elastic-membership plane (DESIGN.md §15).
        metric("garfield_active_workers", "gauge",
               "Workers currently active under the autoscale controller.",
               [({}, float(autos["active_workers"]))])
        metric("garfield_autoscale_actions_total", "counter",
               "Autoscale membership actions taken.",
               [({"action": "spawn"}, float(autos["spawns"])),
                ({"action": "retire"}, float(autos["retires"]))])
    fed = hub.federated_stats()
    if fed is not None:
        # v10: the federated round engine (DESIGN.md §19).
        metric("garfield_fed_rounds_total", "counter",
               "Federated rounds completed by the sharded round engine.",
               [({}, float(fed["rounds"]))])
        if fed["shards"] is not None:
            metric("garfield_fed_shards", "gauge",
                   "PS shard count of the federated deployment.",
                   [({}, float(fed["shards"]))])
        if fed["last_cohort"] is not None:
            metric("garfield_fed_cohort_size", "gauge",
                   "Active cohort size of the last federated round.",
                   [({}, float(fed["last_cohort"]))])
        metric("garfield_fed_budget_exceeded_total", "counter",
               "Rounds whose realized Byzantine count exceeded the "
               "cohort's priced f budget (simulation audit).",
               [({}, float(fed["budget_exceeded"]))])
        top = hub.client_suspicion_decayed(k=16)
        if top:
            metric("garfield_client_suspicion_decayed", "gauge",
                   "Decayed exclusion frequency of the most-suspect "
                   "sampled clients, keyed by stable GLOBAL client id "
                   "(v10; resampling cannot launder it).",
                   [({"client": str(c)}, float(s))
                    for c, s in sorted(top.items())])
    dfs = hub.defense_stats()
    if dfs is not None:
        # v7: the closed-loop defense (DESIGN.md §16).
        if dfs["level"] is not None:
            metric("garfield_defense_level", "gauge",
                   "Active escalation-ladder level of the closed-loop "
                   "defense.", [({}, float(dfs["level"]))])
        metric("garfield_defense_escalations_total", "counter",
               "Rule-ladder transitions taken by the closed-loop defense.",
               [({"direction": "escalate"}, float(dfs["escalations"])),
                ({"direction": "deescalate"},
                 float(dfs["deescalations"]))])
        if dfs["min_w"] is not None:
            metric("garfield_defense_min_weight", "gauge",
                   "Smallest suspicion weight applied so far.",
                   [({}, float(dfs["min_w"]))])
    dpd = hub.data_defense_stats()
    if dpd is not None:
        # v9: the data-plane defense (DESIGN.md §18) — per-rank spectral
        # outlier scores from the last audited quorum plus the detector
        # counters.
        metric("garfield_dataplane_outlier_score", "gauge",
               "Spectral outlier score of each rank's gradient "
               "fingerprint at the last data-defense round (v9).",
               [({"rank": str(r)}, float(s))
                for r, s in sorted(dpd["scores"].items())])
        metric("garfield_dataplane_flagged_total", "counter",
               "Rank-rounds flagged by the data-plane detectors.",
               [({}, float(dpd["flagged"]))])
        if dpd["min_w"] is not None:
            metric("garfield_dataplane_min_weight", "gauge",
                   "Smallest data-plane suspicion weight applied so far.",
                   [({}, float(dpd["min_w"]))])
    susp = hub.suspicion()
    if susp is not None:
        metric("garfield_rank_suspicion", "gauge",
               "Cumulative exclusion frequency per rank under the active "
               "GAR (the Byzantine-audit signal).",
               [({"rank": str(i)}, float(s)) for i, s in enumerate(susp)])
        if hub._halflife is not None:
            susp_d = hub.suspicion_decayed()
            metric("garfield_rank_suspicion_decayed", "gauge",
                   "Exclusion frequency over the halflife-decayed window "
                   "(v7; the score a rotated cohort cannot launder).",
                   [({"rank": str(i)}, float(s))
                    for i, s in enumerate(susp_d)])
        metric("garfield_rank_observed_total", "counter",
               "Quorum appearances per rank.",
               [({"rank": str(i)}, float(o))
                for i, o in enumerate(hub._observed)])
        metric("garfield_rank_excluded_total", "counter",
               "Cumulative refused influence per rank.",
               [({"rank": str(i)}, float(e))
                for i, e in enumerate(hub._excluded)])
    return "\n".join(lines) + "\n"
