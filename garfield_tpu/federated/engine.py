"""The federated round engine: sharded PS plane over the hierarchy.

One round = SAMPLE (sampler.py) -> INGEST (every shard streams its d/S
column span of each cohort gradient through its own hierarchical
reducer, aggregators/hierarchy.StreamingAggregator) -> FOLD (per-shard
hier-GAR at the cohort's priced f budget) -> BROADCAST (per-shard model
spans re-published; the unsharded vector exists only where a consumer
reassembles it). ``ShardServer`` is the per-shard half — a standalone
object one OS process runs for exactly one shard, with its own wire
plane (frames stamped with the shard id, cross-shard arrivals are
attributable codec rejects) — and ``FedRoundEngine`` composes S of them
in one process: the simulation driver, the bitwise-equality anchor, and
the single-host deployment shape.

Bitwise anchor: at S=1 with full participation and no stragglers the
engine IS the existing unsharded single-PS streaming path — same
``StreamingAggregator`` programs over the same arrival order, same
``model -= lr * agg`` update — so its trajectory is bitwise equal to
the pre-sharding path (pinned in tests/test_federated.py; fed_bench's
``s1_bitwise`` check records it as ``s1_bitwise_equal``).

Why selection is per shard: each shard's hierarchy sees only its column
span, so krum's inlier geometry (and therefore which clients a bucket
excludes) can differ shard to shard — a client can be excluded in shard
0 and kept in shard 1. That is by design, not an approximation loss: a
Byzantine client must now defeat S independent robust folds to corrupt
the full vector, and each shard's f-composition contract holds verbatim
over its own slice (every cohort member contributes exactly one row per
shard). The flip side — a sharded fold is NOT bitwise the unsharded
fold for S > 1 — is documented in DESIGN.md §19 (XLA:CPU, round 17)
and never hidden behind the S=1 anchor.

Telemetry (schema v10): one ``fed_round`` event per round (cohort size,
f budget, realized-Byzantine audit when the driver knows ground truth,
round wall, per-shard digests) and — with ``audit=True`` — one
``cohort`` event carrying the sampled GLOBAL client ids with their
composed selected weights, which ``telemetry.hub.MetricsHub`` folds
into client-id-keyed decayed suspicion (the score resampling cannot
launder).
"""

import json
import os
import time

import numpy as np

from . import sharding
from ..aggregators import hierarchy
from ..telemetry import hub as tele_hub
from ..telemetry import trace as _trace
from ..utils import wire

__all__ = ["ShardServer", "FedRoundEngine"]


class ShardServer:
    """One PS shard: hierarchy levels + wire plane for one column span.

    ``begin_round(n, f)`` arms the reducer for the round's active cohort
    size at the round's priced f budget; rows arrive via ``push_rows``
    (host blocks — the fleet driver / bench path) or ``push_frame`` /
    ``wire_transform`` (typed wire frames stamped with this shard's id;
    the transform plugs into ``PeerExchange`` waiter threads so decode
    and bucket folding overlap the still-open quorum, exactly like the
    unsharded streaming path). ``finish_round`` folds the remainder and
    returns the (d_shard,) aggregate.
    """

    def __init__(self, shard, spec, *, bucket_gar="krum", top_gar=None,
                 bucket_size=None, levels="auto", wave_buckets=8,
                 audit=False, epoch=None):
        self.shard = sharding.shard_plane(shard, spec.num_shards)
        self.spec = spec
        self.d_shard = spec.width(self.shard)
        self._cfg = dict(
            bucket_gar=bucket_gar, top_gar=top_gar, bucket_size=bucket_size,
            levels=levels, wave_buckets=wave_buckets, audit=audit,
        )
        self._red = None
        self._round = None
        self.wire_bytes_in = 0
        self._fused = wire.wire_fused()
        self._scratch = None
        # Membership epoch this shard serves (controlplane, DESIGN.md
        # §22): None = pre-epoch deployment, frames are not
        # epoch-checked. When set, every wire frame must carry exactly
        # this epoch (wire.decode's expect_epoch) — a stale-epoch frame
        # is the same attributable reject as a cross-shard stamp.
        self.epoch = None if epoch is None else wire.check_epoch(epoch)
        # Round this shard is allowed to serve next after a checkpoint
        # restore (mark_restored) — None once live again.
        self._expect_round = None

    # -- round lifecycle ----------------------------------------------------

    def mark_restored(self, next_round):
        """Pin the ONLY round this shard may serve next: it was just
        restored from the span checkpoint saved after round
        ``next_round - 1`` finished, so ``next_round`` is the one round
        its state is valid for. ``begin_round`` for any other round
        refuses loudly (see there); serving the pinned round clears the
        pin — from then on the shard is live and carries its own
        state."""
        self._expect_round = int(next_round)

    def begin_round(self, round_, n, f):
        """Arm the shard's reducer for ``n`` active cohort members at
        the priced budget ``f``. Reuses the previous round's wave
        buffers when (n, f) repeat — at bench scale the reallocation is
        measurable, and plan identity keeps the fold programs cached.

        A RESTORED shard (``mark_restored``) serves exactly the round
        after its checkpoint: round state is rebuilt from scratch here
        every round, so nothing else would catch a driver resuming at
        the wrong round — the shard would silently fold rows against a
        stale span and broadcast garbage with round-R labels. Refusing
        is the loud form of "I have no span checkpoint for that
        round"."""
        if self._expect_round is not None \
                and int(round_) != self._expect_round:
            raise RuntimeError(
                f"shard {self.shard} was restored from its round "
                f"{self._expect_round - 1} span checkpoint and can only "
                f"serve round {self._expect_round}; asked to begin round "
                f"{int(round_)}, for which it has no span checkpoint — "
                "refusing loudly instead of serving a stale span"
            )
        self._expect_round = None
        if self._red is not None and self._red.n == int(n) \
                and self._red.f == int(f):
            self._red.reset()
        else:
            self._red = hierarchy.StreamingAggregator(
                int(n), int(f), d=self.d_shard, **self._cfg
            )
        self._round = int(round_)
        self.wire_bytes_in = 0
        return self._red.plan

    def push_rows(self, rows, *, stable=False):
        """Ingest a (k, d_shard) block of already-sliced cohort rows in
        arrival order (the in-process fast path — one bulk copy into the
        wave buffer, hierarchy.push_many). ``stable=True`` promises the
        block stays alive and unwritten for the rest of the round, which
        lets whole waves fold zero-copy straight off it
        (hierarchy.push_many's stable contract) — the bench's immutable
        round pool qualifies; a buffer the caller refills per push does
        NOT."""
        return self._red.push_many(rows, stable=stable)

    def push_frame(self, buf):
        """Ingest one typed wire frame: decoded with
        ``expect_plane=shard`` so a frame stamped for another shard is a
        ``WireError`` — ban evidence attributable to its SENDER (the
        stamp is under the CRC; DESIGN.md §19), not a silent mis-fold.
        A frame may carry several whole rows (k·d_shard elements): the
        fleet's clients batch their simulated cohort members into one
        frame per shard per round — so the element count cannot be
        pinned exactly, but it IS bounded by the whole cohort
        (n·d_shard), and ``max_elems`` rejects a header claiming more
        BEFORE a sparse frame's scatter allocates (the sparse dense-size
        claim is otherwise sender-controlled, see wire.decode).

        Fused path (GARFIELD_WIRE_FUSED_DECODE, default on): the frame
        decodes into a REUSABLE per-shard scratch (wire.decode_into) —
        one allocation per high-water frame size instead of one O(k·d)
        transient per frame. The scratch is sized from the header's
        claimed count CLAMPED to the cohort bound (wire.frame_elems is a
        sizing hint, never an allocation grant), so an over-claiming
        frame still rejects on ``max_elems`` before any allocation
        grows past the bound."""
        bound = self._red.n * self.d_shard
        if self._fused:
            claim = min(wire.frame_elems(buf), bound)
            if self._scratch is None or self._scratch.size < claim:
                self._scratch = np.empty(claim, np.float32)
            k = wire.decode_into(buf, self._scratch,
                                 expect_plane=self.shard, max_elems=bound,
                                 expect_epoch=self.epoch)
            vec = self._scratch[:k]
        else:
            vec = wire.decode(buf, expect_plane=self.shard,
                              max_elems=bound, expect_epoch=self.epoch)
        if vec.size % self.d_shard:
            raise wire.WireError(
                f"shard {self.shard} frame has {vec.size} elements — "
                f"not a whole number of {self.d_shard}-wide rows"
            )
        self.wire_bytes_in += len(buf)
        return self._red.push_many(vec.reshape(-1, self.d_shard))

    def push_frames(self, bufs):
        """Bulk wire ingest (ISSUE 20): decode a whole batch of
        single-row frames straight into the reducer's level-0 wave rows
        via ``hierarchy.push_frames`` / ``wire.decode_batch_into`` — one
        vectorized header screen + same-scheme slab dequant instead of a
        Python codec trip per frame. Returns a list the length of
        ``bufs``: per-frame arrival index, or the indexed ``WireError``
        (the sender's ban evidence — one forged frame never poisons its
        batchmates, pinned in tests/test_wire.py).

        The batch fast path requires every frame's HEADER to claim
        exactly one ``d_shard``-wide row (the per-client wire shape; the
        claim is re-validated inside the codec). Batches carrying any
        multi-row fleet frame — or any header too broken to read — fall
        back to a per-frame ``push_frame`` loop in arrival order, so
        bucket assignment never depends on which path ran. Emits one
        v15 ``ingest_batch`` telemetry event per call."""
        bufs = list(bufs)
        t0 = time.perf_counter()
        single_row = True
        for b in bufs:
            try:
                if wire.frame_elems(b) != self.d_shard:
                    single_row = False
                    break
            except wire.WireError:
                single_row = False
                break
        if single_row and bufs:
            results = self._red.push_frames(
                bufs, expect_plane=self.shard, expect_epoch=self.epoch
            )
            batched = True
        else:
            results = []
            for b in bufs:
                try:
                    results.append(self.push_frame(b))
                except wire.WireError as err:
                    results.append(err)
            batched = False
        rejected = 0
        nbytes = 0
        for b, r in zip(bufs, results):
            if isinstance(r, wire.WireError):
                rejected += 1
            else:
                nbytes += len(b)
        if batched:
            # push_frame accounts accepted bytes itself on the fallback.
            self.wire_bytes_in += nbytes
        if tele_hub.current() is not None:
            tele_hub.emit_event(
                "ingest_batch", shard=int(self.shard),
                frames=len(bufs), rejected=int(rejected),
                bytes=int(nbytes), batched=bool(batched),
                dur_s=round(time.perf_counter() - t0, 6),
                step=self._round,
            )
        return results

    def wire_transform(self, idx, payload):
        """``PeerExchange`` transform hook (waiter-thread ingest +
        overlap, like the unsharded streaming path); a WireError
        propagates to the exchange as the peer's stored ban evidence."""
        return self.push_frame(payload)

    def wire_batch_transform(self, items):
        """``PeerExchange`` batch_transform hook: one ``push_frames``
        pass over the whole harvested quorum (``items`` = latched
        ``(peer, frame)`` pairs), per-peer arrival-index-or-WireError
        results — the bulk twin of ``wire_transform``."""
        return self.push_frames([p for _, p in items])

    def arrived(self):
        return 0 if self._red is None else self._red._arrived

    def finish_round(self):
        """Fold the remainder; returns the (d_shard,) float32 aggregate.
        The shard's broadcast payload is exactly this span — a consumer
        reassembles spans, it never receives the full vector from any
        single shard."""
        with _trace.span("fed_shard_fold", shard=int(self.shard),
                         step=self._round):
            return self._red.finalize()

    def audit(self):
        return self._red.audit()


class FedRoundEngine:
    """S in-process shard servers + the round loop (see module doc)."""

    def __init__(self, model_vec, num_shards, sampler, *,
                 bucket_gar="krum", top_gar=None, bucket_size=None,
                 levels="auto", wave_buckets=8, lr=0.1, audit=False,
                 telemetry=False, checkpoint_dir=None, max_to_keep=3,
                 epoch=None):
        self.model = np.asarray(model_vec, np.float32).reshape(-1).copy()
        self.spec = sharding.plan_shards(self.model.size, num_shards)
        self.sampler = sampler
        self.lr = float(lr)
        self._audit = bool(audit)
        self._telemetry = bool(telemetry)
        self._shard_cfg = dict(
            bucket_gar=bucket_gar, top_gar=top_gar,
            bucket_size=bucket_size, levels=levels,
            wave_buckets=wave_buckets, audit=self._audit,
        )
        # Control plane (DESIGN.md §22): ``epoch`` arms membership-epoch
        # enforcement — every shard decodes wire frames with
        # expect_epoch, and each failover / split / merge bumps the
        # epoch (``bump_epoch``). None keeps the pre-epoch wire format
        # (fed_bench's drivers send v1 frames).
        self.epoch = None if epoch is None else wire.check_epoch(epoch)
        self._ckpt_dir = (
            None if checkpoint_dir is None else str(checkpoint_dir)
        )
        self._max_to_keep = int(max_to_keep)
        self.shards = [
            self.build_shard(s) for s in range(self.spec.num_shards)
        ]
        self.round = 0
        self._active_ids = None
        self._weights = None
        self._pos = None  # global id -> cohort arrival position
        self._t0 = None
        self.last_info = None

    def build_shard(self, shard):
        """A fresh ``ShardServer`` for span ``shard`` under the current
        spec and deployment config — what __init__ composes, what a
        failover standby promotion (controlplane/failover.py) and a
        ``resize`` rebuild call."""
        return ShardServer(
            shard, self.spec, epoch=self.epoch, **self._shard_cfg
        )

    # -- round lifecycle ----------------------------------------------------

    def begin_round(self, tags=None):
        """Sample the round's cohort, compose staleness (stragglers past
        the cutoff are dropped BEFORE planning — zero-weight rows never
        reach a Gram rule), price f on the active count, arm every
        shard. Returns (active_ids, f_budget)."""
        cohort = self.sampler.cohort(self.round)
        active, w, dropped = self.sampler.cohort_weights(
            self.round, cohort, tags
        )
        if active.size < 1:
            raise ValueError(
                f"round {self.round}: staleness cutoff dropped the "
                "entire cohort"
            )
        f = self.sampler.f_budget(active.size)
        self._active_ids = active
        self._weights = w
        self._dropped = dropped
        self._pos = {int(c): i for i, c in enumerate(active.tolist())}
        for sh in self.shards:
            sh.epoch = self.epoch  # track bumps (failover/split/merge)
            sh.begin_round(self.round, active.size, f)
        self._f = f
        self._t0 = time.perf_counter()
        return active, f

    def ingest(self, client_id, vec):
        """One cohort member's full (d,) gradient: staleness-discounted
        once (host-side; weight 1.0 is a bitwise no-op per IEEE
        multiply, so fresh full-participation rounds stay on the
        unsharded path's exact bytes), then column-sliced into every
        shard's reducer. Rows must arrive in cohort order — arrival
        order IS bucket assignment, shared with the unsharded path."""
        i = self._pos[int(client_id)]
        vec = np.asarray(vec, np.float32).reshape(-1)
        if vec.size != self.spec.d:
            raise ValueError(
                f"client {client_id} gradient has {vec.size} elements, "
                f"expected {self.spec.d}"
            )
        w = float(self._weights[i])
        if w != 1.0:
            vec = (vec * np.float32(w)).astype(np.float32)
        for sh in self.shards:
            sh.push_rows(self.spec.slice_rows(vec[None, :], sh.shard))
        return i

    def ingest_rows(self, rows, *, stable=False):
        """Bulk in-order ingest of a (k, d) block of ACTIVE cohort rows
        (the bench/simulation fast path: rows generated wave-at-a-time,
        weights applied in bulk). ``stable=True`` forwards the zero-copy
        contract to every shard reducer (see ShardServer.push_rows):
        only pass it when ``rows`` stays alive and unwritten until the
        round finishes. Weighted rounds stage a fresh weighted block, so
        they are stable regardless of the caller's buffer discipline."""
        rows = np.asarray(rows, np.float32)
        k = rows.shape[0]
        first = self.shards[0].arrived()
        w = self._weights[first:first + k]
        if not np.all(w == 1.0):
            rows = rows * w[:, None]
            stable = True  # the weighted block is ours and immutable
        for sh in self.shards:
            sh.push_rows(self.spec.slice_rows(rows, sh.shard),
                         stable=stable)
        return first

    def finish_round(self, *, byz_ids=None):
        """Fold every shard, apply the model update on each span, emit
        the v10 telemetry, advance the round counter. Returns an info
        dict (round, cohort/active sizes, f budget, realized-Byzantine
        audit when ``byz_ids`` ground truth is supplied, per-shard
        latencies, wall)."""
        per_shard = {}
        agg_parts = []
        for sh in self.shards:
            t0 = time.perf_counter()
            agg = sh.finish_round()
            per_shard[str(sh.shard)] = {
                "latency_s": round(time.perf_counter() - t0, 6),
                "wire_bytes": int(sh.wire_bytes_in),
            }
            agg_parts.append(agg)
        # Per-span SGD update: each shard updates only its own columns
        # (in deployment each shard process owns its span; here the
        # spans share one buffer). float32 throughout.
        for sh, agg in zip(self.shards, agg_parts):
            lo, hi = self.spec.spans[sh.shard]
            self.model[lo:hi] = (
                self.model[lo:hi] - np.float32(self.lr) * agg
            ).astype(np.float32)
        realized = None
        exceeded = None
        if byz_ids is not None:
            realized = self.sampler.realized_byzantine(
                self._active_ids, byz_ids
            )
            exceeded = realized > self._f
        wall = time.perf_counter() - self._t0
        info = {
            "round": self.round,
            "cohort": int(self.sampler.cohort_size),
            "active": int(self._active_ids.size),
            "dropped": int(self._dropped.size),
            "f_budget": int(self._f),
            "realized_byz": realized,
            "budget_exceeded": exceeded,
            "round_s": wall,
            "per_shard": per_shard,
        }
        if self._telemetry:
            tele_hub.emit_event(
                "fed_round", step=int(self.round),
                shards=int(self.spec.num_shards),
                cohort=int(self._active_ids.size),
                f_budget=int(self._f),
                realized_byz=realized,
                budget_exceeded=exceeded,
                round_s=round(wall, 6),
                per_shard=per_shard,
            )
            if self._audit:
                # Composed per-client selection: a client is kept iff
                # EVERY shard's hierarchy kept it (selection is per
                # shard — see the module docstring), reported against
                # the stable GLOBAL ids so resampling cannot reset it.
                sel = np.ones(self._active_ids.size, np.float32)
                for sh in self.shards:
                    sel *= np.asarray(
                        sh.audit()["selected"], np.float32
                    )
                tele_hub.emit_event(
                    "cohort", step=int(self.round),
                    client_ids=[int(c) for c in self._active_ids],
                    selected=[float(s) for s in sel],
                    f_budget=int(self._f),
                )
        self.last_info = info
        if self._ckpt_dir is not None:
            self.save_checkpoint()
        self.round += 1
        return info

    # -- control plane: checkpoints, failover, membership -------------------

    def _control_dir(self):
        return os.path.join(self._ckpt_dir, "control")

    def save_checkpoint(self):
        """Checkpoint the just-finished round: one per-span checkpoint
        per shard (sharding.save_sharded — in deployment each shard
        process writes only its own span) plus one CONTROL record (round
        number, membership epoch, and the hub's per-client suspicion
        snapshot) so a failover handoff restores the span AND the
        round/suspicion state an epoch-timed attacker would love to see
        dropped (DESIGN.md §22). Called automatically from
        ``finish_round`` when ``checkpoint_dir`` is set; the step key is
        the round just finished."""
        sharding.save_sharded(
            self._ckpt_dir, self.round, self.model, self.spec,
            max_to_keep=self._max_to_keep,
        )
        hub = tele_hub.current()
        snap = hub.client_suspicion_snapshot() if hub is not None else {}
        rec = {
            "round": int(self.round),
            "epoch": None if self.epoch is None else int(self.epoch),
            "num_shards": int(self.spec.num_shards),
            "suspicion": {
                str(cid): [float(o), float(e)]
                for cid, (o, e) in snap.items()
            },
        }
        # The control record is tiny host-side metadata with
        # variable-length content — a plain JSON file with an atomic
        # replace, not a Checkpointer (orbax restore needs fixed
        # shapes), GC'd to the same history bound as the span files.
        cdir = self._control_dir()
        os.makedirs(cdir, exist_ok=True)
        path = os.path.join(cdir, f"ctl_{int(self.round)}.json")
        with open(path + ".tmp", "w") as fp:
            json.dump(rec, fp)
        os.replace(path + ".tmp", path)
        for st in self.control_steps()[: -self._max_to_keep]:
            os.remove(os.path.join(cdir, f"ctl_{st}.json"))

    def control_steps(self):
        """Sorted steps with a control record (see ``save_checkpoint``)."""
        cdir = self._control_dir()
        if not os.path.isdir(cdir):
            return []
        return sorted(
            int(n[4:-5]) for n in os.listdir(cdir)
            if n.startswith("ctl_") and n.endswith(".json")
        )

    def load_control(self, step):
        """The control record saved at ``step`` (round/epoch/suspicion)."""
        with open(os.path.join(
            self._control_dir(), f"ctl_{int(step)}.json"
        )) as fp:
            return json.load(fp)

    def resume(self, step=None):
        """Restore the newest COMPLETE checkpoint — a step every span
        AND the control record agree on (a torn save never restores
        mixed rounds) — and pin every shard to the one round it can now
        serve. Returns the restored round number R; the next
        ``begin_round`` must be for round R + 1 (any other round is the
        loud ShardServer.begin_round refusal — the resumed engine has
        no span checkpoint for it). The hub (when installed) absorbs
        the checkpointed per-client suspicion via max-merge, so a
        crash/restore cycle cannot launder exclusion history."""
        if self._ckpt_dir is None:
            raise RuntimeError("engine has no checkpoint_dir to resume from")
        complete = set(sharding.sharded_steps(self._ckpt_dir, self.spec))
        complete &= set(self.control_steps())
        if step is None:
            if not complete:
                raise FileNotFoundError(
                    f"no complete checkpoint (all {self.spec.num_shards} "
                    f"spans + control record) under {self._ckpt_dir}"
                )
            step = max(complete)
        elif int(step) not in complete:
            raise FileNotFoundError(
                f"round {step} has no complete checkpoint under "
                f"{self._ckpt_dir} (complete: {sorted(complete)})"
            )
        self.model[:] = sharding.restore_sharded(
            self._ckpt_dir, self.spec, step=int(step)
        )
        ctl = self.load_control(step)
        if int(ctl["round"]) != int(step):
            raise ValueError(
                f"control record at step {step} claims round "
                f"{ctl['round']} — torn control plane"
            )
        self.round = int(step) + 1
        if ctl.get("epoch") is not None:
            self.epoch = wire.check_epoch(int(ctl["epoch"]))
        hub = tele_hub.current()
        if hub is not None and ctl.get("suspicion"):
            hub.absorb_client_suspicion({
                int(cid): (float(o), float(e))
                for cid, (o, e) in ctl["suspicion"].items()
            })
        for sh in self.shards:
            sh.epoch = self.epoch
            sh.mark_restored(self.round)
        return int(step)

    def bump_epoch(self, action, *, shard=None):
        """Advance the membership epoch by exactly one — every
        failover, split or merge is one epoch, so a frame stamped with
        any previous epoch is attributably stale (wire expect_epoch).
        Emits the v13 ``membership`` telemetry event. No-op epoch-wise
        when epoch enforcement is off (pre-epoch deployment), but the
        event still lands so the action is visible."""
        if self.epoch is not None:
            self.epoch = wire.check_epoch(self.epoch + 1)
            for sh in self.shards:
                sh.epoch = self.epoch
        if self._telemetry:
            tele_hub.emit_event(
                "membership",
                epoch=None if self.epoch is None else int(self.epoch),
                action=str(action),
                shard=None if shard is None else int(shard),
                num_shards=int(self.spec.num_shards),
                step=int(self.round),
            )
        return self.epoch

    def resize(self, num_shards):
        """Split/merge the shard group to ``num_shards`` spans BETWEEN
        rounds (the shard autoscaler's apply half,
        controlplane/shardscale.py): re-plan the contiguous balanced
        partition, rebuild every ShardServer over the new spans, bump
        the membership epoch once. The model vector itself is
        untouched — a repartition moves span boundaries, not bytes.
        Raises (and changes nothing) when the resize is impossible:
        past the wire header's 16 shard slots, or more shards than
        parameters — callers rescind the controller action on that
        refusal (utils/autoscale.rescind)."""
        num_shards = int(num_shards)
        if num_shards == self.spec.num_shards:
            return self.spec
        grew = num_shards > self.spec.num_shards
        self.spec = sharding.plan_shards(self.model.size, num_shards)
        self.shards = [
            self.build_shard(s) for s in range(self.spec.num_shards)
        ]
        self.bump_epoch("split" if grew else "merge")
        return self.spec
