"""D-SHARDING: partition the parameter vector across a PS shard group.

The MSMW topology replicates the parameter server for FAULT TOLERANCE —
every replica holds the full model and ingests every client (PAPER.md's
f_ps axis). This module adds the orthogonal axis the paper era never
needed: PARTITION the flat parameter/gradient vector into ``S``
contiguous shards, each owned by a PS shard process that runs its own
hierarchy levels (aggregators/hierarchy.py) and its own wire plane
(utils/exchange.py register slots), so wave ingest, hier-GAR folds and
model broadcast parallelize across shards — round time scales ~1/S
(XLA:CPU, round 17) because every shard touches only d/S of each client.

Shard identity on the wire
--------------------------
Shard ``s``'s frames travel on exchange plane ``s`` AND carry ``s`` in
the wire codec header's spare plane nibble (utils/wire.py, DESIGN.md
§15) — the frames are self-describing end to end, so a frame that
arrives at the wrong shard is an attributable codec reject
(``wire.decode(buf, expect_plane=s)`` raises ``WireError``), exactly
like a CRC failure: a Byzantine client cannot smuggle a d/S-sized
payload for shard 0 into shard 1's fold and have the mismatch blamed on
the network. The nibble holds 16 values, so ``MAX_SHARDS = 16`` — a
deployment that needs more shards must widen the header (a new wire
version), not truncate ids (the capacity guard raises loudly at
publish/encode time, never wraps).

Sharded checkpoints
-------------------
``save_sharded``/``restore_sharded`` write one ``utils.checkpoint``
checkpoint PER SHARD (each shard process persists only its own span —
no shard ever materializes the full model), and restore reassembles the
spans bitwise into the unsharded vector (pinned by the tier-1
round-trip test at pima scale).
"""

import os

import numpy as np

from ..utils import checkpoint as ckpt_lib
from ..utils import wire

__all__ = [
    "MAX_SHARDS",
    "ShardSpec",
    "plan_shards",
    "shard_plane",
    "reassemble",
    "save_sharded",
    "restore_sharded",
    "restore_span",
    "latest_sharded_step",
    "sharded_steps",
]

# The shard id rides the wire codec header's spare plane nibble (and the
# transport header's plane byte is clamped to the same range by
# PeerExchange(planes<=16)) — 16 shard slots, enforced loudly.
MAX_SHARDS = wire.MAX_PLANE + 1


def shard_plane(shard, num_shards=None):
    """Exchange/wire plane of shard ``shard`` — the identity mapping,
    guarded: an out-of-range shard id must fail at the call site that
    would stamp it, never truncate into a foreign shard's nibble."""
    s = int(shard)
    if isinstance(shard, bool) or s != shard:
        raise TypeError(f"shard id must be an integer, got {shard!r}")
    hi = (MAX_SHARDS if num_shards is None else int(num_shards)) - 1
    if not 0 <= s <= hi:
        raise ValueError(
            f"shard id {s} out of range [0, {hi}]: the shard tag rides "
            f"the wire header's spare plane nibble ({MAX_SHARDS} slots); "
            "a larger shard group needs a wider wire header, not a "
            "truncated id"
        )
    return s


class ShardSpec:
    """Contiguous balanced partition of a ``d``-element flat vector into
    ``num_shards`` spans (larger spans first, like the hierarchy's
    balanced buckets — no tiny remainder shard)."""

    __slots__ = ("d", "num_shards", "spans")

    def __init__(self, d, num_shards):
        d = int(d)
        s = int(num_shards)
        if d < 1:
            raise ValueError(f"d must be >= 1, got {d}")
        if not 1 <= s <= MAX_SHARDS:
            raise ValueError(
                f"num_shards must be in [1, {MAX_SHARDS}] (the wire "
                f"header's shard nibble), got {num_shards}"
            )
        if s > d:
            raise ValueError(
                f"cannot split {d} parameters across {s} shards"
            )
        self.d = d
        self.num_shards = s
        base, rem = divmod(d, s)
        sizes = [base + 1] * rem + [base] * (s - rem)
        spans, off = [], 0
        for size in sizes:
            spans.append((off, off + size))
            off += size
        self.spans = tuple(spans)

    def width(self, shard):
        lo, hi = self.spans[shard_plane(shard, self.num_shards)]
        return hi - lo

    def slice_rows(self, rows, shard):
        """Shard ``shard``'s column span of an (k, d) block (or a (d,)
        vector) — the per-shard view every client publish and every
        shard ingest takes."""
        lo, hi = self.spans[shard_plane(shard, self.num_shards)]
        return rows[..., lo:hi]

    def __repr__(self):
        return f"<ShardSpec d={self.d} shards={self.num_shards}>"


def plan_shards(d, num_shards):
    return ShardSpec(d, num_shards)


def reassemble(spec, parts):
    """Concatenate per-shard (d_s,) vectors back to the unsharded (d,)
    float32 vector — bitwise: a pure span copy, no arithmetic."""
    if len(parts) != spec.num_shards:
        raise ValueError(
            f"expected {spec.num_shards} shard parts, got {len(parts)}"
        )
    out = np.empty(spec.d, np.float32)
    for s, (lo, hi) in enumerate(spec.spans):
        part = np.asarray(parts[s], np.float32).reshape(-1)
        if part.size != hi - lo:
            raise ValueError(
                f"shard {s} part has {part.size} elements, expected "
                f"{hi - lo}"
            )
        out[lo:hi] = part
    return out


# --- sharded checkpoints -----------------------------------------------------


def _shard_dir(directory, shard):
    return os.path.join(str(directory), f"shard_{int(shard):02d}")


def save_sharded(directory, step, model_vec, spec, *, shards=None,
                 max_to_keep=3):
    """Per-shard checkpoint of a flat model vector through
    ``utils.checkpoint.Checkpointer`` — one step-keyed checkpoint per
    shard subdirectory, each carrying its span so restore can verify the
    partition. ``shards`` restricts the write to a subset (a shard
    process saves only its own span); default all."""
    model_vec = np.asarray(model_vec, np.float32).reshape(-1)
    if model_vec.size != spec.d:
        raise ValueError(
            f"model has {model_vec.size} elements, spec expects {spec.d}"
        )
    for s in (range(spec.num_shards) if shards is None else shards):
        lo, hi = spec.spans[shard_plane(s, spec.num_shards)]
        ckpt_lib.Checkpointer(
            _shard_dir(directory, s), max_to_keep=max_to_keep
        ).save(step, {
            "model": model_vec[lo:hi].copy(),
            "span": np.asarray([lo, hi], np.int64),
            "meta": np.asarray([spec.d, spec.num_shards], np.int64),
        })


def sharded_steps(directory, spec):
    """Sorted steps present in EVERY shard subdirectory — the complete
    (untorn) checkpoints. A step some shards are missing never appears:
    restoring it would mix rounds across spans."""
    steps = None
    for s in range(spec.num_shards):
        mine = set(ckpt_lib.Checkpointer(_shard_dir(directory, s)).steps())
        steps = mine if steps is None else steps & mine
        if not steps:
            return []
    return sorted(steps)


def latest_sharded_step(directory, spec):
    """Newest step present in EVERY shard subdirectory (a torn save —
    some shards ahead of others — must not restore mixed rounds), or
    None when any shard has no checkpoint."""
    steps = sharded_steps(directory, spec)
    return steps[-1] if steps else None


def restore_span(directory, spec, shard, step):
    """ONE shard's span from its per-span checkpoint — the restore half
    of the failover handoff (controlplane/failover.py): a standby
    taking over span ``shard`` reads only that shard's subdirectory,
    never the full model. Verifies the recorded span/meta against the
    spec exactly like ``restore_sharded``. Returns the (d_s,) float32
    span, bitwise the bytes ``save_sharded`` wrote."""
    s = shard_plane(shard, spec.num_shards)
    lo, hi = spec.spans[s]
    like = {
        "model": np.zeros(hi - lo, np.float32),
        "span": np.zeros(2, np.int64),
        "meta": np.zeros(2, np.int64),
    }
    state = ckpt_lib.Checkpointer(_shard_dir(directory, s)).restore(
        like, step=int(step)
    )
    span = tuple(int(x) for x in np.asarray(state["span"]))
    meta = tuple(int(x) for x in np.asarray(state["meta"]))
    if span != (lo, hi) or meta != (spec.d, spec.num_shards):
        raise ValueError(
            f"shard {s} checkpoint was written for span {span} of a "
            f"d={meta[0]}, S={meta[1]} model; the spec expects span "
            f"({lo}, {hi}) of d={spec.d}, S={spec.num_shards}"
        )
    return np.asarray(state["model"], np.float32)


def restore_sharded(directory, spec, step=None):
    """Reassemble the unsharded (d,) model vector from per-shard
    checkpoints — bitwise equal to the vector ``save_sharded`` split
    (pinned). Raises if any shard is missing, a span mismatches the
    spec, or ``step`` is absent from a shard."""
    step = latest_sharded_step(directory, spec) if step is None else step
    if step is None:
        raise FileNotFoundError(
            f"no complete sharded checkpoint under {directory}"
        )
    return reassemble(spec, [
        restore_span(directory, spec, s, step)
        for s in range(spec.num_shards)
    ])
