"""Epoch-numbered membership views: who owns which span, provably.

The deployment layer the paper era never needed (ROADMAP item 3,
DESIGN.md §22): fed_bench ran 10^6 clients/round with S shard
processes sharing one host and STATIC membership — no record of which
host:port serves which span, so a failover or a span split has nowhere
to publish the new truth and no way to invalidate the old one. This
module is that record: a ``MembershipView`` binds an **epoch number**
to the full shard→(host, port, span) assignment, serialized as a
length-checked, CRC-tagged binary record (``encode``/``decode`` — the
wire codec's loud-reject discipline applied to control metadata: every
malformation is a ``ViewError``, never a partial parse), small enough
to ride the existing host-agnostic exchange plane as an opaque payload
(``PeerExchange.publish`` on the control plane — the record is
transport-free bytes, exactly like a gradient frame).

Epochs are the control plane's replay armor. Every membership change —
failover promotion, span split, span merge — is EXACTLY one epoch
increment, and data-plane frames carry their sender's epoch in the wire
header (``utils.wire`` version-2 header, CRC-seeded). The two rules
compose into the handoff invariant DESIGN.md §22 pins:

- a ``MembershipDirectory`` accepts only strictly newer views
  (``install``): replaying a pre-failover view — the epoch-timed
  attacker's cheapest move, resurrecting a dead shard's claim to its
  span — is an attributable ``StaleViewError``;
- a shard serving epoch E rejects frames stamped with any other epoch
  (``wire.decode(expect_epoch=E)``): a client or peer still talking to
  the OLD membership cannot leak rows into the new one's folds.

What the view does NOT do: it is not consensus. One coordinator (the
engine driver / deployment controller) authors views; the directory
and the wire stamps make every consumer's acceptance decision local,
deterministic and attributable. Byzantine-fault-tolerant view AGREEMENT
is the paper's f_ps replication axis, orthogonal to this record format.
"""

import struct
import zlib

from ..federated import sharding
from ..utils import wire

__all__ = [
    "ViewError",
    "StaleViewError",
    "Seat",
    "MembershipView",
    "MembershipDirectory",
    "CONTROL_PLANE",
]

# Membership records ride exchange plane 0 — the pre-plane default every
# role already watches, so a view update needs no new register slots.
CONTROL_PLANE = 0

_MAGIC = b"GV"
_VERSION = 1
# Fixed header: magic, ver, num_seats u8, epoch u32, d u64, crc u32.
_VHDR = struct.Struct("!2sBBIQI")
# Per-seat record: shard u8, port u16, lo u64, hi u64, host_len u8.
_SEAT = struct.Struct("!BHQQB")
_MAX_HOST = 255  # host_len rides a u8 — a DNS name fits with room


class ViewError(ValueError):
    """A membership view record failed validation (bad magic/version,
    truncation, length lie, CRC failure, or a seat table that is not a
    partition). Attributable exactly like ``wire.WireError``: the CRC
    proves the bytes are the author's, so an invalid view is the
    author's fault, never the transport's."""


class StaleViewError(ViewError):
    """A view whose epoch does not advance the directory's — the replay
    of a superseded membership (or a duplicate of the current one).
    Separated from ``ViewError`` because the record itself is
    well-formed; what is Byzantine is WHEN it arrived."""


class Seat:
    """One shard assignment: shard id, owning host:port, column span."""

    __slots__ = ("shard", "host", "port", "lo", "hi")

    def __init__(self, shard, host, port, lo, hi):
        self.shard = sharding.shard_plane(shard)
        self.host = str(host)
        if len(self.host.encode()) > _MAX_HOST:
            raise ViewError(
                f"seat host {self.host[:32]!r}... is "
                f"{len(self.host.encode())} bytes — past the record's "
                f"u8 length field ({_MAX_HOST})"
            )
        self.port = int(port)
        if not 0 <= self.port <= 0xFFFF:
            raise ViewError(f"seat port {port} outside [0, 65535]")
        self.lo = int(lo)
        self.hi = int(hi)
        if not 0 <= self.lo < self.hi:
            raise ViewError(
                f"seat span [{lo}, {hi}) is empty or negative"
            )

    def __eq__(self, other):
        return isinstance(other, Seat) and (
            self.shard, self.host, self.port, self.lo, self.hi
        ) == (other.shard, other.host, other.port, other.lo, other.hi)

    def __repr__(self):
        return (f"<Seat shard={self.shard} {self.host}:{self.port} "
                f"span=[{self.lo},{self.hi})>")


class MembershipView:
    """One epoch's complete shard→seat assignment over a d-vector.

    Construction validates the GLOBAL invariants a consumer relies on
    (the per-seat ones live in ``Seat``): seats are keyed 0..S-1 with
    no gaps or duplicates, their spans tile [0, d) contiguously in
    shard order (the ``ShardSpec`` shape — a hole would orphan
    parameters, an overlap would double-fold them), and the epoch fits
    the wire header's u32 stamp so data frames can carry it.
    """

    __slots__ = ("epoch", "d", "seats")

    def __init__(self, epoch, d, seats):
        self.epoch = wire.check_epoch(epoch)
        self.d = int(d)
        if self.d < 1:
            raise ViewError(f"view d must be >= 1, got {d}")
        seats = tuple(seats)
        if not 1 <= len(seats) <= sharding.MAX_SHARDS:
            raise ViewError(
                f"view must seat 1..{sharding.MAX_SHARDS} shards "
                f"(the wire nibble), got {len(seats)}"
            )
        if [s.shard for s in seats] != list(range(len(seats))):
            raise ViewError(
                f"seats must be keyed 0..{len(seats) - 1} in order, got "
                f"{[s.shard for s in seats]}"
            )
        off = 0
        for s in seats:
            if s.lo != off:
                raise ViewError(
                    f"shard {s.shard} span starts at {s.lo}, expected "
                    f"{off} — spans must tile [0, d) contiguously"
                )
            off = s.hi
        if off != self.d:
            raise ViewError(
                f"seat spans cover [0, {off}) but the view claims "
                f"d={self.d}"
            )
        self.seats = seats

    @property
    def num_shards(self):
        return len(self.seats)

    def spec(self):
        """The view's spans as a ``ShardSpec`` when they match the
        canonical balanced partition (what ``plan_shards`` produces —
        every view this repo's coordinator authors), else ViewError:
        the engine's slicing assumes the balanced shape."""
        spec = sharding.plan_shards(self.d, self.num_shards)
        if tuple(spec.spans) != tuple((s.lo, s.hi) for s in self.seats):
            raise ViewError(
                "view spans are not the canonical balanced partition"
            )
        return spec

    # -- codec ---------------------------------------------------------------

    def encode(self):
        """Serialize to one length-checked, CRC-tagged record. The CRC
        covers the body (every seat) seeded with the epoch bytes —
        the same tamper-evidence construction as the wire codec's v2
        header, so a relay cannot restamp a view's epoch either."""
        body = bytearray()
        for s in self.seats:
            host = s.host.encode()
            body += _SEAT.pack(s.shard, s.port, s.lo, s.hi, len(host))
            body += host
        crc = zlib.crc32(bytes(body),
                         zlib.crc32(struct.pack("!I", self.epoch)))
        return _VHDR.pack(
            _MAGIC, _VERSION, len(self.seats), self.epoch, self.d, crc
        ) + bytes(body)

    @classmethod
    def decode(cls, buf):
        """Parse + validate one record; every malformation — truncation
        at any depth, a host-length lie, trailing bytes, CRC failure,
        or seat tables violating the partition invariants — raises
        ``ViewError`` before any view object exists."""
        buf = bytes(buf)
        if len(buf) < _VHDR.size:
            raise ViewError(
                f"truncated view record: {len(buf)} bytes is shorter "
                f"than the {_VHDR.size}-byte header"
            )
        magic, ver, n_seats, epoch, d, crc = _VHDR.unpack_from(buf)
        if magic != _MAGIC:
            raise ViewError(f"bad view magic {magic!r}")
        if ver != _VERSION:
            raise ViewError(f"unsupported view version {ver}")
        body = buf[_VHDR.size:]
        if zlib.crc32(body, zlib.crc32(struct.pack("!I", epoch))) != crc:
            raise ViewError("view body CRC mismatch")
        seats, off = [], 0
        for _ in range(n_seats):
            if off + _SEAT.size > len(body):
                raise ViewError(
                    f"truncated seat table: {len(body)} body bytes "
                    f"cannot hold seat {len(seats)}'s fixed fields"
                )
            shard, port, lo, hi, hlen = _SEAT.unpack_from(body, off)
            off += _SEAT.size
            if off + hlen > len(body):
                raise ViewError(
                    f"seat {len(seats)} claims a {hlen}-byte host but "
                    f"only {len(body) - off} body bytes remain"
                )
            try:
                host = body[off:off + hlen].decode()
            except UnicodeDecodeError as e:
                raise ViewError(f"seat {len(seats)} host is not UTF-8: {e}")
            off += hlen
            try:
                seats.append(Seat(shard, host, port, lo, hi))
            except (ViewError, TypeError, ValueError) as e:
                raise ViewError(f"seat {len(seats)} invalid: {e}")
        if off != len(body):
            raise ViewError(
                f"{len(body) - off} trailing bytes after the seat table"
            )
        try:
            return cls(epoch, d, seats)
        except (TypeError, ValueError) as e:
            # wire.check_epoch raises bare TypeError/ValueError — a
            # decoded record's failures must all be ViewError.
            if isinstance(e, ViewError):
                raise
            raise ViewError(str(e))

    @classmethod
    def for_engine(cls, engine, *, host="127.0.0.1", ports=None):
        """The canonical view of a ``FedRoundEngine``'s current
        membership: one seat per shard over its spec's spans, at the
        engine's epoch (0 when epoch enforcement is off — a view can
        describe a pre-epoch deployment, it just cannot protect it)."""
        spans = engine.spec.spans
        ports = list(ports) if ports is not None else [0] * len(spans)
        if len(ports) != len(spans):
            raise ViewError(
                f"{len(ports)} ports for {len(spans)} shards"
            )
        return cls(
            engine.epoch if engine.epoch is not None else 0,
            engine.spec.d,
            [Seat(s, host, ports[s], lo, hi)
             for s, (lo, hi) in enumerate(spans)],
        )

    def __eq__(self, other):
        return isinstance(other, MembershipView) and (
            self.epoch == other.epoch and self.d == other.d
            and self.seats == other.seats
        )

    def __repr__(self):
        return (f"<MembershipView epoch={self.epoch} d={self.d} "
                f"shards={self.num_shards}>")


class MembershipDirectory:
    """A consumer's local, monotone record of the current view.

    ``install`` accepts only strictly newer epochs — the replay ban:
    once the directory has seen epoch E, every view at epoch <= E is
    ``StaleViewError`` forever (the epoch-timed attacker cannot
    resurrect the membership that still listed its crashed shard).
    Rejections are counted and the last reason kept, mirroring the wire
    plane's ban-evidence accounting.
    """

    def __init__(self, view=None):
        self.view = None
        self.installs = 0
        self.rejects = 0
        self.last_reject = None
        if view is not None:
            self.install(view)

    @property
    def epoch(self):
        return None if self.view is None else self.view.epoch

    def install(self, view):
        """Adopt ``view`` iff it strictly advances the epoch; returns
        it. Raises ``StaleViewError`` (counted) otherwise."""
        if not isinstance(view, MembershipView):
            raise TypeError(
                f"expected a MembershipView, got {type(view).__name__}"
            )
        if self.view is not None and view.epoch <= self.view.epoch:
            self.rejects += 1
            self.last_reject = (
                f"view epoch {view.epoch} does not advance the "
                f"directory's epoch {self.view.epoch} — stale/replayed "
                "membership, attributable to its author"
            )
            raise StaleViewError(self.last_reject)
        self.view = view
        self.installs += 1
        return view

    def install_frame(self, buf):
        """Decode + install a serialized record (the exchange-plane
        arrival path). Malformed records raise ``ViewError`` WITHOUT
        counting as stale — they never carried an admissible epoch."""
        return self.install(MembershipView.decode(buf))
