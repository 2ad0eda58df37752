"""Typed wire codec for the host plane (DESIGN.md §11).

The cluster driver's frames used to be bare ``ndarray.tobytes()`` — the
reference's wire format (garfield.proto:24-33) — which (a) ships every
gradient/model/gossip frame at f32 width even though the on-mesh pipeline
already proved bf16 gradients converge (PERF.md r3), and (b) gives the
receiver nothing to validate beyond total length, so a Byzantine process
could only be caught by a wrong-size frame. Every data frame now carries a
16-byte self-describing header:

    magic   2s   b"GW"
    ver     u8   1
    dtype   u8   low nibble: 0 = f32, 1 = bf16, 2 = int8, 3 = int4,
                 4 = topk; HIGH nibble: plane tag
    elems   u64  logical float32 element count
    crc32   u32  zlib.crc32 of the payload bytes

Round 18 (DESIGN.md §20) adds three LOSSY payload schemes behind new
low-nibble tags. int8/int4 are linear per-block quantization — payload
``[u32 block || ceil(elems/block) f32 scales || codes]`` with a
symmetric grid per block; int4 packs biased nibbles (code + 8, so the
honest grid is [1, 15] and nibble 0 is ban evidence). topk is
sparsification — ``k`` little-endian ``(u32 index, f32 value)`` pairs
with strictly-increasing indices ``< elems``. Every semantic violation
(out-of-range scale, a block prefix past the element count, an int8
code -128 / int4 nibble 0 outside the honest grid,
duplicate/descending/out-of-bounds index) raises the same ``WireError``
as a CRC failure: the CRC proves the bytes are the sender's, so invalid
*content* is attributable Byzantine evidence feeding the PR 4
quorum-exclusion ban path. The decoder never allocates more than
O(elems) either — the block prefix is bounded by the element count and
a sparse frame's claimed dense size must be pinned (``expect_elems``)
or bounded (``max_elems``) by the consumer, so no CRC-valid frame can
demand a multi-GB scatter or dequant pad.

The dtype byte's high nibble is the **plane tag** (DESIGN.md §15): only
two of its 256 values were ever used, so the spare bits carry which
logical exchange plane (gradient / model / control) the frame belongs to
— the self-describing half of the per-plane register slots in
``utils.exchange`` (the transport header routes; this tag lets any
consumer label bytes per plane without context). Plane 0 frames are
byte-identical to the pre-plane format, so every committed trajectory
and artifact pins carry over; decoders reject only unknown LOW-nibble
dtype tags, never a nonzero plane.

Round 20 (DESIGN.md §22) adds the **membership epoch** behind a second
header version: ``encode(..., epoch=E)`` emits a 20-byte ``ver=2``
header carrying the sender's control-plane epoch as a u32 between the
element count and the CRC — and the CRC is SEEDED with the epoch bytes,
so the epoch claim is under the same integrity tag as the payload (a
relay cannot restamp a frame's epoch without producing a CRC mismatch;
a stale epoch is provably the SENDER's stale epoch). ``epoch=None``
(the default) emits the version-1 header unchanged — every committed
artifact and trajectory pin predates epochs and stays byte-identical.
Consumers on an epoch-checked plane pass ``expect_epoch=E``: a frame
stamped with any other epoch — or carrying no epoch at all — raises the
same attributable ``WireError`` as a cross-shard plane stamp
(controlplane/membership.py owns what E currently is; this codec only
enforces it).

``GARFIELD_WIRE_DTYPE=f32|bf16|int8|int4`` selects the SEND width
(default f32) and ``GARFIELD_WIRE_TOPK=<divisor>`` (default 0 = off)
overlays top-k sparsification on the GRADIENT plane (cluster policy:
model/gossip broadcasts are absolute state — a sparse model frame would
zero most parameters on any catch-up read, see DESIGN.md §20 — so they
keep the dense width). bf16 halves every gradient, model and gossip
frame on the DCN; int8/int4 cut ~4x/~8x; top-k at the default divisor
32 cuts 16x. The f32 setting keeps the payload bytes BYTE-IDENTICAL to
the pre-codec ``tobytes()`` format (modulo the header), so existing
trajectory pins carry over. Decoding is dtype-driven by the header,
never by the local setting — mixed-width deployments interoperate (each
peer chooses its own send width, exactly like per-link compression).

The bf16 cast is pure numpy (no jax dependency — the exchange bench and
its child processes stay jax-free): round-to-nearest-even on the high 16
bits of the f32 bit pattern, the same rounding XLA's ``convert`` uses, so
a host-decoded gradient matches what the on-mesh bf16 pipeline would have
produced for the same value. Restoring f32 is the exact ``u16 << 16``
view — bf16 -> f32 is lossless.

Why bf16-on-wire is safe UPSTREAM of the GAR: the rules aggregate at f32
(`aggregators/_common` Gram accumulation, cclip's f32 center iteration),
so wire quantization is a bounded per-coordinate perturbation of the
rule's INPUT rows — a strictly weaker disturbance than the Byzantine
value faults the f budget already absorbs, and the honest rows all carry
the same quantization so relative geometry (distances, medians) is
preserved to bf16 precision. The convergence smoke in tests/test_cluster
runs the lie attack over both widths.
"""

import os
import struct
import threading
import zlib

import numpy as np

__all__ = [
    "WIRE_DTYPES",
    "WIRE_SCHEMES",
    "WireError",
    "ErrorFeedback",
    "wire_dtype",
    "wire_topk",
    "wire_fused",
    "wire_batch_decode",
    "ingest_threads",
    "topk_k",
    "check_plane",
    "check_epoch",
    "encode",
    "decode",
    "decode_into",
    "decode_batch_into",
    "frame_plane",
    "frame_scheme",
    "frame_elems",
    "frame_epoch",
    "frame_nbytes",
    "HEADER_NBYTES",
    "HEADER2_NBYTES",
    "MAX_PLANE",
    "MAX_EPOCH",
    "QUANT_BLOCK",
    "DEFAULT_TOPK_DIV",
]

_HDR = struct.Struct("!2sBBQI")
HEADER_NBYTES = _HDR.size  # 16
# Round 20: the epoch-stamped header (ver=2) — same fields plus a u32
# membership epoch between the element count and the CRC. The epoch
# bytes SEED the payload CRC (see module docstring), so the stamp is
# tamper-evident, not advisory.
_HDR2 = struct.Struct("!2sBBQII")
HEADER2_NBYTES = _HDR2.size  # 20
_EPOCH = struct.Struct("!I")
_MAGIC = b"GW"
_VERSION = 1
_VERSION_EPOCH = 2
# Epochs ride a u32: 4 billion membership changes outlives any
# deployment, and a wider field would grow EVERY epoch-stamped frame.
MAX_EPOCH = 0xFFFFFFFF
_TAG_F32 = 0
_TAG_BF16 = 1
# Round 18 (DESIGN.md §20): lossy compressed payload schemes behind new
# LOW-nibble tags — the high (plane/shard) nibble semantics are
# untouched, and tags 0/1 frames stay byte-identical to the PR 4 format.
_TAG_INT8 = 2
_TAG_INT4 = 3
_TAG_TOPK = 4
# Dense send widths selectable via GARFIELD_WIRE_DTYPE; "topk" is a
# separate axis (GARFIELD_WIRE_TOPK) because it composes with a dense
# width per plane rather than replacing it everywhere.
WIRE_DTYPES = ("f32", "bf16", "int8", "int4")
WIRE_SCHEMES = WIRE_DTYPES + ("topk",)
_ITEMSIZE = {_TAG_F32: 4, _TAG_BF16: 2}
_TAG_NAME = {_TAG_F32: "f32", _TAG_BF16: "bf16", _TAG_INT8: "int8",
             _TAG_INT4: "int4", _TAG_TOPK: "topk"}
# Plane tag (high nibble of the dtype byte — see the module docstring).
MAX_PLANE = 0x0F
# Linear-quantization block: one f32 scale per QUANT_BLOCK coordinates.
# 1024 keeps the scale overhead under 0.4% of the codes while keeping a
# single hot coordinate from flattening the whole frame's grid (a
# per-frame scale hands one outlier coordinate veto power over every
# other coordinate's resolution).
QUANT_BLOCK = 1024
# Default top-k sparsification divisor: keep ceil(d / 32) coordinates
# (each an 8-byte index+value pair -> 16x fewer bytes than f32).
DEFAULT_TOPK_DIV = 32


class WireError(ValueError):
    """A frame failed codec validation (bad magic/version/dtype tag,
    truncation, length/element-count mismatch, or CRC failure). On the
    cluster's quorum paths this is BAN EVIDENCE: a Byzantine process
    controls its wire bytes, and a frame that fails the codec proves its
    sender faulty exactly like the old wrong-length check."""


def wire_dtype():
    """The configured send width (``GARFIELD_WIRE_DTYPE``, default f32)."""
    d = os.environ.get("GARFIELD_WIRE_DTYPE", "f32").strip().lower()
    if d not in WIRE_DTYPES:
        raise ValueError(
            f"GARFIELD_WIRE_DTYPE must be one of {WIRE_DTYPES}, got {d!r}"
        )
    return d


def wire_topk():
    """The configured top-k sparsification DIVISOR (``GARFIELD_WIRE_TOPK``,
    default 0 = off): gradient-plane frames keep the ceil(d / divisor)
    largest-magnitude coordinates. A divisor, not an absolute k, so one
    setting scales across every frame size in a deployment."""
    v = os.environ.get("GARFIELD_WIRE_TOPK", "0").strip()
    try:
        div = int(v)
    except ValueError:
        raise ValueError(
            f"GARFIELD_WIRE_TOPK must be a non-negative integer divisor, "
            f"got {v!r}"
        )
    if div < 0:
        raise ValueError(
            f"GARFIELD_WIRE_TOPK must be >= 0 (0 = off), got {div}"
        )
    return div


def wire_fused():
    """Whether frame consumers take the fused decode-into-buffer path
    (``GARFIELD_WIRE_FUSED_DECODE``, default on): ``decode_into``
    straight into the streaming wave buffer / a reusable shard scratch
    instead of materializing a fresh O(elems) array per frame. Purely a
    memory-traffic knob — both paths are bitwise-identical and run the
    same validation (pinned in tests/test_wire.py), so turning it off is
    only for isolating the fused path when debugging."""
    return os.environ.get(
        "GARFIELD_WIRE_FUSED_DECODE", "1"
    ).lower() not in ("", "0", "false")


def wire_batch_decode():
    """Whether bulk frame consumers take the batched decode path
    (``GARFIELD_WIRE_BATCH_DECODE``, default on): ``push_frames`` /
    multi-frame harvests route through ``decode_batch_into`` — one
    vectorized header screen + run-grouped slab dequant — instead of a
    per-frame ``decode_into`` loop. Purely a host-CPU knob: both paths
    are bitwise-identical and raise the same per-frame ``WireError``s
    (pinned in tests/test_wire.py), so turning it off is only for
    isolating the batch path when debugging."""
    return os.environ.get(
        "GARFIELD_WIRE_BATCH_DECODE", "1"
    ).lower() not in ("", "0", "false")


def ingest_threads():
    """Worker-thread count for the batch decoder's CRC pass
    (``GARFIELD_INGEST_THREADS``, default 0 = inline). ``zlib.crc32``
    releases the GIL on sizeable buffers, so on a multi-core host a
    small pool can overlap the integrity scan of wave w+1 with the fold
    of wave w; on the 1-core bench container it only adds dispatch
    overhead (measured in DESIGN.md §24), hence off by default."""
    v = os.environ.get("GARFIELD_INGEST_THREADS", "0").strip()
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"GARFIELD_INGEST_THREADS must be a non-negative integer, "
            f"got {v!r}"
        )
    if n < 0:
        raise ValueError(
            f"GARFIELD_INGEST_THREADS must be >= 0 (0 = inline), got {n}"
        )
    return n


# Shared CRC pool for decode_batch_into: built lazily at first use and
# reused across calls (a per-batch pool would pay thread spawn on every
# wave, drowning the overlap it exists to buy). Guarded by a lock —
# batch decodes run from exchange waiter threads concurrently.
_CRC_POOL = {"n": 0, "exec": None}
_CRC_POOL_LOCK = threading.Lock()


def _crc_pool(n):
    with _CRC_POOL_LOCK:
        if _CRC_POOL["exec"] is None or _CRC_POOL["n"] != n:
            from concurrent.futures import ThreadPoolExecutor

            if _CRC_POOL["exec"] is not None:
                _CRC_POOL["exec"].shutdown(wait=False)
            _CRC_POOL["exec"] = ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="wire-crc"
            )
            _CRC_POOL["n"] = n
        return _CRC_POOL["exec"]


def topk_k(elems, div):
    """Kept-coordinate count for an ``elems``-element frame at divisor
    ``div`` — ceil(elems / div), floored at 1. The single shared
    definition (host codec AND the in-graph twin, parallel/compress.py)
    so the emulated and shipped sparsity cannot drift."""
    elems = int(elems)
    div = int(div)
    if div < 1:
        raise ValueError(f"top-k divisor must be >= 1, got {div}")
    if elems <= 0:
        return 0
    return max(1, -(-elems // div))


def _f32_to_bf16(vec):
    """Round-to-nearest-even truncation of f32 to its high 16 bits (the
    uint32 >> 16 view trick; NaN payload bits survive because the quiet
    bit lives in the kept half)."""
    u = vec.view(np.uint32)
    return ((u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
            >> np.uint32(16)).astype(np.uint16)


def _bf16_to_f32(u16):
    return (u16.astype(np.uint32) << np.uint32(16)).view(np.float32)


def check_plane(plane, what="plane"):
    """Validate a plane/shard tag for the header's spare nibble; returns
    it as an int. The tag has FOUR bits — the federated engine rides
    shard ids on it (federated/sharding.py) — so an id past 15 must
    fail HERE, at stamp time, with the capacity named: masking it into
    the nibble would silently deliver one shard's frames to another
    (the exact cross-shard mis-fold the stamp exists to make
    attributable). Non-integral tags (bools, floats) are rejected too:
    ``int(3.7)`` truncating to plane 3 is the same silent corruption.
    """
    if isinstance(plane, bool) or not isinstance(plane, (int, np.integer)):
        raise TypeError(
            f"{what} tag must be an integer, got {plane!r}"
        )
    plane = int(plane)
    if not 0 <= plane <= MAX_PLANE:
        raise ValueError(
            f"{what} tag {plane} does not fit the wire header's spare "
            f"nibble [0, {MAX_PLANE}] — a larger plane/shard space needs "
            "a wider header (new wire version), not a truncated tag"
        )
    return plane


def check_epoch(epoch, what="epoch"):
    """Validate a membership epoch for the v2 header's u32 field;
    returns it as an int. Same loud-failure contract as ``check_plane``:
    a non-integral epoch (bool, float) or one past the u32 would either
    truncate into a DIFFERENT epoch — exactly the stale/replayed-epoch
    confusion the stamp exists to make attributable — or overflow the
    header, so both fail at stamp time."""
    if isinstance(epoch, bool) or not isinstance(epoch, (int, np.integer)):
        raise TypeError(f"{what} must be an integer, got {epoch!r}")
    epoch = int(epoch)
    if not 0 <= epoch <= MAX_EPOCH:
        raise ValueError(
            f"{what} {epoch} does not fit the wire header's u32 epoch "
            f"field [0, {MAX_EPOCH}]"
        )
    return epoch


def _quant_payload(vec, qmax, block):
    """Linear per-block quantization payload: ``[u32 block || f32
    scales || codes]`` with symmetric grid ``scale = max|x| / qmax`` per
    block and round-to-nearest-even codes. An honest sender MUST fail
    loudly on non-finite input (the scale would be inf/NaN and the
    receiver's range check would turn the honest frame into ban
    evidence); raising here keeps the fault local."""
    if vec.size and not np.isfinite(vec).all():
        raise ValueError(
            "cannot quantize a non-finite vector — an inf/NaN scale "
            "would make this honest frame indistinguishable from a "
            "Byzantine one on the receiver's range check"
        )
    nblocks = -(-vec.size // block) if vec.size else 0
    pad = nblocks * block - vec.size
    x = np.pad(vec, (0, pad)) if pad else vec
    xb = x.reshape(nblocks, block) if nblocks else x.reshape(0, block)
    scales = (np.max(np.abs(xb), axis=1) / np.float32(qmax)).astype(
        np.float32
    )
    safe = np.where(scales > 0, scales, np.float32(1.0))
    codes = np.clip(
        np.rint(xb / safe[:, None]), -qmax, qmax
    ).astype(np.int8).reshape(-1)[: vec.size]
    return (
        np.array([block], "<u4").tobytes() + scales.tobytes(), codes
    )


def _dequant(codes, scales, block, elems):
    nblocks = scales.size
    pad = nblocks * block - elems
    c = np.pad(codes.astype(np.float32), (0, pad)) if pad else \
        codes.astype(np.float32)
    out = (c.reshape(nblocks, block) * scales[:, None].astype(np.float32))
    return out.reshape(-1)[:elems].astype(np.float32)


_PAIR = np.dtype([("i", "<u4"), ("v", "<f4")])


def encode(vec, dtype=None, *, plane=0, epoch=None, k=None, keep_from=None,
           block=QUANT_BLOCK):
    """Encode a flat float32 vector as one typed frame.

    ``dtype`` overrides the env-configured send width, and may also be
    ``"topk"`` (round 18): the payload becomes ``k`` sorted
    ``(u32 index, f32 value)`` pairs — ``k`` explicit, or derived from
    the ``GARFIELD_WIRE_TOPK`` divisor (``DEFAULT_TOPK_DIV`` when
    unset; an explicit ``k=0`` ships no head pairs — only the dense
    tail rides). ``keep_from`` marks the start of an always-kept dense tail
    (the ``[grad || stats]`` frames' BatchNorm segment: state, not an
    additive signal — sparsifying it away would corrupt the robust-stats
    fold, so its coordinates ride along as ordinary pairs). int8/int4
    are linear per-block quantization (``block`` coordinates per f32
    scale, carried in the payload and range-checked on decode). f32
    payload bytes are the exact ``vec.tobytes()`` of the pre-codec
    format. ``plane`` (0..15) stamps the header's spare high-nibble
    plane tag — plane 0 keeps the frame byte-identical to the pre-plane
    format. Out-of-range or non-integral tags fail loudly
    (``check_plane``), never truncate.

    ``epoch`` (round 20) stamps the sender's membership epoch into a
    version-2 header, with the epoch bytes seeding the payload CRC so
    the claim is tamper-evident; ``epoch=None`` (default) emits the
    version-1 header byte-identical to every committed frame.
    """
    vec = np.ascontiguousarray(np.asarray(vec).reshape(-1), np.float32)
    dtype = wire_dtype() if dtype is None else dtype
    plane = check_plane(plane)
    if dtype == "bf16":
        payload = _f32_to_bf16(vec).tobytes()
        tag = _TAG_BF16
    elif dtype == "f32":
        payload = vec.tobytes()
        tag = _TAG_F32
    elif dtype in ("int8", "int4"):
        block = int(block)
        if block < 1:
            raise ValueError(f"quantization block must be >= 1, got {block}")
        # Clamp the block to the vector: past vec.size it only grows the
        # dequant pad (nblocks is 1 either way, so scales and codes — and
        # therefore the decoded values — are identical), and the decoder
        # rejects block > elems as an allocation bomb, so the clamp keeps
        # every honest frame inside that bound.
        block = min(block, max(vec.size, 1))
        qmax = 127 if dtype == "int8" else 7
        head, codes = _quant_payload(vec, qmax, block)
        if dtype == "int8":
            payload = head + codes.tobytes()
            tag = _TAG_INT8
        else:
            nib = (codes.astype(np.int16) + 8).astype(np.uint8)
            if nib.size % 2:
                nib = np.append(nib, np.uint8(8))  # pad nibble = code 0
            payload = head + (nib[0::2] | (nib[1::2] << 4)).tobytes()
            tag = _TAG_INT4
    elif dtype == "topk":
        head_n = vec.size if keep_from is None else int(keep_from)
        if not 0 <= head_n <= vec.size:
            raise ValueError(
                f"keep_from must be in [0, {vec.size}], got {keep_from}"
            )
        if k is None:
            k = topk_k(head_n, wire_topk() or DEFAULT_TOPK_DIV)
        k = int(min(max(k, 0), head_n))
        if k and not np.isfinite(vec[:head_n]).all():
            # NaN never compares > anything: argpartition would silently
            # demote real coordinates below garbage. Same honest-sender
            # loud-failure contract as the quantizers.
            raise ValueError("cannot top-k sparsify a non-finite vector")
        if k == 0:
            # No head pairs — only the always-kept dense tail rides
            # (argpartition with kth == head_n would be out of bounds).
            idx = np.arange(head_n, vec.size, dtype=np.uint32)
        elif k >= head_n:
            idx = np.arange(vec.size, dtype=np.uint32)
        else:
            top = np.argpartition(np.abs(vec[:head_n]), head_n - k)[
                head_n - k:
            ]
            idx = np.concatenate([
                np.sort(top).astype(np.uint32),
                np.arange(head_n, vec.size, dtype=np.uint32),
            ])
        pairs = np.empty(idx.size, _PAIR)
        pairs["i"] = idx
        pairs["v"] = vec[idx.astype(np.int64)]
        payload = pairs.tobytes()
        tag = _TAG_TOPK
    else:
        raise ValueError(f"unknown wire dtype {dtype!r}")
    if epoch is None:
        return _HDR.pack(
            _MAGIC, _VERSION, tag | (plane << 4), vec.size,
            zlib.crc32(payload),
        ) + payload
    epoch = check_epoch(epoch)
    return _HDR2.pack(
        _MAGIC, _VERSION_EPOCH, tag | (plane << 4), vec.size, epoch,
        zlib.crc32(payload, zlib.crc32(_EPOCH.pack(epoch))),
    ) + payload


def decode(buf, *, expect_plane=None, expect_elems=None, max_elems=None,
           expect_epoch=None):
    """Decode a typed frame back to a float32 vector; raises WireError.

    Validation order matters for the ban path: header shape first (magic,
    version, dtype tag), then the length/element-count consistency, then
    the CRC — every random bit flip or truncation of a valid frame fails
    at least one of these (a payload flip breaks the CRC; a header flip
    breaks magic/version/tag/length), so corrupted bytes can never reach
    a GAR (fuzzed in tests/test_wire.py).

    ``expect_plane`` makes the plane/shard stamp load-bearing for the
    federated shard plane (DESIGN.md §19): a consumer that owns plane
    ``s`` rejects frames stamped for any other plane as a codec failure
    — and since the stamp sits in the sender-controlled header, the
    mismatch is attributable ban evidence against the SENDER (a correct
    transport cannot restamp it without also failing magic/CRC), not a
    routing accident to shrug off.

    ``expect_elems`` pins the header's dense element count. For the
    dense and quantized schemes the payload length already corroborates
    ``elems``, but a SPARSE frame's dense size is a bare header claim:
    the k pairs are consistent with any ``elems > idx[-1]``, so a
    Byzantine sender (or a bit flip in the u64) could cheaply demand a
    multi-GB ``np.zeros(elems)`` scatter target. Quorum consumers know
    their plane's d and MUST pass it (``cluster._frame_transform``
    does); the mismatch rejects BEFORE any allocation, as the same
    attributable WireError as the old wrong-length frame.

    ``max_elems`` is the inexact form of the same pin, for consumers
    whose frames legitimately vary in size (the federated shard plane's
    multi-row frames: any whole number of rows up to the cohort) — a
    header claiming more than the bound rejects before any allocation.
    Every Byzantine-facing decode site must pass one of the two: a
    sparse frame decoded with neither is an unbounded allocation the
    sender controls.

    ``expect_epoch`` (round 20, DESIGN.md §22) makes the v2 header's
    membership-epoch stamp load-bearing: a consumer serving membership
    epoch E rejects frames stamped with any OTHER epoch — stale (a
    pre-failover member replaying into the new membership) or ahead (a
    forged view claim) — and rejects epoch-less version-1 frames too,
    so a sender cannot dodge the check by omitting the stamp. The epoch
    bytes seed the CRC, so the mismatch is attributable to the sender
    exactly like a plane-stamp mismatch.
    """
    tag, elems, payload = _checked_frame(
        buf, expect_plane, expect_elems, max_elems, expect_epoch
    )
    if tag == _TAG_BF16:
        return _bf16_to_f32(np.frombuffer(payload, np.uint16))
    if tag == _TAG_F32:
        return np.frombuffer(payload, np.float32)
    if tag in (_TAG_INT8, _TAG_INT4):
        codes, scales, block = _checked_quant(payload, tag, elems)
        return _dequant(codes, scales, block, elems)
    pairs = _checked_pairs(payload, elems)
    out = np.zeros(elems, np.float32)
    out[pairs["i"].astype(np.int64)] = pairs["v"]
    return out


def _checked_frame(buf, expect_plane, expect_elems, max_elems,
                   expect_epoch=None):
    """Shared header + structural + CRC validation of ``decode`` and
    ``decode_into``: returns ``(low-nibble tag, elems, payload)`` only
    for a frame whose bytes are provably the sender's and whose payload
    length is consistent with the header. Semantic payload validation
    (scale range, code grid, index ordering) is per-tag
    (``_checked_quant`` / ``_checked_pairs``) and also precedes any
    output construction."""
    if len(buf) < HEADER_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER_NBYTES}-byte header"
        )
    magic, ver, tag, elems, crc = _HDR.unpack_from(buf)
    if magic != _MAGIC:
        raise WireError(f"bad magic {magic!r}")
    epoch = None
    hdr_nbytes = HEADER_NBYTES
    if ver == _VERSION_EPOCH:
        if len(buf) < HEADER2_NBYTES:
            raise WireError(
                f"truncated frame: {len(buf)} bytes is shorter than the "
                f"{HEADER2_NBYTES}-byte epoch-stamped header"
            )
        magic, ver, tag, elems, epoch, crc = _HDR2.unpack_from(buf)
        hdr_nbytes = HEADER2_NBYTES
    elif ver != _VERSION:
        raise WireError(f"unsupported wire version {ver}")
    if expect_plane is not None and (tag >> 4) != check_plane(
        expect_plane, "expect_plane"
    ):
        raise WireError(
            f"frame stamped for plane/shard {tag >> 4} arrived at a "
            f"consumer of plane/shard {int(expect_plane)} — cross-shard "
            "delivery, attributable to the sender"
        )
    if expect_epoch is not None:
        exp = check_epoch(expect_epoch, "expect_epoch")
        if epoch is None:
            raise WireError(
                f"frame carries no membership epoch but the consumer "
                f"serves epoch {exp} — pre-epoch (v1) frames are not "
                "admissible on an epoch-checked plane, attributable to "
                "the sender"
            )
        if epoch != exp:
            raise WireError(
                f"frame stamped with membership epoch {epoch} arrived at "
                f"a consumer serving epoch {exp} — "
                f"{'stale' if epoch < exp else 'future'}-epoch delivery, "
                "attributable to the sender"
            )
    tag &= 0x0F  # the high nibble is the plane tag (frame_plane)
    if tag not in _TAG_NAME:
        raise WireError(f"unknown dtype tag {tag}")
    if expect_elems is not None and elems != int(expect_elems):
        raise WireError(
            f"frame promises {elems} elements, consumer expected "
            f"{int(expect_elems)}"
        )
    if max_elems is not None and elems > int(max_elems):
        raise WireError(
            f"frame promises {elems} elements, past the consumer's "
            f"bound of {int(max_elems)}"
        )
    payload = buf[hdr_nbytes:]
    # Structural length checks come BEFORE the CRC (cheap, and a
    # truncated frame should say "truncated", not "CRC mismatch"); the
    # semantic payload checks (scale range, index ordering) come AFTER —
    # a frame whose bytes survive the CRC but whose *content* is invalid
    # is exactly the attributable Byzantine case (only the sender could
    # have produced those bytes), and must raise the same WireError that
    # feeds the quorum-exclusion ban path.
    if tag in _ITEMSIZE:
        if len(payload) != elems * _ITEMSIZE[tag]:
            raise WireError(
                f"payload is {len(payload)} bytes but the header promises "
                f"{elems} elements of {_ITEMSIZE[tag]} bytes"
            )
    elif tag in (_TAG_INT8, _TAG_INT4):
        if len(payload) < 4:
            raise WireError(
                f"quantized payload is {len(payload)} bytes — too short "
                "for the u32 block-size prefix"
            )
    else:  # _TAG_TOPK
        if len(payload) % _PAIR.itemsize:
            raise WireError(
                f"sparse payload is {len(payload)} bytes — not a whole "
                f"number of {_PAIR.itemsize}-byte (index, value) pairs"
            )
        if len(payload) // _PAIR.itemsize > elems:
            raise WireError(
                f"sparse payload carries {len(payload) // _PAIR.itemsize} "
                f"pairs but the header promises only {elems} elements"
            )
    # The v2 CRC is seeded with the epoch bytes (module docstring): an
    # in-flight restamp of the epoch field fails here, so an epoch
    # mismatch that passes the CRC is provably the sender's own stamp.
    seed = 0 if epoch is None else zlib.crc32(_EPOCH.pack(epoch))
    if zlib.crc32(payload, seed) != crc:
        raise WireError("payload CRC mismatch")
    return tag, int(elems), payload


def _checked_quant(payload, tag, elems):
    """Semantic validation of a quantized payload (block bound, scale
    range, honest-grid codes) — every check the dequant step relies on,
    BEFORE any dequant output is written, so ``decode_into`` leaves its
    target untouched on ban evidence. Returns ``(codes, scales, block)``."""
    block = int(np.frombuffer(payload, "<u4", count=1)[0])
    if block < 1:
        raise WireError(f"quantization block {block} must be >= 1")
    if block > max(int(elems), 1):
        # An honest encoder clamps its block to the vector (same
        # values, see encode); a larger block is an allocation bomb —
        # the dequant pad is nblocks*block elements, which a
        # block=0xFFFFFFFF prefix on a tiny frame turns into ~17 GB.
        # This bound keeps it under 2x elems.
        raise WireError(
            f"quantization block {block} exceeds the frame's "
            f"{elems} elements"
        )
    nblocks = -(-int(elems) // block) if elems else 0
    codes_nbytes = (
        int(elems) if tag == _TAG_INT8 else (int(elems) + 1) // 2
    )
    if len(payload) != 4 + nblocks * 4 + codes_nbytes:
        raise WireError(
            f"quantized payload is {len(payload)} bytes but "
            f"{elems} elements at block {block} need "
            f"{4 + nblocks * 4 + codes_nbytes}"
        )
    scales = np.frombuffer(payload, "<f4", count=nblocks, offset=4)
    # Range check (the ISSUE's scale gate): a NaN/inf or negative
    # scale lets a Byzantine sender smuggle unbounded or
    # sign-flipped rows through an otherwise-valid frame.
    if nblocks and not (np.isfinite(scales).all()
                        and (scales >= 0).all()):
        raise WireError(
            "quantization scale out of range (non-finite or negative)"
        )
    raw = np.frombuffer(payload, np.uint8, offset=4 + nblocks * 4)
    if tag == _TAG_INT8:
        codes = raw.view(np.int8)
        if codes.size and (codes == -128).any():
            # The symmetric grid is [-127, 127] (encode clips at
            # qmax): code -128 is unreachable by any honest encoder
            # — ban evidence exactly like int4's nibble 0.
            raise WireError(
                "int8 code -128 is outside the symmetric grid"
            )
    else:
        nib = np.empty(raw.size * 2, np.uint8)
        nib[0::2] = raw & 0x0F
        nib[1::2] = raw >> 4
        nib = nib[: int(elems)]
        if nib.size and (nib == 0).any():
            # The biased-nibble grid is [1, 15] (code -7..7 + 8);
            # nibble 0 is unreachable by any honest encoder.
            raise WireError("int4 nibble 0 is outside the biased grid")
        codes = nib.astype(np.int16) - 8
    return codes, scales, block


def _checked_pairs(payload, elems):
    """Semantic validation of a sparse payload: the (index, value) pairs
    ready to scatter. Index validation is the sparse scheme's ban teeth —
    without it a Byzantine sender could double-count a coordinate
    (duplicate index) or write out of bounds."""
    pairs = np.frombuffer(payload, _PAIR)
    idx = pairs["i"]
    if idx.size:
        if int(idx[-1]) >= elems:
            raise WireError(
                f"sparse index {int(idx[-1])} out of bounds for "
                f"{elems} elements"
            )
        if idx.size > 1 and not (np.diff(idx.astype(np.int64)) > 0).all():
            raise WireError(
                "sparse indices must be strictly increasing "
                "(duplicate or descending index)"
            )
    return pairs


def decode_into(buf, out, *, expect_plane=None, expect_elems=None,
                max_elems=None, expect_epoch=None):
    """Decode a typed frame DIRECTLY into a preallocated float32 row;
    returns the element count written (``out[:elems]``).

    The fused half of the streaming ingest path (DESIGN.md §21):
    ``decode`` materializes an O(elems) float32 result that the reducer
    then memcpys into its wave buffer — at federated scale that
    transient is touched exactly once. ``decode_into`` runs the SAME
    validation pipeline (same ``WireError`` texts, same ban evidence)
    and then dequantizes/scatters straight into the caller's buffer
    row, bitwise-identical values to ``decode``:

    - f32/bf16 copy (bf16 via the exact ``u16 << 16`` widening, written
      through a uint32 view of the target);
    - int8/int4 dequantize per block with ``np.multiply(..., out=...)``
      — full blocks as one (nblocks, block) broadcast into the target,
      the ragged tail block against its scalar scale; both are the same
      f32 multiply ``_dequant`` does, minus the pad + slice copies;
    - topk zero-fills then scatters, only after index validation.

    Validation ALWAYS completes before the first byte of ``out`` is
    written: a frame that raises leaves the target untouched (pinned in
    tests/test_wire.py), so a Byzantine frame cannot scribble on a wave
    buffer slot it failed to claim. ``elems`` must fit ``out`` — with
    neither ``expect_elems`` nor ``max_elems`` given, ``out.size`` is
    the implicit allocation bound (the target IS the allocation, so a
    sparse frame's dense-size claim is bounded either way).
    """
    out = np.asarray(out)
    if (out.dtype != np.float32 or out.ndim != 1
            or not out.flags.c_contiguous or not out.flags.writeable):
        raise TypeError(
            "decode_into target must be a writable C-contiguous 1-D "
            f"float32 array, got {out.dtype} ndim={out.ndim}"
        )
    if expect_elems is None and max_elems is None:
        max_elems = out.size
    tag, elems, payload = _checked_frame(
        buf, expect_plane, expect_elems, max_elems, expect_epoch
    )
    if elems > out.size:
        raise WireError(
            f"frame carries {elems} elements but the target row holds "
            f"only {out.size}"
        )
    dst = out[:elems]
    if tag == _TAG_F32:
        dst[...] = np.frombuffer(payload, np.float32)
    elif tag == _TAG_BF16:
        np.left_shift(
            np.frombuffer(payload, np.uint16), np.uint32(16),
            out=dst.view(np.uint32), dtype=np.uint32, casting="unsafe",
        )
    elif tag in (_TAG_INT8, _TAG_INT4):
        codes, scales, block = _checked_quant(payload, tag, elems)
        cf = codes.astype(np.float32)
        nfull = elems // block
        split = nfull * block
        if nfull:
            np.multiply(
                cf[:split].reshape(nfull, block), scales[:nfull, None],
                out=dst[:split].reshape(nfull, block),
            )
        if split < elems:
            np.multiply(cf[split:], scales[nfull], out=dst[split:])
    else:
        pairs = _checked_pairs(payload, elems)
        dst[...] = 0.0
        dst[pairs["i"].astype(np.int64)] = pairs["v"]
    return elems


def decode_batch_into(bufs, out2d, *, expect_plane=None, expect_elems=None,
                      max_elems=None, expect_epoch=None):
    """Decode ``k`` typed frames into the rows of a preallocated 2-D
    float32 slab; frame ``i`` lands in ``out2d[i, :elems_i]``. Returns a
    ``k``-list of per-frame results: the element count written for an
    accepted frame, or the ``WireError`` REJECTING it — never raises per
    frame, so one forged frame bans its sender without poisoning its
    batchmates (the exchange layer's stored-exception convention).

    The batched half of the ingest plane (DESIGN.md §24). Per-frame
    ``decode_into`` pays a full Python trip per client frame — header
    unpack, CRC call, per-frame dequant — which dominated the
    million-client round (XLA:CPU, round 19). This runs the SAME validation
    pipeline restructured into three batch passes:

    1. **vectorized header screen**: the first 20 bytes of every frame,
       packed into one (k, 20) uint8 view — magic/version/dtype-tag/
       plane/epoch/element-count/structural-length checks as numpy
       comparisons over the whole batch at once;
    2. **per-frame CRC** on zero-copy payload slices (``zlib.crc32``
       releases the GIL; ``GARFIELD_INGEST_THREADS`` optionally fans
       this pass over a small shared pool — see ``ingest_threads``);
    3. **run-grouped dequant**: maximal runs of consecutive accepted
       frames sharing (scheme, elems[, block]) decode as ONE vectorized
       op — an (m, elems) int8/int4 code slab times broadcast scales
       instead of m Python calls — written straight into the contiguous
       row range. f32/bf16 rows are single memcpy-bound ops per frame
       already (no dequant to fuse) and topk scatters are inherently
       per-frame, so those run per row inside the batch loop.

    Every multiply is elementwise-identical to ``decode_into``'s, so
    accepted rows are BITWISE-equal to the per-frame path (pinned in
    tests/test_wire.py). Any frame the screen, CRC, or semantic pass
    rejects is re-run through per-frame ``decode_into`` to produce its
    error — the reject text, the validation order, and the
    target-row-untouched guarantee are therefore identical to the
    per-frame path BY CONSTRUCTION, not by parallel maintenance; the
    recompute only ever costs on ban evidence. Allocation pins work
    exactly as in ``decode_into``: with neither ``expect_elems`` nor
    ``max_elems`` given, the slab's row width is the implicit bound, and
    the screen rejects over-claiming headers before any payload-sized
    work.
    """
    out2d = np.asarray(out2d)
    if (out2d.dtype != np.float32 or out2d.ndim != 2
            or not out2d.flags.c_contiguous or not out2d.flags.writeable):
        raise TypeError(
            "decode_batch_into target must be a writable C-contiguous "
            f"2-D float32 array, got {out2d.dtype} ndim={out2d.ndim}"
        )
    k = len(bufs)
    if k > out2d.shape[0]:
        raise ValueError(
            f"{k} frames but the target slab holds only "
            f"{out2d.shape[0]} rows"
        )
    if k == 0:
        return []
    row_elems = out2d.shape[1]
    pins = dict(expect_plane=expect_plane, expect_elems=expect_elems,
                max_elems=max_elems, expect_epoch=expect_epoch)

    # -- pass 1: vectorized header screen over a packed (k, 20) view --
    lens = np.fromiter((len(b) for b in bufs), np.int64, count=k)
    hdr = np.frombuffer(
        b"".join(
            bytes(b[:HEADER2_NBYTES]).ljust(HEADER2_NBYTES, b"\0")
            for b in bufs
        ),
        np.uint8,
    ).reshape(k, HEADER2_NBYTES)
    ver = hdr[:, 2]
    tag = hdr[:, 3] & 0x0F
    plane = hdr[:, 3] >> 4
    # Big-endian field reads via tiny contiguous copies (k*8 bytes).
    # elems stays u64: a forged header can claim up to 2**64-1, and a
    # signed cast could wrap a bomb into a small number that slips the
    # bound screen.
    elems_u = hdr[:, 4:12].copy().view(">u8").reshape(k)
    epoch_u = hdr[:, 12:16].copy().view(">u4").reshape(k)
    isv2 = ver == _VERSION_EPOCH
    ok = lens >= HEADER_NBYTES
    ok &= (hdr[:, 0] == _MAGIC[0]) & (hdr[:, 1] == _MAGIC[1])
    ok &= (ver == _VERSION) | isv2
    ok &= ~(isv2 & (lens < HEADER2_NBYTES))
    ok &= tag <= _TAG_TOPK
    if expect_plane is not None:
        ok &= plane == check_plane(expect_plane, "expect_plane")
    if expect_epoch is not None:
        ok &= isv2 & (epoch_u == check_epoch(expect_epoch, "expect_epoch"))
    if expect_elems is not None:
        ok &= elems_u == int(expect_elems)
    if max_elems is not None:
        ok &= elems_u <= int(max_elems)
    elif expect_elems is None:
        ok &= elems_u <= row_elems  # the implicit allocation bound
    ok &= elems_u <= row_elems  # decode_into's target-row fit check
    # Structural length (same pre-CRC position as _checked_frame's):
    # exact for the fixed-width schemes, the block prefix for quant,
    # whole bounded pairs for topk. Rejected lanes may hold garbage
    # element counts, so the arithmetic runs on a masked copy.
    plen = lens - np.where(isv2, HEADER2_NBYTES, HEADER_NBYTES)
    se = np.where(ok, elems_u, 0).astype(np.int64)
    st = ((tag == _TAG_F32) & (plen == se * 4))
    st |= (tag == _TAG_BF16) & (plen == se * 2)
    st |= ((tag == _TAG_INT8) | (tag == _TAG_INT4)) & (plen >= 4)
    st |= ((tag == _TAG_TOPK) & (plen % _PAIR.itemsize == 0)
           & (plen // _PAIR.itemsize <= se))
    ok &= st

    # -- pass 2: per-frame CRC on zero-copy payload slices --
    crc_hdr = np.where(
        isv2,
        hdr[:, 16:20].copy().view(">u4").reshape(k),
        hdr[:, 12:16].copy().view(">u4").reshape(k),
    )
    off = np.where(isv2, HEADER2_NBYTES, HEADER_NBYTES)
    payloads = [None] * k
    idx_ok = np.flatnonzero(ok)
    for i in idx_ok:
        payloads[i] = memoryview(bufs[i])[int(off[i]):]

    def _crc_ok(i):
        seed = zlib.crc32(_EPOCH.pack(int(epoch_u[i]))) if isv2[i] else 0
        return zlib.crc32(payloads[i], seed) == int(crc_hdr[i])

    nthr = ingest_threads()
    if nthr > 1 and idx_ok.size >= 2 * nthr:
        passed = list(_crc_pool(nthr).map(_crc_ok, idx_ok))
    else:
        passed = [_crc_ok(i) for i in idx_ok]
    for p, i in zip(passed, idx_ok):
        if not p:
            ok[i] = False

    # Quant structural prescreen (integer math only): the block prefix
    # and the exact payload length _checked_quant enforces, per frame,
    # so run grouping below can key on a trusted block.
    blocks = np.zeros(k, np.int64)
    for i in np.flatnonzero(ok & ((tag == _TAG_INT8) | (tag == _TAG_INT4))):
        e = int(elems_u[i])
        b = int.from_bytes(bytes(payloads[i][:4]), "little")
        nblocks = -(-e // b) if (b >= 1 and e) else 0
        cn = e if tag[i] == _TAG_INT8 else (e + 1) // 2
        if (b < 1 or b > max(e, 1)
                or int(plen[i]) != 4 + nblocks * 4 + cn):
            ok[i] = False
        else:
            blocks[i] = b

    # -- pass 3: run-grouped semantic checks + slab dequant --
    results = [None] * k
    fails = list(np.flatnonzero(~ok))
    i = 0
    while i < k:
        if not ok[i]:
            i += 1
            continue
        t = int(tag[i])
        e = int(elems_u[i])
        blk = int(blocks[i])
        j = i + 1
        while (j < k and ok[j] and int(tag[j]) == t
               and int(elems_u[j]) == e and int(blocks[j]) == blk):
            j += 1
        run = list(range(i, j))
        m = len(run)
        if t == _TAG_F32:
            for r in run:
                out2d[r, :e] = np.frombuffer(payloads[r], np.float32)
                results[r] = e
        elif t == _TAG_BF16:
            for r in run:
                np.left_shift(
                    np.frombuffer(payloads[r], np.uint16), np.uint32(16),
                    out=out2d[r, :e].view(np.uint32), dtype=np.uint32,
                    casting="unsafe",
                )
                results[r] = e
        elif t in (_TAG_INT8, _TAG_INT4):
            nblocks = -(-e // blk) if e else 0
            cn = e if t == _TAG_INT8 else (e + 1) // 2
            scales2d = np.empty((m, nblocks), np.float32)
            raw2d = np.empty((m, cn), np.uint8)
            for q, r in enumerate(run):
                scales2d[q] = np.frombuffer(
                    payloads[r], "<f4", count=nblocks, offset=4
                )
                raw2d[q] = np.frombuffer(
                    payloads[r], np.uint8, count=cn, offset=4 + nblocks * 4
                )
            bad = ~(np.isfinite(scales2d).all(axis=1)
                    & (scales2d >= 0).all(axis=1))
            if t == _TAG_INT8:
                codes2d = raw2d.view(np.int8)
                bad |= (codes2d == -128).any(axis=1)
                cf = codes2d.astype(np.float32)
            else:
                nib2d = np.empty((m, cn * 2), np.uint8)
                nib2d[:, 0::2] = raw2d & 0x0F
                nib2d[:, 1::2] = raw2d >> 4
                nib2d = nib2d[:, :e]
                bad |= (nib2d == 0).any(axis=1)
                cf = (nib2d.astype(np.int16) - 8).astype(np.float32)
            # Broadcast the per-block scales to per-element and multiply
            # the whole slab at once — elementwise-identical operands to
            # _dequant/decode_into's per-block multiplies, so the rows
            # are bitwise-equal (IEEE multiply is deterministic per
            # element; the grouping changes nothing).
            sc = np.repeat(scales2d, blk, axis=1)[:, :e] if e else \
                np.empty((m, 0), np.float32)
            np.multiply(cf, sc, out=cf)
            if not bad.any():
                out2d[i:j, :e] = cf
                for r in run:
                    results[r] = e
            else:
                for q, r in enumerate(run):
                    if bad[q]:
                        fails.append(r)
                    else:
                        out2d[r, :e] = cf[q]
                        results[r] = e
        else:  # _TAG_TOPK — scatter is inherently per-row
            for r in run:
                try:
                    pairs = _checked_pairs(payloads[r], e)
                except WireError:
                    fails.append(r)
                    continue
                dst = out2d[r, :e]
                dst[...] = 0.0
                dst[pairs["i"].astype(np.int64)] = pairs["v"]
                results[r] = e
        i = j

    # Every reject re-runs the per-frame path for its error: identical
    # text, identical validation order, target row provably untouched —
    # and if the screen ever under-accepts (it should be exact), the
    # frame simply decodes here instead of raising, keeping the batch
    # path semantics-preserving rather than semantics-approximating.
    for r in fails:
        try:
            results[r] = decode_into(bufs[r], out2d[r], **pins)
        except WireError as err:
            results[r] = err
    return results


def frame_plane(buf):
    """The plane tag of a typed frame's header (0 for pre-plane frames);
    raises WireError on anything too short to carry a header. Reads the
    spare high nibble only — it does NOT validate the payload (the full
    ``decode`` does), so byte-accounting consumers can label a frame's
    plane without paying the CRC."""
    if len(buf) < HEADER_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER_NBYTES}-byte header"
        )
    magic, ver, tag, _, _ = _HDR.unpack_from(buf)
    if magic != _MAGIC:
        raise WireError(f"bad magic {magic!r}")
    return tag >> 4


def frame_epoch(buf):
    """The membership-epoch stamp of a typed frame's header, or None
    for a version-1 (pre-epoch) frame; raises WireError on a short
    header, bad magic, or unknown version. Header-only like
    ``frame_plane`` — the stamp is unvalidated against any view until
    ``decode``/``decode_into`` pins it with ``expect_epoch`` (which
    also proves it under the CRC), so this is strictly a labelling
    read: a directory deciding whether to even attempt a decode, a
    byte-accounting consumer tagging rejects per epoch."""
    if len(buf) < HEADER_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER_NBYTES}-byte header"
        )
    magic, ver, _, _, _ = _HDR.unpack_from(buf)
    if magic != _MAGIC:
        raise WireError(f"bad magic {magic!r}")
    if ver == _VERSION:
        return None
    if ver != _VERSION_EPOCH:
        raise WireError(f"unsupported wire version {ver}")
    if len(buf) < HEADER2_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER2_NBYTES}-byte epoch-stamped header"
        )
    return int(_HDR2.unpack_from(buf)[4])


def frame_scheme(buf):
    """The payload scheme name of a typed frame's header ("f32", "bf16",
    "int8", "int4", "topk"); raises WireError on a short header, bad
    magic, or unknown low-nibble tag. Like ``frame_plane`` this reads
    the header only — byte-accounting consumers label a frame's scheme
    without paying the CRC."""
    if len(buf) < HEADER_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER_NBYTES}-byte header"
        )
    magic, ver, tag, _, _ = _HDR.unpack_from(buf)
    if magic != _MAGIC:
        raise WireError(f"bad magic {magic!r}")
    tag &= 0x0F
    if tag not in _TAG_NAME:
        raise WireError(f"unknown dtype tag {tag}")
    return _TAG_NAME[tag]


def frame_elems(buf):
    """The CLAIMED dense element count of a typed frame's header;
    raises WireError on a short header or bad magic. Header-only like
    ``frame_plane`` — the claim is unvalidated (a sparse frame's count
    is a bare sender assertion until ``decode``/``decode_into`` pins or
    bounds it), so this is strictly a SIZING hint: consumers use it to
    right-size a reusable scratch target, clamped to their own bound,
    and let the full decode reject an over-claiming frame before any
    write."""
    if len(buf) < HEADER_NBYTES:
        raise WireError(
            f"truncated frame: {len(buf)} bytes is shorter than the "
            f"{HEADER_NBYTES}-byte header"
        )
    magic, _, _, elems, _ = _HDR.unpack_from(buf)
    if magic != _MAGIC:
        raise WireError(f"bad magic {magic!r}")
    return int(elems)


def frame_nbytes(elems, dtype=None, *, k=None, block=QUANT_BLOCK,
                 epoch=False):
    """Total wire bytes of an ``elems``-element frame at ``dtype`` —
    the bench/telemetry accounting twin of ``encode``. For ``"topk"``,
    ``k`` is the kept-pair count (default: the GARFIELD_WIRE_TOPK
    divisor's ``topk_k``, falling back to DEFAULT_TOPK_DIV).
    ``epoch=True`` accounts the v2 epoch-stamped header (+4 bytes)."""
    dtype = wire_dtype() if dtype is None else dtype
    elems = int(elems)
    hdr = HEADER2_NBYTES if epoch else HEADER_NBYTES
    if dtype in ("f32", "bf16"):
        return hdr + elems * (2 if dtype == "bf16" else 4)
    if dtype in ("int8", "int4"):
        nblocks = -(-elems // int(block)) if elems else 0
        codes = elems if dtype == "int8" else (elems + 1) // 2
        return hdr + 4 + nblocks * 4 + codes
    if dtype == "topk":
        if k is None:
            k = topk_k(elems, wire_topk() or DEFAULT_TOPK_DIV)
        return hdr + int(k) * _PAIR.itemsize
    raise ValueError(f"unknown wire dtype {dtype!r}")


class ErrorFeedback:
    """Host-side error-feedback accumulators, one residual per key.

    Compressed SGD with a biased compressor (quantization, top-k)
    diverges unless the compression error is fed back into the next
    step's signal (Karimireddy et al., EF-SGD): the sender transmits
    ``C(g + e)`` and keeps ``e' = (g + e) - dequant(C(g + e))``. The
    cluster roles key the accumulator per PLANE — every frame is
    broadcast byte-identical to all peers, so per sender x plane is the
    full resolution ("per peer x plane" collapses to it; a per-LINK
    residual would let the same process drift different totals to
    different receivers).

    Error feedback applies to the GRADIENT plane's additive head segment
    only. Model/gossip broadcasts are absolute state, not an additive
    signal — accumulating their quantization error would smear stale
    parameters into fresh ones (DESIGN.md §20) — and the BN-stats tail
    of a ``[grad || stats]`` frame is robust-stats input, shipped dense.

    RESTART SEMANTICS (documented, not silent): the host accumulator is
    rebuilt at zero when a cluster role restarts — the residual is a
    bounded one-step correction (||e|| <= the per-step compression
    error), so dropping it costs one step of compensation, not
    convergence. Bitwise-reproducible resume lives on the in-graph twin
    (parallel/compress.py), whose residual rides ``TrainState`` through
    checkpoints; the cluster role logs the rebuild via its startup
    banner so a resumed run's telemetry shows the reset.
    """

    def __init__(self):
        self._resid = {}

    def compensate(self, key, vec, *, upto=None):
        """``vec + residual[key]`` over ``[0, upto)`` (default: all of
        ``vec``); returns a fresh f32 array. Shape changes (a different
        model) reset the key's residual to zero loudly-by-construction:
        the stale residual is discarded, not broadcast-added."""
        vec = np.ascontiguousarray(np.asarray(vec).reshape(-1), np.float32)
        e = self._resid.get(key)
        upto = vec.size if upto is None else int(upto)
        out = vec.copy()
        if e is not None and e.size == upto:
            out[:upto] += e
        return out

    def update(self, key, compensated, decoded, *, upto=None):
        """Store ``compensated - decoded`` over ``[0, upto)`` as the
        key's next residual. ``decoded`` must be the receiver-side
        dequantization of the frame actually sent (a full codec round
        trip), so the residual is exactly the error every peer saw."""
        upto = compensated.size if upto is None else int(upto)
        self._resid[key] = (
            compensated[:upto] - decoded[:upto]
        ).astype(np.float32)

    def residual_norm(self, key):
        """L2 norm of the key's residual (0.0 when absent) — the
        telemetry ``ef_residual_norm`` field on the ``wire`` event."""
        e = self._resid.get(key)
        return float(np.linalg.norm(e)) if e is not None else 0.0

    def total_norm(self):
        """L2 norm over ALL keys' residuals — the role-level
        ``ef_residual_norm`` a WireStats flush reports."""
        sq = sum(
            float(np.sum(e.astype(np.float64) ** 2))
            for e in self._resid.values()
        )
        return float(np.sqrt(sq))
