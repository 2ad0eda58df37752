"""Typed wire codec (utils/wire.py) + its cluster integration.

Codec robustness IS Byzantine robustness on the host plane: a Byzantine
PROCESS controls its wire bytes, so the codec's reject surface (magic /
version / dtype tag / element count / crc) is the ban evidence the
quorum paths act on. The fuzz test is the core guarantee: NO corrupted
frame ever decodes — it gets its sender excluded exactly like the old
wrong-length frame did.
"""

import socket
import threading
import time

import numpy as np
import pytest

from garfield_tpu.utils import wire


# --- pure codec (no native / jax dependency) --------------------------------


def test_f32_roundtrip_exact_and_payload_byte_identical():
    """f32 wire must keep trajectory parity with the pre-codec format:
    the payload after the 16-byte header is the exact ``tobytes()``."""
    rng = np.random.default_rng(0)
    v = rng.standard_normal(999).astype(np.float32)
    frame = wire.encode(v, "f32")
    assert frame[wire.HEADER_NBYTES:] == v.tobytes()
    assert len(frame) == wire.frame_nbytes(v.size, "f32")
    out = wire.decode(frame)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, v)


def test_bf16_roundtrip_within_cast_tolerance():
    rng = np.random.default_rng(1)
    v = (rng.standard_normal(2048) * 10.0 ** rng.integers(
        -6, 6, 2048
    )).astype(np.float32)
    frame = wire.encode(v, "bf16")
    assert len(frame) == wire.frame_nbytes(v.size, "bf16")
    out = wire.decode(frame)
    rel = np.abs(out - v) / np.maximum(np.abs(v), 1e-30)
    assert rel.max() <= 2.0 ** -8  # bf16 has 8 mantissa bits

    # Specials survive (the lie attack at cohort=1 publishes NaN — the
    # reference's emergent behavior must not be laundered by the wire).
    specials = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0], np.float32)
    out = wire.decode(wire.encode(specials, "bf16"))
    assert np.isnan(out[0]) and np.isposinf(out[1]) and np.isneginf(out[2])
    assert out[3] == 0.0 and out[4] == 0.0


def test_bf16_matches_xla_convert():
    """The host cast must equal XLA's f32->bf16 convert (round-to-nearest-
    even): a host-decoded gradient is bit-equal to what the on-mesh bf16
    pipeline would have produced for the same value."""
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.default_rng(2)
    v = rng.standard_normal(4096).astype(np.float32)
    host = wire.decode(wire.encode(v, "bf16"))
    xla = np.asarray(jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(host, xla)


def test_plane_tag_round_trip():
    """Schema v6: the plane tag rides the dtype byte's spare high nibble
    — plane 0 frames are byte-identical to the pre-plane format, any
    plane decodes to the same values, and frame_plane reads the tag
    without paying the CRC."""
    v = np.arange(16, dtype=np.float32)
    for dtype in wire.WIRE_DTYPES:
        base = wire.encode(v, dtype)
        assert wire.encode(v, dtype, plane=0) == base  # byte-identical
        for plane in (0, 1, 2, wire.MAX_PLANE):
            frame = wire.encode(v, dtype, plane=plane)
            assert wire.frame_plane(frame) == plane
            np.testing.assert_array_equal(
                wire.decode(frame), wire.decode(base)
            )
    with pytest.raises(ValueError):
        wire.encode(v, "f32", plane=wire.MAX_PLANE + 1)
    with pytest.raises(wire.WireError):
        wire.frame_plane(b"short")
    with pytest.raises(wire.WireError):
        wire.frame_plane(b"XX" + b"\0" * 14)  # bad magic


def test_plane_capacity_guard_boundary():
    """ISSUE 13 satellite: the plane/shard tag has exactly
    ``MAX_PLANE + 1`` values — the boundary encodes, one past it fails
    loudly at publish/encode time (named capacity in the message), and
    non-integral tags are rejected instead of int()-truncated into a
    foreign shard's nibble."""
    v = np.ones(4, np.float32)
    frame = wire.encode(v, plane=wire.MAX_PLANE)  # boundary: fine
    assert wire.frame_plane(frame) == wire.MAX_PLANE
    with pytest.raises(ValueError, match="nibble"):
        wire.encode(v, plane=wire.MAX_PLANE + 1)
    with pytest.raises(ValueError, match="nibble"):
        wire.encode(v, plane=-1)
    with pytest.raises(TypeError):
        wire.encode(v, plane=2.5)
    with pytest.raises(TypeError):
        wire.encode(v, plane=True)
    assert wire.check_plane(np.int64(3)) == 3  # numpy ints are integral


def test_decode_expect_plane_rejects_cross_shard_frames():
    """DESIGN.md §19: a shard consumer decoding with ``expect_plane``
    rejects a frame stamped for any other shard as a WireError — the
    stamp is under the sender's CRC, so the mismatch is attributable
    ban evidence, never a silent mis-fold."""
    v = np.arange(8, dtype=np.float32)
    f1 = wire.encode(v, plane=1)
    np.testing.assert_array_equal(wire.decode(f1, expect_plane=1), v)
    with pytest.raises(wire.WireError, match="cross-shard"):
        wire.decode(f1, expect_plane=0)
    # expect_plane itself is capacity-guarded.
    with pytest.raises(ValueError):
        wire.decode(f1, expect_plane=16)


def test_wire_dtype_env(monkeypatch):
    monkeypatch.delenv("GARFIELD_WIRE_DTYPE", raising=False)
    assert wire.wire_dtype() == "f32"
    monkeypatch.setenv("GARFIELD_WIRE_DTYPE", "bf16")
    assert wire.wire_dtype() == "bf16"
    v = np.ones(4, np.float32)
    assert len(wire.encode(v)) == wire.frame_nbytes(4, "bf16")
    monkeypatch.setenv("GARFIELD_WIRE_DTYPE", "f16")
    with pytest.raises(ValueError):
        wire.wire_dtype()


def test_fuzz_corrupted_frames_never_decode():
    """Every single-bit flip and every truncation of a valid frame must
    raise WireError — corrupted bytes can NEVER reach a GAR — EXCEPT the
    four plane-tag bits (the dtype byte's spare high nibble, schema v6):
    a flip there only relabels the frame's plane, and the decode must
    return the IDENTICAL values (the payload is untouched and
    crc-verified), so nothing corrupted can reach a GAR through that
    nibble either. (A payload flip breaks the crc; any other header flip
    breaks magic/version/tag/length; a truncation breaks the length
    contract.)

    Round 18: the fuzz runs over EVERY payload scheme (int8/int4/topk
    included), decoding as the cluster consumer does — with
    ``expect_elems`` — because a sparse frame's dense size is a bare
    header claim the payload cannot corroborate (an ``elems`` bit flip
    on a topk frame passes every structural check and the CRC, and
    without the pin would scatter into a wrong-sized or multi-GB zeros
    vector)."""
    rng = np.random.default_rng(3)
    v = rng.standard_normal(257).astype(np.float32)
    # dtype byte = header byte 3 ("!2sBBQI"); its high nibble is the
    # plane tag.
    plane_bits = {3 * 8 + b for b in (4, 5, 6, 7)}
    for dtype in wire.WIRE_SCHEMES:
        frame = wire.encode(v, dtype)
        baseline = wire.decode(frame)
        # exhaustive over the header, random over the payload
        bits = list(range(wire.HEADER_NBYTES * 8)) + list(
            rng.integers(wire.HEADER_NBYTES * 8, len(frame) * 8, 400)
        )
        for bit in bits:
            ba = bytearray(frame)
            ba[bit // 8] ^= 1 << (bit % 8)
            if bit in plane_bits:
                np.testing.assert_array_equal(
                    wire.decode(bytes(ba)), baseline
                )
                assert wire.frame_plane(bytes(ba)) != 0
                continue
            with pytest.raises(wire.WireError):
                wire.decode(bytes(ba), expect_elems=v.size)
        for cut in list(range(0, wire.HEADER_NBYTES + 2)) + list(
            rng.integers(0, len(frame), 60)
        ):
            with pytest.raises(wire.WireError):
                wire.decode(frame[:int(cut)], expect_elems=v.size)
        with pytest.raises(wire.WireError):
            # trailing garbage
            wire.decode(frame + b"x", expect_elems=v.size)
    with pytest.raises(wire.WireError):
        wire.decode(b"")  # the SSMW stop sentinel must not decode


def test_fuzz_dense_schemes_self_validate_without_expect_elems():
    """The PR 4 contract stands on its own for the dense/quantized
    schemes: every non-plane header flip and truncation rejects WITHOUT
    ``expect_elems`` (payload length corroborates the element count).
    The sparse scheme is the documented exception — covered above with
    the pin and below by the forged-elems test."""
    rng = np.random.default_rng(7)
    v = rng.standard_normal(129).astype(np.float32)
    plane_bits = {3 * 8 + b for b in (4, 5, 6, 7)}
    for dtype in wire.WIRE_DTYPES:
        frame = wire.encode(v, dtype)
        for bit in range(wire.HEADER_NBYTES * 8):
            if bit in plane_bits:
                continue
            ba = bytearray(frame)
            ba[bit // 8] ^= 1 << (bit % 8)
            with pytest.raises(wire.WireError):
                wire.decode(bytes(ba))


# --- round 18: compressed schemes (int8 / int4 / topk) -----------------------


def _forge(tag, elems, payload, plane=0):
    """A CRC-valid frame with arbitrary payload bytes — what a Byzantine
    sender (who controls its wire bytes, CRC included) can actually
    produce. The semantic rejects below must fire AFTER the CRC passes:
    that ordering is what makes them attributable ban evidence."""
    import struct
    import zlib

    return struct.pack(
        "!2sBBQI", b"GW", 1, (plane << 4) | tag, elems,
        zlib.crc32(payload),
    ) + payload


def _topk_payload(idx, val):
    pairs = np.empty(len(idx), np.dtype([("i", "<u4"), ("v", "<f4")]))
    pairs["i"] = idx
    pairs["v"] = val
    return pairs.tobytes()


def test_int8_roundtrip_error_bound_and_nbytes():
    rng = np.random.default_rng(10)
    v = (rng.standard_normal(3000) * 3).astype(np.float32)
    frame = wire.encode(v, "int8")
    assert len(frame) == wire.frame_nbytes(v.size, "int8")
    out = wire.decode(frame)
    # Linear grid: per-block max error <= scale / 2 = max|block| / 254.
    for b in range(0, v.size, wire.QUANT_BLOCK):
        blk = v[b:b + wire.QUANT_BLOCK]
        bound = np.abs(blk).max() / 127 / 2 + 1e-7
        assert np.abs(out[b:b + wire.QUANT_BLOCK] - blk).max() <= bound
    # Zero vector: zero scale, exact roundtrip.
    z = wire.decode(wire.encode(np.zeros(100, np.float32), "int8"))
    np.testing.assert_array_equal(z, np.zeros(100))


def test_int4_roundtrip_error_bound_and_padding():
    rng = np.random.default_rng(11)
    for n in (7, 8, 257):  # odd sizes exercise the pad nibble
        v = rng.standard_normal(n).astype(np.float32)
        frame = wire.encode(v, "int4")
        assert len(frame) == wire.frame_nbytes(n, "int4")
        out = wire.decode(frame)
        bound = np.abs(v).max() / 7 / 2 + 1e-6
        assert np.abs(out - v).max() <= bound


def test_topk_roundtrip_keeps_largest_and_dense_tail():
    rng = np.random.default_rng(12)
    v = rng.standard_normal(1000).astype(np.float32)
    k = 50
    frame = wire.encode(v, "topk", k=k)
    assert len(frame) == wire.frame_nbytes(v.size, "topk", k=k)
    out = wire.decode(frame)
    kept = np.flatnonzero(out)
    assert kept.size == k
    # The kept coordinates are exactly the k largest magnitudes.
    top = np.sort(np.argpartition(np.abs(v), v.size - k)[v.size - k:])
    np.testing.assert_array_equal(kept, top)
    np.testing.assert_array_equal(out[kept], v[kept])
    # keep_from: the stats tail (BatchNorm segment) always rides along.
    tail_frame = wire.encode(v, "topk", k=10, keep_from=990)
    out = wire.decode(tail_frame)
    np.testing.assert_array_equal(out[990:], v[990:])
    assert np.flatnonzero(out[:990]).size == 10


def test_quantized_encode_rejects_non_finite_loudly():
    """Honest-sender loud failure: a NaN/inf input would produce a
    non-finite scale — indistinguishable on the wire from a Byzantine
    frame — so encode raises a plain ValueError (NOT WireError: there is
    no frame, and nobody to ban) instead of shipping it."""
    bad = np.array([1.0, np.nan, 2.0], np.float32)
    for scheme in ("int8", "int4", "topk"):
        with pytest.raises(ValueError) as ei:
            wire.encode(bad, scheme)
        assert not isinstance(ei.value, wire.WireError)
    inf = np.array([1.0, np.inf], np.float32)
    with pytest.raises(ValueError):
        wire.encode(inf, "int8")
    # bf16/f32 still pass specials through (the NaN-laundering pin in
    # test_bf16_roundtrip_within_cast_tolerance).
    wire.encode(bad, "f32")


def test_quantized_scale_range_rejected_post_crc():
    """The ISSUE's scale gate: CRC-valid frames whose carried scale is
    non-finite or negative reject as WireError with .nbytes — the
    attributable Byzantine case (only the sender makes those bytes)."""
    v = np.ones(8, np.float32)
    honest = wire.encode(v, "int8")
    head = honest[:wire.HEADER_NBYTES]
    payload = bytearray(honest[wire.HEADER_NBYTES:])
    for evil_scale in (np.inf, -np.inf, np.nan, -1.0):
        p = bytearray(payload)
        p[4:8] = np.float32(evil_scale).tobytes()
        frame = _forge(2, v.size, bytes(p))
        with pytest.raises(wire.WireError, match="scale"):
            wire.decode(frame)
    del head
    # block = 0 in the payload prefix: division bomb, rejected by name.
    p = bytearray(payload)
    p[0:4] = np.zeros(1, "<u4").tobytes()
    with pytest.raises(wire.WireError, match="block"):
        wire.decode(_forge(2, v.size, bytes(p)))


def test_int4_nibble_zero_rejected():
    """Nibble 0 is outside the biased [1, 15] grid — unreachable by any
    honest encoder, so its presence is ban evidence, not a value."""
    v = np.ones(4, np.float32)
    honest = wire.encode(v, "int4")
    payload = bytearray(honest[wire.HEADER_NBYTES:])
    payload[-1] &= 0xF0  # zero the low nibble of the last code byte
    with pytest.raises(wire.WireError, match="nibble"):
        wire.decode(_forge(3, v.size, bytes(payload)))


def test_quantized_block_bomb_rejected_post_crc():
    """A CRC-valid int8/int4 frame whose u32 block prefix dwarfs the
    element count passes every length check (nblocks is 1 either way)
    but would pad the dequant to nblocks*block f32 elements — ~17 GB at
    block=0xFFFFFFFF — a receiver-side allocation bomb from an
    attributable frame. The decoder bounds block by the element count
    BEFORE dequantizing; honest encoders clamp, so every honest frame
    sits inside the bound."""
    v = np.ones(8, np.float32)
    for scheme, tag in (("int8", 2), ("int4", 3)):
        honest = wire.encode(v, scheme)
        # The honest frame's block prefix is clamped to the vector.
        pfx = np.frombuffer(honest[wire.HEADER_NBYTES:], "<u4", count=1)
        assert int(pfx[0]) == v.size
        payload = bytearray(honest[wire.HEADER_NBYTES:])
        payload[0:4] = np.array([0xFFFFFFFF], "<u4").tobytes()
        with pytest.raises(wire.WireError, match="block"):
            wire.decode(_forge(tag, v.size, bytes(payload)))
        # One past the element count is already out.
        payload[0:4] = np.array([v.size + 1], "<u4").tobytes()
        with pytest.raises(wire.WireError, match="block"):
            wire.decode(_forge(tag, v.size, bytes(payload)))


def test_int8_code_minus_128_rejected():
    """encode clips int8 codes to the symmetric [-127, 127] grid, so a
    -128 byte is unreachable by any honest encoder — the same
    'invalid content = attributable ban evidence' contract as int4's
    nibble 0 (which already rejects)."""
    v = np.ones(4, np.float32)
    honest = wire.encode(v, "int8")
    payload = bytearray(honest[wire.HEADER_NBYTES:])
    payload[-1] = 0x80  # last code byte -> -128
    with pytest.raises(wire.WireError, match="-128"):
        wire.decode(_forge(2, v.size, bytes(payload)))


def test_topk_k_zero_ships_dense_tail_only():
    """An explicit k=0 is a clean edge, not a numpy argpartition bomb:
    no head pairs ride — only the always-kept dense tail (if any)."""
    v = np.arange(1.0, 11.0, dtype=np.float32)
    frame = wire.encode(v, "topk", k=0)
    assert len(frame) == wire.HEADER_NBYTES  # zero pairs
    np.testing.assert_array_equal(wire.decode(frame), np.zeros(10))
    tail = wire.encode(v, "topk", k=0, keep_from=8)
    out = wire.decode(tail)
    np.testing.assert_array_equal(out[8:], v[8:])
    assert np.flatnonzero(out[:8]).size == 0


def test_decode_max_elems_bounds_sparse_claims():
    """``max_elems``: the inexact consumer pin for variable-size frames
    (the federated shard plane's whole-number-of-rows frames). A sparse
    header claiming 2^40 elements rejects before the scatter allocates;
    honest frames inside the bound pass, for every scheme."""
    payload = _topk_payload([0, 1], [1.0, 2.0])
    with pytest.raises(wire.WireError, match="bound"):
        wire.decode(_forge(4, 2 ** 40, payload), max_elems=1 << 20)
    assert wire.decode(_forge(4, 16, payload), max_elems=16).size == 16
    v = np.ones(16, np.float32)
    for scheme in wire.WIRE_SCHEMES:
        assert wire.decode(wire.encode(v, scheme), max_elems=64).size == 16
        with pytest.raises(wire.WireError, match="bound"):
            wire.decode(wire.encode(v, scheme), max_elems=15)


def test_sparse_index_attacks_rejected_post_crc():
    """Every malformed-sparse shape the ISSUE names, as CRC-valid forged
    frames: duplicate index (double-count), descending index, index out
    of bounds, more pairs than elems, and a non-whole-pair payload. All
    WireError; the quorum path stamps .nbytes (integration test below)."""
    cases = [
        (_topk_payload([3, 3, 5], [1, 2, 3]), "increasing"),   # duplicate
        (_topk_payload([5, 3, 7], [1, 2, 3]), "increasing"),   # descending
        (_topk_payload([0, 2, 16], [1, 2, 3]), "bounds"),      # oob last
        (_topk_payload(range(17), np.ones(17)), "pairs"),      # k > elems
        (_topk_payload([0, 1], [1, 2])[:-3], "pairs"),         # ragged
    ]
    for payload, msg in cases:
        with pytest.raises(wire.WireError, match=msg):
            wire.decode(_forge(4, 16, payload))
    # Monotonicity + in-bounds LAST index suffices: any strictly
    # increasing sequence with an out-of-bounds middle element must have
    # an out-of-bounds last element too.
    ok = wire.decode(_forge(4, 16, _topk_payload([0, 7, 15], [1, 2, 3])))
    np.testing.assert_array_equal(np.flatnonzero(ok), [0, 7, 15])


def test_sparse_elems_claim_pinned_by_consumer():
    """A sparse frame's dense size is a bare header claim (the pairs are
    consistent with ANY larger elems): an honestly-CRC'd frame claiming
    2^40 elements must reject on the consumer's ``expect_elems`` pin
    BEFORE the scatter allocates a 4 TB zeros vector."""
    payload = _topk_payload([0, 1], [1.0, 2.0])
    giant = _forge(4, 2 ** 40, payload)
    with pytest.raises(wire.WireError, match="expected"):
        wire.decode(giant, expect_elems=16)
    # Dense consumers get the same pin for free (belt over the length
    # check) — and honest frames pass it.
    v = np.ones(16, np.float32)
    for scheme in wire.WIRE_SCHEMES:
        out = wire.decode(wire.encode(v, scheme), expect_elems=16)
        assert out.size == 16
        with pytest.raises(wire.WireError):
            wire.decode(wire.encode(v, scheme), expect_elems=17)


def test_unknown_low_nibble_tags_reject_loudly():
    """Forward/backward compat: tags 5..15 are unassigned — a frame
    stamped with one rejects by name on THIS decoder (and tags 2/3/4
    reject identically on a PR 4 decoder, which knew only 0/1), so a
    mixed-version deployment fails loudly instead of misinterpreting
    payload bytes."""
    for tag in range(5, 16):
        with pytest.raises(wire.WireError, match="tag"):
            wire.decode(_forge(tag, 4, b"\x00" * 16))
        with pytest.raises(wire.WireError, match="tag"):
            wire.frame_scheme(_forge(tag, 4, b""))


def test_f32_bf16_golden_frames_unchanged():
    """Backward-compat pin: the PR 4 wire format for f32/bf16 is frozen
    byte-for-byte — adding the compressed tags must not move a single
    bit of the dense frames (a mixed-version fleet keeps interoperating
    on the dense schemes)."""
    v = np.array([0.0, 1.0, -2.5], np.float32)
    f32 = wire.encode(v, "f32")
    assert f32.hex() == (
        "47570100"              # "GW", ver 1, tag 0 (f32, plane 0)
        "0000000000000003"      # elems = 3 (big-endian u64)
        "48f41bf2"              # crc32 of the payload below
        "000000000000803f0000"  # 0.0f, 1.0f, -2.5f little-endian
        "20c0"
    )
    bf16 = wire.encode(v, "bf16")
    assert bf16.hex() == (
        "47570101" "0000000000000003" "7d4c5327"
        "0000803f20c0"          # bf16 halves of the same three values
    )
    assert wire.frame_scheme(f32) == "f32"
    assert wire.frame_scheme(bf16) == "bf16"


def test_frame_scheme_reads_all_tags():
    v = np.ones(8, np.float32)
    for scheme in wire.WIRE_SCHEMES:
        assert wire.frame_scheme(wire.encode(v, scheme)) == scheme
    with pytest.raises(wire.WireError):
        wire.frame_scheme(b"short")


def test_topk_env_divisor_and_topk_k(monkeypatch):
    monkeypatch.delenv("GARFIELD_WIRE_TOPK", raising=False)
    assert wire.wire_topk() == 0
    monkeypatch.setenv("GARFIELD_WIRE_TOPK", "32")
    assert wire.wire_topk() == 32
    v = np.arange(1, 101, dtype=np.float32)
    frame = wire.encode(v, "topk")  # k = ceil(100/32) = 4 from the env
    assert np.flatnonzero(wire.decode(frame)).size == 4
    monkeypatch.setenv("GARFIELD_WIRE_TOPK", "-1")
    with pytest.raises(ValueError):
        wire.wire_topk()
    monkeypatch.setenv("GARFIELD_WIRE_TOPK", "x")
    with pytest.raises(ValueError):
        wire.wire_topk()
    assert wire.topk_k(100, 32) == 4
    assert wire.topk_k(0, 32) == 0
    assert wire.topk_k(1, 1000) == 1
    with pytest.raises(ValueError):
        wire.topk_k(100, 0)


def test_error_feedback_accumulator():
    """EF-SGD's host accumulator: on a CONSTANT signal the residual makes
    the mean sent value converge to the signal exactly (the bias a bare
    quantizer keeps forever); a residual of the wrong size (model resize
    / restart) is discarded, not misapplied."""
    ef = wire.ErrorFeedback()
    signal = np.full(64, 0.01, np.float32)  # far below one int8 step
    sent_sum = np.zeros(64, np.float64)
    n = 50
    for _ in range(n):
        comp = ef.compensate(1, signal)
        frame = wire.encode(comp, "int8")
        dec = wire.decode(frame)
        ef.update(1, comp, dec)
        sent_sum += dec
    np.testing.assert_allclose(sent_sum / n, signal, rtol=1e-5)
    assert ef.residual_norm(1) >= 0
    assert ef.total_norm() == pytest.approx(ef.residual_norm(1))
    # Wrong-size (stale) residual: discarded, compensate is identity.
    other = np.ones(32, np.float32)
    np.testing.assert_array_equal(ef.compensate(1, other), other)
    # Unknown key: identity too.
    np.testing.assert_array_equal(ef.compensate(9, other), other)
    assert ef.residual_norm(9) == 0.0


def test_error_feedback_upto_leaves_tail_uncompensated():
    """``upto`` scopes EF to the additive head segment: the stats tail
    (BatchNorm running stats — state, not a gradient) must never receive
    residual corrections."""
    ef = wire.ErrorFeedback()
    vec = np.concatenate([np.full(8, 0.01), np.ones(4)]).astype(np.float32)
    comp = ef.compensate(0, vec, upto=8)
    frame = wire.encode(comp, "int8")
    dec = wire.decode(frame)
    ef.update(0, comp, dec, upto=8)
    comp2 = ef.compensate(0, vec, upto=8)
    # Head got compensation (the residual is non-zero there)...
    assert not np.array_equal(comp2[:8], vec[:8])
    # ...the tail is passed through untouched.
    np.testing.assert_array_equal(comp2[8:], vec[8:])


# --- exchange integration (native runtime required) -------------------------

pytest.importorskip("garfield_tpu.native")
from garfield_tpu import native  # noqa: E402

_HAVE_NATIVE = native.load() is not None

needs_native = pytest.mark.skipif(
    not _HAVE_NATIVE, reason="native runtime unavailable"
)


def _ports(k):
    socks = [socket.socket() for _ in range(k)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _mesh(n, **kw):
    from garfield_tpu.utils.exchange import PeerExchange

    hosts = [f"127.0.0.1:{p}" for p in _ports(n)]
    return [PeerExchange(i, hosts, **kw) for i in range(n)]


@needs_native
def test_cross_dtype_publish_collect():
    """Mixed-width deployments interoperate: decoding is header-driven,
    never local-setting-driven — a bf16 sender and an f32 sender land in
    the same quorum."""
    rng = np.random.default_rng(4)
    v0 = rng.standard_normal(64).astype(np.float32)
    v1 = rng.standard_normal(64).astype(np.float32)

    def tf(idx, payload):
        return wire.decode(payload)

    peers = _mesh(2)
    try:
        peers[0].publish(3, wire.encode(v0, "f32"))
        peers[1].publish(3, wire.encode(v1, "bf16"))
        for p in peers:
            got = p.collect(3, q=2, timeout_ms=10_000, transform=tf)
            np.testing.assert_array_equal(got[0], v0)
            np.testing.assert_array_equal(
                got[1], wire.decode(wire.encode(v1, "bf16"))
            )
    finally:
        for p in peers:
            p.close()


@needs_native
def test_transform_error_is_stored_not_raised():
    """A transform that raises (codec reject) must surface as the peer's
    stored result — attributed ban evidence, not a missing-peer timeout."""
    from garfield_tpu.apps.cluster import _frame_transform

    peers = _mesh(2)
    try:
        tf = _frame_transform((8, 0))
        frame = bytearray(wire.encode(np.ones(8, np.float32), "f32"))
        frame[-1] ^= 0x40  # payload bit flip -> crc reject
        peers[1].publish(0, bytes(frame))
        peers[0].publish(0, wire.encode(np.zeros(8, np.float32), "f32"))
        got = peers[0].collect(0, q=2, timeout_ms=10_000, transform=tf)
        assert isinstance(got[1], wire.WireError)
        assert got[1].nbytes == len(frame)
        head, tail = got[0]
        np.testing.assert_array_equal(np.asarray(head), np.zeros(8))
        assert tail.size == 0
    finally:
        for p in peers:
            p.close()


@needs_native
def test_gradient_quorum_bans_corrupt_codec_frames():
    """The malformed-frame ban path, end to end: random bit-flipped and
    truncated codec payloads never reach the aggregation and get their
    sender excluded from all future quorums — exactly like the old
    wrong-length frame (ISSUE r8 satellite)."""
    from garfield_tpu.apps.cluster import _gradient_quorum
    from garfield_tpu.telemetry import hub as tele_hub

    d = 32
    rng = np.random.default_rng(5)
    honest = rng.standard_normal(d).astype(np.float32)
    hub = tele_hub.MetricsHub()
    prev = tele_hub.install(hub)
    peers = _mesh(3)  # 0 = PS, 1 = honest worker, 2 = Byzantine bytes
    try:
        for trial, corrupt in enumerate([
            b"\x00" * 10,                                   # garbage
            wire.encode(honest, "f32")[: wire.HEADER_NBYTES + 7],  # trunc
            bytes([b ^ (1 << rng.integers(8)) if i == 20 else b
                   for i, b in enumerate(wire.encode(honest, "bf16"))]),
        ]):
            step = trial
            peers[2].publish(step, corrupt, to=[0])
            # The honest frame arrives LATE so the q=1 quorum closes on
            # the corrupt frame first and the ban path must re-collect.
            t = threading.Timer(
                0.3, lambda s=step: peers[1].publish(
                    s, wire.encode(honest, "f32"), to=[0]
                )
            )
            t.start()
            deadline = time.time() + 10
            while peers[0]._mb.version(2) < trial + 1 and time.time() < deadline:
                time.sleep(0.02)
            got, good = _gradient_quorum(
                peers[0], step, 1, [1, 2], (d, 0),
                republish=lambda: None, timeout_ms=10_000, who="test-ps",
            )
            t.join()
            # The corrupt frame never enters the result; rank 2 is banned.
            assert good == [1]
            assert set(got) == {1}
            np.testing.assert_array_equal(np.asarray(got[1][0]), honest)
        events = [r for r in hub.records()
                  if r.get("event") == "quorum_exclusion"]
        assert events and all(e["rank"] == 2 for e in events)
    finally:
        tele_hub.uninstall()
        if prev is not None:
            tele_hub.install(prev)
        for p in peers:
            p.close()


@needs_native
def test_gradient_quorum_bans_malformed_sparse_frames():
    """Round 18: a CRC-VALID topk frame with duplicate sparse indices (a
    forged frame only its sender could produce — the Byzantine case, not
    line noise) feeds the SAME quorum-exclusion path as a CRC reject:
    never reaches the aggregation, sender banned, ``quorum_exclusion``
    telemetry attributed. Extends the PR 4 codec-reject ban surface to
    the compressed schemes' semantic checks."""
    from garfield_tpu.apps.cluster import _gradient_quorum
    from garfield_tpu.telemetry import hub as tele_hub

    d = 32
    rng = np.random.default_rng(6)
    honest = rng.standard_normal(d).astype(np.float32)
    forged = _forge(
        4, d, _topk_payload([3, 3, 9], [5.0, -5.0, 1.0]), plane=1,
    )
    assert len(forged) >= wire.HEADER_NBYTES
    hub = tele_hub.MetricsHub()
    prev = tele_hub.install(hub)
    peers = _mesh(3)  # 0 = PS, 1 = honest worker, 2 = Byzantine sender
    try:
        peers[2].publish(0, forged, to=[0])
        t = threading.Timer(
            0.3, lambda: peers[1].publish(
                0, wire.encode(honest, "f32", plane=1), to=[0]
            )
        )
        t.start()
        deadline = time.time() + 10
        while peers[0]._mb.version(2) < 1 and time.time() < deadline:
            time.sleep(0.02)
        got, good = _gradient_quorum(
            peers[0], 0, 1, [1, 2], (d, 0),
            republish=lambda: None, timeout_ms=10_000, who="test-ps",
        )
        t.join()
        assert good == [1]
        assert set(got) == {1}
        np.testing.assert_array_equal(np.asarray(got[1][0]), honest)
        events = [r for r in hub.records()
                  if r.get("event") == "quorum_exclusion"]
        assert events and all(e["rank"] == 2 for e in events)
        # The ban evidence carries the observed frame length.
        assert any(e.get("got_bytes") == len(forged) for e in events)
    finally:
        tele_hub.uninstall()
        if prev is not None:
            tele_hub.install(prev)
        for p in peers:
            p.close()


@needs_native
def test_send_queue_drop_event_emitted():
    """Publisher-side backpressure is no longer silent: overflowing a
    hung receiver's bounded sender queue emits ``send_queue_drop``
    (ISSUE r8 satellite — mirrors the receive-side ``plane_drop``)."""
    from garfield_tpu.telemetry import hub as tele_hub
    from garfield_tpu.utils.exchange import PeerExchange

    srv = socket.create_server(("127.0.0.1", 0))
    conns = []

    def sink():  # accepts, never reads: a hung (not crashed) receiver
        try:
            while True:
                conn, _ = srv.accept()
                conns.append(conn)
        except OSError:
            pass

    threading.Thread(target=sink, daemon=True).start()
    p0 = _ports(1)[0]
    hosts = [f"127.0.0.1:{p0}", f"127.0.0.1:{srv.getsockname()[1]}"]
    hub = tele_hub.MetricsHub()
    prev = tele_hub.install(hub)
    ex = PeerExchange(0, hosts, send_queue_frames=1, send_timeout_ms=2_000)
    try:
        big = b"\x00" * (8 << 20)  # 8 MB: sendall blocks on TCP buffers
        deadline = time.time() + 20
        while not hub.wire_counters()["send_queue_drops"]:
            ex.publish(0, big, to=[1])
            assert time.time() < deadline, "no send_queue_drop emitted"
            time.sleep(0.05)
        drops = [r for r in hub.records()
                 if r.get("event") == "send_queue_drop"]
        assert drops and drops[0]["peer"] == 1
    finally:
        tele_hub.uninstall()
        if prev is not None:
            tele_hub.install(prev)
        ex.close()
        srv.close()
        for c in conns:
            c.close()


# ---------------------------------------------------------------------------
# decode_into (PR 19): the fused dequantize-into-fold entry point.


class TestDecodeInto:
    SCHEMES = ["f32", "bf16", "int8", "int4", "topk"]

    def _vec(self, n, seed=0):
        return np.random.default_rng(seed).normal(
            size=n).astype(np.float32)

    @pytest.mark.parametrize("scheme", SCHEMES)
    @pytest.mark.parametrize("n", [0, 1, 3, 1023, 1024, 1025])
    def test_bitwise_parity_with_decode(self, scheme, n):
        vec = self._vec(n, seed=n + 1)
        frame = wire.encode(vec, dtype=scheme, plane=3)
        want = wire.decode(frame, expect_plane=3, expect_elems=n)
        out = np.full(n, np.float32(np.nan))
        k = wire.decode_into(frame, out, expect_plane=3, expect_elems=n)
        assert k == n
        np.testing.assert_array_equal(out, want)

    def test_oversized_target_decodes_prefix_only(self):
        vec = self._vec(100, seed=9)
        frame = wire.encode(vec, dtype="int8")
        out = np.full(130, np.float32(7.5))
        k = wire.decode_into(frame, out)  # max_elems defaults to out.size
        assert k == 100
        np.testing.assert_array_equal(out[:100], wire.decode(frame))
        # the tail beyond the frame's claim is untouched
        np.testing.assert_array_equal(out[100:], np.float32(7.5))

    @pytest.mark.parametrize("corrupt", ["crc", "truncate", "elems",
                                         "plane", "too_small"])
    def test_errors_leave_target_untouched(self, corrupt):
        vec = self._vec(64, seed=4)
        frame = bytearray(wire.encode(vec, dtype="int8", plane=1))
        sentinel = np.full(64, np.float32(-3.25))
        out = sentinel.copy()
        kwargs = {"expect_plane": 1, "expect_elems": 64}
        if corrupt == "crc":
            frame[-1] ^= 0x55
        elif corrupt == "truncate":
            frame = frame[:20]
        elif corrupt == "elems":
            kwargs["expect_elems"] = 63
        elif corrupt == "plane":
            kwargs["expect_plane"] = 2
        else:
            out = sentinel[:10].copy()
            kwargs = {"expect_plane": 1}
        with pytest.raises(wire.WireError):
            wire.decode_into(bytes(frame), out, **kwargs)
        np.testing.assert_array_equal(out, sentinel[:out.size])

    def test_rejects_unusable_targets_loudly(self):
        frame = wire.encode(self._vec(8))
        with pytest.raises(TypeError, match="float32"):
            wire.decode_into(frame, np.zeros(8, np.float64))
        with pytest.raises(TypeError, match="1-D"):
            wire.decode_into(frame, np.zeros((2, 4), np.float32))
        ro = np.zeros(8, np.float32)
        ro.flags.writeable = False
        with pytest.raises(TypeError, match="writable"):
            wire.decode_into(frame, ro)

    def test_frame_elems_header_only_sizing(self):
        frame = wire.encode(self._vec(321), dtype="int4")
        assert wire.frame_elems(frame) == 321
        with pytest.raises(wire.WireError):
            wire.frame_elems(frame[:10])
        bad = bytearray(frame)
        bad[0] = 0x00  # break the magic
        with pytest.raises(wire.WireError):
            wire.frame_elems(bytes(bad))

    def test_wire_fused_env_knob(self, monkeypatch):
        monkeypatch.delenv("GARFIELD_WIRE_FUSED_DECODE", raising=False)
        assert wire.wire_fused() is True  # default on
        monkeypatch.setenv("GARFIELD_WIRE_FUSED_DECODE", "0")
        assert wire.wire_fused() is False
        monkeypatch.setenv("GARFIELD_WIRE_FUSED_DECODE", "on")
        assert wire.wire_fused() is True


class TestEpochStamp:
    """The v2 epoch-stamped header (round 20, DESIGN.md §22): the
    membership epoch rides every frame under an epoch-seeded CRC, so a
    consumer pinned to its directory's epoch rejects stale, future,
    pre-epoch (v1) and restamped frames as attributable ban evidence."""

    SCHEMES = ["f32", "bf16", "int8", "int4", "topk"]

    def _vec(self, n=257, seed=0):
        return np.random.default_rng(seed).normal(
            size=n).astype(np.float32)

    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_roundtrip_every_scheme(self, scheme):
        vec = self._vec()
        frame = wire.encode(vec, dtype=scheme, plane=2, epoch=7)
        # +4 header bytes vs the v1 frame of the same payload.
        assert len(frame) == len(
            wire.encode(vec, dtype=scheme, plane=2)) + 4
        assert len(frame) == wire.frame_nbytes(
            vec.size, scheme, epoch=True)
        assert wire.frame_epoch(frame) == 7
        assert wire.frame_plane(frame) == 2
        want = wire.decode(wire.encode(vec, dtype=scheme))
        out = wire.decode(frame, expect_plane=2, expect_epoch=7)
        np.testing.assert_array_equal(out, want)
        # decode_into sees the same stamp
        tgt = np.zeros(vec.size, np.float32)
        assert wire.decode_into(
            frame, tgt, expect_plane=2, expect_epoch=7) == vec.size
        np.testing.assert_array_equal(tgt, want)

    def test_v1_frames_carry_no_epoch(self):
        frame = wire.encode(self._vec(), "f32")
        assert wire.frame_epoch(frame) is None
        wire.decode(frame)  # unpinned consumers accept v1 unchanged

    def test_stale_future_and_epochless_rejected(self):
        vec = self._vec()
        stale = wire.encode(vec, "int8", epoch=6)
        with pytest.raises(wire.WireError, match="stale-epoch"):
            wire.decode(stale, expect_epoch=7)
        future = wire.encode(vec, "int8", epoch=8)
        with pytest.raises(wire.WireError, match="future-epoch"):
            wire.decode(future, expect_epoch=7)
        v1 = wire.encode(vec, "int8")
        with pytest.raises(wire.WireError, match="no membership epoch"):
            wire.decode(v1, expect_epoch=7)
        # Accepted exactly at the pin.
        np.testing.assert_array_equal(
            wire.decode(stale, expect_epoch=6), wire.decode(v1))

    def test_epoch_restamp_is_crc_mismatch(self):
        """A relay rewriting the header's epoch bytes to match the
        consumer's pin still fails: the CRC is seeded with the epoch,
        so the restamped frame is a codec failure, not a valid frame
        from a newer epoch."""
        frame = bytearray(wire.encode(self._vec(), "f32", epoch=6))
        off = wire._HDR2.size - 8  # epoch u32 sits before the crc u32
        assert int.from_bytes(frame[off:off + 4], "big") == 6
        frame[off:off + 4] = (7).to_bytes(4, "big")
        with pytest.raises(wire.WireError, match="CRC"):
            wire.decode(bytes(frame), expect_epoch=7)
        sentinel = np.full(257, np.float32(-1.5))
        out = sentinel.copy()
        with pytest.raises(wire.WireError):
            wire.decode_into(bytes(frame), out, expect_epoch=7)
        np.testing.assert_array_equal(out, sentinel)

    def test_check_epoch_validation(self):
        assert wire.check_epoch(0) == 0
        assert wire.check_epoch(wire.MAX_EPOCH) == wire.MAX_EPOCH
        for bad in (-1, wire.MAX_EPOCH + 1):
            with pytest.raises(ValueError):
                wire.check_epoch(bad)
        for bad in (True, 1.5, "7", None):
            with pytest.raises(TypeError):
                wire.check_epoch(bad)
        with pytest.raises(ValueError):
            wire.encode(self._vec(8), "f32", epoch=wire.MAX_EPOCH + 1)

    def test_frame_epoch_header_only_rejects(self):
        frame = wire.encode(self._vec(), "f32", epoch=3)
        with pytest.raises(wire.WireError):
            wire.frame_epoch(frame[:10])
        with pytest.raises(wire.WireError):
            wire.frame_epoch(frame[:18])  # v2 header cut short
        bad = bytearray(frame)
        bad[0] = 0x00
        with pytest.raises(wire.WireError):
            wire.frame_epoch(bytes(bad))
        bad = bytearray(frame)
        bad[2] = 0x09  # unknown version byte
        with pytest.raises(wire.WireError):
            wire.frame_epoch(bytes(bad))


# --- batched decode (decode_batch_into — ISSUE 20) ---------------------------


class TestDecodeBatchInto:
    """The vectorized batch decoder is pinned BITWISE to the per-frame
    ``decode_into`` loop: same outputs, same per-frame rejects with the
    same error text, same pins — for every scheme and both header
    versions. A forged frame in a batch bans its sender (an indexed
    ``WireError`` in the result list) and never poisons batchmates or
    touches its own target row."""

    SCHEMES = ("f32", "bf16", "int8", "int4", "topk")

    def _frames(self, scheme, k, d, *, plane=0, epoch=None, seed=0):
        rng = np.random.default_rng(seed)
        kw = {} if epoch is None else {"epoch": epoch}
        return [
            wire.encode(
                rng.standard_normal(d).astype(np.float32), scheme,
                plane=plane, **kw,
            )
            for _ in range(k)
        ]

    def _assert_matches_per_frame(self, frames, width, **pins):
        """Batch-decode ``frames`` and check EVERY per-frame verdict —
        accepted elems, written prefix, untouched tail/reject rows,
        and reject error text — against the per-frame decode_into
        reference. Returns the batch results."""
        k = len(frames)
        out = np.full((k, width), np.float32(-1.5))
        res = wire.decode_batch_into(frames, out, **pins)
        assert len(res) == k
        for i, fr in enumerate(frames):
            ref = np.full(width, np.float32(-1.5))
            try:
                want = wire.decode_into(fr, ref, **pins)
            except wire.WireError as exc:
                assert isinstance(res[i], wire.WireError), (i, res[i])
                assert str(res[i]) == str(exc)
            else:
                assert res[i] == want, (i, res[i])
            np.testing.assert_array_equal(out[i], ref)
        return res

    @pytest.mark.parametrize("epoch", [None, 7])
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_bitwise_parity_every_scheme_and_header(self, scheme, epoch):
        # 257 elems: a partial quant block + odd int4 nibble padding.
        k, d = 9, 257
        frames = self._frames(scheme, k, d, plane=1, epoch=epoch)
        res = self._assert_matches_per_frame(
            frames, d, expect_plane=1, expect_elems=d, expect_epoch=epoch,
        )
        assert res == [d] * k

    def test_mixed_schemes_and_sizes_in_one_batch(self):
        # Adjacent same-scheme runs of differing widths + scheme
        # switches: the slab-dequant run grouping must break correctly.
        rng = np.random.default_rng(3)
        frames, widths = [], []
        for rep in range(2):
            for j, scheme in enumerate(self.SCHEMES):
                d = 64 + 17 * j + 128 * rep
                frames.append(wire.encode(
                    rng.standard_normal(d).astype(np.float32), scheme,
                ))
                widths.append(d)
        res = self._assert_matches_per_frame(frames, max(widths))
        assert res == widths

    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_corrupt_frame_never_poisons_batchmates(self, scheme):
        k, d = 7, 129
        frames = self._frames(scheme, k, d, seed=11)
        bad = bytearray(frames[3])
        bad[-1] ^= 0xFF  # payload flip: CRC must catch it
        frames[3] = bytes(bad)
        res = self._assert_matches_per_frame(frames, d, expect_elems=d)
        assert isinstance(res[3], wire.WireError)
        assert [r for i, r in enumerate(res) if i != 3] == [d] * (k - 1)

    def test_fuzz_byte_flips_match_per_frame_verdicts(self):
        """Flip one byte at a stride of positions in each scheme's
        frame; the batch verdict for EVERY frame (the corrupted one and
        its batchmates) must equal the per-frame path's — reject text
        included. No assumption about WHICH rejection fires: the pin is
        agreement, exactly the fuzz discipline of the per-frame fuzz
        above."""
        d = 65
        rng = np.random.default_rng(17)
        base = [
            wire.encode(rng.standard_normal(d).astype(np.float32), s)
            for s in self.SCHEMES
        ]
        for victim, fr in enumerate(base):
            for pos in range(0, len(fr), max(1, len(fr) // 13)):
                frames = list(base)
                bad = bytearray(fr)
                bad[pos] ^= 0x5A
                frames[victim] = bytes(bad)
                self._assert_matches_per_frame(frames, d)

    def test_truncated_and_garbage_frames_reject_in_batch(self):
        d = 48
        good = self._frames("f32", 1, d, seed=2)[0]
        frames = [good, good[:10], b"", b"not-a-frame", good[:-3], good]
        res = self._assert_matches_per_frame(frames, d)
        assert res[0] == d and res[5] == d
        assert all(isinstance(r, wire.WireError) for r in res[1:5])

    def test_pins_enforced_per_frame_in_batch(self):
        d = 33
        rng = np.random.default_rng(23)
        v = rng.standard_normal(d).astype(np.float32)
        v2 = rng.standard_normal(2 * d).astype(np.float32)
        frames = [
            wire.encode(v, "f32", plane=2, epoch=7),   # cross-plane
            wire.encode(v, "f32", plane=1, epoch=7),   # accepted
            wire.encode(v2, "f32", plane=1, epoch=7),  # wrong elems
            wire.encode(v, "f32", plane=1, epoch=6),   # stale epoch
            wire.encode(v, "f32", plane=1),            # epochless vs pin
            wire.encode(v, "int4", plane=1, epoch=7),  # accepted
        ]
        res = self._assert_matches_per_frame(
            frames, 2 * d, expect_plane=1, expect_elems=d, expect_epoch=7,
        )
        assert res[1] == d and res[5] == d
        for i in (0, 2, 3, 4):
            assert isinstance(res[i], wire.WireError), i

    def test_max_elems_bounds_sparse_claims_pre_allocation(self):
        """A CRC-valid topk frame claiming 2^40 dense elems must reject
        on ``max_elems`` in the batch path exactly like decode_into —
        BEFORE any payload-sized allocation (the allocation-bomb ban
        surface, Baruch-style)."""
        import struct
        import zlib

        d = 64
        pairs = np.zeros(2, np.dtype([("i", "<u4"), ("v", "<f4")]))
        pairs["i"] = [0, 1]
        pairs["v"] = [5.0, -5.0]
        payload = pairs.tobytes()
        giant = struct.pack(
            "!2sBBQI", b"GW", 1, 4, 2 ** 40, zlib.crc32(payload)
        ) + payload
        honest = self._frames("topk", 2, d, seed=5)
        frames = [honest[0], giant, honest[1]]
        res = self._assert_matches_per_frame(frames, d, max_elems=d)
        assert res[0] == d and res[2] == d
        assert isinstance(res[1], wire.WireError)

    def test_crc_thread_pool_is_bitwise_identical(self, monkeypatch):
        """GARFIELD_INGEST_THREADS only parallelizes the CRC pass —
        verdicts and decoded bytes must not depend on it."""
        k, d = 12, 257
        frames = self._frames("int8", k, d, seed=7)
        bad = bytearray(frames[5])
        bad[-1] ^= 0xFF
        frames[5] = bytes(bad)
        outs = []
        for threads in ("0", "2"):
            monkeypatch.setenv("GARFIELD_INGEST_THREADS", threads)
            out = np.zeros((k, d), np.float32)
            res = wire.decode_batch_into(frames, out, expect_elems=d)
            outs.append((out, res))
        (out0, res0), (out1, res1) = outs
        np.testing.assert_array_equal(out0, out1)
        assert [str(r) for r in res0] == [str(r) for r in res1]
        assert isinstance(res0[5], wire.WireError)

    def test_env_knobs_parse(self, monkeypatch):
        monkeypatch.delenv("GARFIELD_WIRE_BATCH_DECODE", raising=False)
        assert wire.wire_batch_decode() is True  # default on
        monkeypatch.setenv("GARFIELD_WIRE_BATCH_DECODE", "0")
        assert wire.wire_batch_decode() is False
        monkeypatch.setenv("GARFIELD_WIRE_BATCH_DECODE", "false")
        assert wire.wire_batch_decode() is False
        monkeypatch.delenv("GARFIELD_INGEST_THREADS", raising=False)
        assert wire.ingest_threads() == 0  # default inline
        monkeypatch.setenv("GARFIELD_INGEST_THREADS", "3")
        assert wire.ingest_threads() == 3
        monkeypatch.setenv("GARFIELD_INGEST_THREADS", "bogus")
        with pytest.raises(ValueError, match="GARFIELD_INGEST_THREADS"):
            wire.ingest_threads()

    def test_rejects_unusable_slabs_loudly(self):
        frames = self._frames("f32", 2, 16)
        with pytest.raises((TypeError, ValueError)):
            wire.decode_batch_into(frames, np.zeros((2, 16), np.float64))
        with pytest.raises((TypeError, ValueError)):
            wire.decode_batch_into(frames, np.zeros(32, np.float32))
        wide = np.zeros((2, 32), np.float32)
        with pytest.raises((TypeError, ValueError)):
            wire.decode_batch_into(frames, wide[:, ::2])  # non-contiguous
