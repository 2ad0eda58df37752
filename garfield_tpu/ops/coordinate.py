"""Pallas TPU kernels for coordinate-wise robust statistics.

Two kernels, mirroring the two CUDA kernels the reference dedicates to this
layer (SURVEY P13):

  - ``coordinate_median``: lower coordinate-wise median of an (n, d) stack
    (py_median/median.cu counterpart). torch semantics: for even n the lower
    of the two middle values; NaN sorts last, so up to ceil(n/2)-1 NaNs per
    coordinate do not contaminate the result (median.py:39).
  - ``averaged_median_mean``: Bulyan's second phase (py_bulyan/bulyan.cu
    counterpart, bulyan.py:77-84): per coordinate, take the beta values
    closest to the lower median (stable ties: lowest row index wins) and
    average them. Fused into one kernel so the (s, d) stack is read from HBM
    exactly once; the jnp fallback needs a sort, an argsort and a gather.

Design notes (see /opt/skills/guides/pallas_guide.md):
  - n is tiny (worker count, <= MAX_SORT_N) and d is huge, so the kernel
    grid tiles d in LANE-multiple blocks and each program fully sorts its
    (n, TILE) block with an odd-even transposition network unrolled at trace
    time. Compare-exchange on strict ``<`` keeps the network STABLE, which
    is what makes tie-breaking match ``jnp.argsort(..., stable=True)``.
  - The comparator implements the jnp/torch sort total order for floats:
    ascending with NaN last — swap iff (b < a) or (a is NaN and b is not).
  - d is padded to a TILE multiple host-side; columns are independent so the
    pad values are irrelevant and sliced off.
"""

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Largest stack the sorting-network kernels accept: the unrolled network is
# O(n^2) vector ops per tile, which is fine for realistic worker counts
# (the reference's own GAR bench sweeps n <= 512 but runs Byzantine configs
# at n <= a few dozen) and keeps compile times bounded. Above it the XLA
# path is used — which for averaged_median_mean is the gather-free
# threshold formulation (``averaged_median_mean_xla``), NOT the
# catastrophic sort+argsort+gather, so n > 32 degrades gracefully; a
# one-time warning still flags the switch (PERF.md).
MAX_SORT_N = 32

_LANES = 128
# Lanes per program. Swept on the v5e chip (r5, n=8 d=11.2M f32): 1024 ->
# 5.8 ms, 4096 -> 4.4, 8192 -> 3.8 (best), 16384+ regress — the old 1024
# default optimized for a 128 KiB VMEM budget that is ~100x below the
# ~16 MB/core reality, and its 10.9k-program grid paid per-program
# overhead. Worst case (n = MAX_SORT_N + out + padding) stays under 2 MB.
_TILE = 8192

_warned_large_n = set()


def _warn_large_n(op, n):
    """Loud, once-per-op notice that the fused Pallas path is off (VERDICT
    r1: the n > MAX_SORT_N fallback used to be silent)."""
    if op not in _warned_large_n:
        _warned_large_n.add(op)
        warnings.warn(
            f"{op}: n={n} exceeds the Pallas sorting-network bound "
            f"MAX_SORT_N={MAX_SORT_N}; using the XLA path (graceful for "
            "median/tmean/averaged_median_mean, but not the fused "
            "single-HBM-pass kernel). For federated-scale n, use the "
            "hierarchical bucketed rules (garfield_tpu.aggregators."
            "hierarchy, e.g. gars['hier-krum']): robust buckets of <= "
            "MAX_SORT_N keep every fold on the fast path.",
            stacklevel=3,
        )


def use_pallas(n=None, op=None):
    """True when the Pallas path should be used (TPU backend, n in range)."""
    if os.environ.get("GARFIELD_NO_PALLAS"):
        return False
    if n is not None and n > MAX_SORT_N:
        if op is not None and jax.default_backend() == "tpu":
            _warn_large_n(op, n)
        return False
    return jax.default_backend() == "tpu"


def _swap_mask(a, b):
    """Swap iff a must sort after b: ascending, NaN last (strict => stable)."""
    return (b < a) | (jnp.isnan(a) & ~jnp.isnan(b))


def _oddeven_exchange(keys, payloads=None):
    """In-place-style odd-even transposition sort of a list of row vectors.

    Sorts ``keys`` (list of n equal-shape arrays) ascending under the
    NaN-last total order; ``payloads`` (optional parallel list) is permuted
    identically. Unrolled: n rounds of adjacent compare-exchange.
    """
    n = len(keys)
    keys = list(keys)
    payloads = list(payloads) if payloads is not None else None
    for rnd in range(n):
        for i in range(rnd % 2, n - 1, 2):
            m = _swap_mask(keys[i], keys[i + 1])
            keys[i], keys[i + 1] = (
                jnp.where(m, keys[i + 1], keys[i]),
                jnp.where(m, keys[i], keys[i + 1]),
            )
            if payloads is not None:
                payloads[i], payloads[i + 1] = (
                    jnp.where(m, payloads[i + 1], payloads[i]),
                    jnp.where(m, payloads[i], payloads[i + 1]),
                )
    return keys if payloads is None else (keys, payloads)


def _pad_cols(g, tile):
    d = g.shape[-1]
    pad = (-d) % tile
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
    return g, d


def _load_rows(x_ref, n, sel=None):
    """Rows upcast to f32 in VMEM: Mosaic on current targets rejects bf16
    compares ("Target does not support this comparison" — caught by the
    on-device tests, tests/test_ops_tpu.py), and bf16 -> f32 is exact and
    order-preserving, so the sort network is unchanged semantically while
    HBM traffic stays bf16.

    ``sel`` (optional, STATIC): list of (row_index, scale) pairs — the
    folded-attack remap (parallel/fold.py): logical row i is
    ``scale * block[row_index]``. Duplicate indices (lie's shared fake
    row) are free VMEM re-reads; the indexing and scaling unroll at trace
    time, so the poisoned stack is never materialized anywhere."""
    if sel is None:
        return [x_ref[i, :].astype(jnp.float32) for i in range(n)]

    def one(idx, scale):
        if scale == 0.0:
            # Exact zeros, not 0*row: the crash attack's where-path writes
            # literal zero rows, and 0*inf/0*nan would leak NaN into the
            # sort where the reference semantics have 0.
            return jnp.zeros_like(x_ref[idx, :], jnp.float32)
        row = x_ref[idx, :].astype(jnp.float32)
        return row if scale == 1.0 else row * scale

    return [one(idx, scale) for idx, scale in sel]


def _median_kernel(n, sel, x_ref, o_ref):
    rows = _oddeven_exchange(_load_rows(x_ref, n, sel))
    o_ref[0, :] = rows[(n - 1) // 2].astype(o_ref.dtype)


def _tmean_kernel(n, f, sel, x_ref, o_ref):
    rows = _oddeven_exchange(_load_rows(x_ref, n, sel))
    acc = rows[f]
    for i in range(f + 1, n - f):
        acc = acc + rows[i]
    o_ref[0, :] = (acc / (n - 2 * f)).astype(o_ref.dtype)


def _avgmed_kernel(s, beta, quant_dtype, x_ref, o_ref):
    vals = _load_rows(x_ref, s)
    med = _oddeven_exchange(list(vals))[(s - 1) // 2]
    # Deviations are the SORT KEYS and must carry the LOGICAL input
    # dtype's rounding: the spec computes |g - med| in the caller's dtype,
    # where bf16 rounding creates ties (broken stably by row index) that
    # exact f32 deviations would order differently. ``quant_dtype`` is the
    # caller's dtype — the kernel itself now always runs on f32 blocks
    # (_dispatch upcasts half inputs), so x_ref.dtype no longer carries
    # it. Quantize, then upcast for the comparisons Mosaic supports.
    devs = [
        jnp.abs(v - med).astype(quant_dtype).astype(jnp.float32)
        for v in vals
    ]
    _, picked = _oddeven_exchange(devs, vals)
    acc = picked[0]
    for i in range(1, beta):
        acc = acc + picked[i]
    o_ref[0, :] = (acc / beta).astype(o_ref.dtype)


def _column_call(kernel, g, tile, interpret, name):
    """Run a (n, TILE) -> (1, TILE) kernel over d-tiles of g. ``name`` is
    the custom call's name in the compiled program, and so its events' name
    in a device trace (``%coordinate_median.N``)."""
    if tile % _LANES:
        raise ValueError(f"tile must be a multiple of {_LANES}, got {tile}")
    g, d = _pad_cols(g, tile)
    n, dp = g.shape
    out = pl.pallas_call(
        kernel,
        grid=(dp // tile,),
        in_specs=[pl.BlockSpec((n, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), g.dtype),
        interpret=interpret,
        name=name,
    )(g)
    return out[0, :d]


# --- public entry points ---------------------------------------------------


def coordinate_median_reference(g):
    """jnp spec: lower coordinate-wise median, NaN-resilient (median.py:39)."""
    n = g.shape[0]
    return jnp.sort(g, axis=0)[(n - 1) // 2]


def trimmed_mean_reference(g, f):
    """jnp spec: drop the f smallest/largest per coordinate, average rest
    (NaN sorts last, so up to f NaNs per coordinate land in the tail)."""
    n = g.shape[0]
    return jnp.mean(jnp.sort(g, axis=0)[f : n - f], axis=0)


def averaged_median_mean_reference(g, beta):
    """jnp spec for Bulyan phase 2 (bulyan.py:77-84)."""
    med = coordinate_median_reference(g)
    dev = jnp.abs(g - med[None, :])
    idx = jnp.argsort(dev, axis=0, stable=True)[:beta]
    return jnp.mean(jnp.take_along_axis(g, idx, axis=0), axis=0)


def averaged_median_mean_xla(g, beta):
    """Gather-free Bulyan phase 2: threshold + stable tie rank.

    Semantics-equal to ``averaged_median_mean_reference`` but without the
    argsort+gather pair, whose (s, d) gather is the catastrophic XLA path
    at large d (PERF.md). Per coordinate: rows with deviation strictly
    below the beta-th smallest are all selected; the remaining quota among
    exact-threshold ties goes to the lowest row indices (the stable
    tie-break of ``argsort(stable=True)``). One sort + O(s) elementwise.
    """
    s = g.shape[0]
    med = coordinate_median_reference(g)
    dev = jnp.abs(g - med[None, :])
    thresh = jnp.sort(dev, axis=0)[beta - 1]  # (d,); NaN sorts last
    lt = dev < thresh[None, :]
    eq = dev == thresh[None, :]
    quota = beta - jnp.sum(lt, axis=0)  # ties to admit per coordinate
    tie_rank = jnp.cumsum(eq, axis=0)  # 1-based rank among tie rows
    mask = lt | (eq & (tie_rank <= quota[None, :]))
    out = jnp.sum(jnp.where(mask, g, 0), axis=0) / beta
    # >s-beta NaN deviations per coordinate: the reference mean is NaN
    # (NaN rows enter the argsort tail); comparisons with a NaN threshold
    # selected nothing, so restore the NaN explicitly.
    return jnp.where(jnp.isnan(thresh), jnp.nan, out)


def _dispatch(g, kernel, fallback_fn, tile, interpret, n, op):
    """Route to the Pallas kernel or the XLA fallback.

    The Pallas branch is selected by the *lowering* platform
    (``lax.platform_dependent``), not the process-default backend — a
    computation jitted for CPU devices on a TPU host takes the XLA path
    instead of failing to lower (ADVICE r1). ``use_pallas`` (and its
    large-n warning) is consulted only when the kernel is NOT forced via
    ``interpret=True`` — an interpret-mode call runs the kernel and must
    not warn or consume the once-per-op warning budget.
    """
    # Half-precision inputs run the KERNEL in f32: Mosaic's packed (2, 1)
    # sublane loads + per-row converts made the bf16 kernel SLOWER than
    # the f32 one despite half the HBM traffic (measured r5: 7.8 vs
    # 3.8 ms at n=8 d=11.2M), so one XLA convert outside the kernel wins
    # ~2x. bf16 -> f32 is exact, selection ops (median) round-trip
    # losslessly, and the mean-producing kernels (tmean/avgmed) gain f32
    # accumulation accuracy before the single round back.
    orig = g.dtype
    half = orig in (jnp.bfloat16, jnp.float16)

    def run_kernel(a, interp):
        out = _column_call(
            kernel, a.astype(jnp.float32) if half else a, tile, interp, op
        )
        return out.astype(orig) if half else out

    if interpret:
        return run_kernel(g, True)
    if not use_pallas(n, op=op):
        return fallback_fn(g)
    return jax.lax.platform_dependent(
        g,
        tpu=lambda a: run_kernel(a, False),
        default=fallback_fn,
    )


def _remap_sel(g, row_map, row_scale):
    """Normalize the folded-attack remap to a static ``sel`` list (or None)
    plus the logical row count; validates bounds against g's physical rows.
    ``row_map``/``row_scale`` must be concrete (numpy) — the remap is baked
    into the kernel at trace time."""
    import numpy as np

    if row_map is None and row_scale is None:
        return None, g.shape[0]
    ne = g.shape[0]
    row_map = (
        np.arange(ne) if row_map is None else np.asarray(row_map, np.int64)
    )
    n = row_map.size
    row_scale = (
        np.ones(n) if row_scale is None else np.asarray(row_scale, np.float64)
    )
    if row_scale.size != n:
        raise ValueError(
            f"row_scale has {row_scale.size} entries for {n} mapped rows"
        )
    if row_map.min() < 0 or row_map.max() >= ne:
        raise ValueError(
            f"row_map references rows outside the {ne}-row stack"
        )
    return [
        (int(i), float(s)) for i, s in zip(row_map, row_scale)
    ], n


def _remap_fallback(g, sel):
    """XLA form of the remap: one static gather + row scaling. Zero scales
    produce exact zero rows (see ``_load_rows``: 0*inf must not leak NaN
    where the where-path's crash attack writes literal zeros)."""
    import numpy as np

    idx = jnp.asarray(np.array([i for i, _ in sel]))
    scale_np = np.array([s for _, s in sel])
    scale = jnp.asarray(scale_np, g.dtype)
    eff = g[idx] * scale[:, None]
    zero = scale_np == 0.0
    if zero.any():
        eff = jnp.where(jnp.asarray(zero)[:, None], 0.0, eff).astype(eff.dtype)
    return eff


def coordinate_median(g, *, row_map=None, row_scale=None, interpret=False,
                      tile=_TILE):
    """Lower coordinate-wise median of an (n, d) stack -> (d,).

    ``row_map``/``row_scale`` (static) apply the folded-attack remap INSIDE
    the kernel — logical row i is ``row_scale[i] * g[row_map[i]]`` — so the
    poisoned stack of a deterministic attack is never materialized
    (parallel/fold.py)."""
    g = jnp.asarray(g)
    sel, n = _remap_sel(g, row_map, row_scale)
    if n == 1:
        return g[0] if sel is None else _remap_fallback(g, sel)[0]
    fallback = (
        coordinate_median_reference if sel is None
        else lambda a: coordinate_median_reference(_remap_fallback(a, sel))
    )
    return _dispatch(
        g, functools.partial(_median_kernel, n, sel),
        fallback, tile, interpret,
        n, "coordinate_median",
    )


def trimmed_mean(g, f, *, row_map=None, row_scale=None, interpret=False,
                 tile=_TILE):
    """Coordinate-wise trimmed mean: average of rows f..n-f-1 per sorted
    column, fused into the sorting-network kernel (one HBM pass).
    ``row_map``/``row_scale``: see ``coordinate_median``."""
    g = jnp.asarray(g)
    sel, n = _remap_sel(g, row_map, row_scale)
    if not (0 <= f and n - 2 * f >= 1):
        raise ValueError(f"need n - 2f >= 1, got n={n}, f={f}")
    if n == 1:
        return g[0] if sel is None else _remap_fallback(g, sel)[0]
    fallback = (
        (lambda a: trimmed_mean_reference(a, f)) if sel is None
        else (lambda a: trimmed_mean_reference(_remap_fallback(a, sel), f))
    )
    return _dispatch(
        g, functools.partial(_tmean_kernel, n, f, sel),
        fallback, tile, interpret,
        n, "trimmed_mean",
    )


def _sortnet_split(g, axis):
    """``g`` split into per-index slices along ``axis`` (upcast-for-compare),
    bounds-checked against MAX_SORT_N — the shared front half of every jnp
    sorting-network entry point."""
    n = g.shape[axis]
    if n > MAX_SORT_N:
        raise ValueError(
            f"sorting-network path is bounded by MAX_SORT_N={MAX_SORT_N}, "
            f"got n={n}; use the XLA sort or bucket hierarchically"
        )
    rows = [jax.lax.index_in_dim(g, i, axis, keepdims=False)
            for i in range(n)]
    if g.dtype in (jnp.bfloat16, jnp.float16):
        rows = [r.astype(jnp.float32) for r in rows]
    return rows


def _sortnet_rows(g, axis):
    """Rows of ``g`` along ``axis``, sorted by the odd-even network.

    The network is the SAME ``_oddeven_exchange`` the Pallas kernels unroll
    — plain jnp here, so it lowers on every backend and under ``vmap``
    (``pallas_call`` batching is what the hierarchical bucket fold must not
    depend on). Half inputs are upcast to f32 for the compares exactly like
    ``_dispatch``/``_load_rows`` (bf16 -> f32 is exact and order-preserving)
    and the caller rounds back. O(n^2) compare-exchanges: only sane for
    n <= MAX_SORT_N, which is the bucket-size contract.
    """
    return _oddeven_exchange(_sortnet_split(g, axis))


def _oddeven_exchange_vec(keys, payload):
    """Index-carrying odd-even transposition along axis 0, one vectorized
    compare-exchange per round.

    The SAME network schedule as ``_oddeven_exchange`` (n rounds of
    adjacent compare-exchange under the strict-< NaN-last comparator, so
    ties keep ascending payload order — ``jnp.argsort(..., stable=True)``
    parity), but each round's pairs swap as two strided slices instead of
    n scalar chains. The list form with payloads compiles PATHOLOGICALLY
    on XLA:CPU (~50 s at n=30: the 2n² interleaved key/payload SSA chains
    defeat the fusion pass; measured, see DESIGN.md §21) while this form
    is O(n) HLO ops and compiles in ~1 s with identical semantics.
    """
    n = keys.shape[0]
    for rnd in range(n):
        off = rnd % 2
        npairs = (n - off) // 2
        if npairs == 0:
            continue
        end = off + 2 * npairs
        lo, hi = keys[off:end:2], keys[off + 1:end:2]
        m = _swap_mask(lo, hi)
        merged = jnp.stack(
            [jnp.where(m, hi, lo), jnp.where(m, lo, hi)], axis=1
        ).reshape((2 * npairs,) + keys.shape[1:])
        keys = jnp.concatenate([keys[:off], merged, keys[end:]], axis=0)
        plo, phi = payload[off:end:2], payload[off + 1:end:2]
        pm = jnp.stack(
            [jnp.where(m, phi, plo), jnp.where(m, plo, phi)], axis=1
        ).reshape((2 * npairs,) + payload.shape[1:])
        payload = jnp.concatenate([payload[:off], pm, payload[end:]], axis=0)
    return keys, payload


def _sortnet_index(g, axis):
    """(sorted keys, permuted index payload) along ``axis`` (moved to axis
    0): the index-carrying network behind argmin/top_m/argsort. Bounds and
    upcast exactly like ``_sortnet_split``; the emitted permutation is the
    stable NaN-last order of ``jnp.argsort(..., stable=True)`` — strict
    ``<`` never swaps equal keys, so ties keep ascending index order.
    This is what makes sortnet selection substitutable for the stable-
    argsort selection on the krum/multi-krum/bulyan Gram paths.
    """
    n = g.shape[axis]
    if n > MAX_SORT_N:
        raise ValueError(
            f"sorting-network path is bounded by MAX_SORT_N={MAX_SORT_N}, "
            f"got n={n}; use the XLA sort or bucket hierarchically"
        )
    keys = jnp.moveaxis(g, axis, 0)
    if g.dtype in (jnp.bfloat16, jnp.float16):
        keys = keys.astype(jnp.float32)
    shape = (n,) + (1,) * (keys.ndim - 1)
    idx = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32).reshape(shape), keys.shape
    )
    return _oddeven_exchange_vec(keys, idx)


def sortnet_median(g, *, axis=-2):
    """Lower coordinate-wise median along ``axis`` via the jnp sorting
    network — bitwise-equal to ``coordinate_median_reference`` (same
    NaN-last total order, same lower-middle pick) but ~15x faster than
    XLA's variadic sort on CPU at n <= MAX_SORT_N, and batch/vmap-safe on
    every backend. This is the hierarchical bucket fold's coordinate-rule
    fast path (aggregators/hierarchy.py): buckets are <= MAX_SORT_N by
    construction, so every fold stays on a sorting network."""
    g = jnp.asarray(g)
    n = g.shape[axis]
    out = _sortnet_rows(g, axis)[(n - 1) // 2]
    return out.astype(g.dtype)


def sortnet_trimmed_mean(g, f, *, axis=-2):
    """Coordinate-wise trimmed mean along ``axis`` via the jnp sorting
    network: drop the f smallest/largest per coordinate, average the rest
    with the SAME sequential f32 accumulation as the Pallas
    ``_tmean_kernel`` (rows f..n-f-1 added in index order, one divide)."""
    g = jnp.asarray(g)
    n = g.shape[axis]
    if not (0 <= f and n - 2 * f >= 1):
        raise ValueError(f"need n - 2f >= 1, got n={n}, f={f}")
    rows = _sortnet_rows(g, axis)
    acc = rows[f]
    for i in range(f + 1, n - f):
        acc = acc + rows[i]
    return (acc / (n - 2 * f)).astype(g.dtype)


def sortnet_sort(keys, *, axis=-1):
    """``jnp.sort(keys, axis=axis)`` via the odd-even network: same total
    order (ascending, NaN last), bitwise-identical output — values are
    permuted by ``where`` swaps, never recomputed. Bounded by MAX_SORT_N
    along ``axis`` (loud ValueError above it); vmap/batch-safe on every
    backend. Half inputs compare (and return) in f32."""
    keys = jnp.asarray(keys)
    return jnp.stack(_sortnet_rows(keys, axis), axis=axis)


def sortnet_argsort(keys, *, axis=-1):
    """``jnp.argsort(keys, axis=axis, stable=True)`` via the index-carrying
    network (int32 indices): stable ties, NaN-last. The full permutation —
    Bulyan's phase-1 scatter needs all n positions; prefer
    ``sortnet_argmin``/``sortnet_top_m`` when only a prefix is consumed."""
    keys = jnp.asarray(keys)
    _, idx = _sortnet_index(keys, axis)
    return jnp.moveaxis(idx, 0, axis % keys.ndim)


def sortnet_argmin(keys, *, axis=-1):
    """Index of the minimum along ``axis`` (first index on ties, NaN last)
    — ``jnp.argsort(keys, stable=True)[..., 0]`` without materializing the
    permutation. Shape: ``keys`` with ``axis`` removed; int32."""
    keys = jnp.asarray(keys)
    _, idx = _sortnet_index(keys, axis)
    return idx[0]


def sortnet_top_m(keys, m, *, axis=-1):
    """Indices of the m smallest along ``axis``, best first — the stable
    NaN-last prefix ``jnp.argsort(keys, stable=True)[..., :m]``. This is
    (multi-)krum's selection: m best-scored rows, ties to the lowest
    index."""
    keys = jnp.asarray(keys)
    n = keys.shape[axis]
    if not (1 <= m <= n):
        raise ValueError(f"m must be in [1, {n}], got {m}")
    _, idx = _sortnet_index(keys, axis)
    return jnp.moveaxis(idx[:m], 0, axis % keys.ndim)


def sortnet_row_sums(dist, k, *, axis=-1):
    """Sum of the k smallest entries along ``axis`` in EXPLICIT ascending
    order — krum's score without materializing the full sorted matrix.

    The accumulation is a sequential add chain over the network's sorted
    rows (smallest first), the same idiom as ``sortnet_trimmed_mean`` /
    the Pallas ``_tmean_kernel``. A chain is the bitwise-robust form: XLA
    never reassociates explicit float adds, whereas ``jnp.sum`` over an
    axis is free to regroup its reduce per fusion context — measured on
    XLA:CPU to flip last bits between programs computing the SAME
    ``jnp.sum(jnp.sort(d)[..., :k])`` expression (DESIGN.md §21). Krum's
    slow path chains the sorted slices identically, so toggling
    GARFIELD_SORTNET_SELECT cannot move a trajectory. Half inputs sum
    (and return) in f32, like every sortnet entry point."""
    dist = jnp.asarray(dist)
    n = dist.shape[axis]
    if not (1 <= k <= n):
        raise ValueError(f"k must be in [1, {n}], got {k}")
    rows = _sortnet_rows(dist, axis)
    acc = rows[0]
    for i in range(1, k):
        acc = acc + rows[i]
    return acc


def averaged_median_mean(g, beta, *, interpret=False, tile=_TILE):
    """Mean of the beta rows closest (per coordinate) to the lower median.

    Equivalent to ``averaged_median_mean_reference`` (ties broken stably by
    row index, NaN deviations sort last) but fused into a single HBM pass.
    Off the Pallas path (n > MAX_SORT_N, non-TPU lowering, or
    GARFIELD_NO_PALLAS) it uses the gather-free ``averaged_median_mean_xla``
    — NOT the argsort+gather spec, whose gather is catastrophic at large d.
    """
    g = jnp.asarray(g)
    s = g.shape[0]
    if not (1 <= beta <= s):
        raise ValueError(f"beta must be in [1, {s}], got {beta}")
    return _dispatch(
        g, functools.partial(_avgmed_kernel, s, beta, g.dtype),
        lambda a: averaged_median_mean_xla(a, beta), tile, interpret,
        s, "averaged_median_mean",
    )
