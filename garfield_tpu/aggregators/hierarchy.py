"""Hierarchical bucketed robust aggregation (DESIGN.md §13).

The flat GARs are built for tens of workers: every rule makes one pass over
an (n, d) stack, the coordinate kernels fall off the Pallas fast path past
``MAX_SORT_N`` = 32 (ops/coordinate.py), and the single-shot rules stay
graceful only to n ≈ 512 (XLA:CPU, rounds 3-4). Federated scale — the
ROADMAP's "millions of users" — needs Byzantine resilience that COMPOSES:

  1. partition the n client gradients into buckets of ≤ ``bucket_size``
     (default MAX_SORT_N, the Pallas sorting-network sweet spot);
  2. robust-aggregate each bucket with a bucket GAR (vmapped over buckets:
     Gram rules batch their MXU matmuls, coordinate rules run the jnp
     sorting network ``ops.sortnet_median`` — the Pallas kernel's
     algorithm, batch-safe on every backend);
  3. robust-aggregate the bucket summaries with a (possibly different)
     top-level GAR — recursing while more than ``bucket_size`` summaries
     remain (``levels="auto"``), so memory and sort widths stay bounded.

This is the bucketing construction of Karimireddy et al. ("Byzantine-Robust
Learning on Heterogeneous Datasets via Bucketing") crossed with the
hierarchical aggregation of FL systems à la Bonawitz et al., expressed over
this repo's GAR registry.

f-composition
-------------
If every bucket at a level tolerates ``f_l`` Byzantine members, corrupting
one bucket summary costs the adversary ``f_l + 1`` clients — REGARDLESS of
placement. A global budget of ``f`` Byzantine clients therefore corrupts at
most ``f // (f_l + 1)`` summaries, which becomes the Byzantine budget of
the next level up; recursively, a hierarchy with per-level tolerances
``f_0, f_1, …, f_top`` withstands ``prod(f_l + 1) · (f_top + 1) − 1``
Byzantine clients. ``plan_hierarchy`` derives the per-level split (each
``f_l`` clamped into the level rule's contract at the smallest bucket of
that level), ``check``/``upper_bound`` expose the composed contract so the
``hier-*`` rules register in ``gars[...]`` like any flat rule, and the
adversarial-placement tests (tests/test_hierarchy.py) pin that concentrated
and spread cohorts both stay inside the tolerance.

Streaming ingest
----------------
``StreamingAggregator`` is the wave-based reducer for clients arriving in
order over the host plane: each pushed vector fills the current bucket;
completed buckets fold in vmapped waves the moment they close, and their
summaries cascade up the level states the same way. Peak memory is
O(wave · bucket_size · d) per level — O(log n) buffers, NOT O(n · d) — so
n = 2^17 clients at d = 1e5 fit a 1-core container (XLA:CPU, round 10).
``push_frame``/``wire_transform`` accept typed wire frames (utils/wire.py);
the transform plugs straight into ``PeerExchange.collect_begin`` so decode +
bucket folding runs in the exchange's pre-registered waiter threads, and a
codec reject propagates as the sender's ban evidence exactly like the
cluster quorum paths. Streaming and batch aggregation are BITWISE equal
(pinned): both paths fold through the same jitted per-bucket programs, and
vmap width does not change per-element results.

Telemetry: with ``telemetry=True`` the reducer derives per-client
observed/selected weights (bucket-level ``gram_select`` exclusions composed
with the exclusion of whole bucket summaries above) and emits them as a
``hier_exclusion`` event, which ``telemetry.hub.MetricsHub`` folds into the
same per-client suspicion score the in-graph taps feed (docs/TELEMETRY.md).
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import gars, register
from ._common import as_stack, concat_stack, num_gradients, unflatten_vec
from ..ops import coordinate as _coord
from ..telemetry import trace as _trace
from ..utils import tools

__all__ = [
    "DEFAULT_BUCKET_SIZE",
    "SUPPORTED_RULES",
    "HierPlan",
    "plan_hierarchy",
    "max_tolerated_f",
    "aggregate",
    "aggregate_with_audit",
    "check",
    "upper_bound",
    "tree_aggregate",
    "StreamingAggregator",
    "make_hier_gar",
    "parse_hier_name",
]

DEFAULT_BUCKET_SIZE = _coord.MAX_SORT_N

# (min_f, max_f(n)) each rule's contract + breakdown point admits — the
# single source the f-composition derives from (mirrors each rule module's
# ``check``; ``average`` is contract-legal at any f but TOLERATES none, so
# it may only serve levels whose derived Byzantine budget is zero).
# ``condense`` (needs an rng key per call) and ``brute`` (exponential in n)
# are deliberately unsupported.
_TOLERANCE = {
    "krum": (1, lambda n: (n - 3) // 2),
    "median": (0, lambda n: (n - 1) // 2),
    "tmean": (1, lambda n: (n - 1) // 2),
    "bulyan": (1, lambda n: (n - 3) // 4),
    "aksel": (1, lambda n: (n - 1) // 2),
    "cclip": (0, lambda n: (n - 1) // 2),
    "average": (0, lambda n: 0),
}
SUPPORTED_RULES = tuple(sorted(_TOLERANCE))


def _tolerance(rule, n):
    """(min_f, max_f) the rule admits at n inputs; max < min means the
    bucket is too small for the rule at any tolerance."""
    lo, hi = _TOLERANCE[rule]
    return lo, hi(n)


def _min_n(rule, f):
    """Smallest input count at which ``rule`` admits tolerance ``f``."""
    lo, _ = _TOLERANCE[rule]
    f = max(f, lo)
    if rule == "krum":
        return 2 * f + 3
    if rule == "bulyan":
        return 4 * f + 3
    if rule in ("tmean", "aksel", "cclip"):
        return 2 * f + 1
    return 1  # median / average accept any n >= 1


def _balanced_into(n, num):
    """Partition n into exactly ``num`` contiguous buckets with sizes
    differing by at most 1 (larger buckets first) — no tiny remainder
    bucket for the adversary to overwhelm cheaply."""
    base, rem = divmod(n, num)
    return (base + 1,) * rem + (base,) * (num - rem)


class _Level:
    """One bucketing level: ``sizes[b]`` clients/summaries per bucket,
    every bucket aggregated by ``rule`` at tolerance ``f``."""

    __slots__ = ("sizes", "f", "rule")

    def __init__(self, sizes, f, rule):
        self.sizes = tuple(sizes)
        self.f = int(f)
        self.rule = rule

    def __repr__(self):
        return (f"<level {self.rule} x{len(self.sizes)} buckets "
                f"(sizes {min(self.sizes)}..{max(self.sizes)}) f={self.f}>")


class HierPlan:
    """Derived hierarchy: bucketing levels bottom-up, then the final fold.

    ``bucket_levels[0]`` consumes the n client gradients; each subsequent
    level consumes the previous level's bucket summaries; ``final_rule`` at
    tolerance ``final_f`` folds the last ``final_n`` summaries to (d,).
    """

    __slots__ = ("n", "f", "bucket_levels", "final_rule", "final_f",
                 "final_n")

    def __init__(self, n, f, bucket_levels, final_rule, final_f, final_n):
        self.n = n
        self.f = f
        self.bucket_levels = list(bucket_levels)
        self.final_rule = final_rule
        self.final_f = final_f
        self.final_n = final_n

    @property
    def num_levels(self):
        return len(self.bucket_levels) + 1

    @property
    def num_buckets(self):
        return len(self.bucket_levels[0].sizes) if self.bucket_levels else 1

    def __repr__(self):
        return (f"<HierPlan n={self.n} f={self.f} "
                f"levels={self.bucket_levels} "
                f"final={self.final_rule}@n={self.final_n},f={self.final_f}>")


def _resolve(bucket_gar, top_gar, bucket_size):
    top_gar = bucket_gar if top_gar is None else top_gar
    bucket_size = DEFAULT_BUCKET_SIZE if bucket_size is None else int(
        bucket_size)
    for rule in (bucket_gar, top_gar):
        if rule not in _TOLERANCE:
            raise ValueError(
                f"hierarchy supports rules {SUPPORTED_RULES}, got {rule!r} "
                "(condense needs an rng key per fold; brute is exponential)"
            )
    if bucket_size < 2:
        raise ValueError(f"bucket_size must be >= 2, got {bucket_size}")
    return bucket_gar, top_gar, bucket_size


def plan_hierarchy(n, f, bucket_gar="krum", top_gar=None, bucket_size=None,
                   levels="auto", _hint=True):
    """Derive the level structure and the per-level f split for (n, f).

    ``levels="auto"`` keeps bucketing while more than ``bucket_size``
    inputs remain (and the next level would still leave the top rule a
    viable final count); an int ``levels >= 2`` fixes the total depth
    (levels - 1 bucketing levels + the final fold, whatever count that
    leaves). Raises ValueError when f cannot be composed — the registered
    rules surface that message through ``check``. (``_hint`` is internal:
    ``max_tolerated_f`` probes with it off so failure messages do not
    recursively re-derive the capacity they are reporting.)
    """
    bucket_gar, top_gar, bucket_size = _resolve(
        bucket_gar, top_gar, bucket_size)
    n = int(n)
    if n < 1:
        raise ValueError(f"expected at least one gradient, got n={n}")
    if not isinstance(f, (int, np.integer)) or isinstance(f, bool) or f < 0:
        raise ValueError(
            f"invalid number of Byzantine clients to tolerate, got f={f!r}, "
            "expected an int >= 0"
        )
    f = int(f)
    if levels != "auto":
        levels = int(levels)
        if levels < 2:
            raise ValueError(f"levels must be >= 2 or 'auto', got {levels}")
    max_bucket_levels = None if levels == "auto" else levels - 1

    bucket_levels = []
    remaining = f
    count = n
    while count > bucket_size and (
        max_bucket_levels is None or len(bucket_levels) < max_bucket_levels
    ):
        num_nat = -(-count // bucket_size)
        is_last = (
            len(bucket_levels) == max_bucket_levels - 1
            if max_bucket_levels is not None
            else num_nat <= bucket_size
        )
        if not is_last:
            sizes = _balanced_into(count, num_nat)
            lo, hi = _tolerance(bucket_gar, min(sizes))
            if hi < lo:
                raise ValueError(
                    f"bucket rule {bucket_gar!r} cannot run on buckets of "
                    f"{min(sizes)} (needs n >= {_min_n(bucket_gar, lo)})"
                )
            f_l = min(hi, max(lo, remaining))
            bucket_levels.append(_Level(sizes, f_l, bucket_gar))
            remaining = remaining // (f_l + 1)
            count = num_nat
            continue
        # Last bucketing level: the bucket count B is ALSO the final fold's
        # input count, so grow B past ceil(count / bucket_size) until the
        # top rule's contract admits the budget B inherits (e.g. krum needs
        # >= 2f+3 summaries — 4 buckets of 32 can never feed a krum top;
        # 5 buckets of ~26 can). Smaller buckets only help the bucket rule,
        # so the search is monotone and bounded by 2-member buckets.
        chosen = None
        for num in range(num_nat, count // 2 + 1):
            lo, hi = _tolerance(bucket_gar, count // num)
            if hi < lo:
                break  # buckets now below the bucket rule's floor
            f_l = min(hi, max(lo, remaining))
            rem2 = remaining // (f_l + 1)
            lo_t, hi_t = _tolerance(top_gar, num)
            f_fin2 = max(lo_t, rem2)
            if num >= _min_n(top_gar, f_fin2) and f_fin2 <= hi_t:
                chosen = (num, f_l, rem2)
                break
        if chosen is None:
            hint = ""
            if _hint:
                cap = max_tolerated_f(n, bucket_gar, top_gar, bucket_size,
                                      levels)
                hint = f" (max composable f = {cap})"
            raise ValueError(
                f"f={f} does not compose: no bucket count over {count} "
                f"inputs gives the top rule {top_gar!r} a viable final "
                f"fold under bucket rule {bucket_gar!r}{hint}"
            )
        num, f_l, remaining = chosen
        bucket_levels.append(
            _Level(_balanced_into(count, num), f_l, bucket_gar))
        count = num
        break

    lo, hi = _tolerance(top_gar, count)
    f_fin = max(lo, remaining)
    if hi < lo or f_fin > hi:
        hint = ""
        if _hint:
            cap = max_tolerated_f(n, bucket_gar, top_gar, bucket_size,
                                  levels)
            hint = f" (max composable f = {cap})"
        raise ValueError(
            f"f={f} does not compose: after {len(bucket_levels)} bucketing "
            f"level(s) the top rule {top_gar!r} over {count} summaries must "
            f"tolerate {f_fin} corrupted summaries but admits at most "
            f"{max(hi, 0)}{hint}"
        )
    return HierPlan(n, f, bucket_levels, top_gar, f_fin, count)


def max_tolerated_f(n, bucket_gar="krum", top_gar=None, bucket_size=None,
                    levels="auto"):
    """Largest global f the hierarchy composes for, or None when even f=0
    is impossible (e.g. the final count is below the top rule's floor).
    The derivation is monotone in f, so binary search is exact."""
    def ok(f):
        try:
            plan_hierarchy(n, f, bucket_gar, top_gar, bucket_size, levels,
                           _hint=False)
            return True
        except ValueError:
            return False

    if not ok(0):
        return None
    lo, hi = 0, max(1, int(n))
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if ok(mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


# --- per-bucket dispatch ----------------------------------------------------


def _rule_kwargs(rule, f):
    # Every supported rule accepts f via **kwargs; krum/tmean/bulyan/aksel
    # require it, median/average ignore it, cclip defaults it.
    return {"f": f}


def _bucket_call(rule, g, f):
    """(s, d) -> (d,) robust fold of one bucket — traced under vmap for the
    wave folds. Coordinate rules at s <= MAX_SORT_N take the jnp sorting
    network (batch-safe everywhere, 15x faster than XLA's variadic sort on
    CPU, bitwise-equal to the reference semantics); everything else runs
    the rule's own fast path (krum/average: the Gram matmul batches
    straight onto the MXU)."""
    s = g.shape[0]
    if s <= _coord.MAX_SORT_N:
        if rule == "median":
            return _coord.sortnet_median(g, axis=0)
        if rule == "tmean":
            return _coord.sortnet_trimmed_mean(g, f, axis=0)
    return gars[rule].unchecked(g, **_rule_kwargs(rule, f))


def _bucket_weights(rule, g, f):
    """(s,) selection weights of one bucket when the rule exposes its
    Gram-form selection (krum, average): the audit signal bucket-level
    exclusions are derived from. Rules without ``gram_select``
    (coordinate-wise medians) have no discrete selection — every member is
    'kept' and only whole-summary exclusions above are attributable."""
    r = gars[rule]
    if r.gram_select is None:
        return jnp.ones((g.shape[0],), jnp.float32)
    acc = jnp.promote_types(g.dtype, jnp.float32)
    gram = jnp.matmul(g, g.T, preferred_element_type=acc)
    return r.gram_select(gram, f)


_JIT_CACHE = {}
_JIT_LOCK = threading.Lock()


def _wave_jit(rule, f, audit):
    """Jitted (W, s, d) -> (W, d) [+ (W, s) weights] vmapped bucket fold.

    ONE callable per (rule, f, audit) — jax retraces per concrete shape, so
    the batch path (W = all buckets of a level) and the streaming path
    (W = wave) share the same program family; per-element results are
    identical across W (pinned by the streaming-vs-batch equality test)."""
    key = ("wave", rule, f, bool(audit))
    with _JIT_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is None:
            if audit:
                def fold(stack):
                    return (
                        jax.vmap(lambda g: _bucket_call(rule, g, f))(stack),
                        jax.vmap(lambda g: _bucket_weights(rule, g, f))(
                            stack),
                    )
            else:
                def fold(stack):
                    return jax.vmap(lambda g: _bucket_call(rule, g, f))(stack)
            fn = _JIT_CACHE[key] = jax.jit(fold)
    return fn


def _final_jit(rule, f, audit):
    """Jitted (m, d) -> (d,) [+ (m,) weights] final fold."""
    key = ("final", rule, f, bool(audit))
    with _JIT_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is None:
            if audit:
                def fold(stack):
                    return (_bucket_call(rule, stack, f),
                            _bucket_weights(rule, stack, f))
            else:
                def fold(stack):
                    return _bucket_call(rule, stack, f)
            fn = _JIT_CACHE[key] = jax.jit(fold)
    return fn


def _split_runs(sizes):
    """Contiguous (count, size) runs of equal bucket size — balanced
    partitions have at most two."""
    runs = []
    for s in sizes:
        if runs and runs[-1][1] == s:
            runs[-1][0] += 1
        else:
            runs.append([1, s])
    return [(c, s) for c, s in runs]


def _fold_level(x, level, audit):
    """(count_in, d) -> (num_buckets, d) batch fold of one level (pure jax,
    jit/trace-compatible — the registered hier rules run inside jit'd train
    steps like any flat rule). Returns (summaries, weights|None)."""
    outs, ws = [], []
    off = 0
    for count, size in _split_runs(level.sizes):
        chunk = jax.lax.slice_in_dim(x, off, off + count * size, axis=0)
        stack = chunk.reshape((count, size) + x.shape[1:])
        if audit:
            o, w = _wave_jit(level.rule, level.f, True)(stack)
            ws.append(w.reshape(-1))
        else:
            o = _wave_jit(level.rule, level.f, False)(stack)
        outs.append(o)
        off += count * size
    summaries = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    weights = None
    if audit:
        weights = ws[0] if len(ws) == 1 else jnp.concatenate(ws)
    return summaries, weights


def aggregate(gradients, f, *, bucket_gar="krum", top_gar=None,
              bucket_size=None, levels="auto", **kwargs):
    """Batch hierarchical aggregation of an (n, d) stack (or vector list).

    Robust within buckets, robust across summaries; see the module
    docstring for the f-composition contract. Pure and jit-compatible with
    static n and f, like every flat rule.
    """
    stack = as_stack(gradients)
    plan = plan_hierarchy(stack.shape[0], f, bucket_gar, top_gar,
                          bucket_size, levels)
    x = stack
    for level in plan.bucket_levels:
        x, _ = _fold_level(x, level, audit=False)
    return _final_jit(plan.final_rule, plan.final_f, False)(x)


def aggregate_with_audit(gradients, f, *, bucket_gar="krum", top_gar=None,
                         bucket_size=None, levels="auto"):
    """(aggregate, audit): the batch fold plus per-client observed/selected
    weights — 'selected' is the product of the client's in-bucket selection
    (binary, from ``gram_select`` where the rule exposes one) and the
    survival of every summary above it. The streaming reducer emits the
    same signal as a ``hier_exclusion`` telemetry event."""
    stack = as_stack(gradients)
    n = stack.shape[0]
    plan = plan_hierarchy(n, f, bucket_gar, top_gar, bucket_size, levels)
    keep = np.ones(n, np.float32)
    spans = [(i, i + 1) for i in range(n)]
    x = stack
    for level in plan.bucket_levels:
        x, w = _fold_level(x, level, audit=True)
        w = np.asarray(w)
        new_spans, off = [], 0
        for size in level.sizes:
            members = spans[off:off + size]
            for j, (a, b) in enumerate(members):
                if w[off + j] == 0:
                    keep[a:b] = 0.0
            new_spans.append((members[0][0], members[-1][1]))
            off += size
        spans = new_spans
    agg, w_fin = _final_jit(plan.final_rule, plan.final_f, True)(x)
    w_fin = np.asarray(w_fin)
    for j, (a, b) in enumerate(spans):
        if w_fin[j] == 0:
            keep[a:b] = 0.0
    return agg, {
        "observed": np.ones(n, np.float32),
        "selected": keep,
        "plan": plan,
    }


def check(gradients, f, *, bucket_gar="krum", top_gar=None, bucket_size=None,
          levels="auto", **kwargs):
    """Registry-style check: None when (n, f) composes, else the message."""
    n = num_gradients(gradients)
    try:
        plan_hierarchy(n, f, bucket_gar, top_gar, bucket_size, levels)
    except (ValueError, TypeError) as e:
        return str(e)
    return None


def upper_bound(n, f, d, *, bucket_gar="krum", top_gar=None,
                bucket_size=None, levels="auto"):
    """Conservative composed variance/norm bound: the minimum of the
    per-level flat bounds (each level must hold for its own inputs, so the
    tightest level governs). None when no constituent exposes a bound."""
    plan = plan_hierarchy(n, f, bucket_gar, top_gar, bucket_size, levels)
    bounds = []
    for level in plan.bucket_levels:
        ub = gars[level.rule].upper_bound
        if ub is not None:
            bounds.append(ub(min(level.sizes), level.f, d))
    ub = gars[plan.final_rule].upper_bound
    if ub is not None:
        bounds.append(ub(plan.final_n, plan.final_f, d))
    return min(bounds) if bounds else None


def tree_aggregate(grads_tree, f, *, bucket_gar="krum", top_gar=None,
                   bucket_size=None, levels="auto", key=None, **kwargs):
    """Stacked-tree twin: concat-first (the Bulyan/cclip layout,
    _common.concat_stack) — one axis-1 concat, the flat hierarchy, one
    unflatten. At hierarchy scale the (n, d) stack dominates anyway; the
    twin exists so the hier rules slot into the topologies' tree dispatch
    like any registered rule."""
    leaves, treedef = jax.tree.flatten(grads_tree)
    stack, shapes = concat_stack(leaves)
    vec = aggregate(stack, f, bucket_gar=bucket_gar, top_gar=top_gar,
                    bucket_size=bucket_size, levels=levels)
    return unflatten_vec(vec, treedef, shapes)


# --- streaming ingest -------------------------------------------------------


class StreamingAggregator:
    """Wave-based streaming hierarchical reducer (see module docstring).

    Clients join buckets in ARRIVAL order: position k lands in the bucket
    covering k under the plan's contiguous balanced partition. Completed
    buckets fold in vmapped waves of ``wave_buckets`` (plus one
    smaller fold at each bucket-size run boundary), their summaries cascade
    into the next level's state immediately, and ``finalize`` flushes the
    levels and runs the final fold — so peak memory is
    O(levels · wave · bucket_size · d), never O(n · d).

    Thread-safe: ``push``/``push_frame``/``wire_transform`` may be called
    from ``PeerExchange`` waiter threads concurrently.
    """

    def __init__(self, n, f, *, bucket_gar="krum", top_gar=None,
                 bucket_size=None, levels="auto", wave_buckets=8,
                 audit=False, telemetry=False, d=None, double_buffer=None):
        self.plan = plan_hierarchy(n, f, bucket_gar, top_gar, bucket_size,
                                   levels)
        self.n = int(n)
        self.f = int(f)
        self.wave = max(1, int(wave_buckets))
        self._telemetry = bool(telemetry)
        self._audit = bool(audit) or self._telemetry
        # Double-buffered wave fold (GARFIELD_HIER_DOUBLE_BUFFER, default
        # on; ``double_buffer=`` overrides for the equality tests): each
        # level keeps TWO wave buffers, a dispatched wave folds on device
        # while ingest threads fill the other buffer, and the blocking
        # summary readback moves to the next wave's dispatch (the swap
        # point). Fold boundaries and cascade order are unchanged, so
        # streaming==batch bitwise equality is untouched; the cost is one
        # extra O(wave · bucket · d) buffer per level.
        if double_buffer is None:
            double_buffer = os.environ.get(
                "GARFIELD_HIER_DOUBLE_BUFFER", "1"
            ).lower() not in ("", "0", "false")
        self._double = bool(double_buffer)
        from ..utils import wire as _wire

        self._fused = _wire.wire_fused()
        self._lock = threading.RLock()
        self._arrived = 0
        # Row width: learned from the first ingested row, or pinned up
        # front via ``d``. Wire-facing deployments SHOULD pin it — it is
        # what lets push_frame bound a sparse frame's claimed dense size
        # BEFORE the scatter allocates (see push_frame).
        if d is not None and int(d) < 1:
            raise ValueError(f"row width d must be >= 1, got {d}")
        self._d = int(d) if d is not None else None
        self._keep = np.ones(self.n, np.float32) if self._audit else None
        # Per bucketing level: a PREALLOCATED contiguous wave buffer
        # (allocated lazily once d is known) + the pending rows' client
        # spans and the index of the next bucket to fold. Contiguity is a
        # measured 1.65x on the whole streaming path vs a list-of-rows +
        # np.stack design: each ingest is one row memcpy and each fold
        # hands XLA one contiguous (take, size, d) view. ing_t0/ing_dur
        # accumulate the wall start + duration of the row copies feeding
        # the level's NEXT wave (tracing on only), reported as ONE
        # hier_ingest span per dispatched wave (trace.emit) so ingest
        # attribution counts align 1:1 with hier_wave/hier_h2d.
        self._levels = [
            {"level": lv, "bufs": [None, None], "active": 0,
             "pending": None, "fill": 0, "spans": [], "cursor": 0,
             "ing_t0": None, "ing_dur": 0.0}
            for lv in self.plan.bucket_levels
        ]
        self._final_rows = []
        self._final_spans = []
        self._result = None

    # -- ingestion ----------------------------------------------------------

    def push(self, vec):
        """Ingest one client gradient (numpy/jax vector, any shape —
        raveled); returns the client's arrival index."""
        with self._lock:
            return self._push_one(vec)

    def push_many(self, rows, *, stable=False):
        """Ingest a (k, d) block of clients in row order (one lock
        acquisition; the bench's wave ingest path). Returns the arrival
        index of the first row.

        Bulk path: the block is copied into the level-0 wave buffer in
        contiguous chunks (arrival order IS bucket order, so a block
        lands as one or two memcpys per drain cycle) instead of the
        per-row ``_push_one`` loop — at federated-shard widths (d/S a
        few thousand) the per-row Python overhead otherwise dominates
        the fold and flattens the 1/S round-time scaling fed_bench
        measures. Fold boundaries are unchanged (``_drain`` triggers at
        the same cursor positions regardless of ingest granularity), so
        streaming-vs-batch bitwise equality holds verbatim.

        ``stable=True`` promises the caller's block is STABLE: it stays
        alive and unwritten until after the NEXT wave dispatch (or
        finalize) — e.g. an immutable round pool. Whole waves then fold
        directly on slices of ``rows`` (jnp.asarray is zero-copy for
        aligned C-contiguous f32 on the CPU backend), skipping the
        staging memcpy entirely — at 10^6 clients × d=10^4 that is
        ~10 MB/wave of pure overhead removed. Fold boundaries, cascade
        order and per-bucket programs are IDENTICAL, so the result
        stays bitwise equal to the copying path (pinned). Blocks that
        are not C-contiguous f32 (e.g. a sharded column slice) fall
        back to the copy path automatically; so do tail rows that do
        not complete a wave.
        """
        rows = np.asarray(rows, np.float32)
        if rows.ndim != 2:
            rows = rows.reshape(len(rows), -1)
        with self._lock:
            first = self._arrived
            k = rows.shape[0]
            if k == 0:
                return first
            if self._result is not None:
                raise RuntimeError("finalize() already ran")
            if self._arrived + k > self.n:
                raise ValueError(
                    f"pushing {k} rows past the {self.n}-client plan "
                    f"({self._arrived} already ingested)"
                )
            if self._d is None:
                self._d = rows.shape[1]
            elif rows.shape[1] != self._d:
                raise ValueError(
                    f"rows have {rows.shape[1]} elements, expected "
                    f"{self._d}"
                )
            if not self._levels:
                # n <= bucket_size: rows feed the final fold directly.
                for j in range(k):
                    idx = self._arrived
                    self._arrived += 1
                    self._final_rows.append(rows[j].copy())
                    self._final_spans.append((idx, idx + 1))
                return first
            state = self._levels[0]
            i = 0
            if (stable and state["fill"] == 0
                    and rows.dtype == np.float32
                    and rows.flags["C_CONTIGUOUS"]):
                # Zero-copy wave dispatch straight off the caller's
                # block. Only whole waves (as _ready would cut them off
                # an empty buffer) qualify; the tail falls through to
                # the copy loop below.
                while i < k:
                    take, size = self._ready(state, False, avail=k - i)
                    if take == 0:
                        break
                    used = take * size
                    base = self._arrived
                    if self._audit:
                        spans = [(base + j, base + j + 1)
                                 for j in range(used)]
                    else:
                        spans = base  # dense spans, see _drain
                    self._arrived += used
                    self._dispatch_wave(0, state, take, size,
                                        rows[i:i + used], spans,
                                        from_buf=False)
                    i += used
            while i < k:
                # Re-fetched EVERY iteration: the _drain below swaps the
                # active buffer in double-buffer mode, so a cached ``buf``
                # would keep writing rows into the buffer the in-flight
                # wave aliases (caught by the streaming==batch pin).
                buf = self._buf_for(state)
                take = min(k - i, buf.shape[0] - state["fill"])
                if take <= 0:  # full buffer with nothing drainable: bug
                    raise RuntimeError("level-0 wave buffer stalled")
                fill = state["fill"]
                if _trace.enabled():
                    t0w, t0 = time.time(), time.perf_counter()
                    buf[fill:fill + take] = rows[i:i + take]
                    if state["ing_t0"] is None:
                        state["ing_t0"] = t0w
                    state["ing_dur"] += time.perf_counter() - t0
                else:
                    buf[fill:fill + take] = rows[i:i + take]
                if self._audit:
                    base = self._arrived
                    state["spans"].extend(
                        (base + j, base + j + 1) for j in range(take)
                    )
                state["fill"] = fill + take
                self._arrived += take
                i += take
                self._drain(0, flush=False)
            return first

    def push_frame(self, buf, *, expect_plane=None, expect_epoch=None):
        """Ingest one typed wire frame (utils/wire.py). A frame that fails
        the codec raises WireError — ban evidence for the caller, exactly
        like the cluster quorum paths. ``expect_plane``/``expect_epoch``
        thread straight to the codec's header pins (a cross-plane or
        stale-epoch frame rejects before any payload work).

        Once the row width is known (the ctor's ``d``, or the first
        ingested row) it pins the frame's element count, so a sparse
        frame claiming a huge dense size rejects BEFORE the scatter
        allocates (wire.decode's expect_elems). Before the width is
        known, a sparse frame is refused outright: its dense size is a
        bare header claim nothing here can corroborate, i.e. a
        sender-controlled allocation — wire-facing deployments pass
        ``d=`` at construction to accept a sparse first frame.

        Fused path (GARFIELD_WIRE_FUSED_DECODE, default on): once the
        row width is known the frame dequantizes/scatters DIRECTLY into
        the level-0 wave buffer slot it will occupy (wire.decode_into)
        — no O(d) transient array per frame, one memory pass instead of
        decode + memcpy. Identical bytes, identical validation: a
        rejected frame raises BEFORE the first write, so the slot is
        never claimed nor scribbled on, and the arrival index commits
        only after the decode succeeds."""
        from ..utils import wire

        d = self._d
        if d is None and wire.frame_scheme(buf) == "topk":
            raise wire.WireError(
                "sparse frame arrived before the reducer's row width is "
                "known — its dense element count is an unverifiable "
                "header claim (sender-controlled allocation); construct "
                "the StreamingAggregator with d= to accept sparse first "
                "frames"
            )
        if d is not None and self._fused and self._levels:
            with self._lock:
                if self._result is not None:
                    raise RuntimeError("finalize() already ran")
                if self._arrived >= self.n:
                    raise ValueError(
                        f"already ingested all {self.n} clients"
                    )
                state = self._levels[0]
                row = self._buf_for(state)[state["fill"]]
                if _trace.enabled():
                    t0w, t0 = time.time(), time.perf_counter()
                    wire.decode_into(buf, row, expect_elems=d,
                                     expect_plane=expect_plane,
                                     expect_epoch=expect_epoch)
                    if state["ing_t0"] is None:
                        state["ing_t0"] = t0w
                    state["ing_dur"] += time.perf_counter() - t0
                else:
                    wire.decode_into(buf, row, expect_elems=d,
                                     expect_plane=expect_plane,
                                     expect_epoch=expect_epoch)
                idx = self._arrived
                self._arrived += 1
                state["fill"] += 1
                if self._audit:
                    state["spans"].append((idx, idx + 1))
                self._drain(0, flush=False)
                return idx
        return self.push(wire.decode(buf, expect_elems=d,
                                     expect_plane=expect_plane,
                                     expect_epoch=expect_epoch))

    def push_frames(self, bufs, *, expect_plane=None, expect_epoch=None):
        """Bulk wire ingest: decode a batch of frames DIRECTLY into
        consecutive level-0 wave-buffer rows via one
        ``wire.decode_batch_into`` pass (vectorized header screen,
        same-scheme slab dequant — see utils/wire.py), zero intermediate
        copies. Returns a list the length of ``bufs``: the frame's
        arrival index, or the ``WireError`` that rejected it.

        Per-frame isolation is the whole contract: one forged frame
        yields its indexed WireError (the sender's ban evidence) while
        every batchmate decodes bit-identically to a ``push_frame`` loop
        — rejected frames never claim an arrival index, never touch a
        buffer row that survives (accepted rows behind a reject are
        compacted down so the wave stays contiguous), and never shift a
        batchmate's fold boundary relative to the frames that actually
        landed.

        Falls back to a per-frame ``push_frame`` loop (same results
        list, exceptions caught per index) when the row width is not yet
        known, the fused path is off, there are no bucketing levels, or
        ``GARFIELD_WIRE_BATCH_DECODE`` disables batching. Raises
        ValueError up front if the batch could not fit the plan even
        with zero rejects (conservative: the caller sized the round)."""
        from ..utils import wire

        bufs = list(bufs)
        k = len(bufs)
        results = [None] * k
        if k == 0:
            return results
        if not (self._d is not None and self._fused and self._levels
                and wire.wire_batch_decode()):
            for i, b in enumerate(bufs):
                try:
                    results[i] = self.push_frame(
                        b, expect_plane=expect_plane,
                        expect_epoch=expect_epoch)
                except wire.WireError as err:
                    results[i] = err
            return results
        d = self._d
        with self._lock:
            if self._result is not None:
                raise RuntimeError("finalize() already ran")
            if self._arrived + k > self.n:
                raise ValueError(
                    f"pushing {k} frames past the {self.n}-client plan "
                    f"({self._arrived} already ingested)"
                )
            state = self._levels[0]
            i = 0
            while i < k:
                # Re-fetched every iteration (double-buffer swap), like
                # push_many.
                buf = self._buf_for(state)
                fill = state["fill"]
                take = min(k - i, buf.shape[0] - fill)
                if take <= 0:
                    raise RuntimeError("level-0 wave buffer stalled")
                if _trace.enabled():
                    t0w, t0 = time.time(), time.perf_counter()
                res = wire.decode_batch_into(
                    bufs[i:i + take], buf[fill:fill + take],
                    expect_elems=d, expect_plane=expect_plane,
                    expect_epoch=expect_epoch)
                # Compact accepted rows over rejected holes: row j only
                # moves DOWN (ngood <= j), each accepted frame's bytes
                # are already fully decoded, and rejected frames' target
                # rows were never written — so the surviving wave is
                # exactly what a push_frame loop over the accepted
                # frames would have staged.
                base = self._arrived
                ngood = 0
                for j, r in enumerate(res):
                    if isinstance(r, wire.WireError):
                        results[i + j] = r
                        continue
                    if ngood != j:
                        buf[fill + ngood] = buf[fill + j]
                    results[i + j] = base + ngood
                    ngood += 1
                if _trace.enabled():
                    if state["ing_t0"] is None:
                        state["ing_t0"] = t0w
                    state["ing_dur"] += time.perf_counter() - t0
                if self._audit:
                    state["spans"].extend(
                        (base + j, base + j + 1) for j in range(ngood)
                    )
                state["fill"] = fill + ngood
                self._arrived += ngood
                i += take
                self._drain(0, flush=False)
            return results

    def wire_transform(self, idx, payload):
        """``PeerExchange`` transform hook: decode + ingest in the waiter
        thread the moment the frame lands (collect/compute overlap), return
        the arrival index as the peer's collect result. A WireError
        propagates to the exchange, which stores it as the peer's
        attributable result."""
        return self.push_frame(payload)

    def wire_batch_transform(self, items):
        """``PeerExchange`` batch_transform hook (collect_begin): the
        harvest hands every latched ``(peer, frame)`` here at once and
        the whole quorum ingests through ONE ``push_frames`` /
        ``decode_batch_into`` pass. Returns one arrival index or
        WireError per item — the exchange stores an exception as that
        peer's ban evidence, same attribution as the per-frame
        ``wire_transform``."""
        return self.push_frames([p for _, p in items])

    def _push_one(self, vec):
        if self._result is not None:
            raise RuntimeError("finalize() already ran")
        if self._arrived >= self.n:
            raise ValueError(f"already ingested all {self.n} clients")
        vec = np.asarray(vec, np.float32).reshape(-1)
        if self._d is None:
            self._d = vec.size
        elif vec.size != self._d:
            raise ValueError(
                f"client {self._arrived} has {vec.size} elements, "
                f"expected {self._d}"
            )
        idx = self._arrived
        self._arrived += 1
        self._ingest(0, vec, (idx, idx + 1))
        return idx

    def _buf_for(self, state):
        i = state["active"]
        if state["bufs"][i] is None:
            # One wave of the level's largest buckets plus spill room for
            # the partially-filled next bucket — folds trigger the moment
            # a wave (or a size-run tail) completes, so fill never
            # exceeds this. The second buffer (double-buffer mode only)
            # allocates lazily on the first swap.
            cap = (self.wave + 1) * max(state["level"].sizes)
            state["bufs"][i] = np.empty((cap, self._d), np.float32)
        return state["bufs"][i]

    def _ingest(self, lvl_idx, row, span):
        if lvl_idx == len(self._levels):
            self._final_rows.append(row)
            self._final_spans.append(span)
            return
        state = self._levels[lvl_idx]
        buf = self._buf_for(state)
        if _trace.enabled():
            # Accumulate this slice into the level's per-wave ingest
            # span (drained by _dispatch_wave); zero clock reads when
            # tracing is off — the zero-cost contract.
            t0w, t0 = time.time(), time.perf_counter()
            buf[state["fill"]] = row
            if state["ing_t0"] is None:
                state["ing_t0"] = t0w
            state["ing_dur"] += time.perf_counter() - t0
        else:
            buf[state["fill"]] = row
        state["fill"] += 1
        if self._audit or lvl_idx > 0:
            # Level-0 spans with audit off are reconstructed
            # arithmetically in _drain — skip the tuple churn.
            state["spans"].append(span)
        self._drain(lvl_idx, flush=False)

    def reset(self):
        """Re-arm the reducer for a fresh pass over the SAME (n, f,
        rules) plan, keeping the allocated wave buffers and the cached
        fold programs — the federated round engine runs one pass per
        ROUND, and reallocating O(levels · wave · bucket · d) buffers
        every round is measurable at bench scale. Equivalent to a fresh
        construction bit for bit (the buffers are fully overwritten
        before any fold reads them)."""
        with self._lock:
            self._arrived = 0
            self._result = None
            if self._keep is not None:
                self._keep = np.ones(self.n, np.float32)
            for state in self._levels:
                state["fill"] = 0
                state["spans"] = []
                state["cursor"] = 0
                # A dropped in-flight wave only READS its buffer; its
                # result is never consumed, so the fresh round may refill
                # immediately.
                state["pending"] = None
                state["active"] = 0
                state["ing_t0"] = None
                state["ing_dur"] = 0.0
            self._final_rows = []
            self._final_spans = []

    # -- folding ------------------------------------------------------------

    def _ready(self, state, flush, avail=None):
        """(take, size): how many same-size complete buckets to fold now.

        Folds trigger at a full wave, at the end of an equal-size run (the
        balanced partition has at most one boundary per level — waiting for
        a wave that can never fill would grow the buffer unboundedly), or
        at flush time. ``avail`` overrides the buffered-row count for the
        zero-copy stable path, which folds straight out of the caller's
        block without staging rows in the wave buffer first.
        """
        sizes = state["level"].sizes
        cur = state["cursor"]
        if cur >= len(sizes):
            return 0, 0
        size = sizes[cur]
        if avail is None:
            avail = state["fill"]
        take, used = 0, 0
        while (cur + take < len(sizes) and sizes[cur + take] == size
               and used + size <= avail and take < self.wave):
            used += size
            take += 1
        if take == 0:
            return 0, 0
        run_ends = cur + take == len(sizes) or sizes[cur + take] != size
        if take == self.wave or run_ends or flush:
            return take, size
        return 0, 0

    def _dispatch_wave(self, lvl_idx, state, take, size, src, spans, *,
                       from_buf):
        """Dispatch one wave fold on ``src`` (a contiguous (take*size, d)
        f32 block: the level's wave buffer prefix, or — the zero-copy
        stable path — a slice of the caller's own block).

        jnp.asarray of an aligned f32 numpy array is ZERO-COPY on the CPU
        backend (the stack aliases ``src``) — safe ONLY because the
        ``np.asarray(out)`` readback blocks before ``src`` is written
        again. Sync mode blocks right here; double-buffer mode moves the
        block to the NEXT wave's dispatch (``_complete_pending`` below,
        the swap point), so the fold overlaps ingest filling the other
        buffer. ``from_buf=False`` (stable path) extends that contract to
        the CALLER: their block must stay alive and unwritten until the
        next wave's dispatch (or flush) reads this one back.

        Trace spans (schema v5/v12/v15): hier_h2d is the staging of one
        wave, hier_wave its dispatch (+ readback in sync mode), and the
        level's ingest accumulator drains here as ONE pre-timed
        hier_ingest record per wave — emitted even when the accumulated
        duration is zero (the stable path's whole point), so per-level
        span counts obey count(hier_ingest) == count(hier_wave) ==
        count(hier_h2d) exactly (round 19's records undercounted ingest).
        """
        level = state["level"]
        if _trace.enabled():
            t0 = state["ing_t0"]
            _trace.emit("hier_ingest",
                        time.time() if t0 is None else t0,
                        state["ing_dur"], level=int(lvl_idx),
                        buckets=int(take), size=int(size))
            state["ing_t0"] = None
            state["ing_dur"] = 0.0
        with _trace.span("hier_wave", level=int(lvl_idx),
                         buckets=int(take), size=int(size)):
            with _trace.span("hier_h2d", level=int(lvl_idx),
                             buckets=int(take), size=int(size)):
                stack = jnp.asarray(src.reshape(take, size, -1))
            fn = _wave_jit(level.rule, level.f, self._audit)
            if self._audit:
                out, w = fn(stack)
            else:
                out, w = fn(stack), None
            if not self._double:
                # blocks: summaries host-side, frees src
                out = np.asarray(out)
                if w is not None:
                    w = np.asarray(w)
        del stack
        # The dispatched buckets leave the level's accounting NOW —
        # ``_ready`` must see the cursor past them whether or not their
        # summaries have landed host-side yet.
        state["cursor"] += take
        if self._double:
            # Swap point: the previous wave's readback must land before
            # the buffer it aliased is written again — the sync
            # invariant, one wave later. Completing FIRST also keeps the
            # cascade in bucket order, which is what pins
            # streaming==batch.
            self._complete_pending(lvl_idx)
            state["pending"] = {"out": out, "w": w, "spans": spans,
                                "take": take, "size": size}
            if from_buf:
                state["active"] ^= 1
        else:
            self._cascade(lvl_idx, out, w, spans, take, size)

    def _drain(self, lvl_idx, flush):
        state = self._levels[lvl_idx]
        while True:
            take, size = self._ready(state, flush)
            if take == 0:
                break
            used = take * size
            buf = self._buf_for(state)
            if lvl_idx == 0 and not self._audit:
                # Dense-span arithmetic: with audit off, level-0 spans
                # are ALWAYS width-1 consecutive rows, so the whole
                # tuple list collapses to one int — the arrival index of
                # pending row 0 (``_cascade`` rebuilds any bucket's span
                # from it). At 10^6 clients/round this skips building
                # 10^6 throwaway tuples on the hot ingest path.
                spans = self._arrived - state["fill"]
            else:
                spans = state["spans"][:used]
                del state["spans"][:used]
            self._dispatch_wave(lvl_idx, state, take, size, buf[:used],
                                spans, from_buf=True)
            left = state["fill"] - used
            if self._double:
                # ``active`` swapped inside _dispatch_wave: shift the
                # spill (the partially-filled next bucket) into the
                # OTHER buffer — the dispatched wave still aliases
                # ``buf``, which is only read from here on.
                other = self._buf_for(state)
                if left:
                    other[:left] = buf[used:state["fill"]]
                state["fill"] = left
            else:
                # Shift the spill to the buffer front; at most one
                # bucket's worth, so the copy is negligible next to the
                # fold it unblocks.
                if left:
                    buf[:left] = buf[used:state["fill"]].copy()
                state["fill"] = left
        if flush:
            self._complete_pending(lvl_idx)

    def _complete_pending(self, lvl_idx):
        """Block on the in-flight wave's summary readback and cascade it —
        the double-buffer swap point. The buffer the wave aliased is free
        for refill the moment this returns. No-op in sync mode (nothing is
        ever pending) or when no wave is in flight."""
        state = self._levels[lvl_idx]
        p, state["pending"] = state["pending"], None
        if p is None:
            return
        with _trace.span("hier_fold_wait", level=int(lvl_idx),
                         buckets=int(p["take"]), size=int(p["size"])):
            out = np.asarray(p["out"])
            w = np.asarray(p["w"]) if p["w"] is not None else None
        self._cascade(lvl_idx, out, w, p["spans"], p["take"], p["size"])

    def _cascade(self, lvl_idx, out, w, spans, take, size):
        """Host-side tail of one completed wave: audit bookkeeping and the
        summary cascade into the next level (identical for the sync and
        double-buffered paths — completion order is bucket order in both,
        so the upper levels see the exact same ingest sequence)."""
        excluded = 0
        if isinstance(spans, (int, np.integer)):
            # Dense level-0 spans (audit off — see _drain): bucket b
            # covers arrival indices [lo + b*size, lo + (b+1)*size).
            lo = int(spans)
            for b in range(take):
                self._ingest(lvl_idx + 1, out[b],
                             (lo + b * size, lo + (b + 1) * size))
        else:
            for b in range(take):
                members = spans[b * size:(b + 1) * size]
                if self._audit:
                    for j, (a, bb) in enumerate(members):
                        if w[b, j] == 0:
                            self._keep[a:bb] = 0.0
                            excluded += 1
                bspan = (members[0][0], members[-1][1])
                self._ingest(lvl_idx + 1, out[b], bspan)
        if self._telemetry:
            from ..telemetry import hub as _hub

            _hub.emit_event(
                "hier_wave", level=lvl_idx, buckets=int(take),
                size=int(size), excluded_members=int(excluded),
            )

    def finalize(self):
        """Flush every level, run the final fold, return the (d,) numpy
        aggregate (idempotent). Raises unless all n clients arrived."""
        with self._lock:
            if self._result is not None:
                return self._result
            if self._arrived != self.n:
                raise ValueError(
                    f"only {self._arrived}/{self.n} clients ingested"
                )
            with _trace.span("hier_finalize", levels=len(self._levels)):
                for lvl_idx in range(len(self._levels)):
                    self._drain(lvl_idx, flush=True)
                stack = jnp.asarray(np.stack(self._final_rows))
                fn = _final_jit(self.plan.final_rule, self.plan.final_f,
                                self._audit)
                if self._audit:
                    out, w_fin = fn(stack)
                    w_fin = np.asarray(w_fin)
                    for j, (a, b) in enumerate(self._final_spans):
                        if w_fin[j] == 0:
                            self._keep[a:b] = 0.0
                else:
                    out = fn(stack)
                self._result = np.asarray(out)
            self._final_rows = []
            if self._telemetry:
                from ..telemetry import hub as _hub

                _hub.emit_event(
                    "hier_exclusion",
                    observed=[1.0] * self.n,
                    selected=[float(v) for v in self._keep],
                    buckets=self.plan.num_buckets,
                    levels=self.plan.num_levels,
                )
            return self._result

    def audit(self):
        """Per-client observed/selected (after finalize) — the same signal
        ``aggregate_with_audit`` returns and the telemetry event carries."""
        if not self._audit:
            raise ValueError("reducer built without audit/telemetry")
        return {
            "observed": np.ones(self.n, np.float32),
            "selected": None if self._keep is None else self._keep.copy(),
        }


# --- registry ---------------------------------------------------------------


def parse_hier_name(name):
    """'hier-<bucket>[-<top>]' -> (bucket_gar, top_gar|None)."""
    parts = name.split("-")
    if len(parts) < 2 or parts[0] != "hier":
        raise ValueError(f"not a hierarchical rule name: {name!r}")
    if len(parts) == 2:
        return parts[1], None
    if len(parts) == 3:
        return parts[1], parts[2]
    raise ValueError(f"not a hierarchical rule name: {name!r}")


def make_hier_gar(bucket_gar, top_gar=None, *, bucket_size=None,
                  levels="auto", name=None):
    """Build + register one hierarchical GAR. Rule resolution is lazy (the
    registry auto-import reaches this module before krum/median register),
    so construction never touches ``gars``."""
    bucket_gar_r, top_gar_r, bucket_size = _resolve(
        bucket_gar, top_gar, bucket_size)
    if name is None:
        name = f"hier-{bucket_gar_r}" + (
            "" if top_gar is None or top_gar == bucket_gar_r
            else f"-{top_gar_r}"
        )
    cfg = dict(bucket_gar=bucket_gar_r, top_gar=top_gar_r,
               bucket_size=bucket_size, levels=levels)

    def _aggregate(gradients, f, **kwargs):
        return aggregate(gradients, f, **cfg)

    def _check(gradients, f, **kwargs):
        return check(gradients, f, **cfg)

    def _upper_bound(n, f, d):
        return upper_bound(n, f, d, **cfg)

    def _tree_aggregate(grads_tree, f, key=None, **kwargs):
        return tree_aggregate(grads_tree, f, **cfg)

    return register(name, _aggregate, _check, upper_bound=_upper_bound,
                    tree_aggregate=_tree_aggregate)


# Default instances: same-rule hierarchies for the bench grid plus the two
# cross combinations the composition tests exercise.
make_hier_gar("krum")
make_hier_gar("median")
make_hier_gar("tmean")
make_hier_gar("krum", "median")
make_hier_gar("median", "krum")

# ``hier`` alias: the deployment-picked hierarchy, configured as
# GARFIELD_HIER_GAR="<bucket>[:<top>]" (default krum at both levels).
_env = os.environ.get("GARFIELD_HIER_GAR", "krum").strip() or "krum"
try:
    _b, _, _t = _env.partition(":")
    make_hier_gar(_b, _t or None, name="hier")
except ValueError as _e:
    tools.warning(f"GARFIELD_HIER_GAR={_env!r} invalid ({_e}); "
                  "defaulting hier=krum")
    make_hier_gar("krum", name="hier")
del _env
