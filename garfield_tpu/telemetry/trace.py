"""Host-side distributed round tracing: lightweight spans (ISSUE 8).

The telemetry plane so far could COUNT a round (step times, event
totals) but not explain it: PRs 4-7 made a cluster round genuinely
concurrent — eager decode + H2D in exchange waiter threads,
pre-registered round watchers, async stale-frame reuse — and a scalar
``step_time_s`` cannot say where the wall clock went across those
PS/worker/waiter-thread boundaries. This module records *where*: each
instrumented phase of a round emits one **span** — wall-clock start,
monotonic duration, phase name, round/step tag, the owning role and a
per-thread track id — through the existing process-global MetricsHub
hook as a schema-v5 ``span`` JSONL record.

Contract (the taps' purity contract, host-side edition):

- **off by default, zero-cost when disabled**: ``span(...)`` checks one
  module-level flag and returns a shared no-op context manager — no
  clock reads, no allocation beyond the call itself. Nothing in-graph
  changes EVER (spans are host code only), so taps-on/off bitwise
  purity and the ``--chunk_steps`` trajectories are untouched; the
  tracing-on vs tracing-off trajectory pin in tests/test_trace.py
  asserts the host-side half.
- **crash-safe**: spans ride the hub's streaming JSONL sink (one
  flushed line per span), so a run that dies dark — the round-5
  bench post-mortem this plane exists for — keeps every span up to the
  crash.
- **thread-correct**: spans are emitted from exchange waiter threads
  (wire decode, H2D staging) concurrently with the role's main loop;
  the ``tid`` tag keeps them on separate tracks so the report's Chrome
  trace shows the collect/compute overlap instead of garbling it.

Enable with ``--trace`` on any app (implies ``--telemetry`` — spans
need the JSONL sink) or ``GARFIELD_TRACE=1``. Consume with
``python -m garfield_tpu.telemetry.report`` (cross-process merge,
causal timeline, critical-path attribution — see report.py).

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
and tags: in any profiler trace taken of the process (``--profile_dir``,
which turns the spans on by itself, or a trace a cluster role takes) the
spans lie on ``/host:CPU`` on the device trace's own clock, so an idle gap
on the device can be read against ``dispatch``, ``eval``, ``checkpoint``,
``collect``, ``decode``, ... The device side of the same trace is named by
the step's phase scopes (``parallel/core.py``: ``phase.grads`` ...).

Phase vocabulary (kept small and stable so the report can reason about
it; producers may add more):

  exchange:   publish, collect, decode, gather, latest_wait
  PS roles:   broadcast, quorum, gar_apply, bn_stats, model_gather
  worker:     model_wait, grad_compute, straggle
  LEARN node: grad_compute, quorum, update, gossip
  app loop:   dispatch (tag chunk=k), eval, checkpoint
  hierarchy:  hier_ingest, hier_wave, hier_h2d, hier_fold_wait,
              hier_finalize (hier_ingest is PRE-TIMED — one record per
              dispatched wave via ``emit``, accumulated from that
              wave's row copies/decodes, so per-wave counts align with
              hier_wave/hier_h2d exactly)
  federated:  fed_shard_fold, selection (ingest attribution rides the
              hierarchy's hier_ingest spans)
  soak:       soak_round (tag scenario=steady|rolling_restart|
              partition|churn — one span per sustained round; the
              soak_bench SLO percentiles come from its phase stats)
"""

import itertools
import os
import threading
import time

from . import hub as _hub

__all__ = ["span", "emit", "enable", "disable", "enabled", "requested",
           "Span"]

# One mutable cell instead of rebindable module globals: ``span`` reads
# it on every call (the disabled fast path), and a cell read is as cheap
# as a global read while keeping enable/disable race-free under threads.
_STATE = {"enabled": False, "who": None, "annotate": None}

# Small per-thread track ids for the report's Chrome-trace lanes: the
# main loop gets 0, waiter/watcher threads get 1, 2, ... in first-use
# order. OS thread ids are huge and unstable run-to-run; these are not.
_tid_counter = itertools.count(1)
_tids = threading.local()


def _tid():
    t = getattr(_tids, "id", None)
    if t is None:
        t = 0 if threading.current_thread() is threading.main_thread() \
            else next(_tid_counter)
        _tids.id = t
    return t


def requested(args=None):
    """Whether tracing was asked for: ``--trace`` or ``GARFIELD_TRACE``."""
    if args is not None and getattr(args, "trace", False):
        return True
    return os.environ.get("GARFIELD_TRACE", "").lower() not in (
        "", "0", "false",
    )


def enable(who=None):
    """Turn span recording on; ``who`` tags every span with the role
    (e.g. ``cluster-ps``, ``cluster-worker-2``) so the report can merge
    per-role streams without guessing from filenames."""
    import jax.profiler

    _STATE["who"] = who
    _STATE["annotate"] = jax.profiler.TraceAnnotation
    _STATE["enabled"] = True


def disable():
    _STATE["enabled"] = False
    _STATE["who"] = None


def enabled():
    return _STATE["enabled"]


class _NullSpan:
    """The disabled path: a shared, reusable no-op context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **tags):
        return self


_NULL = _NullSpan()


class Span:
    """One timed phase. Context-manager use only::

        with trace.span("quorum", step=i) as sp:
            got = collect(...)
            sp.set(arrived=len(got))

    The record is emitted at ``__exit__`` (through the process-global
    hub hook — a no-op if no hub is installed), stamped with the
    wall-clock START (``t_wall``, for cross-process alignment) and the
    monotonic DURATION (``dur_s``, immune to wall-clock steps). An
    exception inside the span still records it (tagged ``error``) and
    propagates — a phase that dies is exactly the one worth seeing.
    Nesting works: each span carries its own clocks; the report keeps
    outermost spans for attribution and all of them for the timeline.
    """

    __slots__ = ("phase", "tags", "_t_wall", "_t0", "_annotation")

    def __init__(self, phase, tags):
        self.phase = phase
        self.tags = tags

    def set(self, **tags):
        """Attach tags discovered mid-span (arrived counts, byte
        totals); later values win."""
        self.tags.update(tags)
        self._annotation.set_metadata(**tags)
        return self

    def __enter__(self):
        # The same span on the profiler's clock (free while no profiler
        # session runs): host activity beside the device's operations.
        self._annotation = _STATE["annotate"](self.phase, **self.tags)
        self._annotation.__enter__()
        self._t_wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        tags = self.tags
        if exc_type is not None:
            tags = dict(tags, error=exc_type.__name__)
        who = _STATE["who"]
        if who is not None and "who" not in tags:
            tags = dict(tags, who=who)
        _hub.emit_span(
            self.phase, t_wall=self._t_wall, dur_s=dur, tid=_tid(), **tags
        )
        return False


def span(phase, **tags):
    """A span context manager for ``phase``, or the shared no-op when
    tracing is disabled (the zero-cost contract). ``step``/``round``
    tags are what the report keys rounds on — pass them whenever the
    phase belongs to one."""
    if not _STATE["enabled"]:
        return _NULL
    return Span(phase, tags)


def emit(phase, t_wall, dur_s, **tags):
    """Emit one PRE-TIMED span record (same shape as ``Span`` emits).

    For producers whose phase work is scattered across many small slices
    that only become one logical unit later — the hierarchy's per-wave
    ingest accounting (ISSUE 20) accumulates each row copy's duration
    and reports ONE ``hier_ingest`` span per dispatched wave, so span
    counts align 1:1 with the wave's ``hier_wave``/``hier_h2d`` records
    instead of undercounting attribution by whatever the ingest
    granularity happened to be. Callers time their own slices (and
    should skip the clock reads entirely when ``enabled()`` is False —
    the zero-cost contract is theirs to keep on this path)."""
    if not _STATE["enabled"]:
        return
    who = _STATE["who"]
    if who is not None and "who" not in tags:
        tags = dict(tags, who=who)
    _hub.emit_span(phase, t_wall=t_wall, dur_s=dur_s, tid=_tid(), **tags)
