"""The measured window: chained, asynchronous, bounded steps in flight.

The harness's copy of the program's training loop without ``--bench``
(`apps/common.train`): step i takes batch ``i % len(batches)``, the state is
chained (and donated by the program), dispatch stays asynchronous, and the
host blocks on the loss of step i - in_flight after dispatching step i,
recording the host time at which each step was seen complete. The window
opens at the first dispatch and closes when the last dispatched step is
complete after ``seconds`` have passed. Every step counts.
"""

import collections
import contextlib
import time

# Steps the host lets run ahead of the one it waits for, as the program's
# training loop does.
IN_FLIGHT = 2
# ``step_p95_ms`` reads spans of this many steps: a time read from the
# host's clock, which is off by some half a millisecond, has to span a
# quarter of a second, and a step here is 70-90 ms. One constant for every
# cell, so that no cell chooses how far its tail is diluted.
SPAN_STEPS = 4


def percentile(values, q):
    """The q-th percentile (0..100) with linear interpolation."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no values")
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def spans(t_open, done, span_steps=1):
    """Seconds from each step's completion to the completion ``span_steps``
    steps later, the first from the window's opening: sliding, so every step
    of the window lies in some span, and with ``span_steps == 1`` they are
    the intervals between consecutive completions and sum to the window."""
    times = [t_open, *done]
    return [b - a for a, b in zip(times, times[span_steps:])]


def summarize(t_open, done, images_per_step, span_steps=SPAN_STEPS):
    """End-to-end numbers of a closed window. A step's time is read from
    sliding spans of ``span_steps`` steps and given per step: a stall of t in
    one step reads t / span_steps in that many spans. Beside them, for the
    record and under no bound, the same percentiles of the single intervals
    between consecutive completions (``interval_*``)."""
    window = done[-1] - t_open
    span_steps = min(span_steps, len(done))  # a window of fewer is one span
    per_step = [s / span_steps for s in spans(t_open, done, span_steps)]
    single = spans(t_open, done)
    return {
        "steps": len(done),
        "window_s": window,
        "images_per_s": images_per_step * len(done) / window,
        "step_p95_ms": 1e3 * percentile(per_step, 95),
        "step_p50_ms": 1e3 * percentile(per_step, 50),
        "interval_p95_ms": 1e3 * percentile(single, 95),
        "interval_max_ms": 1e3 * max(single),
    }


def run(step, state, batches, seconds, *, in_flight=IN_FLIGHT, wait=None,
        annotate=None, before_dispatch=None, clock=time.perf_counter):
    """Drive ``state, loss = step(state, *batches[i % len])`` for ``seconds``.

    ``wait(loss)`` blocks until that step is complete; ``annotate(name)`` is
    a context manager naming what the host does (trace spans);
    ``before_dispatch(elapsed, completed_times)`` runs once per step before
    its dispatch (the traced run starts the profiler from it). Returns
    ``(state, t_open, done, losses)``."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    pending = collections.deque()
    done, losses = [], []
    t_open = clock()
    i = 0
    while True:
        if before_dispatch is not None:
            before_dispatch(clock() - t_open, done)
        with annotate("bench.dispatch"):
            state, loss = step(state, *batches[i % len(batches)])
        pending.append(loss)
        losses.append(loss)
        i += 1
        if len(pending) > in_flight:
            with annotate("bench.wait"):
                wait(pending.popleft())
            done.append(clock())
        if clock() - t_open >= seconds:
            break
    while pending:
        with annotate("bench.wait"):
            wait(pending.popleft())
        done.append(clock())
    return state, t_open, done, losses
