"""One reader per per-layer metric, found by the metric's name:
``read(trace, facts)`` takes the reduced trace (`harness.reduce_trace`) and
the run's facts, and returns the number, or None where it finds nothing to
read."""
