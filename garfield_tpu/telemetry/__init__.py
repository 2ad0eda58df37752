"""Telemetry plane: in-graph GAR audit taps, host aggregation, exporters.

The repo's runtime observability layer (ISSUE 2). Three layers:

  - ``taps`` (in-graph): a small, fixed-shape ``TapBundle`` pytree —
    per-rank selection mask / scores, cclip's tau + clip fraction —
    recomputed inside the jit'd step from the SAME poisoned stack and PRNG
    keys the GAR consumed. The taps never feed back into ``TrainState``,
    so taps-on and taps-off trajectories are bitwise identical; when
    disabled (the default) nothing is traced at all — zero cost, not
    masked-out cost.
  - ``hub`` (host): a ring-buffered ``MetricsHub`` that merges per-step
    taps with ``profiling.StepTimer`` timings and the liveness/wait-n-f
    events the cluster driver and ``utils.exchange`` emit through the
    process-global hook (``install``/``emit_event``), and derives per-rank
    *suspicion scores* — cumulative exclusion frequency under the active
    GAR, the audit signal that makes Byzantine ranks visible without
    ground truth.
  - ``exporters``: schema-versioned JSONL, Prometheus text exposition,
    and stdlib schema validation so malformed records fail loudly.

See docs/TELEMETRY.md for the record schema and overhead numbers.
"""

from .exporters import (  # noqa: F401
    JsonlExporter,
    SCHEMA,
    SCHEMA_VERSION,
    make_record,
    prometheus_text,
    validate_jsonl,
    validate_record,
)
from .hub import (  # noqa: F401
    MetricsHub,
    current,
    emit_event,
    emit_span,
    install,
    uninstall,
)
from . import trace  # noqa: F401  (span tracing, schema v5 — ISSUE 8)

__all__ = [
    "emit_span",
    "trace",
    "JsonlExporter",
    "MetricsHub",
    "SCHEMA",
    "SCHEMA_VERSION",
    "current",
    "emit_event",
    "install",
    "make_record",
    "prometheus_text",
    "uninstall",
    "validate_jsonl",
    "validate_record",
]
