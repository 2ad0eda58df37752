"""Schema-check every committed telemetry JSONL artifact.

The round-5 bench post-mortem rule, mechanized: a bench capture that drifts
from the telemetry schema must fail LOUDLY at commit time, not parse
half-way in a later analysis session. This walks the repo root for
``*_r*.jsonl`` artifacts (EXCHBENCH_r*, HIERBENCH_r*, ...) plus every
committed fixture stream under ``tests/fixtures/``, and runs
``telemetry.exporters.validate_jsonl`` over each — wired into tier-1 by
``tests/test_trace.py::TestValidateArtifacts`` so schema drift in a
future round fails the suite. Covers every registered record kind,
including the schema-v7 ``defense_bench`` rows (DEFBENCH_r*: the
adaptive-attack / closed-loop-defense accuracy cells) and the v7
event/summary additions (attack_adapt, defense_weights,
defense_escalate, attack_fallback, suspicion_decayed) — and the v8
threat-model-matrix additions (ps_attack_adapt, targeted_eval,
plane-tagged defense events, the DEFBENCH_r02 grid rows with
plane/confusion/asr columns) — and the v9 data-plane-defense additions
(the data_defense event with matched-length scores/flags/weights/ranks
lists, summary.data_defense, the asr_baseline field on targeted_eval
events and DEFBENCH_r03's defense_bench rows with the composed
data/escalate+data defense strings) — and the v10 federated additions
(the ``fed_bench`` kind behind FEDBENCH_r*'s scaling / s1_bitwise /
fleet rows, the ``fed_round`` event with its per-shard digest, the
``cohort`` event's matched-length client_ids/selected lists, and
``summary.federated`` with its client-id-keyed top_clients map) — and
the v11 compression additions (the ``wire`` event's per-scheme byte
breakdown + compression_ratio/ef_residual_norm, ``summary.wire_schemes``,
and EXCHBENCH_r05's ``--robust`` exchange_bench rows with their
cell/matched_accuracy/headroom columns; auto-globbed like every
``*_r*.jsonl``) — and the v12 selection-kernel additions (FEDBENCH_r02's
``fed_bench`` scaling rows with their per-phase ``phases`` p50/p95
attribution — ingest/h2d/fold/selection — and SELBENCH-style
``gar_bench`` rows with grid/impl/wave_buckets/per_bucket_s columns) —
and the v13 control-plane additions (the ``soak_bench`` kind behind
SOAKBENCH_r*'s steady / rolling_restart / partition / churn rows with
their p50/p95/p99 SLO columns and the measured ``kill_cost_rounds``,
plus the ``membership`` event — one epoch bump per failover / split /
merge; both auto-globbed like every ``*_r*.jsonl``) — and the v14
slot-fused-transformer additions (the ``trans_bench`` kind behind
TRANSBENCH_r*'s rows: fused-vs-unrolled A/B latency cells with their
``dw_mode``/``dce_guard``/``per_slot_grad_s``/``speedup`` columns and
the token-backdoor robustness cells with ``asr``/``asr_baseline``/
``accuracy`` per defense; auto-globbed like every ``*_r*.jsonl``) — and
the v15 batched-wire-ingest additions (the ``ingest_batch`` event —
per-bulk-call shard/frames/rejected/bytes with ``rejected <= frames``
and accepted-only byte accounting — plus the ``fed_bench`` kind's
``check="ingest_micro"`` row family behind INGESTBENCH_r*'s
batch-vs-per-frame decode A/B cells and FEDBENCH_r03's scaling rows
with per-phase attribution on every row; both auto-globbed like every
``*_r*.jsonl``).

  python scripts/validate_artifacts.py            # repo root auto-found
  python scripts/validate_artifacts.py /some/repo
"""

import glob
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def find_artifacts(root=None):
    """Sorted list of committed JSONL artifacts under ``root``: the
    ``*_r*.jsonl`` bench captures at the top level and every fixture
    ``*.jsonl`` under tests/fixtures/."""
    root = root or _REPO
    paths = sorted(glob.glob(os.path.join(root, "*_r*.jsonl")))
    paths += sorted(glob.glob(
        os.path.join(root, "tests", "fixtures", "**", "*.jsonl"),
        recursive=True,
    ))
    return paths


def find_json_twins(root=None):
    """The ``*_r*.json`` twins of the JSONL artifacts (EXCHBENCH_r04's
    scaleup/learn rows and friends): not schema-versioned, but a twin
    that fails to parse is the same dark-artifact failure mode."""
    root = root or _REPO
    return sorted(glob.glob(os.path.join(root, "*_r*.json")))


def main(root=None, argv=None):
    import json

    if argv:
        root = argv[0]
    sys.path.insert(0, root or _REPO)
    from garfield_tpu.telemetry import validate_jsonl

    paths = find_artifacts(root)
    if not paths:
        print("validate_artifacts: no *_r*.jsonl artifacts found",
              file=sys.stderr)
        return 1
    total = 0
    for path in paths:
        count = validate_jsonl(path)  # raises ValueError on drift
        total += count
        print(f"ok {os.path.relpath(path, root or _REPO)} "
              f"({count} records)")
    twins = 0
    for path in find_json_twins(root):
        with open(path) as fp:
            json.load(fp)  # raises on a torn/truncated capture
        twins += 1
    print(f"validate_artifacts: {len(paths)} artifacts, "
          f"{total} records, all schema-valid "
          f"(+{twins} parseable .json twins)")
    return 0


if __name__ == "__main__":
    sys.exit(main(argv=sys.argv[1:]))
