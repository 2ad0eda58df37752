"""Data-plane defense, end to end (slow, multi-process).

The deployment twin of tests/test_dataplane.py (DESIGN.md §18): a REAL
backdoor-poisoning worker process (``--attack backdoor`` — trigger
stamps + target labels on its own shard, honest gradients of the
poisoned task) against an SSMW PS running ``--defense escalate+data``
(the GAR-side suspicion ladder AND the fingerprint detectors over the
wire frames it decodes), over PeerExchange on localhost.

Registered in conftest._RUN_LAST (multi-process e2e discipline): spawns
subprocess fleets and compiles per process — slow-marked, collects last.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ports(k):
    socks = [socket.socket() for _ in range(k)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    env["GARFIELD_SURROGATE_MARGIN"] = "1.35"
    env["GARFIELD_SURROGATE_LABEL_NOISE"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_backdoor_worker_vs_dataplane_defending_ps(tmp_path):
    """1 PS (--defense escalate+data) + 6 workers, one a real backdoor
    poisoner: every role exits rc 0, the PS stream carries schema-v9
    ``data_defense`` events and the ``summary.data_defense`` digest,
    and the detector history concentrates its flags on the poisoning
    worker's rank — the wire-frame twin of the in-graph detectors."""
    from garfield_tpu.utils import multihost

    n_w = 6
    byz = n_w - 1
    pp = _ports(1 + n_w)
    cfg_path = str(tmp_path / "cluster.json")
    multihost.generate_config(
        cfg_path,
        ps=[f"127.0.0.1:{pp[0]}"],
        workers=[f"127.0.0.1:{p}" for p in pp[1:]],
        task_type="ps", task_index=0,
    )
    env = _env()
    tele = str(tmp_path / "tele")
    base = [
        sys.executable, "-m", "garfield_tpu.apps.aggregathor",
        "--cluster", cfg_path,
        "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
        "--batch", "16", "--fw", "1", "--gar", "krum",
        "--num_iter", "40", "--acc_freq", "20",
        "--opt_args", '{"lr":"0.05"}',
        "--cluster_timeout_ms", "120000",
    ]
    ps = subprocess.Popen(
        base + ["--task", "ps:0", "--defense", "escalate+data",
                "--defense_params", '{"dp_halflife": 4.0}',
                "--suspicion_halflife", "10", "--telemetry", tele],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    workers = []
    for k in range(n_w):
        argv = base + ["--task", f"worker:{k}"]
        if k == byz:
            argv += ["--attack", "backdoor",
                     "--attack_params",
                     '{"source": 0, "target": 1, "poison_frac": 1.0}']
        workers.append(subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        ))
    try:
        out, _ = ps.communicate(timeout=600)
        assert ps.returncode == 0, f"PS failed:\n{out[-2000:]}"
        for k, w in enumerate(workers):
            w.wait(timeout=180)
            assert w.returncode == 0, f"worker {k} rc {w.returncode}"
    finally:
        for p in [ps, *workers]:
            if p.poll() is None:
                p.kill()
    recs = [
        json.loads(l)
        for l in open(os.path.join(tele, "cluster-ps.telemetry.jsonl"))
    ]
    # Schema-v9 plumbing: data_defense events landed in the stream and
    # every record (the new event shape included) validates.
    dd = [r for r in recs if r.get("event") == "data_defense"]
    assert dd, "PS emitted no data_defense events"
    from garfield_tpu.telemetry import validate_jsonl

    validate_jsonl(os.path.join(tele, "cluster-ps.telemetry.jsonl"))
    summaries = [r for r in recs if r["kind"] == "summary"]
    assert summaries and summaries[-1]["data_defense"] is not None
    assert summaries[-1]["data_defense"]["rounds"] > 0
    # Detector attribution: the poisoning worker's rank collects the
    # most flags, and by the final rounds its composed weight is below
    # every honest rank's.
    flags_by_rank = {}
    for r in dd:
        for rank, fl in zip(r["ranks"], r["flags"]):
            flags_by_rank[rank] = flags_by_rank.get(rank, 0) + int(fl)
    assert flags_by_rank.get(byz, 0) > 0, flags_by_rank
    assert flags_by_rank[byz] == max(flags_by_rank.values()), (
        flags_by_rank
    )
    last = dd[-1]
    w_by_rank = dict(zip(last["ranks"], last["weights"]))
    if byz in w_by_rank:
        assert w_by_rank[byz] <= min(
            v for r, v in w_by_rank.items() if r != byz
        ), w_by_rank
