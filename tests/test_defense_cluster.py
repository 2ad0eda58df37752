"""Adaptive adversary vs closed-loop defense, end to end (slow).

The multi-process twin of tests/test_adaptive.py / test_defense.py
(DESIGN.md §16): a REAL suspicion-aware Byzantine worker process
(``--attack adaptive-lie`` — bisection magnitude fed by the broadcast
model delta) against an SSMW PS running ``--defense escalate``
(suspicion-weighted quorums + the rule ladder) with the windowed
suspicion score, over PeerExchange on localhost. Plus the on-mesh CLI
closed loop (apps/common.py escalation rebuild) driven through
app_aggregathor.main.

Registered in conftest._RUN_LAST (multi-process e2e discipline): these
spawn subprocess fleets and compile per process — minutes by design, so
they are slow-marked and collect last.
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
    env["GARFIELD_SURROGATE_MARGIN"] = "30"
    env["GARFIELD_SURROGATE_LABEL_NOISE"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_adaptive_attacker_vs_escalating_ps(tmp_path):
    """1 PS (--defense escalate, windowed suspicion) + 6 workers, one of
    them a real adaptive-lie process: the deployment must finish with
    every role rc 0, the attacker must have closed real probes through
    the model-delta channel, and the PS summary must carry the schema-v7
    defense digest."""
    from garfield_tpu.utils import multihost

    n_w = 6
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
        "--num_iter", "50", "--acc_freq", "10",
        "--opt_args", '{"lr":"0.05"}',
        "--cluster_timeout_ms", "120000",
    ]
    ps = subprocess.Popen(
        base + ["--task", "ps:0", "--defense", "escalate",
                "--defense_params",
                '{"patience": 3, "theta_up": 0.35, "theta_down": 0.1}',
                "--suspicion_halflife", "10", "--telemetry", tele],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    honest = [
        subprocess.Popen(
            base + ["--task", f"worker:{k}"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        )
        for k in range(n_w - 1)
    ]
    attacker = subprocess.Popen(
        base + ["--task", f"worker:{n_w - 1}", "--attack", "adaptive-lie",
                "--attack_params", '{"mag_max": 4.0}',
                "--telemetry", tele],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        out, _ = ps.communicate(timeout=600)
        assert ps.returncode == 0, f"PS failed:\n{out[-2000:]}"
        summary = json.loads(
            [l for l in out.splitlines() if l.startswith("{")][-1]
        )
        assert summary["steps"] == 50
        aout, _ = attacker.communicate(timeout=180)
        assert attacker.returncode == 0, f"attacker:\n{aout[-1500:]}"
        asum = json.loads(
            [l for l in aout.splitlines() if l.startswith("{")][-1]
        )
        # The controller closed real probes through the delta channel.
        assert asum["attack_adapt"]["probes"] > 10
        for w in honest:
            w.wait(timeout=180)
            assert w.returncode == 0
    finally:
        for p in [ps, attacker, *honest]:
            if p.poll() is None:
                p.kill()
    # Schema-v7 plumbing landed in the PS stream: defense digest (the
    # per-round suspicion weights were folded) + windowed suspicion.
    recs = [
        json.loads(l)
        for l in open(os.path.join(tele, "cluster-ps.telemetry.jsonl"))
    ]
    summaries = [r for r in recs if r["kind"] == "summary"]
    assert summaries, "PS wrote no summary"
    s = summaries[-1]
    assert s["defense"] is not None and s["defense"]["rounds"] > 0
    assert s["suspicion_decayed"] is not None
    assert any(r.get("event") == "defense_weights" for r in recs)
    # The attacker's own stream carries its controller telemetry.
    wrecs = [
        json.loads(l) for l in open(os.path.join(
            tele, f"cluster-worker-{n_w - 1}.telemetry.jsonl"
        ))
    ]
    assert any(r.get("event") == "attack_adapt" for r in wrecs)


def test_onmesh_cli_closed_loop(tmp_path):
    """The on-mesh CLI loop: app_aggregathor under adaptive-lie with
    --defense escalate must train, emit attack_adapt + defense_weights
    events, and write a v7 summary with both digests."""
    from garfield_tpu.apps import aggregathor as app_aggregathor

    tele = str(tmp_path / "tele")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        app_aggregathor.main([
            "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
            "--batch", "16", "--num_workers", "8", "--fw", "2",
            "--gar", "krum", "--attack", "adaptive-lie",
            "--attack_params", '{"mag_max": 4.0}',
            "--defense", "escalate",
            "--defense_params",
            '{"patience": 3, "theta_up": 0.35, "theta_down": 0.1}',
            "--suspicion_halflife", "12",
            "--opt_args", '{"lr":"0.05"}',
            "--num_iter", "40", "--acc_freq", "20",
            "--telemetry", tele,
        ])
    finally:
        os.chdir(cwd)
    recs = [
        json.loads(l)
        for l in open(os.path.join(tele, "telemetry.jsonl"))
    ]
    assert any(r.get("event") == "attack_adapt" for r in recs)
    assert any(r.get("event") == "defense_weights" for r in recs)
    s = [r for r in recs if r["kind"] == "summary"][-1]
    assert s["attack_adapt"]["events"] == 40
    assert s["defense"] is not None and s["defense"]["rounds"] == 40
    assert s["suspicion_decayed"] is not None


def test_learn_per_plane_defense_with_adaptive_gossip_node(tmp_path):
    """6 LEARN nodes, one a real adaptive-lie GOSSIP poisoner
    (--model_attack adaptive-lie: collusion fake over its last gathered
    gossip stack, forward delta-probe feedback), every honest node
    running --defense escalate with INDEPENDENT per-plane ladders
    (DESIGN.md §17). Every role must exit rc 0, the attacker must close
    real probes, and an honest node's stream must carry plane-tagged
    defense events."""
    from garfield_tpu.utils import multihost

    n = 6
    pp = _ports(n)
    cfg_path = str(tmp_path / "cluster.json")
    multihost.generate_config(
        cfg_path, nodes=[f"127.0.0.1:{p}" for p in pp],
        task_type="node", task_index=0,
    )
    env = _env()
    base = [
        sys.executable, "-m", "garfield_tpu.apps.learn",
        "--cluster", cfg_path,
        "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
        "--batch", "16", "--fw", "1", "--gar", "krum",
        "--num_iter", "10", "--acc_freq", "0",
        "--opt_args", '{"lr":"0.05"}',
        "--cluster_timeout_ms", "120000",
    ]
    tele = str(tmp_path / "tele")
    procs = []
    for k in range(n):
        argv = base + ["--task", f"node:{k}"]
        if k == n - 1:
            argv += ["--model_attack", "adaptive-lie",
                     "--model_attack_params", '{"mag_max": 4.0}']
        else:
            argv += ["--defense", "escalate",
                     "--suspicion_halflife", "8",
                     "--telemetry", tele]
        procs.append(subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    for k, p in enumerate(procs):
        out, _ = p.communicate(timeout=600)
        outs.append(out)
        assert p.returncode == 0, f"node {k} failed:\n{out[-2000:]}"
    # The attacker ran REAL probes through the gossip delta channel.
    atk = json.loads(
        [l for l in outs[-1].splitlines() if l.startswith("{")][-1]
    )
    assert atk["model_attack_adapt"]["probes"] > 0
    # An honest node's stream carries plane-tagged defense evidence for
    # BOTH planes (independent histories).
    recs = [
        json.loads(l)
        for l in open(os.path.join(tele, "cluster-node-0.telemetry.jsonl"))
    ]
    planes = {
        r.get("plane") for r in recs
        if r.get("event") == "defense_weights"
    }
    esc_planes = {
        r.get("plane") for r in recs
        if r.get("event") == "defense_escalate"
    }
    assert planes <= {"gradient", "gossip"}
    assert esc_planes <= {"gradient", "gossip"}
    # Every record (v8 events included) is schema-valid.
    from garfield_tpu.telemetry import validate_jsonl

    validate_jsonl(os.path.join(tele, "cluster-node-0.telemetry.jsonl"))


def test_msmw_defense_and_adaptive_byzantine_ps(tmp_path):
    """3 PS replicas (one a real adaptive-lie Byzantine PS probing the
    replica gather) + 6 workers (one labelflip): the honest replicas run
    the MSMW gradient-plane defense; everyone exits rc 0 and the
    Byzantine PS closes real model-plane probes."""
    from garfield_tpu.utils import multihost

    n_ps, n_w = 3, 6
    pp = _ports(n_ps + n_w)
    cfg_path = str(tmp_path / "cluster.json")
    multihost.generate_config(
        cfg_path,
        ps=[f"127.0.0.1:{p}" for p in pp[:n_ps]],
        workers=[f"127.0.0.1:{p}" for p in pp[n_ps:]],
        task_type="ps", task_index=0,
    )
    env = _env()
    tele = str(tmp_path / "tele")
    base = [
        sys.executable, "-m", "garfield_tpu.apps.byzsgd",
        "--cluster", cfg_path,
        "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
        "--batch", "16", "--fw", "1", "--fps", "1",
        "--gar", "krum", "--model_gar", "median",
        "--num_iter", "10", "--acc_freq", "0",
        "--opt_args", '{"lr":"0.05"}',
        "--cluster_timeout_ms", "120000",
    ]
    procs = []
    for k in range(n_ps):
        argv = base + ["--task", f"ps:{k}"]
        if k == n_ps - 1:
            argv += ["--ps_attack", "adaptive-lie",
                     "--ps_attack_params", '{"mag_max": 4.0}']
        else:
            argv += ["--defense", "escalate",
                     "--suspicion_halflife", "8", "--telemetry", tele]
        procs.append(("ps", k, subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )))
    for k in range(n_w):
        argv = base + ["--task", f"worker:{k}"]
        if k == n_w - 1:
            argv += ["--attack", "labelflip"]
        procs.append(("worker", k, subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )))
    byz_out = None
    for role, k, p in procs:
        out, _ = p.communicate(timeout=600)
        assert p.returncode == 0, f"{role} {k} failed:\n{out[-2000:]}"
        if role == "ps" and k == n_ps - 1:
            byz_out = out
    atk = json.loads(
        [l for l in byz_out.splitlines() if l.startswith("{")][-1]
    )
    assert atk["ps_attack_adapt"]["probes"] > 0
    # Honest replica telemetry: gradient-plane defense weights landed.
    recs = [
        json.loads(l)
        for l in open(os.path.join(tele, "cluster-ps-0.telemetry.jsonl"))
    ]
    assert any(r.get("event") == "defense_weights" for r in recs)
