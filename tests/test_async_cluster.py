"""Bounded-staleness async cluster deployments (DESIGN.md §14), e2e.

Multi-process TCP twins of tests/test_staleness.py: real OS processes
over PeerExchange with ``--async``. Coverage: a 10x-class injected
straggler cannot set the PS's pace (stale-frame reuse, discounted
weights), the acceptance lie-attack smoke with a SLOW Byzantine rank at
8-rank scale, churn (kill + relaunch a worker mid-run — re-admission is
its fresh frames re-entering the admissible set), a network partition
(SIGSTOP past the staleness cutoff, SIGCONT recovery), and the
``--max_staleness 0`` bitwise-equality contract against the synchronous
trajectory. Registered in conftest._RUN_LAST (multi-process e2e files
collect last).
"""

import json
import os
import pickle
import signal
import socket
import subprocess
import sys
import time

import pytest

pytest.importorskip("garfield_tpu.native")

# Multi-process deployments compile per process: minutes per test by
# design. The tier-1 fast shard (-m "not slow") skips them.
pytestmark = pytest.mark.slow
from garfield_tpu import native

if native.load() is None:
    pytest.skip("native runtime unavailable", allow_module_level=True)

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


def _cluster_setup(tmp_path, n_w, name="cluster.json"):
    from garfield_tpu.utils import multihost

    pp = _ports(1 + n_w)
    cfg_path = str(tmp_path / name)
    multihost.generate_config(
        cfg_path,
        ps=[f"127.0.0.1:{pp[0]}"],
        workers=[f"127.0.0.1:{p}" for p in pp[1:]],
        task_type="ps", task_index=0,
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    env["GARFIELD_SURROGATE_MARGIN"] = "30"
    env["GARFIELD_SURROGATE_LABEL_NOISE"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return cfg_path, env


def _launch(role, cfg_path, env, extra=()):
    return subprocess.Popen(
        [
            sys.executable, "-m", "garfield_tpu.apps.aggregathor",
            "--cluster", cfg_path, "--task", role,
            "--dataset", "mnist", "--model", "convnet", "--batch", "16",
            "--fw", "1", "--gar", "median", "--num_iter", "60",
            "--acc_freq", "10", "--train_size", "512",
            "--cluster_timeout_ms", "120000", *extra,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )


def _summary(out):
    return json.loads(
        [l for l in out.splitlines() if l.startswith("{")][-1]
    )


def _staleness_events(tele_dir):
    events = []
    with open(os.path.join(tele_dir, "cluster-ps.telemetry.jsonl")) as fp:
        for line in fp:
            rec = json.loads(line)
            if rec["kind"] == "event" and rec.get("event") == "staleness":
                events.append(rec)
    return events


def test_async_straggler_reused_and_converges(tmp_path):
    """The tentpole scenario: one worker sleeps 3 s per gradient while
    honest peers run at full speed, and fw=0 makes the quorum q = n — in
    sync mode EVERY round would wait out the straggler (the exact
    one-straggler-sets-the-pace failure the async plane removes, with no
    f budget to hide it in). Bounded staleness REUSES the straggler's
    admissible stale frames (discounted), so the PS sustains a rate set
    by the cutoff and the fast ranks, still converges, and the telemetry
    plane pins the straggler: staleness events carry its round lag and
    its discount deficit tops the suspicion ranking."""
    n_w, n_iter = 4, 60
    cfg_path, env = _cluster_setup(tmp_path, n_w)
    tele = str(tmp_path / "tele")
    extra = (
        "--fw", "0", "--async", "--max_staleness", "8",
        "--num_iter", str(n_iter), "--telemetry", tele,
    )
    t0 = time.time()
    ps = _launch("ps:0", cfg_path, env, extra=extra)
    workers = [
        _launch(
            f"worker:{w}", cfg_path, env,
            extra=extra + (
                ("--straggler_ms", "3000") if w == n_w - 1 else ()
            ),
        )
        for w in range(n_w)
    ]
    try:
        out, _ = ps.communicate(timeout=400 + 5 * n_iter)
        wall = time.time() - t0
        assert ps.returncode == 0, f"PS failed:\n{out[-2000:]}"
        summary = _summary(out)
        assert summary["steps"] == n_iter
        first_acc = float(
            [l for l in out.splitlines() if l.startswith("Step: 0 ")][0]
            .split()[3]
        )
        assert summary["final_accuracy"] > max(0.3, first_acc + 0.1), (
            summary
        )
        # Rate decoupling: 60 rounds synchronized on a 3 s straggler
        # would spend >= ~180 s inside the loop alone; the async PS loop
        # (wall minus startup) must come in far under that.
        assert summary["wall_s"] < 120, summary
        for w in workers:
            wout, _ = w.communicate(timeout=120)
            assert w.returncode == 0, f"worker failed:\n{wout[-1500:]}"
        events = _staleness_events(tele)
        assert events, "async PS emitted no staleness events"
        strag = n_w - 1  # worker index of the straggler
        max_tau = max(
            t for e in events
            for r, t in zip(e["ranks"], e["staleness"]) if r == strag
        )
        assert max_tau >= 1, "straggler never entered a quorum stale"
        assert any(e["reused"] > 0 for e in events)
        # Suspicion: the straggler's cumulative discount deficit must
        # rank it top (summary record of the PS's hub).
        with open(os.path.join(
            tele, "cluster-ps.telemetry.jsonl"
        )) as fp:
            summaries = [
                json.loads(l) for l in fp
                if json.loads(l)["kind"] == "summary"
            ]
        susp = summaries[-1]["suspicion"]
        assert susp.index(max(susp)) == strag, susp
        assert summaries[-1]["staleness"]["count"] > 0
    finally:
        for p in [ps, *workers]:
            if p.poll() is None:
                p.kill()


def test_async_lie_attack_with_slow_byzantine_rank(tmp_path):
    """The acceptance smoke: the 8-rank deployment (1 PS + 7 workers)
    under a REAL lie-attack process that is ALSO a straggler. Three of
    the seven workers are slow (two honest + the Byzantine one), so the
    q = 5 freshest-arrivals quorum MUST keep admitting stale discounted
    rows — the lie rows included — every round; median at fw=2 must
    still clear the same accuracy bar as the synchronous lie smoke
    (test_cluster.py)."""
    n_w, n_iter = 7, 120
    cfg_path, env = _cluster_setup(tmp_path, n_w)
    extra = (
        "--fw", "2", "--async", "--max_staleness", "4",
        "--num_iter", str(n_iter),
    )
    slow_honest = ("--straggler_ms", "1200")
    ps = _launch("ps:0", cfg_path, env, extra=extra)
    workers = [
        _launch(
            f"worker:{w}", cfg_path, env,
            extra=extra + (
                ("--attack", "lie", "--attack_params", '{"cohort": 2}',
                 "--straggler_ms", "1500")
                if w == n_w - 1
                else slow_honest if w in (0, 1) else ()
            ),
        )
        for w in range(n_w)
    ]
    try:
        out, _ = ps.communicate(timeout=500 + 5 * n_iter)
        assert ps.returncode == 0, f"PS failed:\n{out[-2000:]}"
        summary = _summary(out)
        assert summary["steps"] == n_iter
        first_acc = float(
            [l for l in out.splitlines() if l.startswith("Step: 0 ")][0]
            .split()[3]
        )
        assert summary["final_accuracy"] > max(0.3, first_acc + 0.1), (
            f"async median did not ride out the slow lie attacker: "
            f"{summary}"
        )
        for w in workers:
            wout, _ = w.communicate(timeout=120)
            assert w.returncode == 0, f"worker failed:\n{wout[-1500:]}"
    finally:
        for p in [ps, *workers]:
            if p.poll() is None:
                p.kill()


def test_async_max_staleness_zero_bitwise_equals_sync(tmp_path):
    """--max_staleness 0 contract: exact-round admission, all weights
    exactly 1, the unweighted update program — the async trajectory is
    BITWISE the synchronous one. fw=0 with 2 workers makes the quorum
    composition deterministic (every worker in every quorum), so the
    final checkpointed models must match byte for byte."""
    n_w, n_iter = 2, 25

    def run(tag, async_flags):
        cfg_path, env = _cluster_setup(tmp_path, n_w, name=f"{tag}.json")
        env["GARFIELD_CKPT_BACKEND"] = "pickle"
        ckpt = str(tmp_path / f"ckpt_{tag}")
        extra = (
            "--fw", "0", "--gar", "average", "--num_iter", str(n_iter),
            "--acc_freq", "0", "--checkpoint_dir", ckpt,
            "--checkpoint_freq", str(n_iter), *async_flags,
        )
        ps = _launch("ps:0", cfg_path, env, extra=extra)
        workers = [
            _launch(f"worker:{w}", cfg_path, env, extra=extra)
            for w in range(n_w)
        ]
        try:
            out, _ = ps.communicate(timeout=400)
            assert ps.returncode == 0, f"PS failed:\n{out[-2000:]}"
            for w in workers:
                wout, _ = w.communicate(timeout=120)
                assert w.returncode == 0, f"worker failed:\n{wout[-1500:]}"
        finally:
            for p in [ps, *workers]:
                if p.poll() is None:
                    p.kill()
        with open(os.path.join(ckpt, f"ckpt_{n_iter}.pkl"), "rb") as f:
            return pickle.load(f)["flat"]

    import numpy as np

    flat_sync = run("sync", ())
    flat_async = run("async", ("--async", "--max_staleness", "0"))
    assert np.array_equal(flat_sync, flat_async), (
        float(np.abs(flat_sync - flat_async).max())
    )


def test_async_churn_worker_leave_and_rejoin(tmp_path):
    """Churn: SIGKILL a worker mid-run and relaunch it on the same
    rank/port (join). While it is gone its frames expire past the cutoff
    and the q = 3 quorum flows over the survivors; the relaunched
    process re-reads its shard (re-admit becomes re-shard), catches up
    through read_latest, and its fresh frames re-enter the admissible
    set — the PS completes all rounds and converges, and the rejoined
    worker contributes real rounds. Every worker carries a moderate
    --straggler_ms so the run spans the rejoiner's cold start (python +
    jax boot is tens of seconds on this box; at the unpaced async rate
    the PS would finish before the new process could even listen)."""
    n_w, n_iter = 4, 100
    cfg_path, env = _cluster_setup(tmp_path, n_w)
    extra = (
        "--async", "--max_staleness", "8", "--num_iter", str(n_iter),
    )
    pace = ("--straggler_ms", "800")
    ps = _launch("ps:0", cfg_path, env, extra=extra)
    workers = [
        _launch(f"worker:{w}", cfg_path, env, extra=extra + pace)
        for w in range(n_w)
    ]
    victim_idx = n_w - 1
    rejoined = None
    try:
        first_acc = None
        for line in ps.stdout:
            if line.startswith("Step: 0 "):
                first_acc = float(line.split()[3])
            if line.startswith("Step: 10 "):
                break
        else:
            pytest.fail(f"PS exited early: rc={ps.wait()}")
        workers[victim_idx].send_signal(signal.SIGKILL)
        workers[victim_idx].wait(timeout=30)
        rejoined = _launch(f"worker:{victim_idx}", cfg_path, env,
                           extra=extra + pace)
        rest = ps.stdout.read()
        assert ps.wait(timeout=500) == 0, f"PS failed:\n{rest[-2000:]}"
        summary = _summary(rest)
        assert summary["steps"] == n_iter
        assert first_acc is not None
        assert summary["final_accuracy"] > max(0.3, first_acc + 0.1), (
            summary
        )
        for w in workers[:victim_idx]:
            wout, _ = w.communicate(timeout=200)
            assert w.returncode == 0, f"survivor failed:\n{wout[-1500:]}"
        rout, _ = rejoined.communicate(timeout=200)
        assert rejoined.returncode == 0, (
            f"rejoined worker failed:\n{rout[-1500:]}"
        )
        rsummary = _summary(rout)
        assert rsummary["steps"] >= 1, (
            f"rejoined worker never contributed: {rsummary}"
        )
    finally:
        procs = [ps, *workers] + ([rejoined] if rejoined else [])
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_async_partition_sigstop_recovers(tmp_path):
    """Partition: freeze a worker (SIGSTOP) for ~20 s mid-run — its
    staleness climbs past the cutoff and it drops out of the admissible
    set, the PS keeps pacing on the survivors — then SIGCONT: the thawed
    worker catches up via read_latest and re-enters the quorums. The PS
    completes and converges; the worker exits 0 having skipped rounds."""
    n_w, n_iter = 4, 60
    cfg_path, env = _cluster_setup(tmp_path, n_w)
    tele = str(tmp_path / "tele")
    extra = (
        "--async", "--max_staleness", "6", "--num_iter", str(n_iter),
        "--telemetry", tele,
    )
    ps = _launch("ps:0", cfg_path, env, extra=extra)
    workers = [
        _launch(f"worker:{w}", cfg_path, env, extra=extra)
        for w in range(n_w)
    ]
    victim = workers[-1]
    try:
        first_acc = None
        for line in ps.stdout:
            if line.startswith("Step: 0 "):
                first_acc = float(line.split()[3])
            if line.startswith("Step: 10 "):
                break
        else:
            pytest.fail(f"PS exited early: rc={ps.wait()}")
        victim.send_signal(signal.SIGSTOP)
        time.sleep(20)
        victim.send_signal(signal.SIGCONT)
        rest = ps.stdout.read()
        assert ps.wait(timeout=500) == 0, f"PS failed:\n{rest[-2000:]}"
        summary = _summary(rest)
        assert summary["steps"] == n_iter
        assert first_acc is not None
        assert summary["final_accuracy"] > max(0.3, first_acc + 0.1), (
            summary
        )
        for w in workers:
            wout, _ = w.communicate(timeout=200)
            assert w.returncode == 0, f"worker failed:\n{wout[-1500:]}"
        events = _staleness_events(tele)
        assert events and any(e["reused"] > 0 for e in events), (
            "partition run recorded no stale reuse"
        )
    finally:
        for p in [ps, *workers]:
            if p.poll() is None:
                p.kill()


# --- LEARN per-plane async gossip (DESIGN.md §15) ---------------------------


def _learn_cluster(tmp_path, n, name="learn.json"):
    from garfield_tpu.utils import multihost

    pp = _ports(n)
    cfg_path = str(tmp_path / name)
    multihost.generate_config(
        cfg_path, nodes=[f"127.0.0.1:{p}" for p in pp],
        task_type="node", task_index=0,
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    env["GARFIELD_SURROGATE_MARGIN"] = "30"
    env["GARFIELD_SURROGATE_LABEL_NOISE"] = "0"
    env["GARFIELD_CKPT_BACKEND"] = "pickle"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return cfg_path, env


def _launch_learn(k, cfg_path, env, iters, extra=()):
    return subprocess.Popen(
        [
            sys.executable, "-m", "garfield_tpu.apps.learn",
            "--cluster", cfg_path, "--task", f"node:{k}",
            "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
            "--batch", "16", "--fw", "0", "--gar", "average",
            "--num_iter", str(iters), "--acc_freq", "0",
            "--cluster_timeout_ms", "120000", *extra,
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )


def test_learn_async_straggler_decouples_and_victim_tops_suspicion(
    tmp_path,
):
    """LEARN --async over per-plane register slots: a 1.5 s/round victim
    node must NOT set the honest nodes' pace even at fw=0 (where the
    synchronous protocol waits on EVERYONE every round: 40 rounds would
    cost >= 60 s in-loop) — stale-frame reuse plus the swarm catch-up
    jump keep the honest loop an order of magnitude faster, the victim
    finishes alongside by SKIPPING rounds, and its per-plane discount
    deficits top every honest node's suspicion."""
    n, n_iter = 3, 40
    cfg_path, env = _learn_cluster(tmp_path, n)
    tele = str(tmp_path / "tele")
    extra = ("--async", "--max_staleness", "8", "--telemetry", tele)
    procs = [
        _launch_learn(
            k, cfg_path, env, n_iter,
            extra=extra + (
                ("--straggler_ms", "1500") if k == n - 1 else ()
            ),
        )
        for k in range(n)
    ]
    try:
        summaries = []
        for p in procs:
            out, _ = p.communicate(timeout=400)
            assert p.returncode == 0, f"node failed:\n{out[-2000:]}"
            summaries.append(_summary(out))
        for s in summaries[:-1]:  # honest nodes
            assert s["steps"] == n_iter and s["dropped_at"] is None, s
            # Decoupling: sync fw=0 would spend >= n_iter * 1.5 s = 60 s
            # in-loop; the honest async wall (incl. startup) must come in
            # far under that.
            assert s["wall_s"] < 30, s
        # The victim completes too — by skipping rounds, not by stalling
        # the swarm.
        assert summaries[-1]["skipped"] > 0, summaries[-1]
        with open(os.path.join(
            tele, "cluster-node-0.telemetry.jsonl"
        )) as fp:
            recs = [json.loads(l) for l in fp]
        stale = [
            r for r in recs
            if r["kind"] == "event" and r.get("event") == "staleness"
        ]
        assert stale and any(e["reused"] > 0 for e in stale)
        assert {e.get("plane") for e in stale} >= {"grad", "model"}
        summ = [r for r in recs if r["kind"] == "summary"][-1]
        susp = summ["suspicion"]
        assert susp.index(max(susp)) == n - 1, susp
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_learn_async_max_staleness_zero_checkpoint_bitwise(tmp_path):
    """--max_staleness 0 on the per-plane LEARN deployment: exact-round
    admission, all weights exactly 1.0, the unweighted jit programs —
    every node's final checkpoint is BYTE-equal to the synchronous
    trajectory's."""
    n, n_iter = 3, 12

    def run(tag, async_flags):
        cfg_path, env = _learn_cluster(tmp_path, n, name=f"{tag}.json")
        ckpt = str(tmp_path / f"ckpt_{tag}")
        extra = (
            "--checkpoint_dir", ckpt, "--checkpoint_freq", str(n_iter),
            *async_flags,
        )
        procs = [
            _launch_learn(k, cfg_path, env, n_iter, extra=extra)
            for k in range(n)
        ]
        try:
            for p in procs:
                out, _ = p.communicate(timeout=400)
                assert p.returncode == 0, f"node failed:\n{out[-2000:]}"
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        flats = []
        for k in range(n):
            with open(os.path.join(
                ckpt, f"node_{k}", f"ckpt_{n_iter}.pkl"
            ), "rb") as fp:
                flats.append(pickle.load(fp)["flat"])
        return flats

    import numpy as np

    sync = run("sync", ())
    asyn = run("async", ("--async", "--max_staleness", "0"))
    for k in range(n):
        assert np.array_equal(sync[k], asyn[k]), (
            k, float(np.abs(sync[k] - asyn[k]).max())
        )


def test_autoscale_ps_spawns_workers_and_completes(tmp_path):
    """Elastic membership e2e (DESIGN.md §15): ONE launched process (the
    PS, --autoscale) owns its worker fleet. All workers carry a 400 ms
    sleep per gradient, so the aggregate fresh-frame rate genuinely
    scales with the worker count even on the 1-core box; the target rate
    is set above what the initial pair can deliver, so the controller
    must spawn reserve ranks (launched with the PS's own CLI re-targeted
    at worker:K) mid-run. The run completes, the summary carries the
    schema-v6 autoscale digest, and every spawned worker is reaped."""
    n_w, n_iter = 4, 120
    cfg_path, env = _cluster_setup(tmp_path, n_w)
    tele = str(tmp_path / "tele")
    ps = _launch(
        "ps:0", cfg_path, env,
        extra=(
            "--fw", "0", "--async", "--max_staleness", "8",
            "--num_iter", str(n_iter), "--straggler_ms", "400",
            "--autoscale", "--autoscale_min", "2", "--target_rate", "20",
            "--autoscale_window", "6", "--autoscale_cooldown", "4",
            "--telemetry", tele,
        ),
    )
    try:
        out, _ = ps.communicate(timeout=600)
        assert ps.returncode == 0, f"PS failed:\n{out[-3000:]}"
        summary = _summary(out)
        assert summary["steps"] == n_iter
        with open(os.path.join(
            tele, "cluster-ps.telemetry.jsonl"
        )) as fp:
            recs = [json.loads(l) for l in fp]
        summ = [r for r in recs if r["kind"] == "summary"][-1]
        autos = summ["autoscale"]
        assert autos is not None and autos["spawns"] >= 1, summ
        assert autos["active_workers"] > 2, summ
        events = [
            r for r in recs
            if r["kind"] == "event" and r.get("event") == "autoscale"
        ]
        assert events and all(
            e["action"] in ("spawn", "retire") for e in events
        )
        # The PS spawned its own initial workers too: their logs landed
        # in the telemetry dir (the _AutoscalePlane log sink).
        logs = [f for f in os.listdir(tele) if f.startswith("worker_")]
        assert len(logs) >= 3, logs
    finally:
        if ps.poll() is None:
            ps.kill()
