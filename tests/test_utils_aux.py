"""Tests for aux subsystems: multihost config/faults, profiling accounting,
checkpointing, and the microbenchmark harnesses (SURVEY §5 parity)."""

import json
import os

import numpy as np
import pytest

from garfield_tpu.utils import checkpoint, multihost, profiling


def test_cluster_config_roundtrip(tmp_path):
    path = tmp_path / "cluster.json"
    multihost.generate_config(
        path, workers=["h1:2222", "h2:2222"], ps=["h0:2222"],
        task_type="worker", task_index=1, gar="krum", fw=1,
    )
    cfg = multihost.ClusterConfig(path)
    assert cfg.hosts == ["h0:2222", "h1:2222", "h2:2222"]
    assert cfg.coordinator == "h0:2222"
    assert cfg.num_processes == 3
    # ps ranks come first (reference convention, trainer.py:217)
    assert cfg.process_id == 2
    assert cfg.garfield == {"gar": "krum", "fw": 1}


def test_cluster_config_from_env_inline(monkeypatch):
    spec = {"cluster": {"worker": ["a:1", "b:1"]},
            "task": {"type": "worker", "index": 0}}
    monkeypatch.setenv("GARFIELD_CONFIG", json.dumps(spec))
    cfg = multihost.ClusterConfig.from_env()
    assert cfg.process_id == 0 and cfg.num_processes == 2


def test_init_distributed_single_process_noop():
    assert multihost.init_distributed(config=None) == (1, 0)


def test_fault_schedule_crash_and_straggler():
    sched = multihost.FaultSchedule(
        4, crashes={2: 10}, stragglers={1: 1.0}, seed=7
    )
    # Before the crash step host 2 is alive.
    assert not sched.byz_mask(5, 8).any()
    m = sched.byz_mask(10, 8)
    assert m.tolist() == [False] * 4 + [True, True] + [False] * 2
    # Straggler host 1 always suspected: q = n-1, floored at n-f.
    assert sched.subset(3, 8, f=2) == 7
    assert sched.subset(3, 8, f=0) == 8
    # Replayable.
    assert sched.subset(3, 8, 2) == sched.subset(3, 8, 2)


def test_collective_bytes_topologies():
    kw = dict(num_workers=8, d=1000, bytes_per_el=4)
    assert profiling.collective_bytes("centralized", **kw) == 0
    agg = profiling.collective_bytes("aggregathor", **kw)
    assert agg == int(8 * 1000 * 4 * 7 / 8)
    byz = profiling.collective_bytes("byzsgd", num_ps=3, **kw)
    assert byz > agg
    # One device: no inter-chip traffic at all.
    assert profiling.collective_bytes("aggregathor", axis_size=1, **kw) == 0


def test_step_timer():
    t = profiling.StepTimer()
    with t.step():
        pass
    s = t.summary()
    assert s["count"] == 1 and s["total_s"] >= 0


def test_checkpointer_pickle_fallback(tmp_path, monkeypatch):
    monkeypatch.setattr(checkpoint, "_HAVE_ORBAX", False)
    ck = checkpoint.Checkpointer(tmp_path / "ck", max_to_keep=2)
    state = {"w": np.arange(3.0), "step": np.int32(5)}
    for s in (1, 2, 3):
        ck.save(s, state)
    assert ck.latest_step() == 3
    assert ck._pickle_steps() == [2, 3]  # bounded history
    out = ck.restore(state)
    np.testing.assert_array_equal(out["w"], state["w"])


def test_evalset_matches_list_path():
    """parallel.EvalSet (one scanned program) must count exactly like the
    per-batch list path — uniform batches, a ragged tail, and the binary
    threshold path."""
    import jax
    import jax.numpy as jnp

    from garfield_tpu import parallel

    rng = np.random.default_rng(0)

    def eval_fn(state, x):
        return jnp.asarray(x) @ state  # logits = x @ W

    # Multiclass with a ragged tail batch (like pima's 100+68 test split).
    state = jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
    batches = [
        (rng.standard_normal((4, 5)).astype(np.float32),
         rng.integers(0, 3, 4))
        for _ in range(3)
    ] + [(rng.standard_normal((2, 5)).astype(np.float32),
          rng.integers(0, 3, 2))]
    want = parallel.compute_accuracy(state, eval_fn, batches)
    got = parallel.compute_accuracy(
        state, eval_fn, parallel.EvalSet(batches)
    )
    assert got == want

    # Binary path: single sigmoid-like output, labels (n, 1) float.
    bstate = jnp.asarray(rng.standard_normal((5, 1)), jnp.float32)

    def beval(state, x):
        return jax.nn.sigmoid(jnp.asarray(x) @ state)

    bbatches = [
        (rng.standard_normal((4, 5)).astype(np.float32),
         rng.integers(0, 2, (4, 1)).astype(np.float32))
        for _ in range(2)
    ]
    want_b = parallel.compute_accuracy(bstate, beval, bbatches, binary=True)
    got_b = parallel.compute_accuracy(
        bstate, beval, parallel.EvalSet(bbatches, binary=True)
    )
    assert got_b == want_b

    # ADVICE r2: empty test_batches must raise a clear error, not an
    # opaque jnp.stack failure.
    with pytest.raises(ValueError, match="at least one test batch"):
        parallel.EvalSet([])


def test_multihost_config_cli(tmp_path):
    """Flag-driven config generator writes one valid per-task JSON per host
    (reference config_generator.py parity)."""
    from garfield_tpu.utils import multihost

    files = multihost._cli([
        str(tmp_path), "--workers", "h1:9901", "h2:9901", "h3:9901",
        "--ps", "h0:9901", "--gar", "krum", "--fw", "1", "--attack", "lie",
    ])
    assert len(files) == 4
    for i, f in enumerate(files):
        cfg = multihost.ClusterConfig(f)
        assert cfg.num_processes == 4
        assert cfg.coordinator == "h0:9901"
        assert cfg.garfield["gar"] == "krum"
        assert cfg.process_id == i  # ps first, then workers, stable order


def test_multihost_config_cli_validation(tmp_path):
    from garfield_tpu.utils import multihost

    with pytest.raises(SystemExit):  # no workers
        multihost._cli([str(tmp_path), "--workers"])
    with pytest.raises(SystemExit):  # fw budget too big
        multihost._cli([str(tmp_path), "--workers", "h1", "h2", "--fw", "1"])
    with pytest.raises(SystemExit):  # fps without ps hosts
        multihost._cli([str(tmp_path), "--workers", "h1", "h2", "h3",
                        "--fps", "1"])
