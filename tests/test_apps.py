"""End-to-end CLI tests for the application layer (SURVEY §4: replaces the
reference's run-it-and-see with real integration tests; the LEARN demo's
multi-process-on-localhost harness, demo.py:264-320, becomes plain function
calls on the virtual 8-device mesh from conftest).

The full-training smokes are ``slow``-marked (same tier convention as
test_cluster/test_demo): each is a ~1-minute CPU training run, and a dozen
of them blow the tier-1 wall-clock budget on a 1-core container while
re-covering flows the unit files (test_parallel, test_fold,
test_entry_resilience) already pin piecewise. Tier-1 keeps the
checkpoint/resume roundtrip and the cheap validation tests; run the whole
file without ``-m 'not slow'`` for the full sweep."""

import json
import os

import pytest

from garfield_tpu.apps import (
    aggregathor as app_aggregathor,
    byzsgd as app_byzsgd,
    centralized as app_centralized,
    garfield_cc as app_garfield_cc,
    learn as app_learn,
)

FAST = [
    "--dataset", "mnist", "--model", "convnet", "--loss", "nll",
    "--batch", "8", "--num_iter", "3", "--train_size", "256",
    "--acc_freq", "2",
]


@pytest.mark.slow
def test_centralized_runs():
    state, summary = app_centralized.main(FAST)
    assert summary["final_accuracy"] >= 0.0
    assert int(state.step) == 3


@pytest.mark.slow
def test_aggregathor_krum_lie():
    state, summary = app_aggregathor.main(
        FAST + ["--num_workers", "8", "--fw", "2", "--gar", "krum",
                "--attack", "lie"]
    )
    assert int(state.step) == 3


@pytest.mark.slow
def test_async_eval_matches_sync(capsys):
    """Overlapped accuracy (the default, mirroring the reference's side
    thread at Aggregathor/trainer.py:251-264) must report the same values
    as the inline --sync_eval path, and all reports must flush before the
    summary line."""
    flags = FAST + ["--num_workers", "8", "--gar", "average"]
    outs = []
    for mode in ([], ["--sync_eval"]):
        app_aggregathor.main(flags + mode)
        lines = capsys.readouterr().out.splitlines()
        # Strip the wall-clock suffix: only epoch + accuracy must match.
        accs = [l.split(" Time:")[0] for l in lines if l.startswith("Epoch:")]
        summary_idx = max(
            i for i, l in enumerate(lines) if l.startswith("Epoch:")
        )
        assert any(l.startswith('{"tag"') for l in lines[summary_idx:])
        outs.append(accs)
    assert outs[0] == outs[1]
    assert len(outs[0]) >= 2  # acc_freq=2 over 3 iters -> evals at 0 and 2


@pytest.mark.slow
def test_aggregathor_subset_and_layer_granularity():
    _, summary = app_aggregathor.main(
        FAST + ["--num_workers", "8", "--fw", "1", "--gar", "median",
                "--subset", "6", "--granularity", "layer"]
    )
    assert summary["final_loss"] is not None


@pytest.mark.slow
def test_byzsgd_with_byz_ps():
    state, _ = app_byzsgd.main(
        FAST + ["--num_workers", "8", "--num_ps", "4", "--fw", "1",
                "--fps", "1", "--gar", "median", "--attack", "reverse",
                "--ps_attack", "random", "--mesh", "ps=2,workers=4"]
    )
    assert int(state.step) == 3


@pytest.mark.slow
def test_learn_non_iid():
    state, _ = app_learn.main(
        FAST + ["--num_workers", "8", "--fw", "1", "--gar", "median",
                "--non_iid"]
    )
    assert int(state.step) == 3


@pytest.mark.slow
def test_pima_ragged_test_set_evalset():
    """pima's 168-sample test set batches into (100, 68) — EvalSet must
    handle the ragged tail the app loop now always wraps (regression: the
    first EvalSet stacked blindly and died at startup on pima)."""
    state, summary = app_learn.main([
        "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
        "--batch", "16", "--num_iter", "3", "--acc_freq", "2",
        "--num_workers", "8", "--fw", "1", "--gar", "median",
    ])
    assert int(state.step) == 3
    assert 0.0 <= summary["final_accuracy"] <= 1.0


@pytest.mark.slow
def test_garfield_cc_modes():
    for mode in ("vanilla", "aggregathor"):
        _, summary = app_garfield_cc.main(
            FAST + ["--mode", mode, "--num_workers", "8", "--fw", "1",
                    "--gar", "median"]
        )
        assert summary["final_loss"] is not None


@pytest.mark.slow
def test_garfield_cc_guanyu_layer_granularity():
    state, summary = app_garfield_cc.main(
        FAST + ["--mode", "guanyu", "--num_workers", "4", "--num_ps", "2",
                "--fw", "1", "--fps", "0", "--gar", "median",
                "--mesh", "ps=2,workers=4"]
    )
    assert int(state.step) == 3 and summary["final_loss"] is not None


# Two full app runs + a resume — the single heaviest test in the suite;
# off the tier-1 fast shard for wall-time budget. Resume semantics stay
# tier-1-covered by test_federated's TestFailoverDeterminism.
@pytest.mark.slow
def test_checkpoint_resume(tmp_path):
    ckpt_args = FAST + [
        "--num_workers", "8", "--gar", "average",
        "--checkpoint_dir", str(tmp_path / "ckpt"), "--checkpoint_freq", "2",
    ]
    state1, _ = app_aggregathor.main(ckpt_args)
    # Resume continues from the persisted step, not from scratch.
    state2, _ = app_aggregathor.main(
        [a if a != "3" else "5" for a in ckpt_args] + ["--resume"]
    )
    assert int(state2.step) == 5


@pytest.mark.slow
def test_fault_crash_schedule():
    """--fault_crashes: host 3 dies at step 2; the run re-jits the step with
    that slot as a zero-gradient Byzantine row and still converges on the
    remaining honest workers (SURVEY §5 failure simulation; the reference's
    mar='crash', Garfield_CC/trainer.py:97,137)."""
    state, summary = app_aggregathor.main(
        FAST + ["--num_workers", "8", "--fw", "2", "--gar", "median",
                "--num_iter", "5",
                "--fault_crashes", json.dumps({"3": 2})]
    )
    assert int(state.step) == 5
    assert summary["final_loss"] is not None
    import numpy as np

    assert np.isfinite(summary["final_loss"])


def test_fault_crashes_rejects_attack_combo():
    with pytest.raises(SystemExit):
        app_aggregathor.main(
            FAST + ["--num_workers", "8", "--fw", "2", "--gar", "median",
                    "--attack", "lie",
                    "--fault_crashes", json.dumps({"0": 1})]
        )


def test_fault_crashes_validates_budget_and_layout():
    base = FAST + ["--num_workers", "8", "--gar", "median", "--num_iter", "5"]
    with pytest.raises(SystemExit):  # 3 dead slots > fw=2
        app_aggregathor.main(
            base + ["--fw", "2",
                    "--fault_crashes", json.dumps({"0": 0, "1": 0, "2": 0})]
        )
    with pytest.raises(SystemExit):  # hosts don't divide slots
        app_aggregathor.main(
            base + ["--fw", "2", "--fault_hosts", "3",
                    "--fault_crashes", json.dumps({"0": 0})]
        )
    with pytest.raises(SystemExit):  # host id out of range
        app_aggregathor.main(
            base + ["--fw", "2", "--fault_crashes", json.dumps({"9": 0})]
        )


@pytest.mark.slow
def test_fault_crash_learn_model_gossip():
    """In LEARN, a crashed node must not gossip its (honest) model either:
    the fault wiring sets the model-space crash attack alongside the
    gradient one."""
    state, summary = app_learn.main(
        FAST + ["--num_workers", "8", "--fw", "2", "--gar", "median",
                "--num_iter", "4",
                "--fault_crashes", json.dumps({"2": 1})]
    )
    assert int(state.step) == 4
    import numpy as np

    assert np.isfinite(summary["final_loss"])


# Cheap end-to-end config for the chunked-loop tests: pimanet compiles in
# seconds where the mnist convnet costs ~1 min/run on the 1-core container.
PIMA_FAST = [
    "--dataset", "pima", "--model", "pimanet", "--loss", "bce",
    "--batch", "8", "--acc_freq", "3", "--num_workers", "8",
    "--gar", "median",
]


def _params_equal(a, b):
    import jax
    import numpy as np

    for la, lb in zip(
        jax.tree.leaves(jax.device_get(a.params)),
        jax.tree.leaves(jax.device_get(b.params)),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_chunked_checkpoint_resume_matches_unchunked(tmp_path):
    """Mid-chunk checkpoint/resume: --chunk_steps 3 with a non-aligned
    checkpoint cadence 2 clips chunks at every save, a 'killed' run
    (shorter --num_iter) resumes from the persisted step, and the final
    params are bitwise the unchunked full run's."""
    ref, _ = app_aggregathor.main(PIMA_FAST + ["--num_iter", "5"])
    ck = ["--checkpoint_dir", str(tmp_path / "ck"), "--checkpoint_freq",
          "2", "--chunk_steps", "3"]
    killed, _ = app_aggregathor.main(PIMA_FAST + ["--num_iter", "3"] + ck)
    assert int(killed.step) == 3
    resumed, _ = app_aggregathor.main(
        PIMA_FAST + ["--num_iter", "5", "--resume"] + ck
    )
    assert int(resumed.step) == 5
    _params_equal(ref, resumed)


def test_chunked_telemetry_fans_out_per_step_records(tmp_path):
    """K steps per dispatch must still land K per-step records in the
    hub: the JSONL has one 'step' record per training step, in order,
    and the artifact validates against the schema."""
    tel = str(tmp_path / "tel")
    app_aggregathor.main(
        PIMA_FAST + ["--num_iter", "5", "--chunk_steps", "4",
                     "--attack", "lie", "--fw", "2", "--gar", "krum",
                     "--telemetry", tel]
    )
    from garfield_tpu.telemetry.exporters import validate_jsonl

    path = os.path.join(tel, "telemetry.jsonl")
    assert validate_jsonl(path) >= 7  # run + 5 steps + summary
    recs = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in recs if r["kind"] == "step"] == list(range(5))


def test_resume_build_gets_remaining_num_iter(tmp_path, monkeypatch):
    """The run-length hint (core.slot_path_decision's unroll-amortization
    input) must be the REMAINING steps on a resumed/re-jit build, not the
    original total — a resumed program only serves what is left."""
    import functools

    from garfield_tpu.parallel import aggregathor as topo

    seen = []
    real = topo.make_trainer

    @functools.wraps(real)
    def spy(*a, **kw):
        seen.append(kw.get("num_iter"))
        return real(*a, **kw)

    monkeypatch.setattr(topo, "make_trainer", spy)
    ck = ["--checkpoint_dir", str(tmp_path / "ck"), "--checkpoint_freq", "2"]
    app_aggregathor.main(PIMA_FAST + ["--num_iter", "2"] + ck)
    assert seen == [2]
    seen.clear()
    app_aggregathor.main(
        PIMA_FAST + ["--num_iter", "6", "--resume", "--chunk_steps", "2"]
        + ck
    )
    assert seen == [4]  # 6 total - 2 already served


@pytest.mark.slow
def test_chunked_crash_boundary_matches_unchunked():
    """A --fault_crashes event must clip the chunk and re-jit exactly as
    the per-step loop does: the chunked trajectory across the crash is
    bitwise the unchunked one."""
    flags = PIMA_FAST + ["--fw", "2", "--num_iter", "5",
                         "--fault_crashes", json.dumps({"3": 2})]
    ref, _ = app_aggregathor.main(flags)
    chunked, _ = app_aggregathor.main(flags + ["--chunk_steps", "4"])
    assert int(chunked.step) == 5
    _params_equal(ref, chunked)


@pytest.mark.slow
def test_chunked_checkpoint_resume_full_variant(tmp_path):
    """The issue-spec numbers on the real smoke config: convnet/mnist,
    --chunk_steps 4 against checkpoint cadence 6 (non-aligned), killed
    mid-stride at step 7 and resumed to 8 — final params bitwise equal to
    the unchunked straight-through run."""
    common = FAST + ["--num_workers", "8", "--gar", "median"]
    base = common + ["--num_iter", "8"]  # last --num_iter wins
    ref, _ = app_aggregathor.main(base)
    ck = ["--checkpoint_dir", str(tmp_path / "ck"), "--checkpoint_freq",
          "6", "--chunk_steps", "4"]
    killed, _ = app_aggregathor.main(common + ["--num_iter", "7"] + ck)
    assert int(killed.step) == 7
    resumed, _ = app_aggregathor.main(base + ["--resume"] + ck)
    assert int(resumed.step) == 8
    _params_equal(ref, resumed)


def test_cluster_host_attack_cohort_math():
    """The cluster attacker's lie/empire statistics must match the
    reference formulas (byzWorker.py:108-143) on a known cohort stack."""
    import numpy as np

    from garfield_tpu.apps.cluster import _host_attack

    stack = np.asarray(
        [[1.0, 2.0, 3.0], [3.0, 6.0, 1.0]], dtype=np.float32
    )
    kind, fn, cohort = _host_attack("lie", {}, fw=2)
    assert (kind, cohort) == ("cohort", 2)
    mu = stack.mean(0)
    sigma = stack.std(0, ddof=1)
    np.testing.assert_allclose(fn(stack), mu + 1.035 * sigma, rtol=1e-6)

    kind, fn, cohort = _host_attack("empire", {"eps": 4.0, "cohort": 3}, fw=2)
    assert (kind, cohort) == ("cohort", 3)
    np.testing.assert_allclose(fn(stack), -4.0 * mu, rtol=1e-6)

    # fw=1 cohort: Bessel sigma is NaN, like torch.std of one sample.
    kind, fn, cohort = _host_attack("lie", {}, fw=1)
    out = fn(stack[:1])
    assert np.isnan(out).all()

    kind, fn, _ = _host_attack("reverse", {}, fw=1)
    assert kind == "post"
    np.testing.assert_allclose(fn(stack[0]), -100.0 * stack[0])

    with pytest.raises(SystemExit):
        _host_attack("unknown-attack", {}, fw=1)
