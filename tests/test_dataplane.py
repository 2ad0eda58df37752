"""Data-plane defense (aggregators/dataplane.py, DESIGN.md §18).

Unit coverage of the fingerprint construction and both detectors
(dual-backend agreement, cohort sensitivity, clean-history identity),
the host ``DataPlaneDefense`` EMA/weight law, the in-graph deployment on
the SSMW step (backdoor cohort down-weighted; dp EMA rides the chunk
carry bitwise), the schema-v9 telemetry plumbing — and the PR-11 bitwise
pin: with the data-plane defense OFF, trajectories (defense off AND
GAR-defense-only) are bit-identical to the fixture captured before this
subsystem existed (tests/fixtures/dataplane_pin.json).
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu import models
from garfield_tpu.aggregators import dataplane as dp, defense as defense_lib
from garfield_tpu.parallel import aggregathor, core
from garfield_tpu.utils import selectors

_FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "dataplane_pin.json",
)

N, C, FH = 16, 1, 64


def _setup():
    module = models.select_model("pimanet", "pima")
    loss = selectors.select_loss("bce")
    opt = selectors.select_optimizer("sgd", lr=0.05, momentum=0.0)
    return module, loss, opt


def _batch_stack(seed=0, bsz=16, nb=3, slots=16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(slots, nb, bsz, 8)).astype(np.float32)
    y = (x.sum(-1, keepdims=True) > 0).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _cohort_heads(seed=0, n=N, f=3, coherent=True):
    """Synthetic head gradients: honest crowd around one direction, a
    Byzantine cohort coherently elsewhere with a shifted bias."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(C, FH)).astype(np.float32)
    H = base[None] + 0.3 * rng.normal(size=(n, C, FH)).astype(np.float32)
    b = 0.3 * rng.normal(size=(n, C)).astype(np.float32)
    coh = rng.normal(size=(C, FH)).astype(np.float32)
    for i in range(n - f, n):
        jitter = 0.05 if coherent else 0.8
        H[i] = -0.8 * base + coh + jitter * rng.normal(
            size=(C, FH)
        ).astype(np.float32)
        b[i] = -2.0 + 0.05 * rng.normal(size=(C,)).astype(np.float32)
    return H, b


# --- fingerprints + detectors ------------------------------------------------


def test_head_spec_and_extraction_agree():
    """``head_spec`` + ``head_from_rows`` (the host wire path) must
    extract exactly what ``head_leaves`` reads off the stacked tree (the
    in-graph path) — the two deployments share one definition of 'the
    classifier head'."""
    module, loss, _ = _setup()
    init_fn, _, _ = core.make_worker_fns(module, loss)
    params, _ = init_fn(jax.random.PRNGKey(0), np.zeros((4, 8), np.float32))
    spec = dp.head_spec(params)
    assert spec is not None
    assert spec.classes == 1 and spec.feat == 64
    assert spec.bias is not None
    # A stacked "gradient" tree: n copies of params scaled per rank.
    stacked = jax.tree.map(
        lambda l: jnp.stack([l * (i + 1) for i in range(4)]), params
    )
    k_tree, b_tree = dp.head_leaves(stacked)
    assert k_tree.shape == (4, 1, 64) and b_tree.shape == (4, 1)
    rows = core.flatten_rows(stacked)
    k_rows, b_rows = dp.head_from_rows(spec, np.asarray(rows))
    np.testing.assert_allclose(np.asarray(k_tree), k_rows, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b_tree), b_rows, rtol=1e-6)


def test_transformer_head_resolution_vit():
    """ViT's head must resolve to the top-level Dense, NOT the
    ``pos_embedding`` table — flax flattens by sorted string key, so the
    lowercase positional param lands AFTER every capitalized module
    scope and the legacy "last 2-D leaf" rule would fingerprint it."""
    from garfield_tpu.models import transformer

    vit = transformer.ViT(dim=24, depth=2, heads=2, mlp_dim=48)
    p = vit.init(
        jax.random.PRNGKey(0), np.zeros((2, 16, 16, 3), np.float32)
    )["params"]
    spec = dp.head_spec(p)
    assert spec.feat == 24 and spec.classes == 10
    assert spec.bias is not None
    stacked = jax.tree.map(lambda l: jnp.stack([l, 2.0 * l]), p)
    k_tree, b_tree = dp.head_leaves(stacked)
    assert k_tree.shape == (2, 10, 24) and b_tree.shape == (2, 10)
    # Identity to the actual head params (class-major transpose), and
    # wire-path agreement with the in-graph extraction.
    np.testing.assert_allclose(
        np.asarray(k_tree[0]), np.asarray(p["Dense_0"]["kernel"]).T,
        rtol=1e-6,
    )
    rows = core.flatten_rows(stacked)
    k_rows, b_rows = dp.head_from_rows(spec, np.asarray(rows))
    np.testing.assert_allclose(np.asarray(k_tree), k_rows, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b_tree), b_rows, rtol=1e-6)


def test_transformer_head_resolution_gpt_untied():
    """Untied GPT: the top-level Dense head wins over both the nested
    ``EncoderBlock_*`` MLP kernels and the ``nn.Embed`` table."""
    from garfield_tpu.models import transformer

    gpt = transformer.GPT(vocab=16, dim=16, depth=1, heads=2, mlp_dim=32)
    p = gpt.init(
        jax.random.PRNGKey(0), np.zeros((2, 6), np.int32)
    )["params"]
    spec = dp.head_spec(p)
    assert spec.feat == 16 and spec.classes == 10
    assert spec.bias is not None
    stacked = jax.tree.map(lambda l: jnp.stack([l, -l]), p)
    k_tree, _ = dp.head_leaves(stacked)
    np.testing.assert_allclose(
        np.asarray(k_tree[0]), np.asarray(p["Dense_0"]["kernel"]).T,
        rtol=1e-6,
    )


def test_tied_gpt_head_refuses_loudly():
    """GPT(tied=True) has NO head distinct from the embedding gradient:
    both the host and the in-graph resolvers must refuse with a clear
    error instead of silently fingerprinting an interior MLP kernel."""
    from garfield_tpu.models import transformer

    gpt = transformer.GPT(
        vocab=16, dim=16, depth=1, heads=2, mlp_dim=32, tied=True
    )
    p = gpt.init(
        jax.random.PRNGKey(0), np.zeros((2, 6), np.int32)
    )["params"]
    with pytest.raises(ValueError, match="embedding-tied"):
        dp.head_spec(p)
    stacked = jax.tree.map(lambda l: jnp.stack([l, l]), p)
    with pytest.raises(ValueError, match="embedding-tied"):
        dp.head_leaves(stacked)


def test_suspect_class_robust_to_small_cohort():
    """At f/n = 1/4 a coherent cohort caps its own mean/std z at
    ~sqrt((n-f)/f) = 1.73 (it corrupts the mean and inflates the std of
    the class it attacks), so one noisy honest rank in a quiet class
    outscored the true target and steered the 2-means at clean rows.
    The median/MAD statistic must keep pointing at the target class."""
    rng = np.random.default_rng(0)
    kern = rng.normal(size=(8, 10, 4)).astype(np.float32)
    b = 0.05 * rng.normal(size=(8, 10)).astype(np.float32)
    b[6:, 3] = -0.9  # coherent 2-of-8 cohort on the target class
    b[1, 7] = 0.4  # one loud honest rank elsewhere
    assert int(dp.suspect_class(kern, b)) == 3
    assert int(dp.suspect_class(jnp.asarray(kern), jnp.asarray(b))) == 3


def test_detect_flags_small_cohort():
    """2-of-8 coherent target-class cohort — the realistic fine-tuning
    quorum shape the spectral tail alone cannot reach (its score is
    rms-normalized by a crowd the cohort itself inflates, bounded by
    sqrt(n/f) = 2.0 = tau): the cluster path must carry it."""
    rng = np.random.default_rng(1)
    H = 0.1 * rng.normal(size=(8, 10, 16)).astype(np.float32)
    b = 0.05 * rng.normal(size=(8, 10)).astype(np.float32)
    coh = rng.normal(size=(16,)).astype(np.float32)
    for i in (6, 7):
        H[i, 3] = 4.0 * coh + 0.02 * rng.normal(size=(16,))
        b[i, 3] = -0.9
    _, flags = dp.detect(H, b, f=2)
    assert flags[6:].all(), f"cohort not flagged: {flags}"
    assert not flags[:6].any(), f"honest ranks flagged: {flags}"


def test_detectors_flag_coherent_cohort_not_clean():
    H, b = _cohort_heads(seed=0, f=3)
    scores, flags = dp.detect(H, b, f=3)
    assert flags[-3:].all(), f"cohort not flagged: {flags}"
    assert not flags[:-3].any(), f"honest ranks flagged: {flags}"
    # Clean crowd: no flags (the detector identity the clean-accuracy
    # delta bar rests on).
    rng = np.random.default_rng(7)
    base = rng.normal(size=(C, FH)).astype(np.float32)
    H2 = base[None] + 0.3 * rng.normal(size=(N, C, FH)).astype(np.float32)
    b2 = 0.3 * rng.normal(size=(N, C)).astype(np.float32)
    _, flags2 = dp.detect(H2, b2, f=3)
    assert not flags2.any()


def test_detect_dual_backend_agrees():
    H, b = _cohort_heads(seed=3, f=3)
    s_np, f_np = dp.detect(H, b, f=3)
    s_j, f_j = dp.detect(jnp.asarray(H), jnp.asarray(b), f=3)
    np.testing.assert_allclose(np.asarray(s_j), s_np, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(f_j), f_np)


def test_cluster_flags_respect_f_budget_and_separation():
    rng = np.random.default_rng(1)
    # Tight cohort of 3 within f=3: flagged.
    rows = rng.normal(size=(12, FH)).astype(np.float32)
    rows[-3:] = rows[-1] + 0.01 * rng.normal(size=(3, FH)).astype(
        np.float32
    ) + 5.0
    flags = dp.cluster_flags(rows, f=3)
    assert flags[-3:].all() and not flags[:-3].any()
    # Same cohort, declared budget f=2: a 3-member cluster is larger
    # than the budget — NOT a cohort verdict.
    assert not dp.cluster_flags(rows, f=2).any()
    # No separation (one Gaussian blob): nothing flagged.
    blob = rng.normal(size=(12, FH)).astype(np.float32)
    assert not dp.cluster_flags(blob, f=3).any()


def test_fingerprints_scale_free():
    """Uniformly rescaling every rank's head gradient leaves the
    fingerprints unchanged up to float noise (the data plane keys on
    per-class structure, not magnitude — magnitude is the GAR's job)."""
    H, b = _cohort_heads(seed=5)
    f1 = dp.fingerprints(H, b)
    f2 = dp.fingerprints(10.0 * H, 10.0 * b)
    np.testing.assert_allclose(f1, f2, atol=1e-4)


# --- host DataPlaneDefense ---------------------------------------------------


def _spec_for_heads():
    """A HeadSpec over rows laid out as [bias | kernel] flat."""
    return dp.HeadSpec(
        kernel=(C, C + C * FH), bias=(0, C), feat=FH, classes=C
    )


def _flat_rows(H, b):
    n = H.shape[0]
    return np.concatenate(
        [b.reshape(n, -1),
         np.swapaxes(H, 1, 2).reshape(n, -1)], axis=1
    ).astype(np.float32)


def test_dataplane_defense_ema_and_weights():
    pdef = dp.DataPlaneDefense(
        N, _spec_for_heads(), f=3, halflife=4.0, floor=0.1
    )
    # Clean history: weights exactly 1.0 -> weights_for returns None
    # (the unweighted-program identity).
    rng = np.random.default_rng(2)
    base = rng.normal(size=(C, FH)).astype(np.float32)
    Hc = base[None] + 0.3 * rng.normal(size=(N, C, FH)).astype(np.float32)
    bc = 0.3 * rng.normal(size=(N, C)).astype(np.float32)
    for _ in range(3):
        pdef.observe(np.arange(N), _flat_rows(Hc, bc))
    assert pdef.weights_for(np.arange(N)) is None
    # Cohort rounds: the flagged ranks' EMA suspicion drives their
    # weights to the floor; honest ranks stay at ~1.
    H, b = _cohort_heads(seed=11, f=3)
    for _ in range(12):
        pdef.observe(np.arange(N), _flat_rows(H, b))
    w = pdef.weights_full()
    assert (w[-3:] <= 0.11).all(), w
    assert (w[:-3] >= 0.9).all(), w
    stats = pdef.stats()
    assert stats["rounds"] == 15 and stats["flagged"] >= 30
    assert stats["min_w"] <= 0.11


def test_dataplane_defense_small_quorum_skips():
    pdef = dp.DataPlaneDefense(N, _spec_for_heads(), f=3)
    rep = pdef.observe([0, 1, 2], np.zeros((3, C + C * FH), np.float32))
    assert not rep["flags"].any() and (rep["scores"] == 0).all()


# --- in-graph deployment -----------------------------------------------------


def _data_trainer(defense, attack="backdoor"):
    module, loss, opt = _setup()
    return aggregathor.make_trainer(
        module, loss, opt, "krum", num_workers=16, f=3,
        attack=attack, attack_params={"source": 0, "target": 1},
        defense=defense,
    )


def test_ingraph_data_defense_downweights_backdoor_cohort():
    """The tentpole contract, on-mesh: under a backdoor cohort the dp
    weights pin the Byzantine slots at the floor within the EMA window
    while honest slots keep ~1.0 — the evidence the GAR-side suspicion
    plane structurally cannot produce (the cell round 15 left open)."""
    init_fn, step_fn, _ = _data_trainer(
        {"weighted": False,
         "data": {"tau": 2.0, "floor": 0.1, "halflife": 8.0}}
    )
    xs, ys = _batch_stack()
    state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
    for i in range(30):
        b = i % 3
        state, m = step_fn(state, xs[:, b], ys[:, b])
    w = np.asarray(m["dataplane_w"])
    assert (w[-3:] <= 0.2).all(), w
    assert (w[:-3] >= 0.8).all(), w
    flags = np.asarray(m["dataplane_flags"])
    assert flags[-3:].sum() >= 2, flags
    scores = np.asarray(m["dataplane_score"])
    assert scores.shape == (16,) and np.isfinite(scores).all()


def test_ingraph_data_defense_chunked_bitwise():
    """The dp EMA twins ride TrainState.defense_state: a chunked scan
    must carry them bitwise like every other state leaf."""
    init_fn, step_fn, _ = _data_trainer(
        {"weighted": False, "data": {"halflife": 8.0}}
    )
    xs, ys = _batch_stack()
    state0 = init_fn(jax.random.PRNGKey(0), xs[0, 0])
    ref, ref_m = state0, []
    for i in range(6):
        ref, m = step_fn(ref, xs[:, i % 3], ys[:, i % 3])
        ref_m.append(jax.device_get(m))
    chunked = core.make_chunked_step(step_fn, 3, 3)
    got, got_m = state0, []
    for i in range(0, 6, 3):
        got, m = chunked(got, xs, ys, np.int32(i))
        got_m.append(jax.device_get(m))
    for a, bb in zip(jax.tree.leaves(jax.device_get(ref)),
                     jax.tree.leaves(jax.device_get(got))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
    ref_stack = jax.tree.map(lambda *ls: np.stack(ls), *ref_m)
    got_stack = jax.tree.map(lambda *ls: np.concatenate(ls), *got_m)
    for a, bb in zip(jax.tree.leaves(ref_stack), jax.tree.leaves(got_stack)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_ingraph_data_composes_with_gar_defense():
    """escalate+data's in-graph half: GAR-suspicion weighting AND the
    data detectors in one step program, both weight vectors surfaced."""
    init_fn, step_fn, _ = _data_trainer(
        {"halflife": 16.0, "data": {"halflife": 8.0}}
    )
    xs, ys = _batch_stack()
    state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
    for i in range(4):
        state, m = step_fn(state, xs[:, i % 3], ys[:, i % 3])
    assert "defense_w" in m and "dataplane_w" in m
    assert np.asarray(m["defense_w"]).shape == (16,)
    assert np.asarray(m["dataplane_w"]).shape == (16,)


def test_defense_validation():
    with pytest.raises(ValueError, match="neither"):
        _data_trainer({"weighted": False})
    with pytest.raises(ValueError, match="unknown defense.data"):
        _data_trainer({"data": {"bogus": 1}})
    with pytest.raises(ValueError, match="tau"):
        _data_trainer({"data": {"tau": -1.0}})


# --- the PR-11 bitwise pin ---------------------------------------------------


def test_dataplane_off_trajectories_bitwise_pinned():
    """Defense-off and GAR-defense-only trajectories must stay BIT-
    identical to the fixture captured at PR 11, before the data plane
    existed: nothing dataplane-shaped may be traced when it is off."""
    fixture = json.load(open(_FIXTURE))
    module, loss, opt = _setup()
    cases = {
        "backdoor-off": ("backdoor", None),
        "labelflip-off": ("labelflip", None),
        "backdoor-gardef": ("backdoor", {"halflife": 16.0}),
    }
    for name, (attack, defense) in cases.items():
        init_fn, step_fn, _ = aggregathor.make_trainer(
            module, loss, opt, "krum", num_workers=16, f=3,
            attack=attack, attack_params={"source": 0, "target": 1},
            defense=defense,
        )
        xs, ys = _batch_stack()
        state = init_fn(jax.random.PRNGKey(0), xs[0, 0])
        losses = []
        for i in range(8):
            state, m = step_fn(state, xs[:, i % 3], ys[:, i % 3])
            losses.append(
                np.asarray(m["loss"], np.float32).tobytes().hex()
            )
        assert losses == fixture[name]["losses"], name
        flat = np.concatenate([
            np.asarray(l, np.float32).reshape(-1)
            for l in jax.tree.leaves(state.params)
        ])
        digest = hashlib.sha256(flat.tobytes()).hexdigest()
        assert digest == fixture[name]["params_sha256"], name


# --- schema-v9 telemetry plumbing --------------------------------------------


def test_data_defense_event_and_summary_validate():
    from garfield_tpu.telemetry import exporters as tele_fmt
    from garfield_tpu.telemetry import hub as hub_lib

    hub = hub_lib.MetricsHub(num_ranks=4)
    rec = hub.record_event(
        "data_defense", step=3, plane="gradient",
        ranks=[0, 1, 2, 3], scores=[0.5, 0.4, 0.3, 3.2],
        flags=[0, 0, 0, 1], weights=[1.0, 1.0, 1.0, 0.1],
    )
    tele_fmt.validate_record(rec)
    stats = hub.data_defense_stats()
    assert stats["rounds"] == 1 and stats["flagged"] == 1
    assert stats["max_score"] == 3.2 and stats["min_w"] == 0.1
    summary = hub.summary()
    tele_fmt.validate_record(summary)
    assert summary["data_defense"] == {
        "rounds": 1, "flagged": 1, "max_score": 3.2, "min_w": 0.1,
    }
    prom = tele_fmt.prometheus_text(hub)
    assert 'garfield_dataplane_outlier_score{rank="3"} 3.2' in prom
    assert "garfield_dataplane_flagged_total 1" in prom
    # Malformed: flags length mismatch fails loudly.
    bad = dict(rec)
    bad["flags"] = [1]
    with pytest.raises(ValueError):
        tele_fmt.validate_record(bad)


def test_targeted_eval_reports_asr_baseline():
    """The clean-model trigger-rate baseline row (schema v9): the
    untriggered target-emission rate over non-target inputs, so ASR
    cells report attributable lift."""
    from garfield_tpu import parallel
    from garfield_tpu.attacks import targeted as targeted_lib
    from garfield_tpu.telemetry import exporters as tele_fmt

    module, loss, _ = _setup()
    init_fn, grad_fn, eval_apply = core.make_worker_fns(module, loss)
    rng = np.random.default_rng(0)
    xt = rng.normal(size=(40, 8)).astype(np.float32)
    yt = (xt.sum(-1) > 0).astype(np.float32)
    eval_set = parallel.EvalSet([(xt, yt)], binary=True)
    params, ms = init_fn(jax.random.PRNGKey(0), xt[:4])
    cfg = targeted_lib.TargetedConfig("backdoor", 0, 1, binary=True)
    rep = parallel.targeted_eval(
        (params, ms),
        lambda s, x: eval_apply(s[0], s[1], x),
        eval_set, source=0, target=1, trigger_cfg=cfg,
    )
    assert rep["asr_baseline"] is not None
    assert 0.0 <= rep["asr_baseline"] <= 1.0
    # An untrained model never saw the trigger: its triggered rate is
    # within noise of the untriggered baseline (the attributable-lift
    # rationale).
    assert abs(rep["asr"] - rep["asr_baseline"]) < 0.5
    rec = tele_fmt.make_record(
        "event", event="targeted_eval", source=0, target=1,
        asr=rep["asr"], asr_baseline=rep["asr_baseline"],
    )
    tele_fmt.validate_record(rec)


def test_poison_mask_step_folding():
    """fold_in(seed, step) poison masks: per-step variation at
    poison_frac < 1, static all-ones at 1.0 (the bitwise-compat leg),
    and host/traced twins each deterministic per (seed, step)."""
    from garfield_tpu.attacks import targeted as targeted_lib

    cfg = targeted_lib.TargetedConfig(
        "backdoor", 0, 1, poison_frac=0.5, binary=True
    )
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(16, 8)
    ).astype(np.float32))
    y = jnp.zeros((16, 1), jnp.float32)
    x0, _ = targeted_lib.poison_batch(cfg, x, y, seed=3, step=0)
    x0b, _ = targeted_lib.poison_batch(cfg, x, y, seed=3, step=0)
    x1, _ = targeted_lib.poison_batch(cfg, x, y, seed=3, step=1)
    np.testing.assert_array_equal(np.asarray(x0), np.asarray(x0b))
    assert (np.asarray(x0) != np.asarray(x1)).any()
    # poison_frac 1.0: step-independent (all samples poisoned).
    cfg1 = targeted_lib.TargetedConfig(
        "backdoor", 0, 1, poison_frac=1.0, binary=True
    )
    xa, _ = targeted_lib.poison_batch(cfg1, x, y, seed=3, step=0)
    xb, _ = targeted_lib.poison_batch(cfg1, x, y, seed=3, step=7)
    np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
