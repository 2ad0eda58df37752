"""The step's phase scopes (parallel/core.py ``phase``) and the Pallas
kernels' names (ops/coordinate.py): what a device trace is read by.

A scope is only ``op_name`` metadata of the compiled program, and JAX's
compile-cache key leaves metadata out: a cache warmed by a source without
the scopes would hand such an executable back. So the programs compiled here
put their metadata into the key.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu import models
from garfield_tpu.ops import coordinate
from garfield_tpu.parallel import aggregathor, byzsgd, core, learn, make_mesh
from garfield_tpu.utils import selectors


@pytest.fixture
def metadata_in_cache_key():
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    yield
    jax.config.update(name, before)


def _compiled_text(make_trainer, *args, **kwargs):
    module = models.select_model("pimanet", "pima")
    loss = selectors.select_loss("bce")
    opt = selectors.select_optimizer("sgd", lr=0.05, momentum=0.9)
    init_fn, step_fn, _ = make_trainer(module, loss, opt, *args, **kwargs)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16, 8)).astype(np.float32))
    y = (x.sum(-1, keepdims=True) > 0).astype(jnp.float32)
    state = init_fn(jax.random.PRNGKey(0), x[0])
    return step_fn.lower(state, x, y).compile().as_text()


def _phases(text):
    return {p for p in core.PHASES if f"/phase.{p}/" in text}


def test_an_unknown_phase_is_refused():
    with pytest.raises(ValueError, match="nonsense"):
        core.phase("nonsense")
    for name in core.PHASES:
        with core.phase(name):
            pass


@pytest.mark.parametrize("fold", [True, False], ids=["folded", "where-path"])
def test_aggregathor_step_names_its_phases(fold, monkeypatch,
                                           metadata_in_cache_key):
    if not fold:
        monkeypatch.setenv("GARFIELD_NO_FOLD", "1")
    text = _compiled_text(
        aggregathor.make_trainer, "krum", num_workers=8, f=2, attack="lie")
    assert _phases(text) >= {
        "grads", "exchange", "attack", "rule", "update"}
    # Forward and backward stay told apart inside the phase, by JAX's own.
    assert "/phase.grads/jvp(" in text
    assert "/phase.grads/transpose(jvp(" in text


def test_a_telemetry_tap_gets_no_phase_of_its_own(metadata_in_cache_key):
    text = _compiled_text(
        aggregathor.make_trainer, "krum", num_workers=8, f=2, attack="lie",
        telemetry=True)
    assert _phases(text) == {"grads", "exchange", "attack", "rule", "update"}


def test_learn_step_names_both_planes(metadata_in_cache_key):
    text = _compiled_text(
        learn.make_trainer, "krum", num_nodes=8, f=1, attack="lie",
        non_iid=True, model_gossip=True)
    assert _phases(text) == set(core.PHASES)


def test_byzsgd_step_names_both_planes(metadata_in_cache_key):
    text = _compiled_text(
        byzsgd.make_trainer, "krum", num_workers=8, num_ps=4, fw=2, fps=1,
        attack="lie", ps_attack="reverse", model_gar="median",
        mesh=make_mesh({"ps": 2, "workers": 4}))
    assert _phases(text) == set(core.PHASES)


@pytest.mark.parametrize("name,call", [
    ("coordinate_median", coordinate.coordinate_median),
    ("trimmed_mean", lambda g: coordinate.trimmed_mean(g, 2)),
    ("averaged_median_mean", lambda g: coordinate.averaged_median_mean(g, 3)),
])
def test_each_column_kernel_carries_its_name_when_lowered_for_tpu(
        name, call, monkeypatch):
    """Lowered for the TPU platform, not compiled: no libtpu is loaded. The
    name is the custom call's in the compiled program and so its events' in
    a device trace (``%coordinate_median.N``)."""
    monkeypatch.setattr(coordinate, "use_pallas", lambda n=None, op=None: True)
    g = jax.ShapeDtypeStruct((8, 20000), jnp.bfloat16)
    text = jax.jit(call).trace(g).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{name}"' in text


# --- the host's side of the same trace ------------------------------------


def _host_events(trace_dir, names):
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in names:
                    found.append(
                        (plane.name, event.name, dict(event.stats)))
    return found


def test_a_span_is_also_an_annotation_on_the_profilers_clock(tmp_path):
    from garfield_tpu.telemetry import trace

    assert trace.span("quorum", step=7) is trace._NULL  # off: the no-op
    trace.enable(who="test")
    try:
        jax.profiler.start_trace(str(tmp_path))
        with trace.span("quorum", step=7) as sp:
            sp.set(arrived=3)
        jax.profiler.stop_trace()
    finally:
        trace.disable()
    ((plane, name, stats),) = _host_events(tmp_path, {"quorum"})
    assert plane == "/host:CPU" and name == "quorum"
    assert int(stats["step"]) == 7 and int(stats["arrived"]) == 3


def test_steps_trace_holds_whole_steps_and_ends_in_a_sync(tmp_path):
    from garfield_tpu.utils import profiling

    step = jax.jit(lambda x: x * 2.0 + 1.0)
    idle = profiling.StepsTrace(None, 2)
    traced = profiling.StepsTrace(tmp_path, first=2, steps=3)
    x = jnp.ones((64, 64))
    for i in range(8):
        for steps_trace in (idle, traced):
            steps_trace.before(i, x)
        assert traced.tracing == (2 <= i < 5)
        with jax.profiler.TraceAnnotation("dispatch", step=i):
            x = step(x)
        for steps_trace in (idle, traced):
            steps_trace.after(i + 1, x)
    assert not idle.tracing and not traced.tracing
    steps = sorted(int(stats["step"]) for _, _, stats in
                   _host_events(tmp_path, {"dispatch"}))
    assert steps == [2, 3, 4]
    # A run that ends inside the trace closes it.
    short = profiling.StepsTrace(tmp_path / "short", first=0, steps=100)
    short.before(0, x)
    short.after(1, x)
    assert short.tracing
    short.after(None, x)
    assert not short.tracing
