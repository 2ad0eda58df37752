"""The held-row kernels (ops/route.py) in interpret mode against their
fallback, `lfm2._rows_permuted` and `lfm2._back_permuted` (today's
broadcast, permutation, mask and weighted sum): the value and the three
cotangents (x, out, weights), over held totals of none, one, a tile's edge
and every row, k = 4 and 8, hidden 128 and 256; then nothing read past the
zeroed tail, `ExpertLayer` on both paths, the tile rule and the path choice.

tokens x k is 1,024 rows here: two of the kernels' tiles of 512, so that a
total under 512 leaves the second tile unwritten, which interpret mode fills
with NaN.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.models import lfm2
from garfield_tpu.ops import attention, coordinate, grouped, route

ROWS = 1024
GROUPS = 4
TOTALS = {"none": 0, "one": 1, "tile_edge": grouped.ROW_TILE, "all": ROWS}
CASES = [(t, k, h) for t in TOTALS for k in (4, 8) for h in (128, 256)]


def _routing(k, total, seed=0):
    """``(order, inverse, sizes)`` of ROWS // k tokens x k pairs of which
    ``total`` are held: token 0's k pairs first where total allows, the rest
    at random, each on one of GROUPS experts."""
    rng = np.random.default_rng(seed + 31 * total + k)
    held = np.zeros(ROWS, bool)
    first = min(total, k) if total >= k else 0
    held[:first] = True
    held[first + rng.choice(ROWS - first, total - first, replace=False)] = True
    slots = np.where(held, rng.integers(0, GROUPS, ROWS), GROUPS)
    order = np.argsort(slots, kind="stable").astype(np.int32)
    sizes = np.bincount(slots[held], minlength=GROUPS).astype(np.int32)
    return (jnp.asarray(order), jnp.asarray(np.argsort(order), jnp.int32),
            jnp.asarray(sizes))


def _operands(k, hidden, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    tokens = ROWS // k
    return (jax.random.normal(keys[0], (tokens, hidden), dtype),
            jax.random.normal(keys[1], (ROWS, hidden), dtype),
            jax.random.uniform(keys[2], (tokens, k), jnp.float32).astype(dtype),
            jax.random.normal(keys[3], (ROWS, hidden), jnp.float32),
            jax.random.normal(keys[4], (tokens, hidden), jnp.float32))


def _kernels_rows(x, order, inverse, total):
    k = order.shape[0] // x.shape[0]
    return route.gather_held(x, order // k, total, interpret=True)


def _kernels_back(out, weights, order, inverse, total):
    return route.combine_held(
        out, weights, order, inverse, total, interpret=True)


def _permuted_rows(x, order, inverse, total):
    return lfm2._rows_permuted(x, order, inverse, total)[0]


def _permuted_back(out, weights, order, inverse, total):
    return lfm2._back_permuted(out, weights, order, inverse, total,
                               out.dtype)


PATHS = {"kernels": (_kernels_rows, _kernels_back),
         "permute": (_permuted_rows, _permuted_back)}


@functools.lru_cache(maxsize=None)
def _both(case, k, hidden, dtype):
    """{path: (rows, d x, y, d out, d weights)} for a random cotangent of
    each, rows and d out under the dispatch's mask (both paths leave rows
    past the total to the next kernel: zero or undefined); as float32 NumPy
    arrays. The permutation runs in float32 on the same values: the
    kernels round once, it rounds each weighted row in bf16."""
    total = TOTALS[case]
    order, inverse, _ = _routing(k, total)
    x, out, weights, d_rows, d_y = _operands(k, hidden, dtype)
    here = (jnp.arange(ROWS) < total)[:, None]

    @functools.partial(jax.jit, static_argnums=0)
    def run(path, x, out, weights):
        dispatch, combine = PATHS[path]
        rows, pull_rows = jax.vjp(
            lambda x: dispatch(x, order, inverse, total), x)
        y, pull_y = jax.vjp(
            lambda o, w: combine(o, w, order, inverse, total), out, weights)
        d_out, d_weights = pull_y(d_y.astype(y.dtype))
        return (jnp.where(here, rows, 0), pull_rows(
            d_rows.astype(rows.dtype))[0], y, jnp.where(here, d_out, 0),
            d_weights)

    f32 = functools.partial(jax.tree.map, lambda a: np.asarray(a, np.float32))
    wide = f32((x, out, weights))
    return {"kernels": f32(run("kernels", x, out, weights)),
            "permute": f32(run("permute", *wide))}


def _close(got, want, dtype):
    """Float32: the sums' order differs. bfloat16: the kernels round a
    float32 sum once, so within a step of bf16's grid at the largest
    entry."""
    assert got.shape == want.shape and np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1.0)
    atol = {"float32": 1e-5, "bfloat16": 2.0 ** -7}[dtype] * scale
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case,k,hidden", CASES)
def test_the_dispatched_rows_equal_the_permutations(case, k, hidden, dtype):
    """A copy: equal to every bit, whatever the dtype."""
    both = _both(case, k, hidden, DTYPES[dtype])
    np.testing.assert_array_equal(both["kernels"][0], both["permute"][0])


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case,k,hidden", CASES)
def test_the_cotangent_of_x_equals_the_permutations(case, k, hidden, dtype):
    both = _both(case, k, hidden, DTYPES[dtype])
    _close(both["kernels"][1], both["permute"][1], dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case,k,hidden", CASES)
def test_the_combined_value_equals_the_permutations(case, k, hidden, dtype):
    both = _both(case, k, hidden, DTYPES[dtype])
    _close(both["kernels"][2], both["permute"][2], dtype)
    if case == "none":
        assert not both["kernels"][2].any()


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case,k,hidden", CASES)
def test_the_cotangent_of_out_equals_the_permutations(case, k, hidden, dtype):
    both = _both(case, k, hidden, DTYPES[dtype])
    _close(both["kernels"][3], both["permute"][3], dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case,k,hidden", CASES)
def test_the_cotangent_of_the_weights_equals_the_permutations(
        case, k, hidden, dtype):
    """A pair not held gets no gradient."""
    both = _both(case, k, hidden, DTYPES[dtype])
    got = both["kernels"][4]
    _close(got, both["permute"][4], dtype)
    order, inverse, _ = _routing(k, TOTALS[case])
    absent = np.asarray(inverse).reshape(got.shape) >= TOTALS[case]
    assert not got[absent].any()


@pytest.mark.parametrize("case", list(TOTALS))
def test_rows_are_zero_to_the_tile_and_unwritten_past_it(case):
    """Zeros from the total to a multiple of the grouped kernels' row tile
    (the kernels' own tile, 512 here); interpret mode leaves NaN in the
    rows past that, which no kernel may read (next test)."""
    total = TOTALS[case]
    order, inverse, _ = _routing(4, total)
    x = _operands(4, 128, jnp.bfloat16)[0]
    rows = np.asarray(
        _kernels_rows(x, order, inverse, jnp.int32(total)), np.float32)
    tile = route.tile((x.shape[0], 4, 128), jnp.bfloat16)
    end = max(-(-total // tile), 1) * tile
    assert tile == 512 and end % grouped.ROW_TILE == 0
    assert np.isfinite(rows[:end]).all() and not rows[total:end].any()
    assert np.isnan(rows[end:]).all(axis=1).all()


@pytest.mark.parametrize("case", ["one", "tile_edge"])
def test_nothing_reads_past_the_zeroed_tail(case):
    """Dispatch, two grouped-matmul kernels and the combine, as the expert
    layer chains them (interpret mode, the grouped kernels' row tile of
    128): with rows past the tail NaN, the value and every gradient — the
    grouped matmul's weights' too — are finite and equal the
    permutations'."""
    total, k, hidden, width = TOTALS[case], 4, 128, 128
    order, inverse, sizes = _routing(k, total)
    x, _, weights, _, d_y = _operands(k, hidden, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    w_in = jax.random.normal(keys[0], (GROUPS, hidden, width)) / 8
    w_out = jax.random.normal(keys[1], (GROUPS, width, hidden)) / 8
    dot = functools.partial(grouped.kernels, sizes=sizes, interpret=True,
                            tiles=(grouped.ROW_TILE, hidden, width))
    back = functools.partial(grouped.kernels, sizes=sizes, interpret=True,
                             tiles=(grouped.ROW_TILE, width, hidden))

    def layer(path, x, weights, w_in, w_out):
        dispatch, combine = PATHS[path]
        rows = dispatch(x, order, inverse, total)
        out = back(jax.nn.silu(dot(rows, w_in)), w_out)
        y = combine(out, weights, order, inverse, total)
        return jnp.sum(y * d_y)

    got, want = (jax.jit(jax.value_and_grad(
        functools.partial(layer, path), (0, 1, 2, 3)))(
            x, weights, w_in, w_out) for path in ("kernels", "permute"))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def _expert_layer(u, monkeypatch=None):
    """``ExpertLayer`` at the tiny preset's widths over 128 tokens (k = 2:
    256 rows, one tile), its output, parameters' gradients and counters;
    with ``monkeypatch`` on the kernels in interpret mode, as on the chip."""
    if monkeypatch is not None:
        monkeypatch.setattr(route, "gather_held", functools.partial(
            route.gather_held, interpret=True))
        monkeypatch.setattr(route, "combine_held", functools.partial(
            route.combine_held, interpret=True))
        monkeypatch.setattr(route, "path", lambda *a, **k: None)
        monkeypatch.setattr(
            route, "either", lambda kernels, fallback, *a, why: kernels(*a))
    layer = lfm2.ExpertLayer(8, (0, 1, 2), 2, 48, dtype=u.dtype,
                             shared_width=32)
    variables = layer.init(jax.random.PRNGKey(0), u)

    def loss(params):
        y, state = layer.apply(
            {"params": params}, u,
            mutable=[lfm2.COUNTER_SUMS, lfm2.COUNTER_MAXES])
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, state)

    (_, (y, state)), grads = jax.value_and_grad(loss, has_aux=True)(
        variables["params"])
    counters = {name: int(v) for c in state.values() for name, v in c.items()}
    return np.asarray(y, np.float32), grads, counters


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_the_expert_layer_equals_its_permutation_path(monkeypatch, dtype):
    u = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 64), DTYPES[dtype])
    want, want_grads, want_counters = _expert_layer(u)
    got, got_grads, got_counters = _expert_layer(u, monkeypatch)
    _close(got, want, dtype)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        _close(np.asarray(a, np.float32), np.asarray(b, np.float32), dtype)
    held = want_counters["moe_pairs_held"]
    assert 0 < held < 256 and want_counters["moe_rows_routed"] == 256
    assert got_counters == dict(want_counters, moe_rows_routed=held)


@pytest.mark.parametrize("shape,dtype,lowered,why", [
    ((4096, 8, 2304), jnp.bfloat16, True, None),
    ((32, 2, 64), jnp.float32, True,
     "tokens x k = 64 is not a multiple of the tile 128"),
    ((128, 2, 64), jnp.float32, True, "hidden = 64 is no multiple of 128 "
     "lanes"),
    ((128, 2, 128), jnp.bfloat16, True, "hidden = 128 is no multiple of 256 "
     "lanes"),
    ((128, 2, 64), jnp.float32, False, None),
    ((64, 4, 256), jnp.float16, True,
     "dtype float16 (the kernels take bfloat16 and float32)"),
    ((40, 16, 256), jnp.bfloat16, True, "tokens = 40 is no multiple of 16"),
    ((65536, 8, 4096), jnp.bfloat16, False,
     "(tokens, hidden) = (65536, 4096) needs 1540 MiB of VMEM"),
])
def test_misfit_says_why(shape, dtype, lowered, why):
    assert route.misfit(shape, dtype, lowered) == why


@pytest.mark.parametrize("shape", [
    # One slot of lfm2n4, mellum2n4 and lagunaxs2n5.
    (4096, 4, 2048), (4096, 8, 2304), (4096, 8, 2048)])
def test_the_tile_rule_reads_shapes_only(shape):
    """The three token cells take the largest tile, within the VMEM limit;
    the rule steps down where the resident arrays leave less room."""
    assert route.tile(shape, jnp.bfloat16) == 512
    assert route._resident_bytes(*shape[::2], jnp.bfloat16, 512) <= (
        route.VMEM_LIMIT_BYTES)
    tokens, k, hidden = shape
    assert route.tile((tokens * 3, k, hidden), jnp.bfloat16) is None
    assert route.tile((8192, 4, 2048), jnp.bfloat16) == 256


def _lines(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if "[route]" in line]


def test_the_path_is_chosen_by_shape_and_lowering_and_said_once(
        monkeypatch, capsys):
    attention._said.clear()
    for _ in range(2):
        assert route.path((32, 2, 64), jnp.float32) is not None
    assert _lines(capsys) == [
        "[route] permute: tokens x k = 64 is not a multiple of the tile 128"]
    assert route.path((4096, 8, 2304), jnp.bfloat16) == "no TPU lowering"
    assert _lines(capsys) == ["[route] permute: no TPU lowering"]
    monkeypatch.setattr(coordinate, "use_pallas", lambda *a, **k: True)
    assert route.path((4096, 8, 2304), jnp.bfloat16) is None
    assert _lines(capsys) == [
        "[route] held rows: (tokens, k, hidden) = (4096, 8, 2304) bfloat16, "
        "tiles of 512 sorted rows, zeros to a multiple of 128, 63.0 MiB of "
        "VMEM"]
    # Lowered for the CPU with the kernels chosen: `either` takes the
    # fallback's branch, which is what runs.
    order, inverse, _ = _routing(4, 100)
    x = _operands(4, 128, jnp.float32)[0]
    rows, routed = jax.jit(lambda x: route.either(
        lfm2._rows_held, lfm2._rows_permuted, x, order, inverse,
        jnp.int32(100), why=None))(x)
    assert int(routed) == ROWS
    np.testing.assert_array_equal(rows, _permuted_rows(x, order, inverse, 100))


def test_the_tiny_presets_take_the_permutation_and_say_why(capsys):
    from garfield_tpu import models

    attention._said.clear()
    for name in ("lfm2_moe_tiny", "mellum2_tiny", "laguna_tiny"):
        module = models.select_model(name, "synthtokens")
        module.init(jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32))
    lines = _lines(capsys)
    assert lines and all(line.startswith("[route] permute: ") for line in lines)


@pytest.fixture(scope="module")
def described_v5e():
    """One chip of a described v5e (compiled for, never run), with the
    persistent compilation cache off: what is compiled for a described chip
    cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module", params=[False, True], ids=["whole", "remat"])
def kernel_step_text(request, described_v5e):
    """The compiled text of a trainer step (aggregathor, n = 4, median under
    lie) of the tiny preset at hidden 128 over 64 tokens a slot (k = 2: 128
    rows, one tile), lowered for the described chip with the Pallas paths
    taken, with and without the benchmark presets' recomputed blocks."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from garfield_tpu.parallel import aggregathor, core
    from garfield_tpu.utils import selectors

    mesh = Mesh(np.array([described_v5e]), ("workers",))
    patch = pytest.MonkeyPatch()
    patch.setattr(core, "step_donation", lambda: (0,))
    patch.setattr(coordinate, "use_pallas", lambda *a, **k: True)
    try:
        module = lfm2.lfm2_moe_tiny(num_classes=64, hidden=128,
                                    remat=request.param)
        init_fn, step_fn, _ = aggregathor.make_trainer(
            module, selectors.select_loss("next-token"),
            selectors.select_optimizer("sgd", lr=0.05), "median",
            num_workers=4, f=1, attack="lie", mesh=mesh)
        tokens = jax.ShapeDtypeStruct(
            (4, 2, 32), jnp.int32, sharding=step_fn.batch_sharding)
        state = jax.eval_shape(
            init_fn, jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))
        state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, PartitionSpec())),
            state)
        return step_fn.lower(state, tokens, tokens).compile().as_text()
    finally:
        patch.undo()


def test_every_held_row_kernel_holds_a_permutation_step_forward_and_backward(
        kernel_step_text):
    """The kernels' counterpart of `tests/test_lfm2.py`'s check on the row
    gathers: every call of the two kernels carries ``route.gather_rows`` or
    ``route.return_rows``, and each step has a forward and a backward call
    (its custom_vjp's backward carries the forward's scope), so
    ``moe_permute_ms`` reads the kernels."""
    calls = [line for line in kernel_step_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.match(r"\s*(ROOT )?%held_rows_(gather|sum)", line)]
    assert calls
    steps = {}
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        step = re.search(r"route\.(gather_rows|return_rows)/", op_name)
        assert step, op_name
        kernel = re.match(r"\s*(?:ROOT )?%(held_rows_\w+?)(\.\d+)? ", line)
        steps.setdefault(step.group(1), set()).add(
            (kernel.group(1), "transpose(" in op_name))
    assert steps == {
        "gather_rows": {("held_rows_gather", False), ("held_rows_sum", True)},
        "return_rows": {("held_rows_sum", False), ("held_rows_gather", True)},
    }
