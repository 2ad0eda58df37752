"""The selective scan's kernels (ops/scan.py) in interpret mode against
`sequential_scan`: y and the cotangents of x, delta, A, B, C and D, over one
and two sequences, a length that is no multiple of the kernels' chunk,
channels 256 (one tile) and 384 (three), state 16, x, B and C in bfloat16 and
float32 with delta float32. Then the tile rule, ``misfit``'s reasons, the
line each path says, the chunked path left as it was, and the SambaY step
compiled for a described v5e: both kernels under ``model.ssm_scan``, no loop
left there, and the forward kernel once a Mamba layer and slot.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from garfield_tpu.ops import attention, coordinate, scan

T = 200  # two chunks of 128, the second padded


def _inputs(n, t, channels, dtype, state=16, seed=0):
    """x, delta, A, B, C, D as the Mamba layer makes them (delta float32,
    positive; A negative), and a weight on y."""
    k = jax.random.split(jax.random.PRNGKey(seed + 7 * channels + n), 7)
    return (jax.random.normal(k[0], (n, t, channels)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (n, t, channels)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (channels, state))),
            jax.random.normal(k[3], (n, t, state)).astype(dtype),
            jax.random.normal(k[4], (n, t, state)).astype(dtype),
            jax.random.normal(k[5], (channels,)),
            jax.random.normal(k[6], (n, t, channels)))


def _value_and_grads(f, args, w):
    def loss(*a):
        y = f(*a)
        return jnp.sum(y.astype(jnp.float32) * w), y
    (_, y), grads = jax.value_and_grad(
        loss, argnums=range(6), has_aux=True)(*args)
    return y, grads


def _close(got, want, name):
    """Within a few float32 ulps of the largest entry (2e-5 of it, as the
    token families' tests hold theirs), or one step of bfloat16's grid
    there: the sums run in another order, and a bfloat16 result may round
    the other way."""
    assert got.dtype == want.dtype and got.shape == want.shape, name
    room = 2.0 ** -7 if got.dtype == jnp.bfloat16 else 2e-5
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want,
        atol=room * float(np.abs(want).max()), err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("channels", [256, 384])
@pytest.mark.parametrize("n", [1, 2])
def test_the_kernels_equal_the_sequential_scan(n, channels, dtype):
    """y and all six cotangents by the kernels (interpret mode) against the
    spec; every cotangent is nonzero."""
    *args, w = _inputs(n, T, channels, dtype)
    y, grads = _value_and_grads(
        functools.partial(scan.kernels, interpret=True), args, w)
    want_y, want_grads = _value_and_grads(scan.sequential_scan, args, w)
    _close(y, want_y, "y")
    for name, got, want in zip(("dx", "ddelta", "dA", "dB", "dC", "dD"),
                               grads, want_grads):
        _close(got, want, name)
        assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0, name


def test_the_kernels_keep_y_and_the_chunk_start_states_by_name():
    """Under a block's ``save_only_these_names(*KEPT)`` policy the backward
    pass runs no forward kernel: what it reads of the forward pass is kept
    (the jaxpr of the gradient holds one forward call)."""
    *args, w = _inputs(1, T, 256, jnp.bfloat16)
    keep = jax.checkpoint_policies.save_only_these_names(*scan.KEPT)
    core = jax.checkpoint(functools.partial(scan.kernels, interpret=True),
                          policy=keep)
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(core, a, w))(
        *args))
    assert text.count("name=selective_scan_forward") == 1
    assert text.count("name=selective_scan_backward") == 1
    bare = jax.checkpoint(functools.partial(scan.kernels, interpret=True))
    text = str(jax.make_jaxpr(lambda *a: _value_and_grads(bare, a, w))(
        *args))
    assert text.count("name=selective_scan_forward") == 2


def test_the_tile_follows_the_shapes():
    """512 channels a step at the cell's state of 16 (8 vregs of state);
    fewer where the channels or a larger state ask for it."""
    assert scan.tile(5120, 16) == 512
    assert scan.tile(384, 16) == 128
    assert scan.tile(256, 16) == 256
    assert scan.tile(5120, 64) == 128
    assert scan.tile(5120, 128) is None
    assert scan.tile(200, 16) is None


@pytest.mark.parametrize("shape,dtype,lowered,why", [
    ((1, 4096, 5120, 16), jnp.bfloat16, True, None),
    ((2, 37, 384, 16), jnp.float32, True, None),
    ((1, 16, 128, 4), jnp.float32, False, None),
    ((1, 4096, 5120, 16), jnp.float16, True,
     "dtype float16 (the kernels take bfloat16 and float32)"),
    ((1, 4096, 5000, 16), jnp.bfloat16, True,
     "channels = 5000 is not a multiple of 128"),
    ((1, 16, 8, 4), jnp.float32, False,
     "channels = 8 is not a multiple of 128"),
    ((1, 4096, 5120, 128), jnp.bfloat16, True,
     "state = 128: a (state, 128) float32 state exceeds 32 KiB"),
    ((1, 16, 128, 4), jnp.float32, True,
     "state = 4 is not a multiple of 8 sublanes"),
], ids=["cell", "padded", "interpret", "float16", "channels", "tiny",
        "state_bytes", "sublanes"])
def test_misfit_says_why(shape, dtype, lowered, why):
    assert scan.misfit(shape, dtype, lowered) == why


def _said(capsys):
    return [line for line in capsys.readouterr().err.splitlines()
            if line.startswith("[ssm]")]


def test_each_path_says_its_line_once(monkeypatch, capsys):
    """The chunked path says why; the kernels say their tile, chunks and
    what they keep, and tell ``kept`` the bytes; each line once."""
    attention._said.clear()
    *args, _ = _inputs(1, T, 256, jnp.bfloat16)
    told = {}
    tell = lambda names, nbytes: told.update({names: nbytes})
    for _ in range(2):
        scan.selective_scan(*args, kept=tell)
    assert _said(capsys) == [
        "[ssm] chunked: no TPU lowering; (n, t, channels, state) = (1, 200, "
        "256, 16), chunks of 64, loop over positions, state float32"]
    assert told == {}
    small = _inputs(1, 48, 8, jnp.float32, state=4)[:-1]
    scan.selective_scan(*small, interpret=True)
    assert _said(capsys) == [
        "[ssm] chunked: channels = 8 is not a multiple of 128; (n, t, "
        "channels, state) = (1, 48, 8, 4), chunks of 32, loop over "
        "positions, state float32"]
    for _ in range(2):
        scan.selective_scan(*args, kept=tell, interpret=True)
    assert _said(capsys) == [
        "[ssm] kernels: (n, t, channels, state) = (1, 200, 256, 16), "
        "channel tile 256, chunks of 128, state float32; kept y + chunk "
        "states 0.164 MB a sequence, interpret mode"]
    # y (256 positions padded x 256 channels, bf16) + 2 chunks' states.
    assert told == {scan.KEPT: 256 * 256 * 2 + 2 * 16 * 256 * 4}
    # Chosen for the TPU but lowered for the CPU: the chunked branch runs.
    monkeypatch.setattr(coordinate, "use_pallas", lambda *a, **k: True)
    y = jax.jit(scan.selective_scan)(*args)
    assert _said(capsys) == [
        "[ssm] kernels: (n, t, channels, state) = (1, 200, 256, 16), "
        "channel tile 256, chunks of 128, state float32; kept y + chunk "
        "states 0.164 MB a sequence"]
    np.testing.assert_array_equal(y, jax.jit(functools.partial(
        scan._chunked, length=64))(*args))


def test_the_chunked_path_is_as_it_was():
    """Where the kernels do not run, ``selective_scan`` is `_chunked` at
    `chunk_length`'s chunks, y and gradients to the bit, at a shape the
    kernels would take on the chip."""
    *args, w = _inputs(2, T, 256, jnp.bfloat16)
    got = _value_and_grads(scan.selective_scan, args, w)
    want = _value_and_grads(functools.partial(
        scan._chunked, length=scan.chunk_length(2, T, 256, 16)), args, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


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


def test_the_sambay_step_runs_both_kernels_in_the_scan_scope(
        described_v5e, capsys):
    """A trainer step (aggregathor, n = 4, median under lie) of the SambaY
    tiny preset at state 16, two Mamba layers and a GMU between them, its
    blocks recomputed as the benchmark's preset runs them, lowered for the
    described chip with the Pallas paths taken: every ``selective_scan_*``
    call carries ``model.ssm_scan`` (the backward's under its forward's
    scope), no ``while`` is left there, and each of the 2 Mamba layers x 4
    slots calls the forward kernel once: the recomputed block keeps y and
    the chunk-start states."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from garfield_tpu.models import phi4flash
    from garfield_tpu.parallel import aggregathor, core
    from garfield_tpu.utils import selectors

    attention._said.clear()
    mesh = Mesh(np.array([described_v5e]), ("workers",))
    patch = pytest.MonkeyPatch()
    patch.setattr(core, "step_donation", lambda: (0,))
    patch.setattr(coordinate, "use_pallas", lambda *a, **k: True)
    try:
        module = phi4flash.phi4flash_tiny(
            num_classes=64, d_state=16, remat=True,
            layer_types=("mamba", "gmu", "mamba"))
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
        text = step_fn.lower(state, tokens, tokens).compile().as_text()
    finally:
        patch.undo()
    calls = {}
    for line in text.splitlines():
        kernel = re.match(r"\s*(?:ROOT )?%(?:\w+_)?(selective_scan_"
                          r"(?:forward|backward))", line)
        if kernel and 'custom_call_target="tpu_custom_call"' in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "model.ssm_scan/" in op_name, op_name
            backward = kernel.group(1).endswith("backward")
            assert ("transpose(" in op_name) == backward, op_name
            calls[kernel.group(1)] = calls.get(kernel.group(1), 0) + 1
        if re.search(r"\bwhile\(", line):
            op_name = re.search(r'op_name="([^"]*)"', line)
            assert not op_name or "model.ssm_scan" not in op_name.group(1)
    assert calls == {"selective_scan_forward": 8,
                     "selective_scan_backward": 8}
    err = capsys.readouterr().err.splitlines()
    assert [line for line in err if line.startswith("[ssm]")] == [
        "[ssm] kernels: (n, t, channels, state) = (2, 32, 128, 16), channel "
        "tile 128, chunks of 128, state float32; kept y + chunk states "
        "0.0737 MB a sequence"]
    remat = [line for line in err if line.startswith("[remat]")]
    assert remat and all("ssm_y, ssm_states" in line for line in remat)
