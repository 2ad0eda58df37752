"""The system under test: the only file of the benchmark that imports the
program (with the adapters it finds by name in `topologies/` and
`optimizers/`). It builds the trainer a cell names, puts the harness's
weights into its state, compiles the one step program, and reads from the
program's state the numbers the correctness check compares.
"""

import hashlib
import importlib
import json
import os
import pathlib
import pickle
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib

import inputs
import references

from . import spec, weights

if str(spec.ROOT) not in sys.path:
    sys.path.insert(0, str(spec.ROOT))


def _path(keys):
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in keys)


def flat_paths(tree):
    """``{path: leaf}`` of a parameter tree, paths as the references spell
    them (``BasicBlock_0/Conv_1/kernel``)."""
    return {_path(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def enable_compile_cache():
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``: a fixed path inside the
    checkout), caching every program however quick its compile. Returns the
    directory."""
    from garfield_tpu.utils import profiling

    cache_dir = profiling.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _norms(tree):
    return jax.tree.map(
        lambda v: jnp.linalg.norm(v.astype(jnp.float32)), tree)


def _delete(tree):
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


class StepCache:
    """The compiled step program, kept whole beside the compile cache
    (``<cache dir>/bench_steps/<key>``), so that a run after a cell's first
    neither traces the program (for r50n16, 161 Pallas kernels are lowered
    again on every ``lower()``) nor asks XLA for it. The key is everything
    the program is made from: the program's, the harness's and the input
    kinds' sources, the configuration, the traffic mix, JAX's versions, the
    devices and the program's environment switches. Anything that goes wrong
    here is said on standard error and the step is compiled the usual way.
    Not used on XLA:CPU, whose loader loses functions of an executable it is
    handed back (a rehearsal's runs then fail at their first step)."""

    @classmethod
    def of(cls, cache_dir, config, traffic):
        """The cell's cache, or None where there is none to use."""
        if not cache_dir or jax.devices()[0].platform == "cpu":
            return None
        return cls(cache_dir, config, traffic)

    def __init__(self, cache_dir, config, traffic):
        h = hashlib.sha256()
        for root in (spec.ROOT / "garfield_tpu", spec.BENCH_DIR / "harness",
                     spec.BENCH_DIR / "inputs"):
            for path in sorted(root.rglob("*.py")):
                h.update(str(path.relative_to(spec.ROOT)).encode())
                h.update(path.read_bytes())
        devices = jax.devices()
        facts = {
            "config": config, "traffic": traffic,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": getattr(devices[0].client, "platform_version", ""),
            "devices": [len(devices), devices[0].device_kind],
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith(("GARFIELD_", "XLA_", "LIBTPU_"))},
        }
        h.update(json.dumps(facts, sort_keys=True).encode())
        self.path = pathlib.Path(cache_dir) / "bench_steps" / h.hexdigest()[:32]

    def load(self, state, batch):
        """The compiled step from an earlier run of this cell here, or
        None."""
        from jax.experimental import serialize_executable

        if not self.path.exists():
            return None
        try:
            with open(self.path, "rb") as fp:
                blob, metrics_tree = pickle.load(fp)
            # The trees are rebuilt, not stored: the state's is the one the
            # program's init just made, in and out.
            in_tree = jax.tree.structure(((state, *batch), {}))
            out_tree = jax.tree.structure(
                (state, jax.tree.unflatten(
                    metrics_tree, [0] * metrics_tree.num_leaves)))
            return serialize_executable.deserialize_and_load(
                blob, in_tree, out_tree)
        except Exception as err:  # a stale or torn entry: compile instead
            print(f"step cache: {self.path.name} not loaded ({err!r})",
                  file=sys.stderr)
            return None

    def save(self, compiled):
        from jax.experimental import serialize_executable

        try:
            blob, _, out_tree = serialize_executable.serialize(compiled)
            metrics_tree = out_tree.children()[1]
            self.path.parent.mkdir(parents=True, exist_ok=True)
            partial = self.path.with_suffix(f".{os.getpid()}.partial")
            with open(partial, "wb") as fp:
                pickle.dump((blob, metrics_tree), fp)
            os.replace(partial, self.path)
        except Exception as err:
            print(f"step cache: not saved ({err!r})", file=sys.stderr)


class System:
    """The trainer of one cell: ``state``, ``batches`` and ``step`` are what
    the window drives. ``phases`` lists the seconds each part of building it
    took."""

    def __init__(self, config, traffic, seed, cache_dir=None):
        from garfield_tpu import models
        from garfield_tpu.utils import selectors

        self.phases = {}
        clock = time.perf_counter()

        def phase(name, *wait_for):
            nonlocal clock
            jax.block_until_ready(wait_for)
            now = time.perf_counter()
            self.phases[name] = now - clock
            clock = now

        model, self.opt = config["model"], config["optimizer"]
        opt = dict(self.opt)
        module = models.select_model(
            config["program"]["model"], config["program"]["dataset"],
            dtype=jnp.dtype(config["model_dtype"]))
        optimizer = selectors.select_optimizer(opt.pop("name"), **opt)
        topology = importlib.import_module(
            f"harness.topologies.{config['topology']}")
        self._read_gradient = importlib.import_module(
            f"harness.optimizers.{self.opt['name']}").first_gradient
        init_fn, step_fn = topology.make_trainer(
            module, selectors.select_loss(config["loss"]), optimizer,
            config, traffic)
        phase("trainer_s")

        key = weights.seed_key(seed)
        n, batch = config["num_workers"], config["batch_per_worker"]
        family = references.family(model["family"])
        kind = inputs.kind(family.INPUT)
        example = kind.example(model)
        scales, rules = weights.stated(family, model, config.get("init"))

        def make_state(k):
            # The program's own init for everything but the weights, which
            # are the harness's: one trace of ``init_fn``.
            state = init_fn(k, example)
            shapes = {p: v.shape for p, v in flat_paths(state.params).items()}
            made = weights.make_params(k, shapes, scales, rules)
            return state.replace(params=jax.tree.unflatten(
                jax.tree.structure(state.params), [made[p] for p in shapes]))

        self.state = jax.jit(make_state)(key)
        # A copy of the start that donation cannot reach; `forget_start`
        # drops it once the first steps have been read.
        self.start = jax.jit(
            lambda tree: jax.tree.map(jnp.copy, tree))(self.state.params)
        phase("state_s", self.state, self.start)

        sharding = step_fn.batch_sharding
        num = weights.NUM_BATCHES

        def make_batches(k):
            xs, ys = kind.batches(k, model, n, batch, num)
            return tuple((xs[b], ys[b]) for b in range(num))

        self.batches = jax.jit(
            make_batches, out_shardings=((sharding, sharding),) * num)(key)
        phase("batches_s", self.batches)

        cache = StepCache.of(cache_dir, config, traffic)
        self.compiled = cache and cache.load(self.state, self.batches[0])
        self.step_from = "step cache"
        if self.compiled is None:
            lowered = step_fn.lower(self.state, *self.batches[0])
            phase("lower_s")
            self.compiled = lowered.compile()
            self.step_from = "compiler"
            if cache:
                cache.save(self.compiled)
        phase("compile_or_load_s")
        self._grad_norms = jax.jit(
            lambda opt_state, start: _norms(
                self._read_gradient(opt_state, start, self.opt)))
        self._change_norms = jax.jit(
            lambda params, start: _norms(
                jax.tree.map(lambda a, b: a - b, params, start)))

    def step(self, state, x, y):
        """One dispatch of the compiled step: ``(state, loss)``."""
        state, metrics = self.compiled(state, x, y)
        return state, metrics["loss"]

    def first_gradient_norms(self, state):
        """``{path: norm}`` of the first aggregated gradient as the optimizer
        got it, from the state after one step."""
        return flat_paths(self._grad_norms(state.opt_state, self.start))

    def change_norms(self, state):
        """``{path: norm}`` of the parameters' change since the start."""
        return flat_paths(self._change_norms(state.params, self.start))

    def forget_start(self):
        """Drop the harness's copy of the start: after the first steps
        nothing reads it, and the window holds the program's bytes alone."""
        _delete(self.start)
        self.start = None

    def free(self, state):
        """Drop every device buffer the program's run holds; ``state`` is
        the last one the window returned."""
        _delete((state, self.state, self.start, self.batches))
        self.state = self.start = self.batches = self.compiled = None
