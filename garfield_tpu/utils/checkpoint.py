"""Checkpoint / resume for training state.

The reference has NO checkpointing of any kind (SURVEY §5: no torch.save /
tf.train.Checkpoint anywhere; runs die with the process). This module is the
deliberate upgrade the survey calls for: orbax-backed save/restore of the
whole ``TrainState`` pytree, keyed by step, with ``latest_step`` discovery so
``--resume`` continues a killed run bit-exactly (state.rng + fold_in(step)
makes the step stream replayable — core.py TrainState docstring).

Falls back to a pickle-of-numpy-leaves format if orbax is unavailable —
and uses it by default on the XLA:CPU backend, where orbax's background
commit threads are unsound (see ``_use_orbax``). ``GARFIELD_CKPT_BACKEND``
forces either backend.
"""

import os
import pickle

import jax
import numpy as np

__all__ = ["save", "restore", "latest_step", "Checkpointer"]

try:  # orbax is in the baked image; guard anyway (zero-install rule)
    import orbax.checkpoint as ocp

    _HAVE_ORBAX = True
except Exception:  # pragma: no cover
    _HAVE_ORBAX = False

# GARFIELD_CKPT_BACKEND=pickle|orbax overrides the automatic choice.
_BACKEND = os.environ.get("GARFIELD_CKPT_BACKEND", "").strip().lower()
if _BACKEND not in ("", "pickle", "orbax"):  # pragma: no cover
    raise ValueError(
        f"GARFIELD_CKPT_BACKEND={_BACKEND!r}: expected 'pickle' or 'orbax'"
    )


def _use_orbax():
    """Orbax on real device backends; pickle on XLA:CPU (or by env).

    orbax's CheckpointManager keeps background commit threads alive past
    ``wait_until_finished``, and on the XLA:CPU runtime a
    native thread touching the runtime while the training thread
    dispatches donating steps is unsound — the process dies with a
    native SIGSEGV/SIGABRT, not an exception (same failure class, and
    same remedy, as the CPU-inline readback guard in
    ``parallel.compute_accuracy_async``). The window only opens when
    compiles are warm enough for steps to dispatch back-to-back, which
    is exactly the cached test/CI configuration. The pickle format is
    per-backend: a run checkpointed on one backend resumes on the same
    backend (cross-backend resume was never supported — shardings
    differ).
    """
    if _BACKEND == "pickle":
        return False
    if _BACKEND == "orbax":
        return _HAVE_ORBAX
    return _HAVE_ORBAX and jax.default_backend() != "cpu"


def _np_leaves(tree):
    return jax.tree.map(lambda l: np.asarray(l), tree)


class Checkpointer:
    """Directory of step-numbered checkpoints with a bounded history."""

    def __init__(self, directory, max_to_keep=3):
        self.directory = os.path.abspath(str(directory))
        self.max_to_keep = max_to_keep
        os.makedirs(self.directory, exist_ok=True)
        if _use_orbax():
            self._mgr = ocp.CheckpointManager(
                self.directory,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=max_to_keep, create=True
                ),
            )
        else:
            self._mgr = None

    def save(self, step, state, wait=True):
        step = int(step)
        if self._mgr is not None:
            self._mgr.save(step, args=ocp.args.StandardSave(state))
            if wait:
                self._mgr.wait_until_finished()
        else:  # pickle fallback
            path = os.path.join(self.directory, f"ckpt_{step}.pkl")
            with open(path + ".tmp", "wb") as f:
                pickle.dump(_np_leaves(state), f)
            os.replace(path + ".tmp", path)
            self._gc()

    def latest_step(self):
        if self._mgr is not None:
            return self._mgr.latest_step()
        steps = self._pickle_steps()
        return steps[-1] if steps else None

    def steps(self):
        """Every step present in this directory, sorted, on BOTH
        backends — what torn-save detection across a shard group needs
        (federated/sharding.latest_sharded_step intersects these; the
        orbax path used to expose only ``latest_step``, which lets a
        shard that is one save ahead hide an older step the others
        still agree on)."""
        if self._mgr is not None:
            return sorted(int(s) for s in self._mgr.all_steps())
        return self._pickle_steps()

    def restore(self, state_like, step=None):
        """Restore into the structure of ``state_like`` (an abstract or
        concrete TrainState from ``init_fn`` — shardings are re-applied by
        the caller's device_put)."""
        step = self.latest_step() if step is None else int(step)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        if self._mgr is not None:
            target = jax.tree.map(np.asarray, state_like)
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(target)
            )
        path = os.path.join(self.directory, f"ckpt_{step}.pkl")
        with open(path, "rb") as f:
            return pickle.load(f)

    def _pickle_steps(self):
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("ckpt_") and name.endswith(".pkl"):
                steps.append(int(name[5:-4]))
        return sorted(steps)

    def _gc(self):
        steps = self._pickle_steps()
        for s in steps[: -self.max_to_keep]:
            os.remove(os.path.join(self.directory, f"ckpt_{s}.pkl"))

    def close(self):
        if self._mgr is not None:
            self._mgr.close()


def save(directory, step, state):
    Checkpointer(directory).save(step, state)


def latest_step(directory):
    return Checkpointer(directory).latest_step()


def restore(directory, state_like, step=None):
    return Checkpointer(directory).restore(state_like, step)
