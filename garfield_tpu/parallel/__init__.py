"""SPMD parallel training core: mesh, roles-as-functions, and the three
Byzantine-resilient topologies of the reference (SURVEY §2.3):

  - ``aggregathor`` — single trusted PS, n workers (SSMW;
    pytorch_impl/applications/Aggregathor/); ``granularity="layer"`` gives
    the Garfield_CC per-parameter collective semantics; num_workers=1, f=0
    degenerates to the Centralized baseline.
  - ``byzsgd``      — replicated Byzantine PS (MSMW / GuanYu;
    pytorch_impl/applications/ByzSGD/).
  - ``learn``       — fully decentralized gossip (LEARN;
    pytorch_impl/applications/LEARN/).

Each exposes ``make_trainer(...) -> (init_fn, step_fn, eval_fn)`` with
``step_fn`` one jit'd SPMD program over the ICI mesh — the reference's
RPC / NCCL / gRPC round trips (SURVEY §2.3 comm-backend row) appear only as
XLA all_gather/psum collectives inside it.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import aggregathor, byzsgd, core, learn, mesh
from .core import TrainState, default_byz_mask, make_worker_fns
from .mesh import make_mesh

__all__ = [
    "aggregathor",
    "byzsgd",
    "learn",
    "core",
    "mesh",
    "TrainState",
    "default_byz_mask",
    "make_worker_fns",
    "make_mesh",
    "topologies",
    "EvalSet",
    "compute_accuracy",
    "compute_accuracy_async",
    "targeted_eval",
]

topologies = {
    "centralized": aggregathor,  # num_workers=1, f=0 (P16)
    "aggregathor": aggregathor,  # P17
    "byzsgd": byzsgd,  # P18
    "learn": learn,  # P19
    "garfield_cc": aggregathor,  # P20 — granularity="layer"
}


class EvalSet:
    """Device-stacked test set evaluated by ONE jitted scanned program.

    The list-of-batches eval path dispatches one program per test batch
    (hundreds for MNIST/CIFAR), each a host dispatch. EvalSet uploads the stacked (B, bsz, ...) arrays once
    and folds the whole accuracy count into a single ``lax.scan`` program
    per ``eval_fn``. Pass it anywhere ``test_batches`` is accepted.
    """

    def __init__(self, test_batches, *, binary=False):
        # DatasetManager keeps the ragged tail batch (data/__init__.py), so
        # stack the uniform prefix and keep differently-shaped stragglers on
        # a per-batch side path.
        batches = [
            (jnp.asarray(x), jnp.asarray(np.asarray(y).reshape(-1)))
            for x, y in test_batches
        ]
        if not batches:
            raise ValueError(
                "EvalSet needs at least one test batch (got an empty "
                "test_batches); check the dataset/test split configuration"
            )
        shape0 = batches[0][0].shape
        uniform = [b for b in batches if b[0].shape == shape0]
        self.ragged = [b for b in batches if b[0].shape != shape0]
        self.xs = jnp.stack([x for x, _ in uniform])
        self.ys = jnp.stack([y for _, y in uniform])
        self.binary = binary
        self.total = int(self.ys.size) + sum(
            int(y.size) for _, y in self.ragged
        )
        self._jitted = {}

    def _batch_hits(self, state, eval_fn, x, y):
        logits = eval_fn(state, x)
        if self.binary:
            pred = (logits.reshape(-1) > 0.5).astype(y.dtype)
            return jnp.sum(pred == y).astype(jnp.int32)
        # Labels are kept flat: one a sample, or one a position for a
        # next-token model's (batch, time, vocabulary) logits.
        return jnp.sum(
            logits.argmax(-1).reshape(-1) == y
        ).astype(jnp.int32)

    def counts(self, state, eval_fn):
        """(correct device scalar, total) — no host sync."""
        key = id(eval_fn)
        fn = self._jitted.get(key)
        if fn is None:

            def count(state, xs, ys):
                def body(correct, xy):
                    x, y = xy
                    return correct + self._batch_hits(state, eval_fn, x, y), None

                correct, _ = jax.lax.scan(
                    body, jnp.zeros((), jnp.int32), (xs, ys)
                )
                return correct

            fn = jax.jit(count)
            self._jitted[key] = fn
        correct = fn(state, self.xs, self.ys)
        for x, y in self.ragged:
            correct = correct + self._batch_hits(state, eval_fn, x, y)
        return correct, self.total


def _accuracy_counts(state, eval_fn, test_batches, *, binary=False):
    """Enqueue the full eval pass; return (correct, total) with ``correct``
    a DEVICE scalar — no host synchronization happens here.

    The per-batch compare+sum runs on device, so the caller decides when to
    pay the host readback (a per-batch ``np.asarray`` would stall the step
    stream once per batch). ``test_batches`` may be an ``EvalSet``
    (one scanned program) or a list of (x, y) batches.
    """
    if isinstance(test_batches, EvalSet):
        return test_batches.counts(state, eval_fn)
    correct = jnp.zeros((), jnp.int32)
    total = 0
    for x, y in test_batches:
        logits = eval_fn(state, jnp.asarray(x))
        y_np = np.asarray(y).reshape(-1)
        yj = jnp.asarray(y_np)
        if binary:
            # pima path: sigmoid output, threshold 0.5 (demo.py accuracy).
            pred = (logits.reshape(-1) > 0.5).astype(yj.dtype)
            correct = correct + jnp.sum(pred == yj)
        else:
            correct = correct + jnp.sum(logits.argmax(-1).reshape(-1) == yj)
        total += int(y_np.shape[0])
    return correct, total


def compute_accuracy(state, eval_fn, test_batches, *, binary=False):
    """Top-1 accuracy over a list of (x, y) test batches.

    Counterpart of ``Server.compute_accuracy`` (server.py:235-254) / the TF
    ``compute_accuracy`` (tensorflow_impl/libs/server.py:152-163). ``binary``
    follows the pima path (single sigmoid logit, byzWorker-era threshold 0.5).
    """
    correct, total = _accuracy_counts(
        state, eval_fn, test_batches, binary=binary
    )
    return int(correct) / max(total, 1)


def compute_accuracy_async(state, eval_fn, test_batches, *, binary=False,
                           on_done=None, after=None):
    """Overlapped accuracy: enqueue the eval pass now, pay the host readback
    in a side thread — the SPMD analog of the reference's accuracy thread
    (Aggregathor/trainer.py:251-264).

    All device work is dispatched AND completed (``block_until_ready``)
    in the caller's thread before returning: a donating ``step_fn(state)``
    call issued while eval consumers of ``state`` are still pending ABORTS
    the XLA:CPU runtime (observed as a Fatal Python error in the app test
    suite) — enqueue ordering alone is not a safety guarantee. What moves
    off the training thread is the device->host scalar readback and the
    report.

    ``after``: a previous thread from this function; the new thread waits
    for it before reporting, so successive reports stay in request order.
    Returns the started (daemon) thread; its ``.exc`` attribute holds any
    exception the readback or ``on_done`` raised — join it and re-raise at
    exit, or the failure is silently dropped.
    """
    import threading

    correct, total = _accuracy_counts(
        state, eval_fn, test_batches, binary=binary
    )
    # Drain the eval's reads of `state` before the caller donates it.
    jax.block_until_ready(correct)
    acc_now = None
    if jax.default_backend() == "cpu":
        # XLA:CPU intermittently aborts when a background host readback
        # races the training thread's dispatches (seen as a Fatal Python
        # error in the app suite). A local readback is ~free, so complete
        # it inline on CPU and keep only the ordered reporting threaded;
        # device backends read back in the side thread.
        acc_now = int(correct) / max(total, 1)

    def _finalize():
        try:
            if after is not None:
                after.join()
            acc = (int(correct) / max(total, 1)  # the one host readback
                   if acc_now is None else acc_now)
            if on_done is not None:
                on_done(acc)
        except BaseException as exc:  # surfaced by the caller at join
            t.exc = exc

    t = threading.Thread(target=_finalize, daemon=True)
    t.exc = None
    t.start()
    return t


def _eval_predictions(state, eval_fn, eval_set, x_transform=None):
    """Host-side (predictions, labels) over an ``EvalSet`` (uniform stack
    + ragged tail). ``x_transform`` optionally rewrites each input batch
    (the backdoor trigger stamp) before the forward pass. Eval-time only
    — one readback per call, never on the training path."""
    preds, labels = [], []

    def one(x, y):
        if x_transform is not None:
            x = x_transform(x)
        logits = eval_fn(state, x)
        if eval_set.binary:
            p = (np.asarray(logits).reshape(-1) > 0.5).astype(np.int64)
        else:
            p = np.asarray(logits).argmax(-1).astype(np.int64).reshape(-1)
        preds.append(p)
        labels.append(np.asarray(y).reshape(-1).astype(np.int64))

    for b in range(int(eval_set.xs.shape[0])):
        one(eval_set.xs[b], eval_set.ys[b])
    for x, y in eval_set.ragged:
        one(x, y)
    return np.concatenate(preds), np.concatenate(labels)


def targeted_eval(state, eval_fn, eval_set, *, source, target,
                  trigger_cfg=None):
    """Per-class accuracy + targeted attack-success-rate (DESIGN.md §17).

    The divergence-based audit plane is blind to a targeted attack —
    global accuracy barely moves — so success is measured where the
    adversary defined it:

      - ``per_class``: top-1 accuracy per true class (the v8 per-class
        eval digest; a labelflip shows up as a crater at ``source``);
      - ``confusion``: P(pred == target | true == source) — the
        labelflip attack-success-rate, whose CLEAN value is the baseline
        the DEFBENCH bar is measured against;
      - ``asr`` (only with ``trigger_cfg``, a ``targeted.TargetedConfig``
        for the backdoor): the trigger is stamped on every NON-target
        test input and ``asr`` is the fraction that flips to ``target``
        — the BadNets success metric, computed with the SAME
        ``apply_trigger`` the poisoned training batches used;
      - ``asr_baseline``: the clean-model trigger-rate baseline row —
        ``P(pred == target | true != target)`` over the UNtriggered
        eval. A model that never saw the trigger still emits the target
        class at this chance rate when the trigger is stamped, so a raw
        ASR cell overstates the attack by exactly this floor; DEFBENCH
        reports ``asr - asr_baseline`` as the attributable lift
        (schema v9, validated).

    Returns a dict with those fields plus ``accuracy`` (global top-1).
    ``eval_set`` must be a ``parallel.EvalSet``.
    """
    from ..attacks import targeted as targeted_lib

    preds, labels = _eval_predictions(state, eval_fn, eval_set)
    classes = sorted(int(c) for c in np.unique(labels))
    per_class = {
        int(c): float((preds[labels == c] == c).mean())
        for c in classes if (labels == c).any()
    }
    src_mask = labels == int(source)
    confusion = (
        float((preds[src_mask] == int(target)).mean())
        if src_mask.any() else None
    )
    base_mask = labels != int(target)
    asr_baseline = (
        float((preds[base_mask] == int(target)).mean())
        if base_mask.any() else None
    )
    asr = None
    if trigger_cfg is not None:
        t_preds, t_labels = _eval_predictions(
            state, eval_fn, eval_set,
            x_transform=lambda x: targeted_lib.apply_trigger(
                trigger_cfg, jnp.asarray(x)
            ),
        )
        non_target = t_labels != int(target)
        asr = (
            float((t_preds[non_target] == int(target)).mean())
            if non_target.any() else None
        )
    return {
        "accuracy": float((preds == labels).mean()),
        "per_class": per_class,
        "source": int(source),
        "target": int(target),
        "confusion": confusion,
        "asr": asr,
        "asr_baseline": asr_baseline,
    }
