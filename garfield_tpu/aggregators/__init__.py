"""Robust Gradient Aggregation Rule (GAR) registry.

TPU-native counterpart of pytorch_impl/libs/aggregators/__init__.py:
  - ``make_gar`` (:42-69) wraps each rule with a checked variant selected
    under ``__debug__``;
  - ``register`` (:71-86) lets each rule module self-register;
  - ``gars`` (:89) is the name -> rule mapping;
  - sibling rule modules are auto-imported (:91-97).

Every rule is a pure function of a stacked ``(n, d)`` gradient array (or a
reference-style list of 1-D vectors) and tolerance ``f``; rules are
jit-compatible with static ``n`` and ``f`` and run as XLA on TPU. The
``native-*`` variants (C++ CPU kernels via the garfield_tpu.native runtime,
mirroring the reference's pytorch_impl/libs/native/) register themselves when
the native toolchain is available.
"""

import importlib
import pkgutil

from ..utils import tools

__all__ = ["gars", "register", "GAR"]


class GAR:
    """A registered aggregation rule.

    Attributes mirror the reference wrapper (aggregators/__init__.py:63-67):
    ``unchecked`` (raw rule), ``checked`` (validates with ``check`` first),
    ``check``, ``upper_bound``, ``influence``. Calling the GAR dispatches to
    ``checked`` when ``__debug__`` else ``unchecked`` (:61).
    """

    def __init__(self, name, unchecked, check, upper_bound=None, influence=None,
                 tree_aggregate=None, gram_select=None, fold_aggregate=None,
                 tree_aggregate_ext=None, fold_flat_aggregate=None,
                 stateful_center=False):
        self.name = name
        self.unchecked = unchecked
        self.check = check
        # Optional fast path: aggregate a stacked gradient TREE (leading n
        # axis per leaf) without materializing the (n, d) flat stack —
        # Gram/matvec-structured rules (average, krum) use per-leaf Gram
        # sums; coordinate-wise rules (median, tmean) and cclip decompose
        # per leaf (_common.tree_coordinatewise). See parallel/
        # aggregathor.py for the dispatch and PERF.md for the measured
        # wins (flat stack ~5 ms/step; median step 21.3 -> 16.2 ms).
        self.tree_aggregate = tree_aggregate
        # Optional Gram-form selection: ``gram_select(gram, f, **params) ->
        # (n,) weights`` such that the aggregate equals ``w @ stack``. Rules
        # exposing it (krum, average) get the folded attack application
        # (attacks.plan_gradient_attack_fold / parallel.fold): deterministic
        # attacks become a static remap+scale of the Gram, the poisoned rows
        # are never written, and the raw Gram keeps fusing into the
        # backward epilogue (PERF.md round 4: 1.16x on krum+lie).
        self.gram_select = gram_select
        # Generalization for rules whose output is NOT one weighted row sum
        # (Bulyan): ``fold_aggregate(gram_p, apply_rows, f, **params)``
        # receives the poisoned Gram plus an ``apply_rows(W)`` closure that
        # materializes ``W @ poisoned_stack`` as a stacked tree for any
        # (r, n) weight matrix — phase-2-style reductions then run on it.
        self.fold_aggregate = fold_aggregate
        # Folded form for coordinate-wise rules (median, tmean, condense):
        # ``tree_aggregate_ext(stacked_tree, extra_tree, row_map,
        # row_scale, **params)`` aggregates the raw stacked tree and,
        # APART, the attack's shared fake row (a tree of row shapes, or
        # None: row n of ``row_map``) under a STATIC row remap/scale — the
        # Pallas kernels take the fake row as a second operand and apply
        # the remap in-register (ops.coordinate_median's extra/row_map/
        # row_scale), so neither the poisoned nor the extended stack ever
        # materializes.
        self.tree_aggregate_ext = tree_aggregate_ext
        # Folded form for iterative row-value rules (cclip): ``
        # fold_flat_aggregate(ext_stack, row_map, row_scale, f, **params)``
        # receives the EXTENDED flat (rows, d) stack (raw rows + the
        # attack's shared fake row) and the static remap/scale; the rule's
        # per-iteration passes (radii, clipped-mean matvec) apply the remap
        # to row-level scalars, so the poisoned stack never materializes
        # (parallel/fold.py dispatch; returns the flat (d,) aggregate).
        self.fold_flat_aggregate = fold_flat_aggregate
        # True for rules that accept a ``center=`` carried across steps
        # (cclip): topologies thread the previous aggregate through
        # TrainState.gar_state as the next v_0 instead of paying a robust
        # init every step (the paper's own recipe; PERF.md r5).
        self.stateful_center = stateful_center

        def checked(gradients, *args, **kwargs):
            message = check(gradients, *args, **kwargs)
            if message is not None:
                raise AssertionError(
                    f"aggregation rule {name!r} cannot be used: {message}"
                )
            return unchecked(gradients, *args, **kwargs)

        self.checked = checked
        self.upper_bound = upper_bound
        self.influence = influence
        self._call = checked if __debug__ else unchecked

    def __call__(self, gradients, *args, **kwargs):
        return self._call(gradients, *args, **kwargs)

    def __repr__(self):
        return f"<GAR {self.name}>"


gars = {}


def register(name, unchecked, check, upper_bound=None, influence=None,
             tree_aggregate=None, gram_select=None, fold_aggregate=None,
             tree_aggregate_ext=None, fold_flat_aggregate=None,
             stateful_center=False):
    """Register an aggregation rule (reference __init__.py:71-86)."""
    if name in gars:
        tools.warning(f"GAR {name!r} already registered; overwriting")
    gar = GAR(name, unchecked, check, upper_bound=upper_bound,
              influence=influence, tree_aggregate=tree_aggregate,
              gram_select=gram_select, fold_aggregate=fold_aggregate,
              tree_aggregate_ext=tree_aggregate_ext,
              fold_flat_aggregate=fold_flat_aggregate,
              stateful_center=stateful_center)
    gars[name] = gar
    return gar


# Auto-import sibling rule modules so each self-registers (reference :91-97).
for _modinfo in pkgutil.iter_modules(__path__):
    if _modinfo.name.startswith("_"):
        continue
    importlib.import_module(f"{__name__}.{_modinfo.name}")
