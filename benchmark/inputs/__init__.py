"""Kinds of input, one file each, found by the name a reference family gives
(its ``INPUT``): ``example(model)`` is the one-sample batch the program's
``init_fn`` is traced with; ``batches(key, model, n, batch, num_batches)``
are the seeded global batches ``(xs, ys)``, leading axes ``(num_batches, n,
batch)``, every row different. Both are pure functions of their arguments;
only a kind and a reference family read the keys of ``model``. None imports
the program under test."""

import importlib


def kind(name):
    return importlib.import_module(f"inputs.{name.replace('-', '_')}")
