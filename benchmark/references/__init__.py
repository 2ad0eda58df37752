"""Plain references, one file each, found by the name a data file gives:
a model family (`<family>.py`), a rule (`rules/<name>.py`), an attack
(`attacks/<name>.py`), a loss (`losses/<name>.py`), an optimizer
(`optimizers/<name>.py`); a dash in a name is an underscore in its file's.
None imports the program under test."""

import importlib


def family(name):
    return importlib.import_module(f"references.{name}")


def rule(name):
    return importlib.import_module(f"references.rules.{name}")


def attack(name):
    return importlib.import_module(f"references.attacks.{name}")


def loss(name):
    return importlib.import_module(
        f"references.losses.{name.replace('-', '_')}").loss


def optimizer(name):
    return importlib.import_module(f"references.optimizers.{name}")
