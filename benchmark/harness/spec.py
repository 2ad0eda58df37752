"""Reads ``BENCHMARK.json`` and finds each cell's data files by name.

A configuration is the file its entry names; a traffic mix is
``traffic/<traffic>.json``; a cell's limits for `correct` are
``limits/<cell>.json``; a per-layer metric's reader is
``layer_metrics/<name>.py``. What a configuration names is a file too: its
``model.family`` the plain reference ``references/<family>.py`` (which may
state how its leaves are made, ``leaf_rules``), that family's ``INPUT`` the
kind of input ``inputs/<kind>.py``, its ``topology`` and ``optimizer.name``
the adapters ``harness/topologies/<name>.py`` and
``harness/optimizers/<name>.py``, its ``loss``, its optimizer and the traffic
mix's ``rule`` and ``attack`` the plain ``references/{losses,optimizers,
rules,attacks}/<name>.py``. Adding a cell, a configuration, a model family, a
kind of input or a metric is adding files and entries — no file that is
there changes (``tests/toy.py`` adds a token model that way).
"""

import importlib
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _read(path):
    with open(path) as fp:
        return json.load(fp)


def load(root=ROOT):
    return _read(pathlib.Path(root) / "BENCHMARK.json")


def _by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise SystemExit(f"no {what} {name!r} in BENCHMARK.json (has: {known})")


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    limits loaded."""

    def __init__(self, spec, name, root=ROOT):
        root = pathlib.Path(root)
        self.spec = spec
        self.entry = _by_name(spec["workloads"], name, "workload")
        self.name = name
        self.chips = self.entry["chips"]
        config = _by_name(spec["configs"], self.entry["config"], "config")
        self.config = _read(root / config["file"])
        bench = root / spec["paths"][0]
        self.traffic = _read(bench / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = _read(bench / "limits" / f"{name}.json")

    def metrics(self, group):
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those with
        no ``workloads`` key, or one that lists this cell."""
        return [
            m for m in self.spec[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


def layer_reader(name):
    """The ``read(trace, facts)`` of ``layer_metrics/<name>.py``; dots and
    dashes of a metric's name are underscores in its file's."""
    module = name.replace(".", "_").replace("-", "_")
    return importlib.import_module(f"layer_metrics.{module}").read
