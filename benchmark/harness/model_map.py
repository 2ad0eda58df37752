"""Which part of the model each operation of a device trace belongs to.

A family may name the parts of its forward pass with
``jax.named_scope("model.<name>")`` (the token family does:
`garfield_tpu/models/lfm2.py`, ``SCOPES``); they nest inside ``phase.grads``,
and this module knows them from the compiled step's text alone. `phase_map`
joins a trace's events to the ``phase.*`` scopes through that text and keeps
no other text, so this module reads the same text for ``model.*`` itself:
with ``model.`` spelled ``phase.`` (and ``phase.`` spelled away)
`phase_map.label_text` gives the same labels by the same rules — a plain
instruction's outermost scope, a fusion's fused instructions (one scope,
``mixed:<a>+<b>``, or ``none``), containers left out — and `phase_map.split`
and `phase_map.name_of` read the trace as they do for phases.

One join is by name, not by scope: XLA:TPU rewrites a ragged dot into its
grouped-matmul kernel and names the kernel's instructions ``ragged-dot-*``
with an ``op_name`` of their own, so the scope is lost there. The program's
only ragged dots are the expert matmuls (`ExpertLayer`), so an instruction of
that name with no scope is ``moe_experts``.

The text comes through `phase_map._compiled_text`, which builds the System
once more (on the chip from the step cache: the seconds are said on standard
error), once per process. Nothing here raises: where no map can be had (a
program without the scopes, as the parent of the PR that added them), the
reason goes to standard error and every reader returns None.
"""

import json
import sys
import time

from . import phase_map

RAGGED_DOT, RAGGED_DOT_SCOPE = "ragged-dot", "moe_experts"

_memo = {}
_said = set()


def label_model_text(text):
    """`phase_map.label_text` of ``text`` for ``model.*`` scopes."""
    made = phase_map.label_text(
        text.replace("phase.", "stage.").replace("model.", "phase."))
    for name, label in made["labels"].items():
        if label == "none" and name.startswith(RAGGED_DOT):
            made["labels"][name] = RAGGED_DOT_SCOPE
    return made


def labels(facts):
    """The map of the cell's step program, made once per process; None, with
    the reason on standard error, where it cannot be had or the program's
    text names no ``model.*`` scope."""
    key = json.dumps([facts["config"], facts["traffic"]], sort_keys=True)
    if key not in _memo:
        t0 = time.perf_counter()
        try:
            made = label_model_text(phase_map._compiled_text(facts))
            if not made["phases"]:
                # As `phase_map.labels` does for a text that names no phase.
                print("model map: the loaded step's text names no model "
                      "scope; compiling again with the metadata in the key",
                      file=sys.stderr)
                made = label_model_text(
                    phase_map._compiled_text(facts, fresh=True))
            if not made["phases"]:
                raise ValueError("the step program's text names no model scope")
        except Exception as err:
            print(f"model map: none ({err!r})", file=sys.stderr)
            made = None
        print(f"model map: made in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        _memo[key] = made
    return _memo[key]


def step_seconds(trace, facts):
    """``{label: device seconds per step}`` on the fullest device, or None
    where there is no map; the whole split goes to standard error once."""
    made = labels(facts)
    if made is None:
        return None
    device = trace["fullest"]
    by_label, left_out = phase_map.split(device["op_seconds"], made)
    per_step = {k: v / device["steps"] for k, v in by_label.items()}
    if id(trace) not in _said:
        _said.add(id(trace))
        rows = {k: round(1e3 * v, 4) for k, v in sorted(
            per_step.items(), key=lambda kv: -kv[1])}
        print(f"model map: ms per step {json.dumps(rows)}; containers left "
              f"out {1e3 * left_out / device['steps']:.4f}", file=sys.stderr)
    return per_step


def scopes_ms(trace, facts, scopes):
    """Device milliseconds per step of the operations wholly in ``scopes``
    (every instruction they hold is in one of them); None where there is no
    map or the text names none of them."""
    made = labels(facts)
    if made is None or not set(scopes) & made["phases"]:
        return None
    return 1e3 * sum(
        seconds for label, seconds in step_seconds(trace, facts).items()
        if phase_map.label_phases(label)
        and phase_map.label_phases(label) <= set(scopes))


def holding_seconds(trace, facts, scope):
    """Device seconds per step of every operation that holds an instruction
    of ``scope``, the mixed ones included; None where there is no map or no
    such operation ran."""
    by_label = step_seconds(trace, facts)
    if by_label is None:
        return None
    total = sum(s for label, s in by_label.items()
                if scope in phase_map.label_phases(label))
    return total or None
