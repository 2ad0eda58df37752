"""Which step of an expert layer's routing each operation of a device trace
belongs to, and how much of what routing moves an expert uses.

The token families name the steps of routing with
``jax.named_scope("route.<name>")`` (`garfield_tpu/models/lfm2.py`,
``ROUTE_STEPS``: the slot lookup, the two sorts, the groups' sizes, the two
row permutations, the weighted sum), nested inside ``model.moe_dispatch``
and ``model.moe_combine``. `model_map` labels an instruction by its outermost
``model.*`` scope and so cannot see them. This module reads the same compiled
text with one more namespace: an instruction is labelled by its ``route.*``
step where it has one, else by its outermost ``model.*`` scope as
``model_<name>`` — so that an operation holding a routing step and anything
else reads ``mixed`` and never counts as wholly in the step — and
`phase_map.label_text` does the rest by its rules (a fusion by its fused
instructions, containers left out). The labels that hold a routing step,
``model_moe_router``, ``model_moe_dispatch`` or ``model_moe_combine`` add up
to `model_map`'s routing on the same text; the ``route map`` line on standard
error says both.

The counters come from the program too: an expert layer writes
``moe_pairs_held``, ``moe_rows_routed``, ``moe_rows_visited`` and
``moe_max_expert_load`` into its model state, and the step's ``metrics`` carry
them per layer, summed over the slots (`parallel.core.step_counters`). The
window's step hands on the loss alone and `run.py` frees its state before a
reader runs, so this module reads them from the System it builds for the
text: seed 0, after the warm-up's ``reference.STEPS`` steps (the harness's
`System` sets every weight from the seed, so the reading is one number a
commit). Summed over the layers here.

The System is built once per process, from the step cache on the chip. JAX's
compile-cache key leaves metadata out, so the loaded step may be a parent's
compile whose text names no routing step: the step is then compiled once more
with the metadata in the key, as `phase_map` does for phases. Nothing here
raises: where no map can be had — a program without the vocabulary, as the
parent of the PR that added it — the reason goes to standard error and every
reader returns None, having built nothing.
"""

import json
import re
import sys
import time

from . import phase_map

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STEP = re.compile(r"route\.(\w+)")
_SCOPE = re.compile(r"model\.(\w+)")
# The label of an instruction that holds no routing step: its model scope's.
MODEL = "model_"
ROUTING_SCOPES = ("moe_router", "moe_dispatch", "moe_combine")
COUNTERS = ("moe_pairs_held", "moe_rows_routed", "moe_rows_visited",
            "moe_max_expert_load")

_memo = {}
_said = set()


def _respell(match):
    parts = []
    for part in match.group(1).split(";"):
        step, scope = _STEP.search(part), _SCOPE.search(part)
        tag = step.group(1) if step else (
            MODEL + scope.group(1) if scope else "")
        parts.append("phase." + tag if tag else "")
    return 'op_name="' + ";".join(parts) + '"'


def label_route_text(text):
    """`phase_map.label_text` of ``text`` with each ``op_name`` respelled to
    its routing step, else to ``model_<outermost model scope>``;
    ``steps`` is the routing steps the text names."""
    made = phase_map.label_text(_OP_NAME.sub(_respell, text))
    made["steps"] = {p for p in made["phases"] if not p.startswith(MODEL)}
    return made


def _program_names_steps():
    """Whether the program under test has the vocabulary at all."""
    try:
        from garfield_tpu.models import lfm2
    except ImportError:
        return False
    return bool(getattr(lfm2, "ROUTE_STEPS", ()))


def _loaded(facts):
    """``(text, counters)`` of the cell's step as the harness loads it
    (`phase_map._compiled_text`'s way), the counters summed over layers
    after ``reference.STEPS`` steps from seed 0."""
    import jax
    import numpy as np

    from . import reference, system

    sut = system.System(facts["config"], facts["traffic"], 0,
                        system.enable_compile_cache())
    state = sut.state
    try:
        text = sut.compiled.as_text()
        if "op_name=" not in text:
            modules = sut.compiled.runtime_executable().hlo_modules()
            text = "\n".join(m.to_string() for m in modules)
        sut.forget_start()
        for i in range(reference.STEPS):
            state, metrics = sut.compiled(
                state, *sut.batches[i % len(sut.batches)])
        got = jax.device_get({n: metrics[n] for n in COUNTERS if n in metrics})
        counters = {n: float(np.max(v) if n == "moe_max_expert_load"
                             else np.sum(v)) for n, v in got.items()}
        return text, counters
    finally:
        sut.free(state)


def labels(facts):
    """The route map of the cell's step program with its ``counters``, made
    once per process; None, with the reason on standard error, where it
    cannot be had or the program names no routing step."""
    key = json.dumps([facts["config"], facts["traffic"]], sort_keys=True)
    if key not in _memo:
        t0 = time.perf_counter()
        try:
            if not _program_names_steps():
                raise ValueError("the program names no routing step")
            text, counters = _loaded(facts)
            made = label_route_text(text)
            if not made["steps"]:
                print("route map: the loaded step's text names no routing "
                      "step; compiling again with the metadata in the key",
                      file=sys.stderr)
                made = label_route_text(
                    phase_map._compiled_text(facts, fresh=True))
            if not made["steps"]:
                raise ValueError(
                    "the step program's text names no routing step")
            made["counters"] = counters
            print(f"route map: counters {json.dumps(counters)} (seed 0, "
                  "summed over layers)", file=sys.stderr)
        except Exception as err:
            print(f"route map: none ({err!r})", file=sys.stderr)
            made = None
        print(f"route map: made in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        _memo[key] = made
    return _memo[key]


def step_seconds(trace, facts):
    """``{label: device seconds per step}`` on the fullest device, or None
    where there is no map; the rows that hold a routing step or a routing
    scope go to standard error once, with the sum of those wholly in them."""
    made = labels(facts)
    if made is None:
        return None
    device = trace["fullest"]
    by_label, _ = phase_map.split(device["op_seconds"], made)
    per_step = {k: v / device["steps"] for k, v in by_label.items()}
    if id(trace) not in _said:
        _said.add(id(trace))
        routing = made["steps"] | {MODEL + s for s in ROUTING_SCOPES}
        rows = {k: round(1e3 * v, 4) for k, v in sorted(
            per_step.items(), key=lambda kv: -kv[1])
            if phase_map.label_phases(k) & routing}
        whole = sum(v for k, v in per_step.items()
                    if phase_map.label_phases(k)
                    and phase_map.label_phases(k) <= routing)
        print(f"route map: ms per step {json.dumps(rows)}; wholly in "
              f"routing {1e3 * whole:.4f}", file=sys.stderr)
    return per_step


def steps_ms(trace, facts, steps):
    """Device milliseconds per step of the operations wholly in ``steps``
    (every instruction they hold is in one of them); None where there is no
    map or the text names none of them."""
    made = labels(facts)
    if made is None or not set(steps) & made["steps"]:
        return None
    return 1e3 * sum(
        seconds for label, seconds in step_seconds(trace, facts).items()
        if phase_map.label_phases(label)
        and phase_map.label_phases(label) <= set(steps))


def counters(facts):
    """The expert layers' counters summed over layers (``moe_max_expert_load``
    their maximum), or None where there is no map."""
    made = labels(facts)
    return None if made is None else made["counters"]
