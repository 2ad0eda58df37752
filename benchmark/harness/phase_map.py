"""Which phase of the step each operation of a device trace belongs to.

The program names the phases of a step with ``jax.named_scope("phase.<name>")``
(`garfield_tpu/parallel/core.py`). A scope reaches the ``op_name`` metadata of
every instruction of the optimized HLO, the instructions inside a fused
computation included, and nothing else: on the chip a trace event's name is
the instruction's HLO line without its metadata. So the scope is joined in
through the instruction's name, from the text of the cell's compiled step
program: ``labels(facts)`` is ``{instruction name: label}``.

The label of a plain instruction is the outermost ``phase.<name>`` of its
``op_name``; of a fusion, the phases of the instructions in its fused
computation: one phase is that phase, several are ``mixed:<a>+<b>``, no
phase is ``none``. A control-flow container (``while``, ``conditional``,
``call``) whose body's operations are events of their own is left out of the
split, so that the phases, ``mixed`` and ``none`` add up to no more than the
device's busy time.

The compiled step comes through the harness's own `system.System`: on the
chip that loads the very bytes that ran from the step cache, on a CPU
rehearsal it compiles again. JAX's compile-cache key leaves metadata out, so
a cache that another source's runs share hands back their executable, whose
text names their scopes, or none: where the text names no phase though the
program has the vocabulary, the step is compiled once more with the metadata
in the key (the instructions' names are the same: metadata is no part of the
optimized program). The parse reads text and imports nothing. Nothing here
raises: where no map can be had, the reason goes to standard error and every
reader that needs the map returns None.
"""

import io
import json
import re
import sys

_PHASE = re.compile(r"phase\.(\w+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)")
_OPCODE = re.compile(r"(?:^|\s)([\w\-]+)\(")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
CONTAINERS = ("while", "conditional", "call")
# A line is read only as far as this (its metadata comes before): what
# follows in a Mosaic kernel's line is its whole payload.
_PAYLOAD = "backend_config="

_memo = {}
_said = set()


def name_of(event):
    """The instruction's name from a trace event's name: the whole HLO line
    on the chip (``%fusion.3 = bf16[8]{0} fusion(...)``), the bare name on
    XLA:CPU. Without the ``%``."""
    return event.split(" = ", 1)[0].strip().lstrip("%")


def phases_of(op_name):
    """The outermost phase of each operation an ``op_name`` names (merged
    instructions list several, ``;``-separated), ``jvp(`` / ``transpose(``
    wrappers and all."""
    found = set()
    for part in op_name.split(";"):
        match = _PHASE.search(part)
        if match:
            found.add(match.group(1))
    return found


def label_of(phases):
    if not phases:
        return "none"
    if len(phases) == 1:
        return next(iter(phases))
    return "mixed:" + "+".join(sorted(phases))


def label_phases(label):
    """The phases a label holds: none, one, or a mixed label's several."""
    if label == "none":
        return set()
    return set(label.removeprefix("mixed:").split("+"))


def parse(text):
    """``(instructions, computations)`` of an HLO module's text, read by
    lines: ``instructions[name] = (opcode, phases, called computations)``,
    ``computations[name] = [instruction names]``."""
    instructions, computations = {}, {}
    current = None
    for line in io.StringIO(text):
        if not line[:1].isspace():
            current = None
            if line.rstrip().endswith("{") and not line.startswith(
                    "HloModule"):
                current = line.removeprefix("ENTRY ").split(None, 1)[0]
                current = current.lstrip("%")
                computations[current] = []
            continue
        if current is None:
            continue
        cut = line.find(_PAYLOAD)
        head = line if cut < 0 else line[:cut]
        match = _INSTRUCTION.match(head)
        if not match:
            continue
        name, rest = match.groups()
        opcode = _OPCODE.search(rest)
        opcode = opcode.group(1) if opcode else ""
        called = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        at = head.rfind(" metadata={")
        op_name = _OP_NAME.search(head, at) if at >= 0 else None
        phases = phases_of(op_name.group(1)) if op_name else set()
        instructions[name] = (opcode, phases, called)
        computations[current].append(name)
    return instructions, computations


def _body(name, instructions, computations, seen=None):
    """Names of every instruction in the computations ``name`` calls, the
    computations those call included."""
    seen = set() if seen is None else seen
    for comp in instructions[name][2]:
        for inner in computations.get(comp, ()):
            if inner not in seen:
                seen.add(inner)
                _body(inner, instructions, computations, seen)
    return seen


def label_text(text):
    """``{"labels": {name: label}, "bodies": {container: names inside},
    "phases": every phase the text names}`` of an HLO module's text."""
    instructions, computations = parse(text)
    labels, bodies, named = {}, {}, set()
    for name, (opcode, phases, called) in instructions.items():
        named |= phases
        if called and (opcode == "fusion" or opcode in CONTAINERS
                       or not phases):
            body = _body(name, instructions, computations)
            inside = set().union(*(instructions[b][1] for b in body))
            phases = inside or phases
            if opcode in CONTAINERS:
                bodies[name] = frozenset(body)
        labels[name] = label_of(phases)
    return {"labels": labels, "bodies": bodies, "phases": named}


_KEY_HOLDS_METADATA = "jax_compilation_cache_include_metadata_in_key"


def _compiled_text(facts, fresh=False):
    """The optimized HLO text of the cell's step program, metadata
    included: of the step as the harness loads it, or with ``fresh`` lowered
    again and compiled under a cache key that holds its metadata."""
    import jax

    from . import system

    cache_dir = system.enable_compile_cache()
    before = getattr(jax.config, _KEY_HOLDS_METADATA)
    jax.config.update(_KEY_HOLDS_METADATA, fresh or before)
    try:
        sut = system.System(facts["config"], facts["traffic"], 0,
                            None if fresh else cache_dir)
    finally:
        jax.config.update(_KEY_HOLDS_METADATA, before)
    try:
        text = sut.compiled.as_text()
        if "op_name=" not in text:
            # An executable handed back without its text's metadata.
            modules = sut.compiled.runtime_executable().hlo_modules()
            text = "\n".join(m.to_string() for m in modules)
        return text
    finally:
        sut.free(sut.state)


def _program_names_phases():
    """Whether the program under test has the vocabulary at all: the one
    question this module asks the program itself."""
    try:
        from garfield_tpu.parallel import core
    except ImportError:
        return False
    return bool(getattr(core, "PHASES", ()))


def labels(facts):
    """The map of the cell's step program (`label_text`), made once per
    process; None, with the reason on standard error, where it cannot be
    had or the program's text names no phase."""
    key = json.dumps([facts["config"], facts["traffic"]], sort_keys=True)
    if key not in _memo:
        try:
            made = label_text(_compiled_text(facts))
            if not made["phases"] and _program_names_phases():
                print("phase map: the loaded step's text names no phase "
                      "(a compile cache another source shares?); compiling "
                      "again with the metadata in the key", file=sys.stderr)
                made = label_text(_compiled_text(facts, fresh=True))
            if not made["phases"]:
                raise ValueError(
                    "the step program's text names no phase scope")
        except Exception as err:
            print(f"phase map: none ({err!r})", file=sys.stderr)
            made = None
        _memo[key] = made
    return _memo[key]


def _labelled(op_seconds, made):
    """``(label, event, seconds)`` of every traced operation, the label None
    for a container whose body's operations are events of their own."""
    names = {name_of(event) for event in op_seconds}
    for event, seconds in op_seconds.items():
        name = name_of(event)
        if made["bodies"].get(name, frozenset()) & names:
            yield None, event, seconds
        else:
            yield made["labels"].get(name, "none"), event, seconds


def split(op_seconds, made):
    """``({label: seconds}, seconds left out)`` of a trace's
    ``{event name: seconds}``: every operation's time under its label
    (``none`` where the map does not know the name), but for the containers
    whose body's operations are events of their own."""
    by_label = {}
    for label, _, seconds in _labelled(op_seconds, made):
        by_label[label] = by_label.get(label, 0.0) + seconds
    return by_label, by_label.pop(None, 0.0)


def step_seconds(trace, facts):
    """``{label: device seconds per step}`` on the fullest device, or None
    where there is no map."""
    made = labels(facts)
    if made is None:
        return None
    device = trace["fullest"]
    by_label, left_out = split(device["op_seconds"], made)
    per_step = {k: v / device["steps"] for k, v in by_label.items()}
    if id(trace) not in _said:
        _said.add(id(trace))
        _say(device, made, per_step, left_out)
    return per_step


def _say(device, made, per_step, left_out):
    """The whole split on standard error, for the record: each label's
    milliseconds per step, by kind of operation and with its largest single
    operation — which pairs ``mixed`` holds, and whose the copies are."""
    from . import reduce_trace

    steps = device["steps"]
    kinds, largest = {}, {}
    for label, event, seconds in _labelled(device["op_seconds"], made):
        if label is None:
            continue
        by_kind = kinds.setdefault(label, {})
        kind = reduce_trace.op_kind(event)
        by_kind[kind] = by_kind.get(kind, 0.0) + 1e3 * seconds / steps
        if seconds > largest.get(label, ("", 0.0))[1]:
            largest[label] = (reduce_trace.short_name(event), seconds)
    rows = {
        label: {"ms": round(1e3 * per_step[label], 4),
                "kinds": {k: round(v, 4) for k, v in sorted(
                    kinds[label].items(), key=lambda kv: -kv[1])[:4]},
                "largest": largest[label][0]}
        for label in sorted(per_step, key=per_step.get, reverse=True)}
    print(f"phase map: ms per step {json.dumps(rows)}; containers left out "
          f"{1e3 * left_out / steps:.4f}; busy "
          f"{1e3 * device['busy_s'] / steps:.4f}", file=sys.stderr)


def phase_ms(trace, facts, phase):
    """Device milliseconds per step of the operations wholly in ``phase``:
    0.0 where the program's text names the phase and no traced operation
    carries it, None where the text does not name it (or there is no
    map)."""
    made = labels(facts)
    if made is None or phase not in made["phases"]:
        return None
    return 1e3 * step_seconds(trace, facts).get(phase, 0.0)


def holding_seconds(trace, facts, phase):
    """Device seconds per step of every operation that holds an instruction
    of ``phase``, the mixed ones included; None where there is no map or no
    such operation ran."""
    by_label = step_seconds(trace, facts)
    if by_label is None:
        return None
    total = sum(s for label, s in by_label.items()
                if phase in label_phases(label))
    return total or None
