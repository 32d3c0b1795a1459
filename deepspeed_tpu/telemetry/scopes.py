"""Device time by the program's own scopes.

The programs name their phases with ``jax.named_scope`` — metadata only,
no operation added or moved:

- the fused train step (``runtime/engine.py``): ``unpack``,
  ``loss_and_grads`` ⊃ ``grad_flatten``, ``grad_exchange`` (the ZeRO
  reduce-scatter / all-reduce, at stage 3 the gathers too), ``optimizer``
  (unscale, norm and clip, the update, loss-scale state), ``cast_params``;
  forward and backward need no scope: JAX writes ``jvp(…)`` and
  ``transpose(jvp(…))`` into an operation's name;
- every model, the same words: ``embed``, ``layer_<i>`` (a looped body
  under ``ut_loop``) ⊃ ``attention`` (norm, projections, rotary, cache
  write, the kernel, the output projection; ⊃ ``sparse_select`` (the
  choice of blocks and nothing else) and ``sparse_attention`` on a
  block-selected layer, ⊃ ``lightning`` ⊃
  ``state_update`` on a linear-attention one) and ``mlp`` or ``moe`` ⊃
  ``router``, ``experts``, ``shared_experts``; ``final_norm``,
  ``lm_head``, ``sample`` (the argmax and what the decode fetch reads);
  BERT's ``pooler``, ``mlm_head``, ``loss``.

A device trace names an event by its compiled instruction
(``%fusion.12 = …``) and carries nothing of where the instruction came
from; the compiled program does (``metadata={op_name="jit(train_step)/
loss_and_grads/transpose(jvp(layer_3))/attention/add_any"}``).
:func:`scope_map` makes the join from one compiled program's text,
:func:`program_scopes` for every program an engine keeps, and
:func:`reduce_xplane` sums a trace by scope with it — what
``DeviceTraceTrigger`` leaves as ``scopes.json`` beside its trace.

Everything here runs when asked: an engine builds no map at set-up or on
the step path.  A program loaded from the persistent compile cache
carries the metadata of whoever compiled it first (the cache key leaves
metadata out), so an entry made by a tree without these scopes maps to
nothing: :func:`placed_share` says how much of a map found a scope, and
the reductions report the time they could not place.
"""

import json
import os
import re

from ..utils.logging import logger

FWD, BWD, NO_DIRECTION = "fwd", "bwd", ""
# path components that are JAX's own structure, not a scope of the program
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "rematted_computation", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "shard_map", "pallas_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_SCOPE = re.compile(r"^[A-Za-z_][\w\-]*$")
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")
# opcodes whose instructions never run as an event of their own
_NO_EVENT = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id"))
# events that hold other events: counted nowhere, their children are
CONTAINERS = frozenset(("while", "conditional", "call"))

# a line of a compiled module's text, and an ``XLA Ops`` event's name
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_REFERENCE = re.compile(r"%([\w.\-]+)")


def _split(op_name):
    """``op_name`` cut at the slashes that stand outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def scope_of(op_name):
    """``(scope path, direction)`` of one instruction's ``op_name``.

    The path keeps the program's scopes in order (``layer_<i>`` as it
    stands: a reader may fold it) and drops what is JAX's: the ``jit(…)``
    wrappers, the primitive at the end, ``while/body`` and the like, an
    einsum's subscripts, a repeat of the outer scopes (``jax.checkpoint``
    writes them again before the recomputation).  The transforms become
    the direction: ``bwd`` under a ``transpose(…)`` or in a
    rematerialised computation, ``fwd`` under a ``jvp(…)`` alone, none
    outside a differentiated function."""
    # where the compiler merged instructions it joined their names
    parts = _split(op_name.split(";")[0])
    if parts and not _WRAPPED.match(parts[-1]):
        parts.pop()       # the primitive
    path, transforms = [], set()
    for part in parts:
        wrapped = _WRAPPED.match(part)
        while wrapped:
            transforms.add(wrapped.group(1))
            if wrapped.group(1) in ("jit", "pjit"):
                part = ""
                break
            part = wrapped.group(2)
            wrapped = _WRAPPED.match(part)
        if part == "rematted_computation":
            transforms.add("transpose")
        if (_SCOPE.match(part) and part not in _STRUCTURAL
                and not _BRANCH.match(part) and part not in path):
            path.append(part)
    direction = (BWD if "transpose" in transforms
                 else FWD if "jvp" in transforms else NO_DIRECTION)
    return "/".join(path), direction


def _computations(text):
    """``{computation: [(instruction, opcode, line)]}`` and the entry
    computation's name, from a compiled module's text."""
    out, entry, current = {}, None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = out.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                current.append((m.group(1), m.group(2), line))
    return out, entry


def _event_computations(computations, entry):
    """The computations whose instructions can show as events: the entry
    and, from there, the bodies and conditions of loops, the branches of
    conditionals and what a ``call`` calls — not a fusion's computation
    and not a reduction's."""
    seen, todo = [], [entry]
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.append(name)
        for _, opcode, line in computations[name]:
            if opcode in CONTAINERS:
                for one, many in _CALLED.findall(line):
                    todo += [one] if one else _REFERENCE.findall(many)
    return seen


def scope_map(compiled):
    """``{instruction name: (scope path, direction)}`` of a compiled
    program (or of its text): every instruction that can show as an event
    of a device trace, a fusion under the metadata the compiler left on
    it.  An instruction the compiler made without metadata (a copy, the
    start and the wait of a prefetch) stands where the instruction that
    consumes it stands, else where its operand does; one that finds no
    scope either way maps to ``("", "")``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    computations, entry = _computations(text)
    out = {}
    for name in _event_computations(computations, entry):
        instructions = computations[name]
        placed, operands = {}, {}
        for instruction, _, line in instructions:
            m = _OP_NAME.search(line)
            scope = scope_of(m.group(1).replace("\\'", "'")) if m \
                else ("", NO_DIRECTION)
            if scope[0]:
                placed[instruction] = scope
            operands[instruction] = _REFERENCE.findall(
                line.split(" = ", 1)[1])
        users = {}
        for instruction, reads in operands.items():
            for read in reads:
                users.setdefault(read, []).append(instruction)
        todo = [i for i, _, _ in instructions if i not in placed]
        while todo:
            found = {}
            for instruction in todo:
                near = [placed[n] for n in users.get(instruction, [])
                        + operands[instruction] if n in placed]
                if near:
                    found[instruction] = near[0]
            if not found:
                break
            placed.update(found)
            todo = [i for i in todo if i not in found]
        for instruction, opcode, _ in instructions:
            if opcode not in _NO_EVENT:
                out[instruction] = placed.get(instruction,
                                              ("", NO_DIRECTION))
    return out


def module_name(compiled):
    """The module's name as a device trace prints it (``jit_train_step``,
    ``jit_decode``, ``jit_prefill_128``)."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    m = re.match(r"HloModule ([\w.\-]+)", text)
    return m.group(1) if m else None


def placed_share(scopes):
    """The share of a map's instructions that found a scope."""
    if not scopes:
        return 0.0
    return sum(1 for path, _ in scopes.values() if path) / len(scopes)


def program_scopes(compiled_programs):
    """``{module name: scope map}`` of ``{program name: compiled}`` (an
    engine's ``memory_ledger.compiled_programs()``).  Two programs that
    compiled under one module name cannot be told apart in a trace: the
    first is kept and the fact logged."""
    out = {}
    for name, compiled in compiled_programs.items():
        text = compiled.as_text()
        module = module_name(text)
        if module in out:
            logger.warning("program_scopes: %s is a second program named "
                           "%s; left out", name, module)
            continue
        out[module] = scope_map(text)
    return out


# -- a trace by scope ------------------------------------------------------

_RUN = re.compile(r"^([\w.\-]+)\(")


def _device_events(planes):
    """``(module, instruction, seconds)`` of the first chip's operations,
    each given to the run of the module (``XLA Modules`` line) that holds
    it, loops and conditionals left out; and the runs by module."""
    lines = {line.name: line for line in
             min(planes, key=lambda p: p.name).lines}
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   _RUN.match(e.name).group(1))
                  for e in (lines["XLA Modules"].events
                            if "XLA Modules" in lines else [])
                  if _RUN.match(e.name))
    events, at = [], 0
    for e in sorted(lines["XLA Ops"].events if "XLA Ops" in lines else [],
                    key=lambda e: e.start_ns):
        while at < len(runs) and runs[at][1] <= e.start_ns:
            at += 1
        if at == len(runs) or runs[at][0] > e.start_ns:
            continue
        # the event's name is the instruction's line of the module's text
        m = _INSTRUCTION.match(e.name)
        if m and m.group(2) not in CONTAINERS:
            events.append((runs[at][2], m.group(1), e.duration_ns * 1e-9))
    counts = {}
    for _, _, module in runs:
        counts[module] = counts.get(module, 0) + 1
    return events, counts


def _host_events(plane):
    """The same of XLA's CPU backend, whose operations are events of the
    host plane's worker threads that name their module and their run."""
    events, runs = [], {}
    for line in plane.lines:
        for e in line.events:
            stats = dict(e.stats)
            if "hlo_module" not in stats or "hlo_op" not in stats:
                continue
            module, instruction = stats["hlo_module"], stats["hlo_op"]
            runs.setdefault(module, set()).add(stats.get("run_id"))
            if instruction.split(".")[0] not in CONTAINERS:
                events.append((module, instruction, e.duration_ns * 1e-9))
    return events, {module: len(ids) for module, ids in runs.items()}


def reduce_xplane(path, scopes):
    """Seconds by scope and direction for every program that ran in the
    ``.xplane.pb`` at ``path`` (first chip; the host's threads where XLA's
    CPU backend ran it), joined with ``scopes`` as :func:`program_scopes`
    gives them: ``{module: {"runs", "seconds", "unplaced_s", "by_scope":
    [[path, direction, seconds], …]}}``.  Each operation is given to the
    run of the module that holds it; loops and conditionals, which hold
    their children, are counted nowhere."""
    import warnings

    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        # the stats iterator's type has no __module__ (jaxlib 0.9.0)
        warnings.simplefilter("ignore", DeprecationWarning)
        planes = list(ProfileData.from_file(path).planes)
        chips = [p for p in planes if p.name.startswith("/device:TPU:")]
        host = [p for p in planes if p.name == "/host:CPU"]
        events, runs = (_device_events(chips) if chips
                        else _host_events(host[0]) if host else ([], {}))
    out = {}
    for module, instruction, seconds in events:
        entry = out.setdefault(module, {
            "runs": runs.get(module, 0), "seconds": 0.0, "unplaced_s": 0.0,
            "by_scope": {}})
        scope, direction = scopes.get(module, {}).get(
            instruction, ("", NO_DIRECTION))
        entry["seconds"] += seconds
        if scope:
            key = (scope, direction)
            entry["by_scope"][key] = entry["by_scope"].get(key, 0.0) + seconds
        else:
            entry["unplaced_s"] += seconds
    for entry in out.values():
        entry["by_scope"] = [[scope, direction, seconds] for
                             (scope, direction), seconds in sorted(
                                 entry["by_scope"].items(),
                                 key=lambda kv: -kv[1])]
    return out


def fold_layers(scope):
    """``layer_<i>`` read as ``layer``: one line for all the layers."""
    return re.sub(r"\blayer_\d+\b", "layer", scope)


def largest(reduced, top=10):
    """The ``top`` largest ``(module, scope, direction, seconds)`` of
    :func:`reduce_xplane`'s result, the layers folded."""
    folded = {}
    for module, entry in reduced.items():
        for scope, direction, seconds in entry["by_scope"]:
            key = (module, fold_layers(scope), direction)
            folded[key] = folded.get(key, 0.0) + seconds
    return sorted(((*key, seconds) for key, seconds in folded.items()),
                  key=lambda row: -row[-1])[:top]


def ms_per_run(entry):
    """``{(scope path, direction): device milliseconds a run}`` of one
    program's entry of :func:`reduce_xplane`'s result (or of a
    ``scopes.json``): what ``FlopsProfiler.profile_train_step`` takes as
    ``device_ms_by_scope``."""
    runs = max(entry.get("runs", 0), 1)
    return {(scope, direction): 1e3 * seconds / runs
            for scope, direction, seconds in entry["by_scope"]}


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output directory,
    None where there is none."""
    found = []
    for folder, _, names in os.walk(trace_dir):
        found += [os.path.join(folder, n) for n in names
                  if n.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def write_scopes_json(trace_dir, scopes):
    """Reduce the newest trace under ``trace_dir`` by ``scopes`` and leave
    ``<trace_dir>/scopes.json``; returns the reduction (None where no
    trace was found)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    reduced = reduce_xplane(path, scopes)
    record = {
        "trace": os.path.relpath(path, trace_dir),
        "placed_share_of_instructions": {
            module: placed_share(m) for module, m in scopes.items()},
        "programs": reduced}
    with open(os.path.join(trace_dir, "scopes.json"), "w",
              encoding="utf-8") as f:
        json.dump(record, f, indent=1)
    return reduced
