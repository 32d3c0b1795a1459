"""A device trace read by the program's own scopes.

The program names its phases with ``jax.named_scope`` and hands out the
join from a compiled instruction to its scope:
``engine.program_scopes()`` is ``{module name as the trace prints it:
{instruction name: (scope path, direction)}}`` (``deepspeed_tpu/telemetry/
scopes.py``), plain data that this module takes from ``ctx["scopes"]``.
The trace is the harness's own (:class:`~benchmarks.trace.xplane.Trace`).

Each ``XLA Ops`` event of the first chip is given to the run of the module
(``XLA Modules`` line) that holds it, so ``fusion.12`` of ``jit_decode`` is
not ``fusion.12`` of a prefill; its full instruction name comes from the
event's text (``Event.name`` has lost its number); container events
(``while``, ``conditional``, ``call``), whose children are events too, are
left out of every sum.

Reducers have the signature of ``reducers.py`` and return None where
there is nothing to read: a program without the scopes (any commit before
PR 38), a harness that puts no ``scopes`` into ``ctx``.  Not wired into
``benchmarks.run`` yet: ``python -m benchmarks.scope_probe`` runs them on
the chip (PERF.md, Open question 4 (h)).
"""

import re
import statistics

from . import reducers, xplane

CONTAINERS = ("while", "conditional", "call")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_MODULE = re.compile(r"^([\w.\-]+)\(")
_LAYER = re.compile(r"\blayer_\d+\b")
_BUCKET = re.compile(r"^(jit_prefill)_\d+$")


def instruction_name(text):
    """``fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text.split("(")[0].strip()


def module_name(text):
    """``jit_decode`` of the module event ``jit_decode(5367…)``."""
    m = _MODULE.match(text)
    return m.group(1) if m else text


def scoped_events(trace, scopes):
    """``[(run index, module, event, scope path, direction)]`` for every
    operation of the first chip that ran inside a module's run, containers
    left out; path ``""`` where ``scopes`` does not place the instruction.
    The runs are those of the ``XLA Modules`` line in order of start."""
    if not trace.ops or not trace.modules:
        return []
    chip = min(trace.ops)
    runs = sorted(trace.modules.get(chip, []), key=lambda r: r.start)
    names = [module_name(r.text) for r in runs]
    out, at = [], 0
    for e in sorted(trace.ops[chip], key=lambda e: e.start):
        while at < len(runs) and runs[at].end <= e.start:
            at += 1
        if at == len(runs) or runs[at].start > e.start:
            continue      # outside every traced run
        if xplane.opcode(e.text) in CONTAINERS:
            continue
        scope, direction = (scopes or {}).get(names[at], {}).get(
            instruction_name(e.text), ("", ""))
        out.append((at, names[at], e, scope, direction))
    return out


def _events(trace, ctx):
    """:func:`scoped_events` of ``ctx["scopes"]``, made once a trace."""
    kept = ctx.get("_scoped_events")
    if kept is None or kept[0] is not trace:
        kept = ctx["_scoped_events"] = (
            trace, scoped_events(trace, ctx.get("scopes")))
    return kept[1]


def _listed(value):
    return [value] if isinstance(value, str) else list(value)


def _matches(scope, direction, args):
    """Whether a placed event counts for ``args``: one of ``scope``'s names
    is a component of its path, and its direction is ``direction`` (a name
    or several; left out: any)."""
    if not set(_listed(args["scope"])) & set(scope.split("/")):
        return False
    wanted = args.get("direction")
    return wanted is None or direction in _listed(wanted)


def scope_ms(trace, args, ctx):
    """Device milliseconds under a scope: the operations of the runs of
    the modules matching ``module`` whose scope path holds ``scope`` (a
    name, or several: their sum) as a component, ``layer_<i>`` or not, in
    ``direction`` (``fwd``, ``bwd``, ``""`` for neither, a list, or left
    out for any).  ``per`` ``step``: summed over the trace ÷ ``ctx["steps"]``;
    ``run_median``: the median over the matching runs of one run's sum.
    Overlapping operations count once.  None where no map places an
    operation of those runs."""
    rx = re.compile(args["module"])
    by_run, placed = {}, False
    for run, module, e, scope, direction in _events(trace, ctx):
        if not rx.search(module):
            continue
        spans = by_run.setdefault(run, [])
        if scope:
            placed = True
            if _matches(scope, direction, args):
                spans.append((e.start, e.end))
    if not placed:
        return None
    sums = [reducers.union_seconds(spans) for spans in by_run.values()]
    if args.get("per", "step") == "run_median":
        return 1e3 * statistics.median(sums)
    return 1e3 * sum(sums) / ctx["steps"]


def unscoped_pct(trace, args, ctx):
    """The share of the operations' time, in the runs of the modules
    matching ``module`` (left out: every run), that no map gives a scope:
    100 for a program without scopes or a map made of another compile."""
    rx = re.compile(args.get("module", ""))
    total, unplaced = [], []
    for _, module, e, scope, _ in _events(trace, ctx):
        if rx.search(module):
            total.append((e.start, e.end))
            if not scope:
                unplaced.append((e.start, e.end))
    if not total:
        return None
    return (100.0 * reducers.union_seconds(unplaced)
            / reducers.union_seconds(total))


def fold(module, scope):
    """One line for all the layers and all of prefill's buckets."""
    return (_BUCKET.sub(r"\1", module), _LAYER.sub("layer", scope))


def breakdown_by_scope(trace, scopes, top=10):
    """Where the traced device time went, by the program's scopes:
    ``device_scopes`` the ``top`` largest ``[module:scope[.direction],
    seconds]`` with ``layer_<i>`` and the prefill buckets folded;
    ``prefetch_wait_s`` the ``…-done`` waits (counted under their scopes
    too); ``unplaced_s``, and ``scoped_s`` + ``unplaced_s`` beside
    ``busy_s`` (the union of every operation, containers or not)."""
    events = scoped_events(trace, scopes)
    if not events:
        return None
    by_name, waits, unplaced = {}, [], []
    for _, module, e, scope, direction in events:
        span = (e.start, e.end)
        if xplane.opcode(e.text).endswith("-done"):
            waits.append(span)
        if not scope:
            unplaced.append(span)
            continue
        module, scope = fold(module, scope)
        name = f"{module}:{scope}" + (f".{direction}" if direction else "")
        by_name.setdefault(name, []).append(span)
    seconds = {name: reducers.union_seconds(spans)
               for name, spans in by_name.items()}
    largest = sorted(seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_scopes": [[k, v] for k, v in largest],
            "scoped_s": sum(seconds.values()),
            "unplaced_s": reducers.union_seconds(unplaced),
            "prefetch_wait_s": reducers.union_seconds(waits),
            "busy_s": reducers.busy_seconds(trace)}


REDUCERS = {f.__name__: f for f in (scope_ms, unscoped_pct)}
