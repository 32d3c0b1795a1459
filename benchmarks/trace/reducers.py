"""Reductions from a :class:`~benchmarks.trace.xplane.Trace` to one number.

Each takes ``(trace, args, ctx)``: ``args`` from the metric's file,
``ctx`` from the run (``steps`` traced, ``window_s``, ``device_kind``,
``counts``).  A reducer that finds nothing to read returns None, and the
harness leaves the metric out of the line.
"""

import re
import statistics

from .. import peaks
from .xplane import opcode

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?$")


_FUSION_KIND = re.compile(r" fusion\(.*kind=(k\w+)")


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    return sum(b - a for a, b in merge(intervals))


def merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def subtract(intervals, holes):
    """The parts of merged ``intervals`` not covered by merged ``holes``."""
    out, holes, j = [], merge(holes), 0
    for a, b in merge(intervals):
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def busy_seconds(trace):
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace; None for a trace with no device operation."""
    if not trace.ops or not any(trace.ops.values()):
        return None
    per_chip = [union_seconds([(e.start, e.end) for e in ops])
                for ops in trace.ops.values()]
    return sum(per_chip) / len(per_chip)


def idle_percent(trace, args, ctx):
    busy = busy_seconds(trace)
    if busy is None:
        return None
    # not clipped at 0: a busy share over 100% means a wrong window, and
    # has to show
    return 100.0 * (1.0 - busy / ctx["window_s"])


def _first_chip(table):
    return table[min(table)] if table else []


def busy_ms_per_step(trace, args, ctx):
    busy = busy_seconds(trace)
    return None if busy is None else 1e3 * busy / ctx["steps"]


def _matching(trace, pattern):
    rx = re.compile(pattern)
    return [e for e in _first_chip(trace.ops) if rx.search(e.text)]


def op_ms_per_step(trace, args, ctx):
    """Summed device time per step of the operations whose text matches
    ``pattern``, on the first chip."""
    found = _matching(trace, args["pattern"])
    if not found:
        return None
    return 1e3 * sum(e.duration for e in found) / ctx["steps"]


def kernel_roofline(trace, args, ctx):
    """The matching kernel calls' share of their roofline.  The benchmark's
    counts are per layer (``calls_per_layer`` kernel calls: forward and
    backward); the layers are the calls found in the trace, so a layer
    that went another way is neither counted nor timed."""
    found = _matching(trace, args["pattern"])
    if not found:
        return None
    layers = len(found) / args["calls_per_layer"]
    share, _ = peaks.roofline_percent(
        layers * ctx["counts"][args["flops"]],
        layers * ctx["counts"][args["bytes"]],
        sum(e.duration for e in found), ctx["device_kind"])
    return share


def module_ms(trace, args, ctx):
    """Device time of one run of the compiled programs whose name matches
    ``pattern``: ``reduce`` is median or mean over the traced runs."""
    rx = re.compile(args["pattern"])
    runs = [e.duration for e in _first_chip(trace.modules)
            if rx.search(e.text)]
    if not runs:
        return None
    how = statistics.median if args.get("reduce") == "median" \
        else statistics.fmean
    return 1e3 * how(runs)


def module_roofline(trace, args, ctx):
    """Bytes that one run of the matching program must move (the run's own
    count) over the HBM peak, over the program's device time."""
    ms = module_ms(trace, args, ctx)
    if not ms:
        return None
    share, _ = peaks.roofline_percent(
        ctx["counts"].get(args.get("flops"), 0.0),
        ctx["counts"][args["bytes"]], ms * 1e-3, ctx["device_kind"])
    return share


def _collective_intervals(trace):
    chip = min(trace.ops)
    sync = [(e.start, e.end) for e in trace.ops[chip]
            if COLLECTIVE.match(opcode(e.text))]
    asyn = [(e.start, e.end) for e in trace.async_ops.get(chip, [])
            if COLLECTIVE.match(opcode(e.text))]
    compute = [(e.start, e.end) for e in trace.ops[chip]
               if not COLLECTIVE.match(opcode(e.text))]
    return sync + asyn, compute


def collective_ms_per_step(trace, args, ctx):
    """All-gather + reduce-scatter (+ any other collective) device time per
    step on the first chip: the union of their spans, start to done."""
    if not trace.ops:
        return None
    collectives, _ = _collective_intervals(trace)
    if not collectives:
        return None
    return 1e3 * union_seconds(collectives) / ctx["steps"]


def collective_exposed_ms_per_step(trace, args, ctx):
    """The part of the collectives' time during which no other operation
    runs on that chip."""
    if not trace.ops:
        return None
    collectives, compute = _collective_intervals(trace)
    if not collectives:
        return None
    exposed = subtract(collectives, compute)
    return 1e3 * sum(b - a for a, b in exposed) / ctx["steps"]


def breakdown(trace, top=10, min_gap=20e-6):
    """The device operations that took most time, and the longest idle
    gaps by the harness span the host was in (first chip)."""
    ops = _first_chip(trace.ops)
    if not ops:
        return None
    by_name = {}
    for e in ops:
        kind = _FUSION_KIND.search(e.text)
        name = f"{e.name}:{kind.group(1)}" if kind else e.name
        by_name[name] = by_name.get(name, 0.0) + e.duration
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = {}
    busy = merge([(e.start, e.end) for e in ops])
    for (_, end), (start, _) in zip(busy[:-1], busy[1:]):
        if start - end < min_gap:
            continue
        mid = 0.5 * (start + end)
        inside = [s for s in trace.spans if s.start <= mid <= s.end]
        name = (min(inside, key=lambda s: s.duration).name
                if inside else "_no_span_")
        gaps[name] = gaps.get(name, 0.0) + (start - end)
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}


REDUCERS = {f.__name__: f for f in (
    idle_percent, busy_ms_per_step, op_ms_per_step, kernel_roofline,
    module_ms, module_roofline, collective_ms_per_step,
    collective_exposed_ms_per_step)}
