"""The program's own spans in a device trace, on the device's clock.

``deepspeed_tpu``'s one span call (``TelemetryManager.span``) writes a
``jax.profiler.TraceAnnotation`` named ``ds:<name>``, so any profiler
session holds the program's host phases on the host plane beside the
harness's ``bench:`` spans.  This module reads them (``read``), puts host
and device stamps on one clock (``align``), says in which span the host
was while the device sat idle (``idle_by_span``) and reduces all that to
single numbers (``REDUCERS``, with the signature of
``benchmarks/trace/reducers.py``).

A program without such spans (any commit before PR 24) gives a trace with
no ``ds:`` event: every reducer here then returns None and ``breakdown``
names the harness's spans alone, as ``reducers.breakdown`` does.

Not wired into ``benchmarks.run`` yet: ``python -m benchmarks.span_probe``
is what runs it on the chip (PERF.md, Open questions).
"""

import fnmatch
import re
import statistics
import warnings
from dataclasses import dataclass, field

from .. import common
from . import reducers, xplane

PROGRAM_PREFIX = "ds:"
AMBIGUOUS, NO_SPAN = "_ambiguous_", "_no_span_"

# How host spans bound the runs of a compiled program (first chip).
# opens: the k-th span of that name opens before the k-th run of the
# module starts (the span holds the call that enqueues it).
OPENS = (
    ("^jit_decode", "ds:decode.dispatch"),
    ("^jit_prefill", "ds:prefill.dispatch"),
    ("^jit_train_step", "ds:dispatch"),
)
# closes: the k-th span closes after run k - lag has ended (the span holds
# a blocking fetch of that run's output; the training harness waits two
# steps behind its dispatches); lag None: after the LAST run.
CLOSES = (
    ("^jit_decode", "ds:decode.fetch", 0),
    ("^jit_prefill", "ds:prefill.fetch", 0),
    ("^jit_train_step", "bench:wait_step", 2),
    ("^jit_train_step", "bench:fence", None),
)


@dataclass
class Span(xplane.Event):
    args: dict = field(default_factory=dict)


@dataclass
class ProgramTrace(xplane.Trace):
    program_spans: list = field(default_factory=list)   # ds: spans, host


@dataclass
class Alignment:
    """Host stamps minus ``offset`` are device stamps, to within
    ``uncertainty`` (both seconds; None where no pairing was found, and
    host stamps are then taken as they are).  ``consistent`` is False
    where the two bounds cross: the clocks drifted inside the trace."""
    offset: float = 0.0
    uncertainty: float = None
    consistent: bool = True
    pairs: int = 0


def read(path):
    """``xplane.read`` plus the program's spans with their arguments."""
    from jax.profiler import ProfileData

    base = xplane.read(path)
    trace = ProgramTrace(ops=base.ops, async_ops=base.async_ops,
                         modules=base.modules, spans=base.spans)
    with warnings.catch_warnings():
        # the stats iterator's type has no __module__ (jaxlib 0.9.0)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        trace.program_spans.append(Span(
                            e.name[len(PROGRAM_PREFIX):], e.name,
                            e.start_ns * 1e-9, e.duration_ns * 1e-9,
                            dict(e.stats)))
    trace.program_spans.sort(key=lambda s: s.start)
    return trace


def program_spans(trace):
    return getattr(trace, "program_spans", [])


def _host_spans(trace):
    """Every host span under the name the breakdown prints: the program's
    with their prefix, the harness's bare (as ``reducers.breakdown``)."""
    return ([(s.text, s) for s in program_spans(trace)]
            + [(s.name, s) for s in trace.spans])


def _named(trace, text):
    """Host spans by their full name (``ds:...`` or ``bench:...``)."""
    if text.startswith(PROGRAM_PREFIX):
        return [s for s in program_spans(trace) if s.text == text]
    return [s for s in trace.spans if s.text == text]


def _runs(trace, pattern):
    rx = re.compile(pattern)
    table = trace.modules
    return [m for m in (table[min(table)] if table else [])
            if rx.search(m.text)]


def align(trace):
    """The host clock's offset from the device's, from what must hold: a
    program cannot start before the span that enqueues it opens, nor end
    after the span that fetches its output closes."""
    lower, upper = [], []
    for pattern, name in OPENS:
        runs, spans = _runs(trace, pattern), _named(trace, name)
        if runs and len(runs) == len(spans):
            lower += [s.start - r.start for s, r in zip(spans, runs)]
    for pattern, name, lag in CLOSES:
        runs, spans = _runs(trace, pattern), _named(trace, name)
        if not runs or not spans:
            continue
        if lag is None:
            upper.append(spans[-1].end - runs[-1].end)
        elif len(runs) == len(spans):
            upper += [s.end - r.end for s, r in zip(spans[lag:], runs)]
    if not lower or not upper:
        return Alignment()
    lo, hi = max(lower), min(upper)
    return Alignment(offset=0.5 * (lo + hi), uncertainty=0.5 * abs(hi - lo),
                     consistent=lo <= hi, pairs=len(lower) + len(upper))


def idle_gaps(trace, min_gap=20e-6):
    """(end of one device operation, start of the next) wherever the first
    chip ran nothing for ``min_gap`` or longer; the trace's two edges are
    not gaps."""
    ops = trace.ops[min(trace.ops)] if trace.ops else []
    busy = reducers.merge([(e.start, e.end) for e in ops])
    return [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])
            if start - end >= min_gap]


def idle_by_span(trace, alignment=None, min_gap=20e-6):
    """Idle seconds of the first chip by the innermost host span — the
    program's or the harness's — that the host was in.  A gap is cut where
    spans begin and end.  The clock's uncertainty moves every host stamp
    together, so a piece keeps its length unless an edge of the gap cuts
    it: a piece is named where it is longer than the uncertainty or lies
    farther than that from both edges of its gap, and goes to
    ``_ambiguous_`` otherwise; one in no span goes to ``_no_span_``.  The
    values add up to the gaps' total."""
    alignment = alignment or align(trace)
    shift, blur = alignment.offset, alignment.uncertainty or 0.0
    longer, within = blur * (1 + 1e-6), blur * (1 - 1e-6)
    spans = [(s.start - shift, s.end - shift, name)
             for name, s in _host_spans(trace)]
    out = {}
    for a, b in idle_gaps(trace, min_gap):
        near = [s for s in spans if s[0] < b and s[1] > a]
        cuts = sorted({a, b} | {t for s in near for t in s[:2] if a < t < b})
        pieces = []   # [innermost span or None, from, to], neighbours merged
        for p, q in zip(cuts, cuts[1:]):
            mid = 0.5 * (p + q)
            inside = [s for s in near if s[0] <= mid <= s[1]]
            inner = min(inside, key=lambda s: s[1] - s[0], default=None)
            if pieces and pieces[-1][0] is inner:
                pieces[-1][2] = q
            else:
                pieces.append([inner, p, q])
        for inner, p, q in pieces:
            # under a nanosecond: two stamps that differ by rounding
            firm = q - p >= 1e-9 and (
                q - p > longer or (p - a >= within and b - q >= within))
            name = (NO_SPAN if inner is None
                    else inner[2] if firm else AMBIGUOUS)
            out[name] = out.get(name, 0.0) + q - p
    return out


_REDUCE = {"median": statistics.median, "mean": statistics.fmean,
           "p95": lambda xs: common.quantile(xs, 0.95)}


def _durations(trace, name):
    return [s.duration for s in program_spans(trace) if s.name == name]


def program_span_ms(trace, args, ctx):
    """Host milliseconds of the program span ``span``: ``reduce`` is
    median, mean or p95 over the spans in the trace."""
    found = _durations(trace, args["span"])
    if not found:
        return None
    return 1e3 * _REDUCE[args.get("reduce", "median")](found)


def program_span_share(trace, args, ctx):
    """Summed time of the spans named ``span`` over that of the spans
    named ``of``, in percent."""
    outer = _durations(trace, args["of"])
    if not outer:
        return None
    return 100.0 * sum(_durations(trace, args["span"])) / sum(outer)


def idle_ms_in_program_spans(trace, args, ctx):
    """Device idle milliseconds per traced step that fell while the host
    was innermost in a program span matching one of ``spans`` (shell
    patterns over the names without their prefix)."""
    if not program_spans(trace) or not trace.ops:
        return None
    wanted = [PROGRAM_PREFIX + p for p in args["spans"]]
    total = sum(seconds for name, seconds in idle_by_span(trace).items()
                if any(fnmatch.fnmatchcase(name, p) for p in wanted))
    return 1e3 * total / ctx["steps"]


def breakdown(trace, top=10, min_gap=20e-6):
    """``reducers.breakdown`` with the idle gaps named by the innermost
    span on the aligned clock, and the alignment itself."""
    out = reducers.breakdown(trace, top, min_gap)
    if out is None:
        return None
    alignment = align(trace)
    gaps = idle_by_span(trace, alignment, min_gap)
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:top]]
    if alignment.uncertainty is not None:
        out["clock_offset_us"] = 1e6 * alignment.offset
        out["clock_uncertainty_us"] = 1e6 * alignment.uncertainty
        out["clock_consistent"] = alignment.consistent
    return out


REDUCERS = {f.__name__: f for f in (
    program_span_ms, program_span_share, idle_ms_in_program_spans)}
