"""Probe one cell for the program's own spans and set-up counters:

    python -m benchmarks.span_probe --workload <name> --seed <n> \
        --seconds <s> [--session tail|whole]

Sets the cell up as ``benchmarks.run`` does (same weights, engine, warm-up
and traffic), drives the window, and traces its last part as ``--trace 1``
does — but reads the trace through ``trace/program_spans.py``, so the
line it prints holds the per-layer readings of ``PROBED`` (the program's
``ds:`` spans and the ``CompileStats`` counters of PR 24) beside a few of
the accepted metrics for comparison, and a breakdown whose idle gaps are
named by the innermost span on the aligned clock.  ``--session whole``
keeps a profiler session open over the WHOLE window instead and reports
the end-to-end rate under it: what the spans cost with tracing on.

A probe, not a cell: no reference, no ``correct``.  The readers of
``PROBED`` are written as ``layer_metrics/<name>.json`` files are, for the
``benchmark`` PR that wires them into ``benchmarks.run`` (PERF.md, Open
questions).  A program without the spans or counters leaves those
readings out.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

SERVING_HOST_LOOP = "serving host loop (inference/engine.py, scheduler.py)"
PROCESS_START = "process start (runtime/compilation/cache.py)"
HOST_SPANS = ["step.sweep", "step.admit", "*.prep", "*.account",
              "step.sample"]
SYNC_SPANS = ["*.dispatch", "*.fetch"]


SERVING = ["gpt2_large.backlog"]
TRAINING = ["bert_large.seq512", "bert_large.seq128"]


def _metric(unit, layer, moves, source, workloads, reader):
    return {"unit": unit, "better": "lower", "layer": layer, "moves": moves,
            "source": source, "workloads": workloads, "reader": reader}


def _trace(reducer, **args):
    return {"kind": "trace", "reducer": reducer, "args": args}


def _counter(key, scale=1.0):
    return {"kind": "counter", "key": key, "scale": scale}


# name -> what its ``layer_metrics`` file and its ``per_layer`` entry
# would hold
PROBED = {
    "decode_prep_ms": _metric(
        "ms", SERVING_HOST_LOOP, "serve_tokens_per_s", "program_span",
        SERVING, _trace("program_span_ms", span="decode.prep",
                        reduce="median")),
    "decode_fetch_ms": _metric(
        "ms", SERVING_HOST_LOOP, "serve_tokens_per_s", "program_span",
        SERVING, _trace("program_span_ms", span="decode.fetch",
                        reduce="median")),
    "serve_idle_host_ms": _metric(
        "ms", SERVING_HOST_LOOP, "serve_tokens_per_s", "program_span",
        SERVING, _trace("idle_ms_in_program_spans", spans=HOST_SPANS)),
    "serve_idle_sync_ms": _metric(
        "ms", SERVING_HOST_LOOP, "serve_tokens_per_s", "program_span",
        SERVING, _trace("idle_ms_in_program_spans", spans=SYNC_SPANS)),
    "prefill_step_share": _metric(
        "%", "entry points (InferenceEngine.step)", "serve_tokens_per_s",
        "program_span", SERVING,
        _trace("program_span_share", span="prefill", of="step")),
    "prefill_padding_pct": _metric(
        "%", "serving programs (inference/model.py)", "serve_tokens_per_s",
        "program_counter", SERVING,
        _counter("prefill_padding_fraction", 100.0)),
    "train_host_ms": _metric(
        "ms", "training host path (runtime/engine.py train_batch)",
        "train_tokens_per_s", "program_span", TRAINING,
        _trace("program_span_ms", span="train_batch", reduce="median")),
    "trace_lower_s": _metric(
        "s", PROCESS_START, "setup_s", "program_counter",
        SERVING + TRAINING, _counter("trace_lower_s")),
    "step_traces": _metric(
        "count", PROCESS_START, "setup_s", "program_counter",
        SERVING + TRAINING, _counter("step_traces")),
}


def read_probed(run, kind):
    """``{name: {"value", "unit"}}`` of the ``PROBED`` metrics of the
    ``kind`` of cell (its workloads), as ``metrics.read_one`` reads a
    metric's file; a reader that finds nothing leaves its metric out."""
    from benchmarks.trace import program_spans

    out = {}
    for name, spec in PROBED.items():
        reader = spec["reader"]
        if not set(spec["workloads"]) & set(kind):
            continue
        if reader["kind"] == "counter":
            value = run["counters"].get(reader["key"])
            if value is not None:
                value *= reader["scale"]
        else:
            value = program_spans.REDUCERS[reader["reducer"]](
                run["trace"], reader["args"], run["ctx"])
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def open_session():
    from benchmarks import tracing

    session = tracing.DeviceTrace(True)
    session.start()
    return session


def close_session(session, read=True):
    """What ``DeviceTrace.stop`` does, but the trace is read with the
    program's spans in it, or dropped unread; returns (trace, seconds the
    session was open)."""
    import jax

    from benchmarks.trace import program_spans, xplane

    window_s = time.perf_counter() - session._t0
    jax.profiler.stop_trace()
    try:
        trace = (program_spans.read(xplane.find_xplane(session._dir))
                 if read else None)
    finally:
        shutil.rmtree(session._dir, ignore_errors=True)
    return trace, window_s


def setup_counters(stats, program):
    """What set-up traced and lowered, at the window's opening; nothing
    where the program's ``CompileStats`` does not count it."""
    if not hasattr(stats, "trace_secs"):
        return {}
    return {"trace_lower_s": stats.trace_secs + stats.lower_secs,
            "step_traces": stats.traces_by_program.get(program, 0),
            "trace_s": stats.trace_secs, "lower_s": stats.lower_secs,
            "trace_s_by_program": dict(sorted(
                stats.trace_secs_by_program.items(),
                key=lambda kv: -kv[1])[:6])}


def span_medians_ms(trace):
    """Median host milliseconds and count of every program span."""
    by_name = {}
    for s in trace.program_spans:
        by_name.setdefault(s.name, []).append(s.duration)
    return {name: [1e3 * statistics.median(xs), len(xs)]
            for name, xs in sorted(by_name.items())}


def probe_serve(spec, seed, seconds, whole, devices):
    from benchmarks import common, serve
    from benchmarks.trace import reducers
    from deepspeed_tpu.runtime.compilation import CompileStats

    stats = CompileStats()
    loop = serve.setup(spec, seed, devices)
    counters = setup_counters(stats, "decode")
    programs_at_open = stats.programs
    traced_s = float(spec["traffic"].get("trace_seconds", 3.0))
    setup_s = time.perf_counter() - T_PROCESS
    loop.t0 = time.perf_counter()
    if whole:
        session = open_session()
        tokens, took, iterations = serve.drive(loop, seconds)
        trace, window_s = close_session(session, read=False)
    else:
        tokens, took, _ = serve.drive(loop, seconds - traced_s)
        session = open_session()
        _, _, iterations = serve.drive(loop, traced_s)
        trace, window_s = close_session(session)
    counters["prefill_padding_fraction"] = \
        loop.engine.observability.padding_waste_fraction()
    line = {"serve_tokens_per_s": tokens / took / len(devices),
            "setup_s": setup_s, "session": "whole" if whole else "tail",
            "compiled_in_window": stats.programs - programs_at_open,
            "iterations": iterations, "counters": counters}
    stats.close()
    if trace is not None:
        ctx = {"steps": max(iterations, 1), "window_s": window_s}
        line["metrics"] = read_probed(
            {"trace": trace, "ctx": ctx, "counters": counters}, SERVING)
        line["accepted"] = {
            "decode_device_ms": reducers.module_ms(
                trace, {"pattern": "^jit_decode", "reduce": "median"}, ctx),
            "prefill_device_ms": reducers.module_ms(
                trace, {"pattern": "^jit_prefill", "reduce": "mean"}, ctx),
            "serve_device_idle": reducers.idle_percent(trace, {}, ctx),
            "host_prep_ms": 1e3 * statistics.median(loop.host_gaps),
            "tpot_p50_ms": 1e3 * common.quantile(loop.gaps, 0.5),
            "tpot_p95_ms": 1e3 * common.quantile(loop.gaps, 0.95)}
        line["prefills_traced"] = [
            [s.args.get("bucket"), s.args.get("prompt_tokens"),
             1e3 * s.duration] for s in trace.program_spans
            if s.name == "prefill"]
    return line, trace


def probe_train(spec, seed, seconds, whole, devices):
    import jax

    from benchmarks import tracing, train
    from benchmarks.trace import reducers
    from deepspeed_tpu.runtime.compilation import CompileStats

    stats = CompileStats()
    ready = train.setup(spec, seed, devices)
    engine, pool = ready["engine"], ready["pool"]
    counters = setup_counters(stats, "train_step")
    programs_at_open = stats.programs
    traced_steps = int(spec["traffic"]["trace_steps"])
    losses, inflight = [], deque()
    first = train.CHECK_STEPS + train.WARMUP_STEPS

    def one_step():      # the window's own step, span for span
        with tracing.span("train_batch"):
            loss = engine.train_batch(
                iter([pool[(first + len(losses)) % len(pool)]]))
        losses.append(loss)
        inflight.append(loss)
        if len(inflight) > 2:
            with tracing.span("wait_step"):
                jax.block_until_ready(inflight.popleft())

    setup_s = time.perf_counter() - T_PROCESS
    untraced_s = seconds - (0 if whole
                            else traced_steps * ready["step_seconds"])
    if whole:
        session = open_session()
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < untraced_s:
        one_step()
    jax.block_until_ready(losses[-1])
    took, steps = time.perf_counter() - t_open, len(losses)
    if whole:
        trace, window_s = close_session(session, read=False)
    else:
        session = open_session()
        for _ in range(traced_steps):
            one_step()
        with tracing.span("fence"):
            jax.block_until_ready(losses[-1])
        trace, window_s = close_session(session)
    line = {"train_tokens_per_s": (steps * ready["tokens_per_step"] / took
                                   / spec["chips"]),
            "setup_s": setup_s, "session": "whole" if whole else "tail",
            "compiled_in_window": stats.programs - programs_at_open,
            "steps": steps, "counters": counters}
    stats.close()
    if trace is not None:
        ctx = {"steps": traced_steps, "window_s": window_s}
        line["metrics"] = read_probed(
            {"trace": trace, "ctx": ctx, "counters": counters}, TRAINING)
        line["accepted"] = {
            "train_step_device_ms": reducers.busy_ms_per_step(
                trace, {}, ctx),
            "train_device_idle": reducers.idle_percent(trace, {}, ctx)}
    return line, trace


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--session", choices=("tail", "whole"),
                        default="tail")
    args = parser.parse_args(argv)

    from benchmarks import common
    from benchmarks.trace import program_spans

    spec = common.load_cell(args.workload)
    devices = common.require_chips(spec["chips"])
    common.configure_compile_cache()
    probe = {"train": probe_train,
             "serve": probe_serve}[spec["config"]["kind"]]
    with common.program_log_on_stderr():
        line, trace = probe(spec, args.seed, args.seconds,
                            args.session == "whole", devices)
    line.update(workload=args.workload, seed=args.seed,
                device=common.device_line(devices))
    if trace is not None:
        line["span_ms_and_count"] = span_medians_ms(trace)
        line["breakdown"] = program_spans.breakdown(trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
