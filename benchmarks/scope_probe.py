"""Probe one cell for its device time by the program's own scopes:

    python -m benchmarks.scope_probe --workload <name> --seed <n> \
        --seconds <s>

Sets the cell up, drives the window and traces its last part as
``benchmarks.run --trace 1`` does (``span_probe``'s set-up and session,
imported, with the program's memory ledger on so that the engine keeps its
compiled programs), takes ``engine.program_scopes()`` before the engine is
freed and reads the trace through ``trace/scopes.py``.  One JSON line: the
readings of ``PROBED`` beside ``train_step_device_ms`` /
``decode_device_ms`` for comparison, ``device_scopes`` (the ten largest
scopes, layers folded), ``scope_map_s`` (what building the maps cost) and
each program's share of instructions that found a scope.

A probe, not a cell: no reference, no ``correct``.  The readers of
``PROBED`` are written as ``layer_metrics/<name>.json`` files and
``per_layer`` entries are, for the ``benchmark`` PR that wires them into
``benchmarks.run`` (PERF.md, Open question 4 (h)).  A program without the
scopes — or one loaded from a compile-cache entry that a tree without them
made — leaves the ``*_ms`` readings out and reads ``*_unscoped_pct`` 100.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmarks import span_probe  # noqa: E402  (T_PROCESS starts there)
from benchmarks.span_probe import TRAINING, _metric, _trace  # noqa: E402

STEP_PROGRAM = "step program (runtime/engine.py fused train_step)"
SERVING_PROGRAMS = "serving programs (inference/model.py)"
SERVING = span_probe.SERVING + ["deepseek_v2_ep8.repo_backlog",
                                "k_exaone_ep8.reason_backlog",
                                "ouro_2_6b.think_backlog"]


def _train(scope, direction=None):
    args = {"module": "^jit_train_step", "scope": scope, "per": "step"}
    if direction is not None:
        args["direction"] = direction
    return _metric("ms", STEP_PROGRAM, "train_tokens_per_s", "device_trace",
                   TRAINING, _trace("scope_ms", **args))


def _decode(scope):
    return _metric("ms", SERVING_PROGRAMS, "serve_tokens_per_s",
                   "device_trace", SERVING,
                   _trace("scope_ms", module="^jit_decode", scope=scope,
                          per="run_median"))


# name -> what its ``layer_metrics`` file and its ``per_layer`` entry
# would hold (ms a step, or a decode run; % of the operations' time)
PROBED = {
    "train_fwd_ms": _train("loss_and_grads", "fwd"),
    # the backward with what follows it inside ``loss_and_grads``: the
    # gradients' flatten and exchange, which have no direction
    "train_bwd_ms": _train("loss_and_grads", ["bwd", ""]),
    "train_optimizer_ms": _train(["optimizer", "cast_params"]),
    "train_head_ms": _train(["mlm_head", "loss"]),
    "train_attention_ms": _train("attention"),
    "train_mlp_ms": _train("mlp"),
    "decode_attention_ms": _decode("attention"),
    "decode_ffn_ms": _decode(["mlp", "moe"]),
    "decode_head_ms": _decode(["final_norm", "lm_head", "sample"]),
    "train_unscoped_pct": _metric(
        "%", "device", "train_tokens_per_s", "device_trace", TRAINING,
        _trace("unscoped_pct")),
    "serve_unscoped_pct": _metric(
        "%", "device", "serve_tokens_per_s", "device_trace", SERVING,
        _trace("unscoped_pct")),
}


def read_probed(trace, ctx, kind):
    """``{name: {"value", "unit"}}`` of the ``PROBED`` metrics of the
    ``kind`` of cell (its workloads), as ``metrics.read_one`` reads a
    metric's file; a reader that finds nothing leaves its metric out."""
    from benchmarks.trace import scopes

    out = {}
    for name, spec in PROBED.items():
        if not set(spec["workloads"]) & set(kind):
            continue
        reader = spec["reader"]
        value = scopes.REDUCERS[reader["reducer"]](trace, reader["args"],
                                                   ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


@contextlib.contextmanager
def kept_setup(module):
    """``module.setup``'s results, kept in the list this yields: the probe
    functions of ``span_probe`` drop the engine with their frame, and the
    scope maps have to be taken from it after the trace."""
    kept, setup = [], module.setup

    def keeping(*args, **kw):
        kept.append(setup(*args, **kw))
        return kept[-1]

    module.setup = keeping
    try:
        yield kept
    finally:
        module.setup = setup


def probe(spec, seed, seconds, devices):
    """The probe's line for the cell ``spec`` (a dict as
    ``common.load_cell`` gives it) on ``devices``."""
    from benchmarks import common, serve, train
    from benchmarks.trace import scopes

    # the engines keep their compiled programs only with the ledger on
    spec["config"]["engine"].setdefault("profiling", {})[
        "memory_ledger"] = True
    serving = spec["config"]["kind"] == "serve"
    run = span_probe.probe_serve if serving else span_probe.probe_train
    with common.program_log_on_stderr(), \
            kept_setup(serve if serving else train) as kept:
        line, trace = run(spec, seed, seconds, False, devices)
        engine = kept[0].engine if serving else kept[0]["engine"]
        t = time.perf_counter()
        maps = engine.program_scopes()
        scope_map_s = time.perf_counter() - t
    del engine, kept
    steps = (line["iterations"] if serving
             else int(spec["traffic"]["trace_steps"]))
    ctx = {"steps": max(steps, 1), "scopes": maps}
    line.update(
        metrics={**line.get("metrics", {}),
                 **read_probed(trace, ctx, SERVING if serving else TRAINING)},
        scope_map_s=scope_map_s,
        programs={module: {
            "instructions": len(m),
            "placed": sum(1 for scope, _ in m.values() if scope)}
            for module, m in maps.items()})
    line.update(scopes.breakdown_by_scope(trace, maps) or {})
    return line


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)

    from benchmarks import common

    spec = common.load_cell(args.workload)
    devices = common.require_chips(spec["chips"])
    common.configure_compile_cache()
    line = probe(spec, args.seed, args.seconds, devices)
    line.update(workload=args.workload, seed=args.seed,
                device=common.device_line(devices))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
