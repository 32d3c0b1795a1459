"""What every kind of cell shares: finding a cell's files by name, the
look for the chips, the contract's result line, and small arithmetic."""

import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


def _read(path):
    with open(path) as f:
        return json.load(f)


def load_traffic(mix):
    """The traffic file ``traffic/<mix>.json``; where it names a
    ``lengths_file`` (a list of (prompt, answer) lengths that several mixes
    share), that file's ``pairs`` with it."""
    traffic = _read(os.path.join(HERE, "traffic", mix + ".json"))
    if "lengths_file" in traffic:
        traffic["pairs"] = _read(os.path.join(
            HERE, "traffic", traffic["lengths_file"]))["pairs"]
    return traffic


def load_cell(workload):
    """The cell ``workload`` of ``BENCHMARK.json`` with its configuration
    and traffic files, found by name."""
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"name": workload, "chips": int(cell["chips"]),
            "config": _read(os.path.join(ROOT, config["file"])),
            "traffic": load_traffic(cell["traffic"]),
            "end_to_end": [m["name"] for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])],
            "per_layer": [m["name"] for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]}


def require_chips(chips):
    """The ``chips`` TPU chips this cell runs on.  No accelerator, or fewer
    chips than the cell asks for: exit non-zero with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmarks.run needs {chips} TPU chip(s); jax found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}) - nothing ran", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def configure_compile_cache():
    """JAX's persistent compilation cache by the program's one rule
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``,
    a fixed path inside the checkout), switched on BEFORE the benchmark's
    own first jit: the engines only configure it when they are built, and
    the seeded weights are made before that (33 s of compiling in every run
    otherwise: my chip runs, PR 23)."""
    from deepspeed_tpu.runtime.compilation import (
        DeepSpeedCompilationConfig, configure_persistent_cache)

    return configure_persistent_cache(DeepSpeedCompilationConfig({}))


def device_line(devices):
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    such statistic: the CPU of the tests)."""
    stats = [d.memory_stats() for d in devices]
    return max((int(s["peak_bytes_in_use"]) for s in stats if s), default=0)


def quantile(values, q):
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics; None for no values."""
    if not values:
        return None
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Phases:
    """Where set-up's seconds go, on stderr: ``mark(name)`` after each
    phase."""

    def __init__(self):
        self._clock = time.perf_counter
        self._last = self._clock()
        self.seconds = {}

    def mark(self, name):
        now = self._clock()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        print(f"setup phase {name} {now - self._last:.2f} s",
              file=sys.stderr, flush=True)
        self._last = now


@contextlib.contextmanager
def program_log_on_stderr():
    """stdout carries the check lines and the result line alone: the
    program's log handlers write to stderr for the length of a run."""
    from deepspeed_tpu.utils.logging import logger

    before = [(h, h.stream) for h in logger.handlers
              if hasattr(h, "stream")]
    for handler, _ in before:
        handler.stream = sys.stderr
    try:
        yield
    finally:
        for handler, stream in before:
            handler.stream = stream


def print_result(correct, attempted, failed, metrics, device, breakdown=None,
                 **extra):
    """The contract's one JSON object, as the last line of stdout."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
