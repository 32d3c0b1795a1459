"""Per-layer metrics: one small reader file each under
``benchmarks/layer_metrics/``, found by listing the directory.  A file
says where its number comes from:

- ``{"kind": "counter", "key": K}``: a number the run counted (``K`` in the
  run's ``counters``), times ``scale``;
- ``{"kind": "span", "span": S, "reduce": R}``: the harness's own host-clock
  readings named ``S`` (seconds), reduced by ``median``, ``mean``, ``p50`` or
  ``p95``, times ``scale``;
- ``{"kind": "trace", "reducer": R, "args": {...}}``: a reduction of the
  device trace by name from ``benchmarks/trace/reducers.py``.

A reader that finds nothing to read gives nothing, and the metric is left
out of the line.
"""

import glob
import json
import os
import statistics

from . import common
from .trace import reducers

_REDUCE = {
    "median": statistics.median,
    "mean": statistics.fmean,
    "p50": lambda xs: common.quantile(xs, 0.50),
    "p95": lambda xs: common.quantile(xs, 0.95),
}


def load_all():
    out = {}
    for path in sorted(glob.glob(os.path.join(common.HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        out[spec["name"]] = spec
    return out


def read_one(spec, run):
    reader = spec["reader"]
    scale = reader.get("scale", 1.0)
    if reader["kind"] == "counter":
        value = run["counters"].get(reader["key"])
    elif reader["kind"] == "span":
        readings = run["spans"].get(reader["span"])
        value = _REDUCE[reader["reduce"]](readings) if readings else None
    elif reader["kind"] == "trace":
        if run.get("trace") is None:
            return None
        value = reducers.REDUCERS[reader["reducer"]](
            run["trace"], reader.get("args", {}), run["ctx"])
    else:
        raise ValueError(f"metric {spec['name']}: reader kind "
                         f"{reader['kind']!r}")
    return None if value is None else float(value) * scale


def per_layer(names, run):
    """``{name: {"value", "unit"}}`` for the metrics of ``names`` whose
    readers found something."""
    specs = load_all()
    out = {}
    for name in names:
        value = read_one(specs[name], run)
        if value is not None:
            out[name] = {"value": value, "unit": specs[name]["unit"]}
    return out
