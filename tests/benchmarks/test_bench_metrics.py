"""Per-layer metric readers: each metric is a file; a reader that finds
nothing to read gives nothing."""

import json
import os

import pytest

from benchmarks import common, metrics
from benchmarks.trace import xplane

FIXTURE = os.path.join(common.HERE, "trace", "fixtures",
                       "probe_1chip.xplane.pb")


def test_every_metric_file_names_a_reader_the_harness_has():
    specs = metrics.load_all()
    assert len(specs) >= 19
    for name, spec in specs.items():
        assert os.path.isfile(os.path.join(common.HERE, "layer_metrics",
                                           name + ".json"))
        reader = spec["reader"]
        if reader["kind"] == "trace":
            assert reader["reducer"] in metrics.reducers.REDUCERS
        elif reader["kind"] == "span":
            assert reader["reduce"] in metrics._REDUCE
        else:
            assert reader["kind"] == "counter" and reader["key"]


def test_counter_span_and_trace_readers():
    run = {"counters": {"compile_cold_s": 2.5, "hbm_peak_bytes": 6.0e9,
                        "slot_occupancy": 99.0},
           "spans": {"token_gap": [0.080, 0.084, 0.090, 0.200],
                     "host_gap": [0.001, 0.003, 0.002]},
           "trace": xplane.read(FIXTURE),
           "ctx": {"steps": 4, "window_s": 0.02,
                   "device_kind": "TPU v5 lite", "counts": {}}}
    got = metrics.per_layer(
        ["compile_cold_s", "train_hbm_peak_gb", "tpot_p50_ms.backlog",
         "tpot_p95_ms.backlog", "host_prep_ms", "train_step_device_ms",
         "train_device_idle", "attn_kernel_ms", "collective_ms",
         "cache_misses"], run)
    assert got["compile_cold_s"] == {"value": 2.5, "unit": "s"}
    assert got["train_hbm_peak_gb"]["value"] == pytest.approx(6.0)
    assert got["tpot_p50_ms.backlog"]["value"] == pytest.approx(87.0)
    assert got["tpot_p95_ms.backlog"]["value"] == pytest.approx(183.5)
    assert got["host_prep_ms"] == {"value": pytest.approx(2.0), "unit": "ms"}
    assert got["train_step_device_ms"]["value"] == pytest.approx(0.0395,
                                                                 rel=0.02)
    assert 98 < got["train_device_idle"]["value"] < 100
    # nothing to read: no Pallas call and no collective in this trace, no
    # such counter in this run
    assert "attn_kernel_ms" not in got
    assert "collective_ms" not in got
    assert "cache_misses" not in got


def test_a_run_without_a_trace_reads_no_trace_metric():
    run = {"counters": {}, "spans": {}, "trace": None, "ctx": {}}
    assert metrics.per_layer(["train_device_idle", "decode_device_ms",
                              "host_prep_ms"], run) == {}


def test_quantile_arithmetic():
    assert common.quantile([], 0.5) is None
    assert common.quantile([3.0], 0.95) == 3.0
    assert common.quantile([1, 2, 3, 4], 0.5) == 2.5
    assert common.quantile([4, 1, 3, 2], 0.0) == 1
    assert common.quantile(list(range(101)), 0.95) == 95


def test_load_cell_finds_its_files_by_name():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        spec = common.load_cell(w["name"])
        assert spec["config"]["name"] == w["config"]
        assert spec["chips"] == w["chips"]
        assert "setup_s" in spec["end_to_end"]
        assert len(spec["end_to_end"]) == 2
        assert spec["per_layer"]
    with pytest.raises(SystemExit):
        common.load_cell("no_such.cell")
