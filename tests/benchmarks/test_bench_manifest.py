"""A lint of ``BENCHMARK.json`` against the contract's limits and against
the files the harness finds by name."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import common, metrics

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(common.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert all(len(w) <= 200 and "\t" not in w for w in bench["command"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_and_units_use_the_allowed_characters(bench):
    named = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
             + bench["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    names = [e["name"] for e in named]
    assert len(names) == len(set(names))


def test_every_cells_files_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        path = os.path.join(common.ROOT, c["file"])
        assert c["file"].startswith("benchmarks/") and os.path.isfile(path)
        with open(path) as f:
            stored = json.load(f)
        assert stored["name"] == c["name"]
        assert stored["reduced"] == c["reduced"]
        assert stored["source"] == c["source"]
        assert os.path.isfile(os.path.join(
            common.HERE, "models", stored["model"] + ".py"))
        assert os.path.isfile(os.path.join(
            common.HERE, "reference", stored["model"] + ".py"))
    used = set()
    for w in bench["workloads"]:
        spec = common.load_cell(w["name"])
        used.add(w["config"])
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(
            common.HERE, "generators",
            spec["traffic"]["generator"] + ".py"))
        assert spec["config"]["name"] in spec["traffic"]["limits"]
    assert used == set(configs)


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    assert "setup_s" in reports and reports["setup_s"] == set(cells)
    files = metrics.load_all()
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in reports
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        stored = files[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert stored[key] == m[key], (m["name"], key)
        assert stored["reader"]["kind"] in ("counter", "span", "trace")
    for cell in cells:
        assert sum(cell in r for r in reports.values()) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_benchmark_does_not_import_the_programs_profiling():
    for folder, _, names in os.walk(common.HERE):
        for name in names:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                imports = [line for line in text.splitlines()
                           if re.match(r"\s*(from|import)\s", line)]
                assert not any("profiling" in line for line in imports), name


def test_run_refuses_a_device_that_is_not_a_tpu(bench):
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "needs" in done.stderr and "TPU" in done.stderr
    assert not any(line.startswith("{") for line in
                   done.stdout.splitlines())
