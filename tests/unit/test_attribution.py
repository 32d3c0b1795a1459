"""Step-time attribution tests (``profiling/attribution.py`` +
``profiling/doctor.py`` + the DSO705 ratchet + the report/bench
surfaces): the phase model on hand-built summaries, the reconciliation
invariant (phases sum to the measured p50, signed residual), the live
engine receipt + gauges, the offline doctor's per-rank verdict and
straggler explanation on fabricated two-rank artifacts, and the CLI
ratchet tripping on a drifted budget fixture."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.profiling import attribution as attr
from deepspeed_tpu.profiling import doctor as doctor_mod
from deepspeed_tpu.profiling.overlap import (KIND_COLLECTIVE, KIND_HOST,
                                             KIND_P2P)
from deepspeed_tpu.telemetry import report as report_mod
from deepspeed_tpu.tools.dslint import programs as dsp
from deepspeed_tpu.tools.dslint.cli import main as dslint_main

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 16


def _summary(compute=1.0, coll=0.2, host=0.3, p2p=0.0, cp=0.8):
    return {"compute_seconds": compute, "critical_path_seconds": cp,
            "exposed_by_kind": {KIND_COLLECTIVE: coll, KIND_HOST: host,
                                KIND_P2P: p2p}}


# ------------------------------------------------------------ the model
def test_program_budget_phases():
    b = attr.program_budget(_summary(compute=1.0, coll=0.2, host=0.3,
                                     p2p=0.1))
    assert b[attr.PHASE_COMPUTE] == 1.0
    assert b[attr.PHASE_COLLECTIVE] == pytest.approx(0.3)  # coll + p2p
    assert b[attr.PHASE_HOST] == 0.3
    assert b["predicted_seconds"] == pytest.approx(1.6)
    assert attr.program_budget(None) is None


def test_program_budget_falls_back_to_nodes_for_old_summaries():
    """Pre-round-13 recorded summaries carry no exposed_by_kind: the
    per-node list (seconds - hidden_seconds) stands in."""
    legacy = {"compute_seconds": 1.0, "critical_path_seconds": 1.0,
              "nodes": [
                  {"kind": KIND_COLLECTIVE, "seconds": 0.5,
                   "hidden_seconds": 0.1},
                  {"kind": KIND_HOST, "seconds": 0.2,
                   "hidden_seconds": 0.0}]}
    b = attr.program_budget(legacy)
    assert b[attr.PHASE_COLLECTIVE] == pytest.approx(0.4)
    assert b[attr.PHASE_HOST] == pytest.approx(0.2)


def test_step_budget_prefers_fused_and_weights_stepwise():
    fused = {"train_step": {"overlap": _summary(compute=2.0)},
             "fwd_bwd": {"overlap": _summary(compute=1.0)}}
    b = attr.step_budget(fused, grad_accumulation_steps=4,
                         driver_seconds=0.5)
    assert b["program"] == "train_step"
    assert b["phases"][attr.PHASE_COMPUTE] == 2.0
    assert b["phases"][attr.PHASE_DRIVER] == 0.5

    stepwise = {"fwd_bwd": {"overlap": _summary(compute=1.0, coll=0.1,
                                                host=0.0)},
                "accum": {"overlap": _summary(compute=0.5, coll=0.0,
                                              host=0.0)},
                "apply_update": {"overlap": _summary(compute=0.25,
                                                     coll=0.0,
                                                     host=2.0)}}
    b = attr.step_budget(stepwise, grad_accumulation_steps=4)
    assert b["program"] == "stepwise"
    # fwd_bwd x4 + accum x3 + apply x1
    assert b["phases"][attr.PHASE_COMPUTE] == pytest.approx(
        4 * 1.0 + 3 * 0.5 + 0.25)
    assert b["phases"][attr.PHASE_COLLECTIVE] == pytest.approx(0.4)
    assert b["phases"][attr.PHASE_HOST] == pytest.approx(2.0)
    assert attr.step_budget({}, 1) is None


def test_reconcile_phases_sum_to_measured_with_signed_residual():
    budget = attr.step_budget({"train_step": {"overlap": _summary(
        compute=1.0, coll=0.2, host=0.3)}}, driver_seconds=0.1)
    rec = attr.reconcile(budget, 2.0)
    assert rec["measured_step_seconds"] == 2.0
    assert sum(rec["phases"].values()) == pytest.approx(2.0)
    assert rec["phases"][attr.PHASE_UNEXPLAINED] == pytest.approx(0.4)
    assert rec["step_unexplained_fraction"] == pytest.approx(0.2)
    # over-prediction stays SIGNED: the residual goes negative, never
    # silently clamped (that drift is what DSO705 catches)
    over = attr.reconcile(budget, 1.0)
    assert over["phases"][attr.PHASE_UNEXPLAINED] == pytest.approx(-0.6)
    assert over["step_unexplained_fraction"] == pytest.approx(-0.6)
    # no measured side yet: predicted-only record, Nones explicit
    dry = attr.reconcile(budget, None)
    assert dry["measured_step_seconds"] is None
    assert dry["step_unexplained_fraction"] is None
    assert dry["phases"][attr.PHASE_UNEXPLAINED] is None


def test_median_of_window_shrugs_one_outlier():
    assert attr.median_of_window([0.002, 0.0021, 0.0019, 0.002, 30.0]) \
        == pytest.approx(0.002)
    assert attr.median_of_window([0.0, None, 0.0]) is None
    assert attr.median_of_window([1.0, 5.0, 9.0], window=2) == 7.0


def test_straggler_explanation_names_the_phase():
    def rank(measured, driver, unexplained):
        return {"measured_step_seconds": measured,
                "phases": {attr.PHASE_DRIVER: driver,
                           attr.PHASE_UNEXPLAINED: unexplained}}

    # slow rank whose extra time is device-side (unexplained)
    ranks = {"rank0": rank(1.0, 0.1, 0.2), "rank1": rank(1.0, 0.1, 0.2),
             "rank2": rank(3.0, 0.1, 2.2)}
    ex = attr.straggler_explanation(ranks)
    assert ex["slowest_rank"] == "rank2"
    assert ex["attributed_phase"] == attr.PHASE_UNEXPLAINED
    assert ex["extra_seconds"] == pytest.approx(2.0)
    # slow rank whose extra time is a slow input pipeline (driver)
    ranks["rank2"] = rank(3.0, 2.1, 0.2)
    assert attr.straggler_explanation(ranks)["attributed_phase"] \
        == attr.PHASE_DRIVER
    assert attr.straggler_explanation({"rank0": rank(1, 0, 0)}) is None


def test_flops_cross_check_flags_2x_disagreement():
    budget = {"phases": {attr.PHASE_COMPUTE: 1.0}}
    peak = 100.0e12
    ok = attr.flops_cross_check(budget, model_flops=60e12,
                                peak_flops_per_sec=peak)
    assert ok["flops_compute_seconds"] == pytest.approx(0.6)
    assert not ok["disagrees"]
    bad = attr.flops_cross_check(budget, model_flops=10e12,
                                 peak_flops_per_sec=peak)
    assert bad["ratio"] == pytest.approx(10.0)
    assert bad["disagrees"]
    # zero-compute sides must stay strict-JSON (None, never inf): one
    # model at zero = maximal disagreement, both at zero = agreement
    zero = attr.flops_cross_check({"phases": {attr.PHASE_COMPUTE: 0.0}},
                                  model_flops=10e12,
                                  peak_flops_per_sec=peak)
    assert zero["ratio"] is None and zero["disagrees"]
    json.dumps(zero)  # strict-JSON serializable
    both = attr.flops_cross_check({"phases": {attr.PHASE_COMPUTE: 0.0}},
                                  model_flops=0,
                                  peak_flops_per_sec=peak)
    assert both["ratio"] == 1.0 and not both["disagrees"]


# --------------------------------------------------- live engine receipt
def _engine(cpu_devices, run_dir, **profiling):
    cfg = base_config(
        steps_per_print=1,
        telemetry={"enabled": True, "run_dir": str(run_dir)},
        profiling=dict({"comm_ledger": True, "memory_ledger": True},
                       **profiling))
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=cfg, mesh=mesh)
    return engine


def test_engine_attribution_receipt_reconciles(cpu_devices, tmp_path):
    engine = _engine(cpu_devices, tmp_path / "run")
    for b in random_batches(4, 16, HIDDEN, seed=0):
        engine.train_batch(iter([b]))
    rec = engine.attribution_receipt()
    assert rec["program"] == "train_step"
    assert rec["measured_step_seconds"] > 0
    assert sum(rec["phases"].values()) == pytest.approx(
        rec["measured_step_seconds"])
    assert rec["phases"][attr.PHASE_DRIVER] > 0  # fused path recorded it
    assert rec["predicted_step_seconds"] == pytest.approx(
        sum(v for p, v in rec["phases"].items()
            if p != attr.PHASE_UNEXPLAINED))
    engine.close()


def test_engine_flops_cross_check_rides_the_receipt(cpu_devices,
                                                    tmp_path):
    """The idle flops profiler wired in as the independent compute
    cross-check: once it has profiled, the attribution receipt reports
    both compute estimates and the disagreement verdict."""
    cfg = base_config(
        steps_per_print=1,
        telemetry={"enabled": True, "run_dir": str(tmp_path / "run")},
        profiling={"comm_ledger": True, "memory_ledger": True},
        flops_profiler={"enabled": True, "profile_step": 2})
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=cfg, mesh=mesh)
    for b in random_batches(3, 16, HIDDEN, seed=0):
        engine.train_batch(iter([b]))
    rec = engine.attribution_receipt()
    check = rec["flops_check"]
    assert check["model_flops"] == engine.flops_profiler.profile.flops
    assert check["flops_compute_seconds"] > 0
    assert check["roofline_compute_seconds"] == pytest.approx(
        rec["phases"][attr.PHASE_COMPUTE])
    assert check["ratio"] >= 1.0 and isinstance(check["disagrees"], bool)
    engine.close()


# ------------------------------------------------------------ the doctor
def _fabricate_sibling(run_dir, rank, p50, driver):
    """A second rank's event stream: latency snapshots + one
    attribution event carrying its driver phase (what a real sibling
    engine would have written into the shared run dir)."""
    rows = []
    for i in range(3):
        rows.append({"schema_version": 1, "seq": len(rows), "rank": rank,
                     "ts": 1000.0 + i, "type": "comm", "step": i + 1,
                     "data": {"kind": "latency", "n": 3, "steps": 3,
                              "last": p50, "mean": p50, "p50": p50,
                              "p95": p50, "max": p50}})
    rows.append({"schema_version": 1, "seq": len(rows), "rank": rank,
                 "ts": 1003.0, "type": "attribution", "step": 3,
                 "data": {"program": "train_step",
                          "phases": {"compute": 0.0,
                                     "exposed_collective": 0.0,
                                     "host_stream": 0.0,
                                     "driver": driver,
                                     "unexplained": p50 - driver},
                          "predicted_step_seconds": driver,
                          "measured_step_seconds": p50,
                          "step_unexplained_fraction":
                              (p50 - driver) / p50}})
    with open(os.path.join(str(run_dir), f"events-rank{rank}.jsonl"),
              "w") as f:
        f.write("\n".join(json.dumps(r) for r in rows) + "\n")


def test_doctor_verdict_and_straggler_explanation(cpu_devices, tmp_path,
                                                  capsys):
    run_dir = tmp_path / "run"
    engine = _engine(cpu_devices, run_dir, program_dump=True)
    for b in random_batches(4, 16, HIDDEN, seed=0):
        engine.train_batch(iter([b]))
    engine.close()
    # a fabricated slow sibling: device-side stall (driver tiny), so
    # the doctor must attribute its extra time to `unexplained`
    _fabricate_sibling(run_dir, 1, p50=5.0, driver=1e-4)
    verdict = doctor_mod.doctor_run_dir(run_dir)
    assert "train_step" in verdict["programs"]
    ranks = verdict["ranks"]
    assert set(ranks) == {"rank0", "rank1"}
    for rec in ranks.values():
        assert sum(rec["phases"].values()) == pytest.approx(
            rec["measured_step_seconds"])
    straggler = verdict["straggler"]
    assert straggler["slowest_rank"] == "rank1"
    assert straggler["attributed_phase"] == attr.PHASE_UNEXPLAINED
    # CLI: human verdict exit 0, --json parseable, prints the verdict
    assert doctor_mod.main([str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert "straggler: rank rank1" in out
    assert "unexplained" in out
    assert doctor_mod.main([str(run_dir), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["straggler"]["slowest_rank"] == "rank1"
    # report integration: --doctor section + --json doctor key
    assert report_mod.main(["report", str(run_dir), "--doctor"]) == 0
    assert "step-time attribution (doctor):" in capsys.readouterr().out
    assert report_mod.main(["report", str(run_dir), "--json",
                            "--doctor"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["report_schema_version"] == 1
    assert set(doc) >= {"summary", "comm", "elastic", "events", "doctor"}
    assert doc["comm"]["measured_p50_seconds"]["rank1"] == 5.0
    assert doc["doctor"]["straggler"]["attributed_phase"] \
        == attr.PHASE_UNEXPLAINED


def test_doctor_exit_2_without_artifacts(tmp_path, capsys):
    os.makedirs(tmp_path / "empty", exist_ok=True)
    assert doctor_mod.main([str(tmp_path / "empty")]) == 2
    assert "cannot load run artifacts" in capsys.readouterr().err
    # report --doctor degrades to an explicit unavailable line
    from deepspeed_tpu.telemetry import EventLog

    log = EventLog(tmp_path / "empty", rank=0)
    log.emit("run_start", step=0, world_size=1)
    log.close()
    assert report_mod.main(["report", str(tmp_path / "empty"),
                            "--doctor"]) == 0
    assert "unavailable:" in capsys.readouterr().out


# ------------------------------------------------- DSO705 metric ratchet
_HLO = (
    "HloModule fixture, is_scheduled=true\n\n"
    "ENTRY %main.1 (p0: f32[4096,4096]) -> f32[4096,4096] {\n"
    "  %p0 = f32[4096,4096]{1,0} parameter(0)\n"
    "  ROOT %dot.1 = f32[4096,4096]{1,0} dot(f32[4096,4096]{1,0} %p0, "
    "f32[4096,4096]{1,0} %p0), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
    "}\n")


def _fixture_run_dir(tmp_path, declared_bytes):
    progdir = tmp_path / "programs"
    os.makedirs(progdir, exist_ok=True)
    artifact = dsp.ProgramArtifact(
        name="train_step", hlo=_HLO, mesh_axes={"data": 1},
        host_state_wire_bytes=declared_bytes,
        host_stream_schedule={"overlap": False},
        device_kind="TPU v5e")
    (progdir / "train_step.hlo").write_text(_HLO)
    (progdir / "train_step.json").write_text(
        json.dumps(artifact.sidecar()))
    return tmp_path


def _baseline(path, metrics):
    path.write_text(json.dumps({"schema_version": 1, "violations": {},
                                "metrics": metrics}))
    return str(path)


def test_dso705_trips_on_drifted_declared_budget(tmp_path):
    """The acceptance fixture: record the budget, drift the DECLARED
    host stream (the budget's biggest term), and the metrics ratchet
    must fail the baselined run while the faithful run stays exit 0."""
    run = _fixture_run_dir(tmp_path / "run", declared_bytes=140_000_000)
    artifacts = dsp.load_run_artifacts(str(run))
    recorded = dsp.attribution_metrics(artifacts)
    key = dsp.predicted_step_metric_key("train_step")
    assert recorded[key] > 0
    baseline = _baseline(tmp_path / "base.json", recorded)
    # faithful: bare --programs clean AND the ratcheted run exit 0
    assert dslint_main(["--programs", str(run), "--select", "DSO705",
                        "--baseline", baseline]) == 0
    # drift the declaration: 4x the host stream -> predicted step far
    # outside the ±25% band -> DSO705, baseline cannot absolve it
    drifted = _fixture_run_dir(tmp_path / "run2",
                               declared_bytes=560_000_000)
    rc = dslint_main(["--programs", str(drifted), "--select", "DSO705",
                      "--baseline", baseline])
    assert rc == 1
    diags = dsp.check_attribution_ratchet(
        [(str(drifted), dsp.load_run_artifacts(str(drifted)))],
        {k: float(v) for k, v in recorded.items()})
    assert len(diags) == 1 and diags[0].rule_id == "DSO705"
    assert "predicted_step_seconds drifted" in diags[0].message


def test_dso705_unexplained_ceiling_needs_measured_evidence(tmp_path):
    """The measured arm: with latency files in the run dir, a
    reconciled unexplained fraction above the recorded ceiling trips;
    without measured evidence the ceiling is never checked."""
    from deepspeed_tpu.profiling.comm import publish_rank_latency

    run = _fixture_run_dir(tmp_path / "run", declared_bytes=140_000_000)
    artifacts = dsp.load_run_artifacts(str(run))
    predicted = dsp.attribution_metrics(artifacts)[
        dsp.predicted_step_metric_key("train_step")]
    ceiling = {dsp.unexplained_metric_key("train_step"): 0.10}
    # no latency files: ceiling not checkable, no finding
    assert dsp.check_attribution_ratchet(
        [(str(run), artifacts)], ceiling) == []
    # measured p50 = 100x predicted -> fraction ~0.99 >> 0.10 + margin
    publish_rank_latency(str(run), 0, {"n": 3, "steps": 3,
                                       "last": predicted * 100,
                                       "mean": predicted * 100,
                                       "p50": predicted * 100,
                                       "p95": predicted * 100,
                                       "max": predicted * 100}, step=3)
    diags = dsp.check_attribution_ratchet(
        [(str(run), dsp.load_run_artifacts(str(run)))], ceiling)
    assert len(diags) == 1 and diags[0].rule_id == "DSO705"
    assert "step_unexplained_fraction" in diags[0].message
    # recording metrics with measured evidence present captures the
    # fraction key too (what --update-baseline writes)
    recorded = dsp.attribution_metrics(
        dsp.load_run_artifacts(str(run)), run_dir=str(run))
    assert dsp.unexplained_metric_key("train_step") in recorded
    assert recorded[dsp.unexplained_metric_key("train_step")] \
        == pytest.approx(0.99, abs=0.01)
