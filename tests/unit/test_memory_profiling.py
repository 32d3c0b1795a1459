"""Memory observability suite (``deepspeed_tpu/profiling/memory`` +
``capacity``): the compiled-program HBM ledger
(records every engine jit entry point's ``memory_analysis`` with zero
step-path cost and bit-identical training), live watermark events at the
steps_per_print cadence, the offload host-buffer registry, the AOT
capacity planner's fit/no-fit verdict on CPU (fail-soft when capacity is
unknowable)."""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.profiling import capacity
from deepspeed_tpu.profiling import memory as mem
from deepspeed_tpu.telemetry import read_events, validate_event

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 16
def tel_config(run_dir, **overrides):
    cfg = base_config(steps_per_print=1,
                      telemetry={"enabled": True, "run_dir": str(run_dir)},
                      profiling={"memory_ledger": True,
                                 "memory_watermarks": True})
    cfg.update(overrides)
    return cfg


def make_engine(config, cpu_devices, dp=4):
    mesh = make_mesh({"data": dp}, devices=cpu_devices[:dp])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=config, mesh=mesh)
    return engine


def run_steps(engine, batches):
    return [float(np.asarray(engine.train_batch(iter([b]))))
            for b in batches]


# ------------------------------------------------------------- the ledger
def test_ledger_records_engine_programs(cpu_devices, tmp_path):
    """Every dispatched jit entry point lands in the ledger with its
    memory_analysis bytes, one schema-clean ``memory`` event per program
    and per-program gauges — all recorded at compile time."""
    run_dir = tmp_path / "tel"
    engine = make_engine(tel_config(run_dir), cpu_devices)
    run_steps(engine, random_batches(2, 16, HIDDEN, seed=0))
    entries = engine.memory_ledger.entries()
    assert "train_step" in entries and "cast_params" in entries
    ts = entries["train_step"]
    assert ts["argument_size_in_bytes"] > 0
    assert ts["alias_size_in_bytes"] > 0          # donated buffers
    assert engine.memory_ledger.predicted_peak_bytes("train_step") > 0
    snap = engine.telemetry.registry.snapshot()
    assert snap["memory/program/train_step/argument_size_in_bytes"][
        "value"] > 0
    engine.close()
    events = [r for r in read_events(run_dir) if r["type"] == "memory"]
    programs = {e["data"]["program"] for e in events
                if e["data"]["kind"] == "program"}
    assert {"train_step", "cast_params"} <= programs
    for e in events:
        assert validate_event(e) == [], e


def test_ledger_training_parity(cpu_devices, tmp_path):
    """The ledger's compiled-executable path must train identically to
    the plain jit path (same programs, donation intact)."""
    batches = random_batches(4, 16, HIDDEN, seed=3)
    plain = run_steps(make_engine(base_config(), cpu_devices), batches)
    ledgered = run_steps(
        make_engine(base_config(profiling={"memory_ledger": True}),
                    cpu_devices), batches)
    assert plain == ledgered


def test_ledgered_jit_falls_back_on_shape_change():
    """A wrapped program keeps answering correctly when callers change
    shapes (falls back to jit retrace) — and records exactly once."""
    ledger = mem.MemoryLedger(enabled=True)
    calls = []

    @jax.jit
    def double(x):
        calls.append(None)  # traced per compile
        return x * 2

    wrapped = ledger.wrap("double", double)
    a = wrapped(jnp.arange(4.0))
    b = wrapped(jnp.arange(4.0))          # compiled path
    c = wrapped(jnp.arange(8.0))          # shape change -> jit fallback
    assert list(np.asarray(a)) == [0, 2, 4, 6]
    assert list(np.asarray(b)) == [0, 2, 4, 6]
    assert list(np.asarray(c))[:3] == [0, 2, 4]
    assert ledger.entry("double") is not None
    assert len(ledger.entries()) == 1


def test_ledgered_jit_static_argnums_and_tracers():
    ledger = mem.MemoryLedger(enabled=True)
    wrapped = ledger.wrap("ws", jax.jit(
        lambda x, spec: x * len(spec), static_argnums=(1,)),
        static_argnums=(1,))
    a = wrapped(jnp.ones(4), ("i", "j"))
    assert float(np.asarray(a)[0]) == 2.0
    # a DIFFERENT static value must not reuse the baked executable
    b = wrapped(jnp.ones(4), ("i", "j", "k"))
    assert float(np.asarray(b)[0]) == 3.0
    # tracer args (an outer trace over the wrapper) delegate cleanly
    g = jax.jit(lambda x: wrapped(x, ("i", "j")))(jnp.ones(4))
    assert float(np.asarray(g)[0]) == 2.0


def test_disabled_ledger_returns_raw_fn():
    ledger = mem.MemoryLedger(enabled=False)
    fn = jax.jit(lambda x: x)
    assert ledger.wrap("f", fn) is fn
    assert ledger.entries() == {}


# --------------------------------------------------- watermarks + buffers
def test_watermark_events_at_print_cadence(cpu_devices, tmp_path):
    """One ``memory``/watermark event per steps_per_print boundary,
    honest about backend capability (CPU reports no stats ->
    reporting=0, sums stay 0 rather than fabricated)."""
    run_dir = tmp_path / "tel"
    engine = make_engine(tel_config(run_dir), cpu_devices)
    run_steps(engine, random_batches(3, 16, HIDDEN, seed=1))
    engine.close()
    marks = [r for r in read_events(run_dir)
             if r["type"] == "memory" and r["data"]["kind"] == "watermark"]
    assert [m["step"] for m in marks] == [1, 2, 3]
    for m in marks:
        data = m["data"]
        assert {"bytes_in_use", "peak_bytes_in_use", "devices",
                "reporting", "host_buffer_bytes"} <= set(data)
        if data["reporting"] == 0:
            assert data["bytes_in_use"] == 0


def test_host_buffer_registry_under_offload(cpu_devices, tmp_path,
                                            monkeypatch):
    """The offload coordinator feeds the pinned-buffer registry: one
    family per host buffer (master + flat optimizer leaves), group
    counts matching the coordinator layout, and one host_buffers event
    carrying the per-step wire bytes."""
    from deepspeed_tpu.runtime.zero import coordinator as coord

    monkeypatch.setenv("DS_OFFLOAD_FORCE_INJIT", "1")
    monkeypatch.setattr(coord, "HOST_GROUP_BYTES", 2 << 20)
    run_dir = tmp_path / "tel"
    mesh = make_mesh({"data": 1}, devices=cpu_devices[:1])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(256, nlayers=3),
        config=tel_config(run_dir,
                          zero_optimization={"stage": 2,
                                             "cpu_offload": True,
                                             "offload_chunk_mb": 1}),
        mesh=mesh)
    registry = engine.memory_ledger.host_buffers
    families = {e["family"]: e for e in registry.entries()}
    assert "master" in families
    assert any(f.startswith("opt/") for f in families)
    bounds, per_family = engine.flat.host_buffer_layout()
    assert families["master"]["count"] == len(bounds) == per_family
    assert registry.total_bytes() > 0
    run_steps(engine, random_batches(1, 16, 256, seed=2))
    engine.close()
    buf_events = [r for r in read_events(run_dir)
                  if r["type"] == "memory"
                  and r["data"]["kind"] == "host_buffers"]
    assert buf_events
    data = buf_events[0]["data"]
    assert data["bytes"] == registry.total_bytes()
    assert data["buffers"] == registry.total_count()
    assert data.get("state_wire_bytes_per_step", 0) > 0


# ------------------------------------------------- shared memory summary
class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_summary_sums_across_devices():
    devs = [_FakeDev({"bytes_in_use": 10, "peak_bytes_in_use": 20,
                      "bytes_limit": 100}),
            _FakeDev({"bytes_in_use": 1, "peak_bytes_in_use": 2,
                      "bytes_limit": 100}),
            _FakeDev(None)]
    s = mem.device_memory_summary(devs)
    assert s == {"bytes_in_use": 11, "peak_bytes_in_use": 22,
                 "bytes_limit": 200, "devices": 3, "reporting": 2}


def test_see_memory_usage_routes_through_shared_helper(monkeypatch):
    """Both historical call sites (runtime.utils + the engine's
    memory_breakdown) resolve to the one cross-device implementation —
    the device-0-only reader is gone."""
    from deepspeed_tpu.runtime.utils import see_memory_usage
    from deepspeed_tpu.utils.logging import logger

    fake = {"bytes_in_use": 3 << 30, "peak_bytes_in_use": 5 << 30,
            "bytes_limit": 32 << 30, "devices": 2, "reporting": 2}
    monkeypatch.setattr(mem, "device_memory_summary", lambda devices=None:
                        dict(fake))
    messages = []
    handler = logging.Handler()
    handler.emit = lambda rec: messages.append(rec.getMessage())
    logger.addHandler(handler)
    try:
        see_memory_usage("after step", force=True)
        see_memory_usage("quiet")          # force=False: no output
    finally:
        logger.removeHandler(handler)
    assert len(messages) == 1
    assert "after step" in messages[0]
    assert "5.0000 GB" in messages[0] and "2/2 local device(s)" \
        in messages[0]
    # the timer's breakdown string comes from the same summary
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    assert "2/2 local device(s)" in SynchronizedWallClockTimer.memory_usage()


# ---------------------------------------------------- capacity planner
def _planner_config(tmp_path):
    path = tmp_path / "plan_config.json"
    path.write_text(json.dumps({
        "train_batch_size": 2,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
    }))
    return str(path)


def _run_planner(tmp_path, capsys, *extra):
    rc = capacity.main([
        "--config", _planner_config(tmp_path),
        "--hidden", "32", "--layers", "1", "--heads", "2",
        "--seq", "64", "--batch", "2", "--json", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_capacity_planner_fit_verdict(tmp_path, capsys):
    """CPU acceptance: compile-only plan, real memory_analysis numbers,
    FIT against an ample capacity — exit 0, no step ever runs."""
    rc, result = _run_planner(tmp_path, capsys, "--capacity-gb", "64")
    assert rc == 0 and result["fit"] is True
    assert result["analysis_available"]
    assert result["predicted_peak_hbm_bytes"] > 0
    assert result["predicted_temp_bytes"] >= 0
    assert result["params_b"] > 0


def test_capacity_planner_no_fit_verdict(tmp_path, capsys):
    rc, result = _run_planner(tmp_path, capsys, "--capacity-gb", "0.0001")
    assert rc == 1 and result["fit"] is False


def test_capacity_planner_fail_soft_without_capacity(tmp_path, capsys):
    """CPU reports no bytes_limit: verdict must degrade to UNKNOWN
    (exit 3), never crash — the fail-soft contract."""
    rc, result = _run_planner(tmp_path, capsys)
    assert rc == 3 and result["fit"] is None
    assert result["predicted_peak_hbm_bytes"] > 0   # analysis still real


def test_capacity_planner_usage_errors_exit_2(tmp_path, capsys):
    """Exit-code contract: 1 is reserved for NO-FIT — a typo'd model or
    a partial --hidden/--layers/--heads spec must exit 2, not plan the
    preset default."""
    cfg = _planner_config(tmp_path)
    assert capacity.main(["--config", cfg, "--model", "gpt2-typo"]) == 2
    assert capacity.main(["--config", cfg, "--hidden", "2048",
                          "--layers", "24"]) == 2  # --heads forgotten
    assert capacity.main(["--config", str(tmp_path / "absent.json")]) == 2
    err = capsys.readouterr().err
    assert "gpt2-typo" in err or "--model" in err
    assert "must all be given together" in err


def _stage_planner_config(tmp_path):
    """One config for the stage-2 vs stage-3 planner arms: the stage is
    the ONLY thing --zero-stage varies, so the verdicts compare exactly
    the ÷dp sharding.  Small collective groups keep the gathered-buffer
    liveness (and the CPU compile) bounded."""
    path = tmp_path / "plan_stage_config.json"
    path.write_text(json.dumps({
        "train_batch_size": 4,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2, "overlap_comm": "auto",
                              "reduce_bucket_size": 12500000,
                              "allgather_bucket_size": 25000000},
    }))
    return str(path)


def _run_stage_planner(cfg, capsys, stage, *extra):
    rc = capacity.main([
        "--config", cfg, "--hidden", "32", "--layers", "1",
        "--heads", "2", "--seq", "64", "--batch", "4", "--dp", "4",
        "--zero-stage", str(stage), "--json", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_capacity_planner_stage3_divdp_receipt(tmp_path, capsys):
    """``--zero-stage 3 --dp 4``: the plan's residency receipt quotes
    the flat fp32 master ÷dp (param_shard_divisor == dp) where the
    stage-2 plan at the SAME geometry quotes the replicated figure ÷1
    — the planner-verified ÷dp receipt of ROADMAP item 2."""
    cfg = _stage_planner_config(tmp_path)
    rc3, r3 = _run_stage_planner(cfg, capsys, 3, "--capacity-gb", "64")
    assert rc3 == 0 and r3["fit"] is True
    assert r3["zero_stage"] == 3 and r3["dp"] == 4
    assert r3["param_shard_divisor"] == 4
    assert r3["param_bytes_per_device"] * 4 == r3["param_bytes_global"]
    rc2, r2 = _run_stage_planner(cfg, capsys, 2, "--capacity-gb", "64")
    assert rc2 == 0 and r2["zero_stage"] == 2
    assert r2["param_shard_divisor"] == 1
    assert r2["param_bytes_per_device"] == r2["param_bytes_global"]
    # same model: the stage-3 per-device claim is a quarter of the
    # replicated one (modulo the flat layout's row/bucket padding)
    assert r3["param_bytes_per_device"] < r2["param_bytes_per_device"] / 3


def test_capacity_planner_stage3_report_prints_shard_line(tmp_path,
                                                          capsys):
    """The human report carries the ÷shard line verbatim."""
    cfg = _stage_planner_config(tmp_path)
    rc = capacity.main([
        "--config", cfg, "--hidden", "32", "--layers", "1",
        "--heads", "2", "--seq", "64", "--batch", "4", "--dp", "4",
        "--zero-stage", "3", "--capacity-gb", "64"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "zero-stage=3 dp=4" in out
    assert "÷4 shard" in out


@pytest.mark.slow
def test_capacity_planner_stage3_fits_what_stage2_rejects(tmp_path,
                                                          capsys):
    """The round-20 capacity acceptance arms: a gpt2-xl-or-larger
    (1.82B params — hidden 4096 over 8 wide layers, more than gpt2-xl's
    1.56B) DEVICE-RESIDENT plan at dp=4 that stage 3 fits (exit 0) and
    stage 2 rejects (exit 1) at the same geometry and capacity.  The
    capacity is derived from the measured peaks rather than hardcoded:
    alias accounting differs between cold and cache-deserialized
    executables (DSP602), so the measure arms run AFTER a warm-up pass
    and the verdict arms re-plan under the same cache state."""
    cfg = _stage_planner_config(tmp_path)
    geom = ("--hidden", "4096", "--layers", "8", "--heads", "32",
            "--seq", "256", "--batch", "4", "--dp", "4")

    def arm(stage, *extra):
        rc = capacity.main(["--config", cfg, *geom, "--zero-stage",
                            str(stage), "--json", *extra])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    arm(3)
    arm(2)                         # warm-up: pin the alias accounting
    rc3, r3 = arm(3)
    rc2, r2 = arm(2)
    assert rc3 == 3 and rc2 == 3   # fail-soft: no capacity known on CPU
    # gpt2-xl or larger (the xl preset's analytic count at its own
    # 1024-position table)
    xl_b = round(capacity.gpt2_param_count(1600, 48) / 1e9, 3)
    assert r3["params_b"] >= xl_b
    assert r3["param_shard_divisor"] == 4
    assert r3["param_bytes_per_device"] * 4 == r3["param_bytes_global"]
    assert r2["param_shard_divisor"] == 1
    p3 = r3["predicted_peak_hbm_bytes"]
    p2 = r2["predicted_peak_hbm_bytes"]
    assert p3 < p2, (p3, p2)
    # verdict arms: capacity strictly between the two measured peaks
    cap_gb = (p3 * 1.02) / capacity.DEFAULT_HEADROOM / (1 << 30)
    assert p2 > p3 * 1.02, (p3, p2)
    rc3, r3 = arm(3, "--capacity-gb", f"{cap_gb:.6f}")
    assert rc3 == 0 and r3["fit"] is True
    rc2, r2 = arm(2, "--capacity-gb", f"{cap_gb:.6f}")
    assert rc2 == 1 and r2["fit"] is False


def test_predicted_peak_accounting():
    entry = {"argument_size_in_bytes": 100, "output_size_in_bytes": 90,
             "alias_size_in_bytes": 80, "temp_size_in_bytes": 50,
             "generated_code_size_in_bytes": 7,
             "host_argument_size_in_bytes": 30,
             "host_output_size_in_bytes": 30,
             "host_alias_size_in_bytes": 30, "host_temp_size_in_bytes": 5}
    assert mem.predicted_peak_bytes(entry) == 100 + 90 - 80 + 50 + 7
    assert mem.predicted_host_bytes(entry) == 30 + 30 - 30 + 5
    assert mem.predicted_peak_bytes(None) is None


# ------------------------------------------------------------ env report
def test_env_report_prints_hbm_capacity(capsys):
    from deepspeed_tpu import env_report

    env_report.main()
    out = capsys.readouterr().out
    assert "hbm capacity" in out
