"""Tier-1 CI self-verify: HEAD's REAL compiled step programs carry zero
DSP6xx program-verifier violations.

The dsverify analog of ``test_dslint_self.py``'s self-lint: the zero2
(dp×tp mesh), pipeline, and offload-in-jit (``DS_OFFLOAD_FORCE_INJIT``,
streamed update + bf16 error-feedback qres donation) step programs are
compiled on the virtual CPU mesh — warm under the suite's persistent
compile cache — then verified through BOTH surfaces: the live
``engine.verify_programs()`` hook and the offline
``dslint --programs <run_dir>`` CLI over the dumped artifacts.  Any
unsuppressed DSP6xx finding fails the suite with the diagnostics in the
assertion message.  (DSP602 downgraded verdicts are allowed: the warm
compile cache legitimately deserializes executables that report
alias=0 — the caveat the rule exists to make explicit.)

Since round 12 the offload-injit leg asserts the overlap analyzer's
verdict (DSO7xx) for the OVERLAPPED world: the double-buffered chunk
pipeline is the default (``offload_overlap: auto``), so the streamed
step program verifies overlap-CLEAN — no DSO702, bare ``--programs``
exits 0 — and the checked-in baseline records its exposed-wire metric
as the DSO704 ratchet.  The serialized control (``offload_overlap:
false``) must still trip DSO702 with STRICTLY MORE exposed wire, and
the (empty-violations) baseline must NOT absolve it: a change that
re-serializes the stream fails CI through exactly that path.
"""

import os

import numpy as np
import pytest

import deepspeed_tpu as deepspeed
import deepspeed_tpu.runtime.zero.coordinator as coord
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.tools.dslint.cli import main as dslint_main

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 64

CHECKED_IN_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "dslint_baseline.json")


def _assert_clean(engine, run_dir=None):
    report = engine.verify_programs()
    assert report is not None and report["programs_checked"] >= 1
    listing = "\n".join(d.format() for d in report["diagnostics"]
                        if not d.suppressed)
    assert report["violations"] == 0, (
        f"DSP6xx program-verifier violations in HEAD's compiled "
        f"programs:\n{listing}")
    if run_dir is not None:
        assert dslint_main(["--programs", str(run_dir)]) == 0
    return report


def _cfg(tmp_path, **overrides):
    cfg = base_config(
        steps_per_print=10 ** 9,
        telemetry={"enabled": True, "run_dir": str(tmp_path / "run")},
        profiling={"comm_ledger": True, "memory_ledger": True})
    cfg.update(overrides)
    return cfg


def test_zero2_dp_tp_step_programs_verify_clean(cpu_devices, tmp_path):
    """The flatten-×tp bug's home turf: a dp×tp mesh with ZeRO-2.  The
    fixed flatten plus the fused step must produce zero DSP6xx
    findings — the all-reduces stay on the data axis, the donation
    aliases materialize."""
    cfg = _cfg(tmp_path, zero_optimization={"stage": 2},
               gradient_clipping=1.0)
    mesh = make_mesh({"data": 2, "model": 2}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=0)[0]]))
    assert engine.flat.master_provenance == "jit_copy"
    report = _assert_clean(engine, run_dir=tmp_path / "run")
    # round 17: the sharding auditor ran (declared spec reconciled
    # against the compiled layout) and priced the step's residency
    sh = report["sharding"]["train_step"]
    assert sh["param_bytes_per_device"] > 0
    assert sh["param_shard_divisor"] >= 1
    engine.close()


def test_pipe_step_programs_verify_clean(cpu_devices, tmp_path):
    """The pipeline (step-wise) path compiles separate fwd_bwd / accum /
    apply_update / cast_params programs — all ride the same ledger hook
    and must verify clean."""
    from deepspeed_tpu.runtime.pipe import LayerSpec, PipelineModule

    class Linear:
        def __init__(self, in_dim, out_dim):
            self.in_dim, self.out_dim = in_dim, out_dim

        def init(self, rng):
            import jax

            k = jax.random.normal(rng, (self.in_dim, self.out_dim))
            return {"w": k * 0.1}

        def apply(self, params, x):
            import jax.numpy as jnp

            return jnp.tanh(x @ params["w"])

    def mse(outputs, labels):
        import jax.numpy as jnp

        return jnp.mean((outputs - labels) ** 2)

    cfg = _cfg(tmp_path)
    cfg["train_micro_batch_size_per_gpu"] = 4
    cfg["gradient_accumulation_steps"] = 4
    cfg.pop("train_batch_size", None)
    mesh = make_mesh({"pipe": 2, "data": 2}, devices=cpu_devices[:4])
    module = PipelineModule([LayerSpec(Linear, HIDDEN, HIDDEN)
                             for _ in range(4)], loss_fn=mse)
    engine, *_ = deepspeed.initialize(model=module, config=cfg, mesh=mesh)
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(8, HIDDEN)).astype(np.float32),
             rng.normal(size=(8, HIDDEN)).astype(np.float32))
            for _ in range(4)]
    engine.train_batch(iter(data))
    _assert_clean(engine, run_dir=tmp_path / "run")
    engine.close()


def _offload_engine(cpu_devices, tmp_path, run_name, overlap="auto"):
    cfg = _cfg(
        tmp_path,
        zero_optimization={
            "stage": 2, "cpu_offload": True, "offload_chunk_mb": 1,
            "offload_uniform_chunks": True,
            "offload_overlap": overlap,
            "offload_state_dtype": {"master": "bf16", "momentum": "bf16",
                                    "variance": "bf16",
                                    "error_feedback": True}})
    cfg["telemetry"]["run_dir"] = str(tmp_path / run_name)
    mesh = make_mesh({"data": 1}, devices=cpu_devices[:1])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(256, nlayers=8), config=cfg, mesh=mesh)
    engine.train_batch(iter([random_batches(
        1, engine.train_micro_batch_size_per_gpu(), 256, seed=0)[0]]))
    return engine


def test_offload_injit_step_programs_verify_clean(cpu_devices, tmp_path,
                                                  monkeypatch):
    """The streamed-offload program (uniform-chunk lax.scan update,
    bf16 host state with error-feedback residuals): master/opt/qres
    buffers are donated through the fused step and the grouped
    pinned-host layout — the heaviest donation surface in the repo —
    and must verify clean under DS_OFFLOAD_FORCE_INJIT on CPU.  Since
    round 12 "clean" includes the overlap verdict: the double-buffered
    pipeline is the default, so NO DSO702 fires and the bare
    ``--programs`` run exits 0 — the baseline no longer needs to
    absolve anything, it records the exposed-wire ratchet metric."""
    monkeypatch.setenv("DS_OFFLOAD_FORCE_INJIT", "1")
    monkeypatch.setattr(coord, "HOST_GROUP_BYTES", 2 << 20)
    engine = _offload_engine(cpu_devices, tmp_path, "run")
    assert engine.flat.master_provenance == "host_staging_device_put"
    assert engine.state.get("qres"), "error-feedback residuals expected"
    assert engine._donation_specs["train_step"][-1] == 12  # qres donated
    sched = engine.host_stream_schedule()
    assert sched["overlap"] is True and sched["form"] == "scan"
    assert sched["prefetch_depth"] >= 2 and sched["chunks"] > 1
    report = engine.verify_programs()
    assert report is not None and report["violations"] == 0, [
        d.format() for d in report["diagnostics"] if not d.suppressed]
    assert report["overlap"] is not None
    assert report["overlap"]["serialized_host_transfers"] == 0
    declared = engine.host_state_bytes_per_step()
    assert declared and declared > 0
    receipt = engine.overlap_receipt()
    assert receipt["program"] == "train_step"
    # the pipeline fill/drain stays exposed (the model never claims a
    # free lunch), but some wire now hides behind the update compute
    assert 0 < receipt["exposed_wire_seconds"] < receipt["wire_seconds"]
    assert 0 < receipt["overlap_fraction"] < 1.0
    engine.close()
    # offline CLI: clean bare (exit 0) AND under the checked-in
    # baseline (exit 0 — the recorded exposed-wire metric holds)
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0
    assert dslint_main(["--programs", str(tmp_path / "run"),
                        "--baseline", CHECKED_IN_BASELINE]) == 0


def _zero2_overlap_engine(cpu_devices, tmp_path, run_name,
                          overlap=True):
    """The round-14 bucketed-exchange fixture: pure-dp ZeRO-2 with
    overlap_comm on (the overlapped schedule) or off (the serialized
    GSPMD control).  Deterministic geometry — the checked-in baseline
    records this fixture's collective exposure as the DSO704 ratchet
    (comm_exposed_wire_seconds keys, next to the offload fixture's
    host-stream keys)."""
    cfg = _cfg(
        tmp_path,
        zero_optimization={"stage": 2, "overlap_comm": overlap,
                           # 8 x 65792-element layers: 4 reduce
                           # buckets, 2 all-gather groups
                           "reduce_bucket_size": 140000,
                           "allgather_bucket_size": 280000},
        gradient_clipping=1.0)
    cfg["telemetry"]["run_dir"] = str(tmp_path / run_name)
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(256, nlayers=8), config=cfg, mesh=mesh)
    engine.train_batch(iter([random_batches(
        1, engine.train_micro_batch_size_per_gpu() * 4, 256,
        seed=0)[0]]))
    return engine


def test_zero2_overlap_step_programs_verify_clean(cpu_devices,
                                                  tmp_path):
    """The round-14 acceptance criterion, overlap side: the bucketed
    zero-2 step verifies CLEAN — per-bucket reduce-scatters + per-group
    all-gathers re-priced by the declared schedule, DSO701 quiet, bare
    ``--programs`` exit 0, and the checked-in baseline's
    comm-exposure metrics hold (DSO704)."""
    engine = _zero2_overlap_engine(cpu_devices, tmp_path, "run")
    assert engine.comm_overlap_enabled()
    sched = engine.collective_schedule()
    assert sched["overlap"] is True and sched["rs_buckets"] == 4, sched
    assert sched["ag_buckets"] == 2, sched
    report = _assert_clean(engine)
    assert report["overlap"] is not None
    # on this CPU toy the compute budget cannot hide every bucket
    # (some stay classified serialized — honestly: there is nothing to
    # hide behind), but real wire DID move behind compute
    agg = report["overlap"]
    assert agg["exposed_wire_seconds"] < agg["wire_seconds"]
    receipt = engine.overlap_receipt()
    assert receipt["program"] == "train_step"
    # fill/drain stays exposed (no free lunch); steady state hides
    assert 0 < receipt["exposed_wire_seconds"] < receipt["wire_seconds"]
    assert 0 < receipt["overlap_fraction"] < 1.0
    engine.close()
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0
    assert dslint_main(["--programs", str(tmp_path / "run"),
                        "--baseline", CHECKED_IN_BASELINE]) == 0


def test_zero2_serialized_control_trips_dso701_and_ratchet(
        cpu_devices, tmp_path):
    """``overlap_comm: false`` — the serialized GSPMD control.  DSO701
    must fire on the fused step with a NONZERO independent-compute
    window (the declared potential the bucketed schedule would free),
    its exposed wire must be STRICTLY higher than the overlapped
    schedule's, and the checked-in baseline must NOT absolve it."""
    eng_on = _zero2_overlap_engine(cpu_devices, tmp_path, "run_on")
    on = eng_on.overlap_receipt()
    eng_on.close()
    eng_off = _zero2_overlap_engine(cpu_devices, tmp_path, "run_off",
                                    overlap=False)
    assert not eng_off.comm_overlap_enabled()
    assert eng_off.collective_schedule()["overlap"] is False
    report = eng_off.verify_programs()
    dso701 = [d for d in report["diagnostics"]
              if d.rule_id == "DSO701"]
    assert dso701 and any("[train_step]" in d.message
                          for d in dso701), [
        d.format() for d in report["diagnostics"]]
    msg = next(d.message for d in dso701 if "[train_step]" in d.message)
    # a NONZERO independent-compute window is quoted in the finding
    import re as _re

    m = _re.search(r"up to ([0-9.]+) ms of independent compute", msg)
    assert m and float(m.group(1)) > 0, msg
    off = eng_off.overlap_receipt()
    eng_off.close()
    assert on["exposed_wire_seconds"] < off["exposed_wire_seconds"]
    assert on["overlap_fraction"] > off["overlap_fraction"]
    assert dslint_main(["--programs", str(tmp_path / "run_off")]) == 1
    assert dslint_main(["--programs", str(tmp_path / "run_off"),
                        "--baseline", CHECKED_IN_BASELINE]) == 1


def test_offload_serialized_control_trips_dso702_and_ratchet(
        cpu_devices, tmp_path, monkeypatch):
    """``offload_overlap: false`` — the serialized control schedule.
    Its exposed wire must be STRICTLY higher than the overlapped
    schedule's (the round-12 acceptance criterion), DSO702 must fire on
    the fused step, and the checked-in baseline must NOT absolve it:
    any future change that re-serializes the stream fails CI through
    this exact path (empty violations baseline + DSO704 metric
    ratchet)."""
    monkeypatch.setenv("DS_OFFLOAD_FORCE_INJIT", "1")
    monkeypatch.setattr(coord, "HOST_GROUP_BYTES", 2 << 20)
    eng_on = _offload_engine(cpu_devices, tmp_path, "run_on")
    on = eng_on.overlap_receipt()
    eng_on.close()
    eng_off = _offload_engine(cpu_devices, tmp_path, "run_off",
                              overlap=False)
    assert eng_off.host_stream_schedule()["overlap"] is False
    assert eng_off._offload_prefetch_depth == 1
    report = eng_off.verify_programs()
    dso702 = [d for d in report["diagnostics"] if d.rule_id == "DSO702"]
    assert len(dso702) == 1 and "[train_step]" in dso702[0].message, [
        d.format() for d in report["diagnostics"]]
    off = eng_off.overlap_receipt()
    eng_off.close()
    # the acceptance criterion: exposed-wire fraction strictly lower
    # with offload_overlap: on than off, same model/geometry
    assert on["exposed_wire_seconds"] < off["exposed_wire_seconds"]
    assert on["overlap_fraction"] > off["overlap_fraction"]
    # the serialized control fails a bare --programs run AND the
    # checked-in (empty-violations) baseline run: re-serialization is
    # CI-fatal through the fresh DSO702 (the DSO704 metric ratchet
    # guards the subtler partial regressions — test_overlap.py)
    assert dslint_main(["--programs", str(tmp_path / "run_off")]) == 1
    assert dslint_main(["--programs", str(tmp_path / "run_off"),
                        "--baseline", CHECKED_IN_BASELINE]) == 1


def _zero3_engine(cpu_devices, tmp_path, run_name, overlap=True):
    """The round-20 stage-3 fixture: the SAME geometry/buckets as
    ``_zero2_overlap_engine`` but with sharded parameters — the flat
    fp32 master is the only persistent parameter surface (÷dp
    resident), and the step program issues the JIT per-group
    all-gathers inline.  ``overlap=False`` is the serialized GSPMD
    control (a single full-tensor gather schedule the analyzer must
    flag)."""
    cfg = _cfg(
        tmp_path,
        zero_optimization={"stage": 3, "overlap_comm": overlap,
                           "reduce_bucket_size": 140000,
                           "allgather_bucket_size": 280000},
        gradient_clipping=1.0)
    cfg["telemetry"]["run_dir"] = str(tmp_path / run_name)
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(256, nlayers=8), config=cfg, mesh=mesh)
    engine.train_batch(iter([random_batches(
        1, engine.train_micro_batch_size_per_gpu() * 4, 256,
        seed=0)[0]]))
    return engine


def test_zero3_step_programs_verify_clean(cpu_devices, tmp_path):
    """Round-20 acceptance criterion, overlap+sharding side: the
    stage-3 step — JIT per-group parameter all-gathers in forward
    order, rematerialized on backward, gradients arriving reduced AND
    sharded through the all-gather transpose — verifies CLEAN.  DSO701
    quiet, DSS801 clean with the ÷dp residency receipt
    (param_shard_divisor == dp), bare ``--programs`` exit 0, and the
    checked-in baseline's tag-qualified pins hold."""
    engine = _zero3_engine(cpu_devices, tmp_path, "run")
    assert engine.comm_overlap_enabled()
    sched = engine.collective_schedule()
    assert sched["overlap"] is True and sched["param_gathers"] is True
    assert sched["rs_buckets"] == 4 and sched["ag_buckets"] == 2, sched
    assert sched["gather_bytes"] > 0
    report = _assert_clean(engine)
    assert report["overlap"] is not None
    agg = report["overlap"]
    assert agg["exposed_wire_seconds"] < agg["wire_seconds"]
    sh = report["sharding"]["train_step"]
    assert sh["param_shard_divisor"] == 4
    # the ÷dp receipt: 528 padded rows × 1024 lanes × 4 B over dp=4
    assert sh["param_bytes_per_device"] == 528 * 1024 * 4 // 4
    receipt = engine.overlap_receipt()
    assert receipt["program"] == "train_step"
    assert 0 < receipt["exposed_wire_seconds"] < receipt["wire_seconds"]
    assert 0 < receipt["overlap_fraction"] < 1.0
    engine.close()
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0
    assert dslint_main(["--programs", str(tmp_path / "run"),
                        "--baseline", CHECKED_IN_BASELINE]) == 0


def test_zero3_serialized_control_trips_dso701_and_ratchet(
        cpu_devices, tmp_path):
    """``overlap_comm: false`` under stage 3 — the serialized control:
    parameters still shard ÷dp but the gathers ride the un-bucketed
    GSPMD schedule.  DSO701 must fire on the fused step with a NONZERO
    independent-compute window, its exposed wire must be STRICTLY
    higher than the overlapped schedule's, and the checked-in baseline
    must NOT absolve it."""
    eng_on = _zero3_engine(cpu_devices, tmp_path, "run_on")
    on = eng_on.overlap_receipt()
    eng_on.close()
    eng_off = _zero3_engine(cpu_devices, tmp_path, "run_off",
                            overlap=False)
    assert not eng_off.comm_overlap_enabled()
    report = eng_off.verify_programs()
    dso701 = [d for d in report["diagnostics"]
              if d.rule_id == "DSO701"]
    assert dso701 and any("[train_step]" in d.message
                          for d in dso701), [
        d.format() for d in report["diagnostics"]]
    msg = next(d.message for d in dso701 if "[train_step]" in d.message)
    import re as _re

    m = _re.search(r"up to ([0-9.]+) ms of independent compute", msg)
    assert m and float(m.group(1)) > 0, msg
    off = eng_off.overlap_receipt()
    eng_off.close()
    assert on["exposed_wire_seconds"] < off["exposed_wire_seconds"]
    assert on["overlap_fraction"] > off["overlap_fraction"]
    assert dslint_main(["--programs", str(tmp_path / "run_off")]) == 1
    assert dslint_main(["--programs", str(tmp_path / "run_off"),
                        "--baseline", CHECKED_IN_BASELINE]) == 1


def test_serving_decode_programs_verify_clean(cpu_devices, tmp_path):
    """Round-17 serving leg of the self-verify suite: the paged-KV
    decode/prefill programs carry a declared spec (``serve|data1`` —
    replicated serve weights + KV cache) and verify clean on BOTH
    surfaces, with the decode program's residency receipt priced (the
    ``serving_param_bytes_per_device`` figure)."""
    import json

    import jax

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadTPU
    from deepspeed_tpu.tools.dslint import programs as dsp

    model = GPT2LMHeadTPU(GPT2Config(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64, embd_dropout=0.0, attn_dropout=0.0,
        resid_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    cfg = {
        "inference": {"kv_block_size": 8, "kv_blocks": 64,
                      "max_batch_slots": 2, "max_seq_len": 64,
                      "prefill_buckets": [16], "token_budget": 256,
                      "max_new_tokens": 4},
        "steps_per_print": 10 ** 9,
        "telemetry": {"enabled": True, "run_dir": str(tmp_path / "run")},
        "profiling": {"comm_ledger": True, "memory_ledger": True},
    }
    engine = InferenceEngine(model, params, config=cfg)
    rng = np.random.default_rng(0)
    for i in range(3):
        engine.submit([int(t) for t in rng.integers(0, 256, size=8)],
                      request_id=f"r{i}")
    engine.run()
    report = engine.verify_programs()
    assert report is not None and report["violations"] == 0, [
        d.format() for d in report["diagnostics"] if not d.suppressed]
    sh = report["sharding"]["serve_decode"]
    assert sh["param_bytes_per_device"] > 0
    assert sh["param_shard_divisor"] == 1        # single-chip serve
    engine.close()
    # the sidecar carries the serve-tagged declaration; offline load
    # agrees and the bare --programs CLI stays clean
    side = json.loads((tmp_path / "run" / "programs" /
                       "serve_decode.json").read_text())
    decl = side["declared_sharding"]
    assert decl["tag"] == "serve|data1"
    assert set(decl["families"]) == {"params", "kv_cache"}
    assert decl["families"]["kv_cache"]["total_bytes"] > 0
    arts = {a.name: a
            for a in dsp.load_run_artifacts(str(tmp_path / "run"))}
    assert arts["serve_decode"].declared_sharding == decl
    assert dslint_main(["--programs", str(tmp_path / "run")]) == 0
