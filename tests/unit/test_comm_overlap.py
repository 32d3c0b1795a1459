"""Bucketed gradient-collective overlap (``overlap_comm``) tests.

Covers the round-14 tentpole end to end: the :class:`BucketPlan`
sub-partition layout (zero/buckets.py), the engine's per-bucket
``psum_scatter`` exchange + per-group master all-gathers, numerical
parity of the bucketed schedule against the serialized (GSPMD fused)
control, canonical-checkpoint compatibility across layouts and dp
degrees, the declared collective schedule, and the config surface.

Parity note (the documented tolerance): the bucketed exchange sums the
same per-rank gradients as GSPMD's fused reduction but in a different
association (per-bucket psum_scatter ring vs the fused all-reduce), so
masters drift by single ulps per step — measured ≤ 1.2e-7 absolute
after 22 steps on the fixture below.  The update math itself is
elementwise and layout-agnostic (bit-identical given identical
gradients); only the reduction order differs.
"""

import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.runtime.zero.buckets import BucketPlan

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 64
# 4 layers x (w 64x64 + b 64): 8 leaves, 16640 elements
NLAYERS = 4


def _cfg(overlap, clip=1.0, acc=1, **over):
    cfg = base_config(
        steps_per_print=10 ** 9,
        zero_optimization={"stage": 2, "overlap_comm": overlap,
                           # small buckets: several per model, multi-leaf
                           "reduce_bucket_size": 3 * HIDDEN * HIDDEN // 2,
                           "allgather_bucket_size": 3 * HIDDEN * HIDDEN},
        gradient_clipping=clip,
        telemetry={"enabled": False})
    if acc > 1:
        cfg["train_batch_size"] = 16 * acc
        cfg["gradient_accumulation_steps"] = acc
    cfg.update(over)
    return cfg


def _engine(cpu_devices, overlap, dp=4, **kw):
    mesh = make_mesh({"data": dp}, devices=cpu_devices[:dp])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=NLAYERS),
        config=_cfg(overlap, **kw), mesh=mesh)
    return engine


def _canonical_state(engine):
    """Canonical (layout-independent) host copies of master + flat opt
    leaves — the checkpoint format."""
    return {
        "master": engine.flat.gather_master_unpadded(
            engine.state["master"]),
        "exp_avg": engine.flat.gather_master_unpadded(
            engine.state["opt"].exp_avg),
        "exp_avg_sq": engine.flat.gather_master_unpadded(
            engine.state["opt"].exp_avg_sq),
    }


# ---------------------------------------------------------------- plan
def test_bucket_plan_layout_and_roundtrips():
    sizes = [1024 * 3 + 5, 2048, 100, 4096 * 2, 7, 1024]
    plan = BucketPlan(sizes, dp=4, reduce_bucket_size=5000,
                      allgather_bucket_size=9000, lanes=1024)
    # leaf-aligned, >= 1 leaf per bucket, oversized leaf alone
    assert [(b.leaf_lo, b.leaf_hi) for b in plan.buckets] == [
        (0, 1), (1, 3), (3, 4), (4, 6)]
    for b in plan.buckets:
        assert b.rows % 4 == 0 and b.piece_rows == b.rows // 4
    assert plan.rows == sum(b.rows for b in plan.buckets)
    assert plan.piece_rows * 4 == plan.rows
    # ag groups: consecutive buckets bounded by allgather_bucket_size
    assert plan.ag_groups == ((0, 2), (2, 3), (3, 4))

    arr = np.random.default_rng(0).normal(
        size=sum(sizes)).astype(np.float32)
    storage = plan.scatter_unpadded(arr)
    assert storage.shape == plan.shape
    assert np.array_equal(plan.gather_unpadded(storage), arr)
    # permutation is an exact involution pair
    canon = plan.canonical_from_storage(storage)
    assert np.array_equal(plan.storage_from_canonical(canon), storage)
    # shard-major property: rank r's contiguous shard holds exactly its
    # piece of every bucket
    S = plan.piece_rows
    for b in plan.buckets:
        block = canon[b.start_row:b.start_row + b.rows].reshape(
            4, b.piece_rows, 1024)
        for r in range(4):
            piece = storage[r * S + b.piece_start:
                            r * S + b.piece_start + b.piece_rows]
            assert np.array_equal(piece, block[r])


def test_bucket_plan_single_oversized_leaf_and_empty():
    plan = BucketPlan([10 ** 6], dp=8, reduce_bucket_size=10,
                      allgather_bucket_size=10)
    assert plan.n_buckets == 1 and plan.buckets[0].elements == 10 ** 6
    empty = BucketPlan([], dp=4, reduce_bucket_size=10,
                       allgather_bucket_size=10)
    assert empty.rows % 4 == 0
    assert empty.gather_unpadded(
        np.zeros(empty.shape, np.float32)).size == 0


# ------------------------------------------------------------- parity
def test_bucketed_parity_vs_serialized_20_steps(cpu_devices):
    """The acceptance criterion: masters/opt state of the bucketed
    schedule track the unbucketed step over >= 20 steps.  Not
    bit-identical — the documented reduction-order tolerance (module
    docstring): the per-bucket psum_scatter and GSPMD's fused exchange
    associate the same per-rank sums differently, a few ulps/step."""
    steps = 22
    batches = random_batches(steps, 16, HIDDEN, seed=0)

    def run(overlap):
        engine = _engine(cpu_devices, overlap)
        assert engine.comm_overlap_enabled() == overlap
        losses = [float(np.asarray(engine.train_batch(iter([b]))))
                  for b in batches]
        state = _canonical_state(engine)
        engine.close()
        return losses, state

    l_on, s_on = run(True)
    l_off, s_off = run(False)
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    for key in ("master", "exp_avg", "exp_avg_sq"):
        np.testing.assert_allclose(s_on[key], s_off[key], atol=5e-6,
                                   err_msg=f"{key} diverged")
    # and the drift really is ulp-scale, not silently at the tolerance
    assert np.abs(s_on["master"] - s_off["master"]).max() < 1e-6


def test_bucketed_parity_with_grad_accumulation(cpu_devices):
    """acc=2: the per-micro-batch bucketed exchange accumulates in the
    scan carry exactly like the fused GSPMD exchange."""
    batches = random_batches(6, 32, HIDDEN, seed=1)

    def halves(batch):
        x, y = batch
        return iter([(x[:16], y[:16]), (x[16:], y[16:])])

    def run(overlap):
        engine = _engine(cpu_devices, overlap, acc=2)
        losses = [float(np.asarray(engine.train_batch(halves(b))))
                  for b in batches]
        state = _canonical_state(engine)
        engine.close()
        return losses, state

    l_on, s_on = run(True)
    l_off, s_off = run(False)
    np.testing.assert_allclose(l_on, l_off, rtol=1e-5)
    np.testing.assert_allclose(s_on["master"], s_off["master"],
                               atol=5e-6)


def test_bucketed_dp1_global_parity(cpu_devices):
    """dp=4 bucketed vs dp=1 single-chip on the SAME global batches:
    the exchange must compute the global mean gradient (a psum-for-
    pmean bug scales it by dp — far outside this band)."""
    batches = random_batches(4, 16, HIDDEN, seed=2)
    engine = _engine(cpu_devices, True)
    losses = [float(np.asarray(engine.train_batch(iter([b]))))
              for b in batches]
    engine.close()
    ref = _engine(cpu_devices, "auto", dp=1)
    assert not ref.comm_overlap_enabled()  # dp=1: nothing to bucket
    ref_losses = [float(np.asarray(ref.train_batch(iter([b]))))
                  for b in batches]
    ref.close()
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)


# -------------------------------------------------- checkpoint/layouts
def test_checkpoint_roundtrip_across_layouts_and_dp(cpu_devices,
                                                    tmp_path):
    """Checkpoints are canonical: bucketed (shard-major) saves restore
    bit-exactly into (a) the same geometry, (b) the serialized layout,
    and (c) a DIFFERENT dp degree's bucketed layout (bucket padding
    depends on dp, the canonical bytes do not)."""
    engine = _engine(cpu_devices, True)
    for b in random_batches(3, 16, HIDDEN, seed=3):
        engine.train_batch(iter([b]))
    want = _canonical_state(engine)
    engine.save_checkpoint(str(tmp_path), tag="ov")
    engine.wait_checkpoint()
    engine.close()

    for name, kwargs in (("same", dict(overlap=True)),
                         ("serialized", dict(overlap=False)),
                         ("dp2", dict(overlap=True, dp=2))):
        other = _engine(cpu_devices, **kwargs)
        path, _ = other.load_checkpoint(str(tmp_path), tag="ov")
        assert path is not None, name
        got = _canonical_state(other)
        for key in want:
            assert np.array_equal(want[key], got[key]), (name, key)
        # restored state trains (donation-safe re-homing)
        other.train_batch(iter([random_batches(1, 16, HIDDEN,
                                               seed=9)[0]]))
        other.close()


# ---------------------------------------------------- schedule/receipts
def test_schedule_declared_and_hlo_bucket_counts(cpu_devices, tmp_path):
    """The declared schedule matches the compiled HLO: exactly
    rs_buckets reduce-scatters and ag_buckets all-gathers in the fused
    step (the tiny loss pmean stays an all-reduce), and the sidecar
    round-trips the schedule for the offline verifier."""
    cfg = _cfg(True, telemetry={"enabled": True,
                                "run_dir": str(tmp_path / "run")},
               profiling={"comm_ledger": True, "memory_ledger": True})
    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=NLAYERS), config=cfg,
        mesh=mesh)
    engine.train_batch(iter([random_batches(1, 16, HIDDEN, seed=0)[0]]))
    sched = engine.collective_schedule()
    assert sched["overlap"] is True
    assert sched["rs_buckets"] > 1 and sched["ag_buckets"] > 1
    plan = engine.flat.bucket_plan
    assert sched["rs_buckets"] == plan.n_buckets
    entry = engine.comm_ledger.entry("train_step")
    assert entry["ops"]["reduce-scatter"]["count"] == plan.n_buckets
    assert entry["ops"]["all-gather"]["count"] == len(plan.ag_groups)
    # reduce-scatter payload = the full fp32 flat buffer, once
    assert entry["ops"]["reduce-scatter"]["payload_bytes"] == (
        plan.rows * 1024 * 4)
    receipt = engine.overlap_receipt()
    assert 0 < receipt["exposed_wire_seconds"] < receipt["wire_seconds"]
    assert 0 < receipt["overlap_fraction"] < 1.0
    engine.close()

    from deepspeed_tpu.tools.dslint import programs as dsp

    arts = {a.name: a for a in dsp.load_run_artifacts(
        str(tmp_path / "run"))}
    assert arts["train_step"].collective_schedule == sched
    assert arts["cast_params"].collective_schedule == sched


def test_zero_stage2_control_unchanged(cpu_devices):
    """overlap_comm: false keeps the pre-round-14 layout: flat buffers
    at the canonical segments shape, no bucket plan, GSPMD exchange."""
    engine = _engine(cpu_devices, False)
    assert engine.flat.bucket_plan is None
    assert engine.flat.flat_shape == engine.segments.shape
    sched = engine.collective_schedule()
    assert sched is not None and sched["overlap"] is False
    engine.close()


# ------------------------------------------------------------- config
def test_overlap_comm_true_raises_on_unsupported(cpu_devices):
    mesh4 = make_mesh({"data": 4}, devices=cpu_devices[:4])
    model = SimpleModel(HIDDEN, nlayers=2)

    def init(zero, mesh=mesh4, **over):
        cfg = base_config(steps_per_print=10 ** 9,
                          zero_optimization=zero, **over)
        return deepspeed.initialize(model=model, config=cfg, mesh=mesh)

    with pytest.raises(ValueError, match="stage 2"):
        init({"stage": 1, "overlap_comm": True})
    with pytest.raises(ValueError, match="dp > 1"):
        init({"stage": 2, "overlap_comm": True},
             mesh=make_mesh({"data": 1}, devices=cpu_devices[:1]))
    with pytest.raises(ValueError, match="pure data-parallel"):
        init({"stage": 2, "overlap_comm": True},
             mesh=make_mesh({"data": 2, "model": 2},
                            devices=cpu_devices[:4]))
    with pytest.raises(ValueError, match="cpu_offload"):
        init({"stage": 2, "overlap_comm": True, "cpu_offload": True})
    with pytest.raises(ValueError, match="Adam"):
        init({"stage": 2, "overlap_comm": True},
             optimizer={"type": "Lamb", "params": {"lr": 1e-3}})


def test_stage3_unmet_requirements_raise_loudly(cpu_devices):
    """Round-20 contract: stage 3 never silently degrades — an
    unsupported composition raises a ValueError NAMING the unmet
    requirement (no 'stage 3 not supported' stubs remain)."""
    mesh4 = make_mesh({"data": 4}, devices=cpu_devices[:4])
    model = SimpleModel(HIDDEN, nlayers=2)

    def init(zero, mesh=mesh4, **over):
        cfg = base_config(steps_per_print=10 ** 9,
                          zero_optimization=zero, **over)
        return deepspeed.initialize(model=model, config=cfg, mesh=mesh)

    # sparse row-sparse exchange cannot ride the ÷dp-sharded parameter
    # space — the error says exactly that (and the fix)
    with pytest.raises(ValueError, match=r"sparse_gradients: true "
                                         r"requires ZeRO stage 0"):
        init({"stage": 3}, sparse_gradients=True)
    # explicit overlap_comm: true under stage 3 names the blocking
    # requirement, same contract as the stage-2 arm above
    with pytest.raises(ValueError, match="dp > 1"):
        init({"stage": 3, "overlap_comm": True},
             mesh=make_mesh({"data": 1}, devices=cpu_devices[:1]))
    with pytest.raises(ValueError, match="cpu_offload"):
        init({"stage": 3, "overlap_comm": True, "cpu_offload": True})
    with pytest.raises(ValueError, match="pure data-parallel"):
        init({"stage": 3, "overlap_comm": True},
             mesh=make_mesh({"data": 2, "model": 2},
                            devices=cpu_devices[:4]))
    with pytest.raises(ValueError, match="Adam"):
        init({"stage": 3, "overlap_comm": True},
             optimizer={"type": "Lamb", "params": {"lr": 1e-3}})


def test_overlap_comm_config_validation():
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

    with pytest.raises(ValueError, match="overlap_comm"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 2, "overlap_comm": "yes"}})
    with pytest.raises(ValueError, match="reduce_bucket_size"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 2, "reduce_bucket_size": 0}})
    with pytest.raises(ValueError, match="allgather_bucket_size"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 2, "allgather_bucket_size": True}})
    cfg = DeepSpeedZeroConfig({"zero_optimization": {"stage": 2}})
    assert cfg.overlap_comm == "auto"  # round-14 default
    # JSON scientific notation (the documented default idiom) parses as
    # an integral float — coerced, not rejected
    cfg = DeepSpeedZeroConfig({"zero_optimization": {
        "stage": 2, "reduce_bucket_size": 5e8,
        "allgather_bucket_size": 2.5e8}})
    assert cfg.reduce_bucket_size == 500000000
    assert isinstance(cfg.reduce_bucket_size, int)
    assert cfg.allgather_bucket_size == 250000000
    with pytest.raises(ValueError, match="reduce_bucket_size"):
        DeepSpeedZeroConfig({"zero_optimization": {
            "stage": 2, "reduce_bucket_size": 1.5}})


def test_auto_disables_on_unsupported_meshes(cpu_devices):
    """auto never raises: multi-axis meshes / stage 1 / dp=1 silently
    keep the GSPMD exchange (and declare no schedule)."""
    mesh = make_mesh({"data": 2, "model": 2}, devices=cpu_devices[:4])
    cfg = base_config(steps_per_print=10 ** 9,
                      zero_optimization={"stage": 2})
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=2), config=cfg, mesh=mesh)
    assert not engine.comm_overlap_enabled()
    assert engine.collective_schedule() is None
    engine.close()


# -------------------------------------------- compression padding unit
def test_compressed_allreduce_internal_padding_vs_reference(cpu_devices):
    """The satellite: ``compressed_allreduce`` pads unaligned sizes to
    8*world internally and trims on return — parity against the numpy
    reference running on the explicitly padded buffers."""
    import jax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.compression import (
        compressed_allreduce, compressed_allreduce_reference,
        padded_size)
    from jax import shard_map

    world, n = 4, 100  # 100 % (8*4) != 0
    n_pad = padded_size(n, world)
    assert n_pad == 128 and padded_size(n_pad, world) == n_pad
    rng = np.random.default_rng(0)
    bufs = rng.normal(size=(world, n)).astype(np.float32)
    werrs = (rng.normal(size=(world, n_pad)) * 0.1).astype(np.float32)
    serrs = (rng.normal(size=(world, n_pad // world)) * 0.1).astype(
        np.float32)

    mesh = make_mesh({"data": world}, devices=cpu_devices[:world])

    def body(b, we, se):
        out, nwe, nse = compressed_allreduce(b[0], we[0], se[0], "data")
        return out[None], nwe[None], nse[None]

    out, nwe, nse = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        axis_names={"data"}, check_vma=False))(bufs, werrs, serrs)
    assert out.shape == (world, n)  # trimmed
    assert nwe.shape == (world, n_pad)  # errors stay padded

    padded_bufs = np.zeros((world, n_pad), np.float32)
    padded_bufs[:, :n] = bufs
    ref_out, ref_werrs, ref_serrs = compressed_allreduce_reference(
        list(padded_bufs), list(werrs), list(serrs))
    for r in range(world):
        np.testing.assert_allclose(np.asarray(out[r]), ref_out[:n],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nwe), np.stack(ref_werrs),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nse), np.stack(ref_serrs),
                               rtol=1e-4, atol=1e-5)


def test_compressed_allreduce_rejects_wrong_error_sizes(cpu_devices):
    import jax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.compression import compressed_allreduce
    from jax import shard_map

    mesh = make_mesh({"data": 4}, devices=cpu_devices[:4])

    def body(b, we, se):
        out, nwe, nse = compressed_allreduce(b[0], we[0], se[0], "data")
        return out[None], nwe[None], nse[None]

    bufs = np.zeros((4, 100), np.float32)
    bad_werrs = np.zeros((4, 100), np.float32)  # must be 128
    serrs = np.zeros((4, 32), np.float32)
    with pytest.raises(AssertionError, match="padded_size"):
        jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")),
            axis_names={"data"}, check_vma=False))(bufs, bad_werrs,
                                                   serrs)
