"""1-bit Adam: compressed allreduce vs host reference, warmup/compression
phases, engine integration (modeled on reference
``tests/onebitadam/test_com_reduce_host.py`` but CI-friendly — virtual
8-device mesh instead of hardcoded MPI hosts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as deepspeed
from deepspeed_tpu.comm.compression import (compressed_allreduce,
                                            compressed_allreduce_reference)
from deepspeed_tpu.parallel import make_mesh

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 16


def test_compressed_allreduce_vs_host_reference(cpu_devices):
    """Distinct per-rank buffers through the shard_map collective must match
    the numpy simulation bit-for-bit in structure (scales, signs, errors)."""
    world, n = 8, 8 * 64
    rng = np.random.default_rng(0)
    bufs = rng.normal(size=(world, n)).astype(np.float32)
    werrs = rng.normal(size=(world, n)).astype(np.float32) * 0.1
    serrs = rng.normal(size=(world, n // world)).astype(np.float32) * 0.1

    mesh = make_mesh({"data": world}, devices=cpu_devices[:world])

    def body(b, we, se):
        out, nwe, nse = compressed_allreduce(b[0], we[0], se[0], "data")
        return out[None], nwe[None], nse[None]

    from jax import shard_map

    out, nwe, nse = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        axis_names={"data"}, check_vma=False))(bufs, werrs, serrs)

    ref_out, ref_werrs, ref_serrs = compressed_allreduce_reference(
        list(bufs), list(werrs), list(serrs))

    # every rank sees the same allreduced output
    for r in range(world):
        np.testing.assert_allclose(np.asarray(out[r]), ref_out, rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(nwe), np.stack(ref_werrs), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(nse), np.stack(ref_serrs), rtol=1e-4,
                               atol=1e-5)


def test_compressed_phase_matches_host_reference(cpu_devices):
    """The optimizer's actual compressed momentum sync — distinct per-rank
    local gradients through the engine's compressed program — tracks the
    numpy simulation of the same algorithm (uncompressed-mean target)."""
    config = base_config(optimizer={
        "type": "OneBitAdam", "params": {"lr": 0.0, "freeze_step": 0,
                                         "betas": (0.0, 0.999)}})
    mesh = make_mesh({"data": 8}, devices=cpu_devices[:8])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=config, mesh=mesh)
    batch = random_batches(1, engine.train_micro_batch_size_per_gpu() * 8,
                           HIDDEN, seed=3)[0]
    engine.train_batch(iter([batch]))
    # beta1=0 => stored momentum is the compressed consensus of the raw
    # per-rank local gradients; with zero error history the consensus is a
    # sign/scale quantization of the true mean — correlation must be high
    m = np.asarray(jax.device_get(engine.state["opt"].exp_avg)).ravel()
    # dense mean gradient via a plain Adam engine on the same batch
    config2 = base_config(optimizer={"type": "Adam", "params": {"lr": 0.0}})
    engine2, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                       config=config2, mesh=mesh)
    engine2.forward(batch)
    g = np.asarray(jax.device_get(engine2._pending_grads)).ravel()
    mask = g != 0
    corr = np.corrcoef(m[mask], g[mask])[0, 1]
    assert corr > 0.5, f"compressed consensus uncorrelated with mean grad ({corr})"


def _train(config, cpu_devices, steps, dp=8, seed=0):
    """Overfit one fixed batch: a monotone-ish loss signal that keeps the
    compression noise visible but not dominant on the tiny model."""
    mesh = make_mesh({"data": dp}, devices=cpu_devices[:dp])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=config, mesh=mesh)
    batch = random_batches(1, engine.train_micro_batch_size_per_gpu() * dp,
                           HIDDEN, seed=seed)[0]
    return [float(np.asarray(engine.train_batch(iter([batch]))))
            for _ in range(steps)]


def test_onebit_adam_trains(cpu_devices):
    """OneBitAdam config (the round-1 crash path) trains through both the
    warmup and the compressed phase on an 8-device mesh."""
    config = base_config(optimizer={
        "type": "OneBitAdam",
        "params": {"lr": 1e-2, "freeze_step": 3}})
    losses = _train(config, cpu_devices, steps=10)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def test_onebit_adam_loss_parity_with_dense(cpu_devices):
    """Post-freeze compressed training must track dense (never-frozen)
    1-bit Adam closely — the error-feedback guarantee (reference blog
    claim: same convergence, ``onebit-adam-blog-post.md``)."""
    dense = _train(base_config(optimizer={
        "type": "OneBitAdam",
        "params": {"lr": 1e-2, "freeze_step": 10 ** 9}}), cpu_devices, steps=16)
    comp = _train(base_config(optimizer={
        "type": "OneBitAdam",
        "params": {"lr": 1e-2, "freeze_step": 2}}), cpu_devices, steps=16)
    # warmup steps are bit-identical (compression not yet selected in)
    np.testing.assert_allclose(comp[:2], dense[:2], rtol=1e-6)
    # compressed phase tracks the dense trajectory (small lag from
    # quantization noise is expected on a 2-layer toy model)
    assert comp[-1] < 0.55 * comp[0], f"compressed did not converge: {comp}"
    # toy-model caveat: with only ~900 parameters the sign-quantization
    # noise floor is coarse; at real scale the gap closes (reference
    # convergence claim) — here we bound the divergence loosely
    assert abs(comp[-1] - dense[-1]) < 0.3 * abs(dense[0]), (
        f"compressed {comp} diverged from dense {dense}")


def _collective_f32_sizes(hlo_text):
    """Element counts of every f32 all-reduce / reduce-scatter in an HLO
    dump (the dense-gradient-sync footprint)."""
    import re

    sizes = []
    for line in hlo_text.splitlines():
        if re.search(r"(all-reduce|reduce-scatter|all-gather|all-to-all)",
                     line) and "f32[" in line:
            m = re.search(r"=\s*\(?f32\[([0-9,]*)\]", line)
            if m:
                dims = [int(d) for d in m.group(1).split(",") if d]
                sizes.append(int(np.prod(dims)) if dims else 1)
    return sizes


def test_onebit_compressed_program_has_no_dense_allreduce(cpu_devices):
    """The compressed phase must not emit any large-fp32 cross-replica
    reduction — its only data-axis traffic is packed uint8 signs + small
    scale gathers (the reference's 5x comm-volume claim,
    onebit-adam-blog-post.md:85).  The warmup program, by contrast, must
    contain the dense gradient sync (detector sanity check)."""
    from deepspeed_tpu.runtime.engine import _pack_batches

    config = base_config(optimizer={
        "type": "OneBitAdam", "params": {"lr": 1e-2, "freeze_step": 1}})
    mesh = make_mesh({"data": 8}, devices=cpu_devices[:8])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=config, mesh=mesh)
    batch = random_batches(1, engine.train_micro_batch_size_per_gpu() * 8,
                           HIDDEN, seed=0)[0]
    packed, spec = _pack_batches([batch])
    args = (engine.state["master"], engine.state["opt"], engine.state["scale"],
            engine.state["skipped"], engine.state["ustep"],
            engine._module_params, packed, spec,
            engine._device_hyperparams(), engine._segment_ids, {})
    n_params = int(np.prod(engine.segments.shape))

    comp_hlo = engine._train_step_compressed_fn.lower(*args).compile().as_text()
    # detector sanity: the packed-sign transport must be visible
    assert "all-to-all" in comp_hlo or "all-gather" in comp_hlo, (
        "no collectives found — HLO introspection broke, test is vacuous")
    comp_sizes = _collective_f32_sizes(comp_hlo)
    assert all(s < max(n_params // 8, 64) for s in comp_sizes), (
        f"compressed program still has dense f32 collectives: {comp_sizes} "
        f"(n_params={n_params})")


def test_onebit_adam_rejects_zero(cpu_devices):
    config = base_config(optimizer={"type": "OneBitAdam",
                                    "params": {"lr": 1e-2}},
                         zero_optimization={"stage": 2})
    mesh = make_mesh({"data": 8}, devices=cpu_devices[:8])
    with pytest.raises(AssertionError, match="incompatible with ZeRO"):
        deepspeed.initialize(model=SimpleModel(HIDDEN), config=config,
                             mesh=mesh)
