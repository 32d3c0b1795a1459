"""ZeRO-Offload's decision table (``runtime/zero/offload.py``) on shapes
alone: an ``OffloadStream`` made from a config, a coordinator over
``ShapeDtypeStruct`` leaves and a device that only answers
``memory_stats()`` — no engine, no buffer, no jit — says which form the
update takes and declares the schedule; and an engine built from the same
config declares the same one.
"""

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as deepspeed
import deepspeed_tpu.runtime.zero.coordinator as coord
from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb
from deepspeed_tpu.ops.op_common import LANES
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.runtime.zero import offload, stream
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

from .simple_model import SimpleModel, base_config

MB_ROWS = (1 << 20) // (LANES * 4)     # rows of a 1 MB chunk
GB = 1 << 30
# 1.2 GB of fp32 state: three chunks of the default 512 MB
BIG = [300 * (1 << 20)]


def _mb(n):
    """Leaf sizes of a model whose flat state is ``n`` MB of fp32."""
    return [n * MB_ROWS * LANES]


class _Device:
    """A device that only says how much memory it has."""

    def __init__(self, bytes_limit):
        self.bytes_limit = bytes_limit

    def memory_stats(self):
        if self.bytes_limit == "raises":
            raise RuntimeError("this backend has no memory_stats")
        return {"bytes_limit": self.bytes_limit} if self.bytes_limit else None


@pytest.fixture
def injit(monkeypatch):
    monkeypatch.setenv("DS_OFFLOAD_FORCE_INJIT", "1")


def _stream(cpu_devices, zero, sizes, bytes_limit=None, optimizer=None,
            eager=False):
    zc = DeepSpeedZeroConfig({"zero_optimization": {
        "stage": 2, "cpu_offload": True, **zero}})
    template = [jax.ShapeDtypeStruct((n,), jnp.float32) for n in sizes]
    flat = coord.FlatParamCoordinator(
        mesh=make_mesh({"data": 1}, devices=cpu_devices[:1]),
        params_template=template, stage=2, dp_size=1, cpu_offload=True,
        **offload.layout_args(zc))
    return flat, offload.OffloadStream(
        zc, flat, flat.segments, optimizer or FusedAdam(),
        _Device(bytes_limit), offload=True, eager=eager,
        host_grads=zc.offload_gradients and not eager, prng_impl=None,
        skip_bad=False, clip=0.0, compute_dtype=jnp.bfloat16,
        param_template=template, param_shardings=None)


def _sched(form, chunks, groups=1, overlap=True, depth=2, **more):
    return {"form": form, "chunks": chunks, "groups": groups,
            "overlap": overlap, "prefetch_depth": depth, **more}


UNIFORM = stream.UNIFORM_MIN_CHUNKS
CASES = {
    # state under the floor (11% of the device's memory): one program
    # holds master + m + v at once
    "under_the_floor_one_shot": dict(
        zero={}, sizes=BIG, bytes_limit=16 * GB, schedule=None),
    "no_memory_stats_keeps_the_16g_floor": dict(
        zero={}, sizes=BIG, bytes_limit=None, schedule=None,
        floor=1792 << 20),
    "memory_stats_raising_keeps_the_16g_floor": dict(
        zero={}, sizes=BIG, bytes_limit="raises", schedule=None,
        floor=1792 << 20),
    # over it: streamed, unrolled under UNIFORM_MIN_CHUNKS chunks
    "over_the_floor_streams_unrolled": dict(
        zero={}, sizes=BIG, bytes_limit=8 * GB,
        schedule=_sched("unrolled", 3), floor=int(8 * GB * 0.11)),
    "an_explicit_chunk_size_overrides_the_floor": dict(
        zero={"offload_chunk_mb": 1}, sizes=_mb(4), bytes_limit=16 * GB,
        schedule=_sched("unrolled", 4)),
    "a_lamb_update_never_streams": dict(
        zero={"offload_chunk_mb": 1}, sizes=_mb(4), optimizer=FusedLamb,
        schedule=None),
    "eager_offload_never_streams": dict(
        zero={"offload_chunk_mb": 1}, sizes=_mb(4), eager=True,
        schedule=None, wire=None),
    # the scan: from UNIFORM_MIN_CHUNKS chunks on, the layout aligned
    "auto_scans_at_uniform_min_chunks": dict(
        zero={"offload_chunk_mb": 1}, sizes=_mb(UNIFORM),
        schedule=_sched("scan", UNIFORM), uniform_chunk_rows=MB_ROWS),
    "auto_stays_unrolled_one_chunk_under": dict(
        zero={"offload_chunk_mb": 1}, sizes=_mb(UNIFORM - 1),
        schedule=_sched("unrolled", UNIFORM - 1), uniform_chunk_rows=None),
    "true_scans_at_any_size": dict(
        zero={"offload_chunk_mb": 1, "offload_uniform_chunks": True},
        sizes=_mb(3), schedule=_sched("scan", 3),
        uniform_chunk_rows=MB_ROWS),
    "false_keeps_the_unrolled_form": dict(
        zero={"offload_chunk_mb": 1, "offload_uniform_chunks": False},
        sizes=_mb(UNIFORM + 2), schedule=_sched("unrolled", UNIFORM + 2),
        uniform_chunk_rows=None),
    "true_on_ragged_geometry_warns_and_unrolls": dict(
        zero={"offload_chunk_mb": 0, "offload_uniform_chunks": True},
        sizes=_mb(5), group_mb=2, schedule=_sched("unrolled", 2, groups=2),
        warns="chunk geometry is not uniform"),
    # what always streams, whatever the floor
    "row_grouped_state_always_streams": dict(
        zero={}, sizes=_mb(5), group_mb=2, bytes_limit=16 * GB,
        schedule=_sched("unrolled", 2, groups=2)),
    "host_gradients_stream": dict(
        zero={"offload_gradients": True}, sizes=_mb(3), bytes_limit=16 * GB,
        schedule=_sched("unrolled", 1,
                        grad_wire_bytes=2 * 3 * MB_ROWS * LANES * 4)),
    "reduced_host_state_streams": dict(
        zero={"offload_state_dtype": "bf16"}, sizes=_mb(3),
        bytes_limit=16 * GB, schedule=_sched("unrolled", 1),
        quant=True, wire=2 * 3 * MB_ROWS * LANES * (2 + 2 + 2)),
    # the pipeline's depth
    "overlap_false_is_the_serialized_schedule": dict(
        zero={"offload_chunk_mb": 1, "offload_overlap": False},
        sizes=_mb(4), schedule=_sched("unrolled", 4, overlap=False, depth=1)),
    "depth_one_under_auto_is_serialized_too": dict(
        zero={"offload_chunk_mb": 1, "offload_prefetch_depth": 1},
        sizes=_mb(4), schedule=_sched("unrolled", 4, overlap=False, depth=1)),
    "a_deeper_queue_is_declared": dict(
        zero={"offload_chunk_mb": 1, "offload_uniform_chunks": True,
              "offload_prefetch_depth": 4},
        sizes=_mb(8), schedule=_sched("scan", 8, depth=4)),
    # contradictory keys
    "overlap_true_at_depth_one_is_refused": dict(
        zero={"offload_chunk_mb": 1, "offload_overlap": True,
              "offload_prefetch_depth": 1},
        sizes=_mb(4), raises="offload_overlap: true contradicts "
                             "offload_prefetch_depth: 1"),
    "overlap_true_on_a_one_shot_update_is_refused": dict(
        zero={"offload_overlap": True}, sizes=BIG, bytes_limit=16 * GB,
        raises="offload_overlap: true but the offloaded update does not "
               "stream"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_offloaded_updates_form_is_decided_from_shapes_alone(
        injit, cpu_devices, monkeypatch, case):
    want = dict(CASES[case])
    if "group_mb" in want:
        monkeypatch.setattr(coord, "HOST_GROUP_BYTES",
                            want.pop("group_mb") << 20)
    warned = []
    monkeypatch.setattr(offload.logger, "warning",
                        lambda msg, *a: warned.append(msg % a))
    kwargs = dict(zero=want["zero"], sizes=want["sizes"],
                  bytes_limit=want.get("bytes_limit"),
                  eager=want.get("eager", False))
    if "optimizer" in want:
        kwargs["optimizer"] = want["optimizer"]()
    if "raises" in want:
        with pytest.raises(ValueError) as err:
            _stream(cpu_devices, **kwargs)
        assert want["raises"] in str(err.value)
        return
    flat, ofs = _stream(cpu_devices, **kwargs)
    schedule = want["schedule"]
    assert ofs.schedule() == schedule
    assert ofs.stream == (schedule is not None)
    assert ofs.uniform == bool(schedule and schedule["form"] == "scan")
    assert ofs.overlap == bool(schedule and schedule["overlap"])
    assert ofs.prefetch_depth == (schedule["prefetch_depth"] if schedule
                                  else 1)
    assert ofs.grads_on_host == bool(schedule
                                     and "grad_wire_bytes" in schedule)
    assert (ofs.quant is not None) == want.get("quant", False)
    if "floor" in want:
        assert ofs.stream_min_bytes == want["floor"]
    if "uniform_chunk_rows" in want:
        # the layout's half of the decision, taken by the same module:
        # rows aligned to whole chunks where, and only where, it scans
        assert flat.uniform_chunk_rows == want["uniform_chunk_rows"]
        assert ofs.rows_per_chunk == MB_ROWS
    if schedule is not None:
        # the chunks the traced functions walk are the declared ones
        assert [len(ofs.chunks(rc)) for _, rc in ofs.bounds].count(0) == 0
        assert sum(len(ofs.chunks(rc)) for _, rc in ofs.bounds) == \
            schedule["chunks"]
    fp32_wire = 2 * ofs.segments.rows * LANES * 4 * 3
    assert ofs.host_state_bytes_per_step == want.get("wire", fp32_wire)
    fallbacks = [w for w in warned if "offload_uniform_chunks" in w]
    assert len(fallbacks) == ("warns" in want)
    assert all(want["warns"] in w for w in fallbacks)


@pytest.mark.parametrize("zero", [
    {"offload_chunk_mb": 1, "offload_uniform_chunks": True},
    {"offload_chunk_mb": 1, "offload_uniform_chunks": False,
     "offload_overlap": False, "offload_gradients": True},
    {"offload_state_dtype": "bf16"},
    {},
], ids=["scan", "unrolled_serialized_host_grads", "reduced_state",
        "one_shot"])
def test_an_engine_declares_the_schedule_its_offload_stream_built(
        injit, cpu_devices, monkeypatch, zero):
    """``engine.host_stream_schedule()`` is the object's own, and an
    ``OffloadStream`` made apart from the engine's inputs declares the
    same: nothing but those inputs decides the form."""
    monkeypatch.setattr(coord, "HOST_GROUP_BYTES", 2 << 20)
    engine, *_ = deepspeed.initialize(
        model=SimpleModel(256, nlayers=8),
        config=base_config(zero_optimization={
            "stage": 2, "cpu_offload": True, **zero}),
        mesh=make_mesh({"data": 1}, devices=cpu_devices[:1]))
    built = engine._offload_stream
    assert engine.host_stream_schedule() == built.schedule()
    assert engine.host_state_bytes_per_step() == \
        built.host_state_bytes_per_step
    assert (engine._offload_uniform, engine._offload_overlap,
            engine._offload_prefetch_depth) == (
        built.uniform, built.overlap, built.prefetch_depth)
    apart = offload.OffloadStream(
        engine._config.zero_config, engine.flat, engine.segments,
        engine.optimizer, _Device(None), offload=True, eager=False,
        host_grads=engine._offload_grads, prng_impl=engine._prng_impl,
        skip_bad=False, clip=0.0, compute_dtype=None,
        param_template=None, param_shardings=None)
    assert apart.schedule() == engine.host_stream_schedule()
    assert apart.host_state_bytes_per_step == \
        engine.host_state_bytes_per_step()
    engine.close()
