"""The trace reducers on a small recorded ``.xplane.pb`` (a v5e chip, four
runs of a three-matmul program under harness spans; recorded by PR 23) and
on hand-made events."""

import os

import pytest

from benchmarks import common
from benchmarks.trace import reducers, xplane
from benchmarks.trace.xplane import Event, Trace

FIXTURE = os.path.join(common.HERE, "trace", "fixtures",
                       "probe_1chip.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.read(FIXTURE)


def test_reads_device_ops_modules_and_harness_spans(recorded):
    assert recorded.chips == [0]
    assert len(recorded.modules[0]) == 4
    assert all(m.text.startswith("jit_probe_step(")
               for m in recorded.modules[0])
    names = {e.name for e in recorded.ops[0]}
    assert {"convolution_tanh_fusion", "convert_reduce_fusion",
            "copy-start", "copy-done"} <= names
    assert len(recorded.ops[0]) == 20
    # only bench:-prefixed spans are the harness's; this trace has none
    assert recorded.spans == []


def test_busy_idle_and_per_op_time_on_the_recorded_trace(recorded):
    busy = reducers.busy_seconds(recorded)
    # four runs of ~39.5 us of back-to-back operations
    assert busy == pytest.approx(4 * 39.5e-6, rel=0.02)
    by_module = sum(m.duration for m in recorded.modules[0])
    assert busy == pytest.approx(by_module, rel=0.02)
    ctx = {"steps": 4, "window_s": 0.02, "device_kind": "TPU v5 lite",
           "counts": {"f": 3 * 2 * 1024 ** 3, "b": 3 * 3 * 1024 * 1024 * 2}}
    assert reducers.idle_percent(recorded, {}, ctx) == pytest.approx(
        100 * (1 - busy / 0.02))
    assert reducers.busy_ms_per_step(recorded, {}, ctx) == pytest.approx(
        0.0395, rel=0.02)
    fused = reducers.op_ms_per_step(
        recorded, {"pattern": r"^%convolution_tanh_fusion"}, ctx)
    assert fused == pytest.approx(0.0263, rel=0.05)    # two of three matmuls
    assert reducers.op_ms_per_step(
        recorded, {"pattern": "no_such_op"}, ctx) is None
    assert reducers.module_ms(
        recorded, {"pattern": "^jit_probe_step", "reduce": "median"},
        ctx) == pytest.approx(0.0396, rel=0.01)
    assert reducers.module_ms(recorded, {"pattern": "^jit_other"},
                              ctx) is None
    # three 1024^3 matmuls in 39.5 us: 6.4 GFLOP at 197 TFLOP/s is 32.7 us
    # as a "kernel" of one call a layer: 12 fused calls are 12 layers of
    # one 1024^3 matmul each (the reduce fusions count as calls here too)
    share = reducers.kernel_roofline(
        recorded, {"pattern": r"^%conv\w+_fusion", "calls_per_layer": 1,
                   "flops": "f", "bytes": "b"},
        dict(ctx, counts={"f": 2 * 1024 ** 3, "b": 3 * 1024 * 1024 * 2}))
    assert 75 < share < 100
    assert reducers.collective_ms_per_step(recorded, {}, ctx) is None
    top = reducers.breakdown(recorded)
    assert top["device_ops"][0][0] == "convolution_tanh_fusion:kOutput"
    assert len(top["device_ops"]) <= 10


def _trace(ops, async_ops=(), spans=()):
    def ev(name, start, dur, text=None):
        return Event(name, text or f"%{name}.1 = f32[8]{{0}} {name}(%x)",
                     start, dur)
    return Trace(ops={0: [ev(*o) for o in ops]},
                 async_ops={0: [ev(*o) for o in async_ops]},
                 spans=[Event(n, "bench:" + n, s, d) for n, s, d in spans])


def test_exposed_collective_time_on_hand_made_events():
    # compute 0-10 and 14-20 ms; an all-gather in flight 8-16 ms (async),
    # a synchronous reduce-scatter 20-23 ms
    trace = _trace(
        ops=[("fusion", 0.000, 0.010), ("fusion", 0.014, 0.006),
             ("all-gather-start", 0.008, 0.0001),
             ("reduce-scatter", 0.020, 0.003)],
        async_ops=[("all-gather-start", 0.008, 0.008)])
    ctx = {"steps": 1, "window_s": 0.030}
    assert reducers.collective_ms_per_step(trace, {}, ctx) == \
        pytest.approx(11.0)
    # exposed: 10-14 of the gather, all 3 of the reduce-scatter
    assert reducers.collective_exposed_ms_per_step(trace, {}, ctx) == \
        pytest.approx(7.0)
    assert reducers.busy_seconds(trace) == pytest.approx(0.023 - 0.004)
    assert reducers.idle_percent(trace, {}, ctx) == pytest.approx(
        100 * (1 - 0.019 / 0.030))


def test_idle_gaps_are_named_by_the_innermost_harness_span():
    trace = _trace(
        ops=[("fusion", 0.000, 0.010), ("fusion", 0.015, 0.005),
             ("copy", 0.030, 0.001)],
        spans=[("engine_step.decode", 0.009, 0.012), ("stamp", 0.0115, 0.002)])
    top = reducers.breakdown(trace)
    assert dict(top["idle_gaps"]) == {
        "stamp": pytest.approx(0.005), "_no_span_": pytest.approx(0.010)}
    assert top["device_ops"][0] == ["fusion", pytest.approx(0.015)]


def test_interval_arithmetic():
    assert reducers.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert reducers.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        [0, 2], [3, 5], [7, 10]]
    assert reducers.subtract([(0, 4), (6, 8)], [(3, 7)]) == [[0, 3], [7, 8]]
    assert reducers.subtract([(0, 1)], []) == [[0, 1]]


def test_instruction_text_is_split_into_name_and_opcode():
    text = ("%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) "
            "all-gather-start(bf16[4,8]{1,0} %p), dimensions={0}")
    assert xplane.op_name(text) == "all-gather-start"
    assert xplane.opcode(text) == "all-gather-start"
    fused = ("%convolution_tanh_fusion.2 = bf16[1024,1024]{1,0:T(8,128)(2,1)}"
             " fusion(bf16[1024,1024]{1,0} %a), kind=kOutput")
    assert xplane.op_name(fused) == "convolution_tanh_fusion"
    assert xplane.opcode(fused) == "fusion"
