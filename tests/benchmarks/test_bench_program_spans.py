"""The program's spans in a trace (``benchmarks/trace/program_spans.py``):
the reducers on hand-made traces, the clock alignment on a planted offset,
what goes to ``_ambiguous_``, and that nothing the accepted readers return
moves; then the probe that runs them, on tiny cells on the CPU."""

import json
import os

import jax
import pytest

from benchmarks import common, span_probe
from benchmarks.trace import program_spans as ps
from benchmarks.trace import reducers, xplane
from benchmarks.trace.xplane import Event

from . import _tiny

MS = 1e-3
FIXTURE = os.path.join(common.HERE, "trace", "fixtures",
                       "probe_1chip.xplane.pb")
LAUNCH = (0.2, 0.3, 0.25, 0.4, 0.2, 0.35)      # ms, dispatch opens -> runs
READBACK = (0.4, 0.6, 0.5, 0.45, 0.7, 0.4)     # ms, run ends -> fetch closes


def serving_trace(planted=1.5 * MS, account=0.3):
    """Six decode iterations 10 ms apart, as the device's clock saw them,
    with every host stamp ``planted`` seconds ahead.  In iteration k the
    8 ms program starts LAUNCH[k] after ``decode.dispatch`` opens and ends
    READBACK[k] before ``decode.fetch`` closes."""
    ops, modules, harness, program = [], [], [], []

    def host(name, a, b, k, **args):
        kind, bare = name.split(":", 1)
        (program if kind == "ds" else harness).append(ps.Span(
            bare, name, a * MS + planted + k * 10 * MS, (b - a) * MS, args))

    for k, (launch, readback) in enumerate(zip(LAUNCH, READBACK)):
        start = k * 10 * MS + (0.3 + launch) * MS
        modules.append(Event(f"jit_decode({k})", f"jit_decode({k})", start,
                             8 * MS))
        for j in range(2):
            ops.append(Event(
                "fusion", "%fusion.1 = f32[8]{0} fusion(%x), kind=kLoop",
                start + j * 4 * MS, 4 * MS))
        got = 8.3 + launch + readback        # ms: the fetch returns
        host("bench:engine_step.decode", -0.02, got + account + 0.12, k)
        host("ds:step", 0.0, got + account + 0.1, k)
        host("ds:step.sweep", 0.0, 0.05, k)
        host("ds:step.admit", 0.05, 0.1, k)
        host("ds:decode", 0.1, got + account, k, active=16)
        host("ds:decode.prep", 0.1, 0.3, k)
        host("ds:decode.dispatch", 0.3, 0.45, k)
        host("ds:decode.fetch", 0.45, got, k)
        host("ds:decode.account", got, got + account, k)
        host("bench:stamp", got + account + 0.15, got + account + 0.4, k)
    return ps.ProgramTrace(ops={0: ops}, modules={0: modules},
                           spans=harness, program_spans=program)


CTX = {"steps": 6, "window_s": 0.06}


def test_span_reducers_read_durations_and_shares():
    trace = serving_trace()
    fetch = sorted(8.3 + l + r - 0.45 for l, r in zip(LAUNCH, READBACK))
    assert ps.program_span_ms(
        trace, {"span": "decode.fetch", "reduce": "median"},
        CTX) == pytest.approx(0.5 * (fetch[2] + fetch[3]))
    assert ps.program_span_ms(
        trace, {"span": "decode.fetch", "reduce": "mean"},
        CTX) == pytest.approx(sum(fetch) / 6)
    assert ps.program_span_ms(
        trace, {"span": "decode.fetch", "reduce": "p95"},
        CTX) == pytest.approx(common.quantile(fetch, 0.95))
    assert ps.program_span_ms(trace, {"span": "decode.prep"},
                              CTX) == pytest.approx(0.2)
    steps = sum(8.3 + l + r + 0.4 for l, r in zip(LAUNCH, READBACK))
    assert ps.program_span_share(
        trace, {"span": "decode.prep", "of": "step"},
        CTX) == pytest.approx(100 * 6 * 0.2 / steps)
    # a window with no prefill in it has a share of 0, not none
    assert ps.program_span_share(trace, {"span": "prefill", "of": "step"},
                                 CTX) == 0.0
    assert ps.program_span_ms(trace, {"span": "prefill"}, CTX) is None
    assert ps.program_span_share(trace, {"span": "step", "of": "prefill"},
                                 CTX) is None


def test_alignment_recovers_a_planted_offset():
    """A planted 1.5 ms between the clocks comes back to within the
    planted launch + readback latency (its smallest of each: 0.2 and
    0.4 ms), and that half-width is what is reported."""
    found = ps.align(serving_trace(planted=1.5 * MS))
    assert found.consistent and found.pairs == 12
    assert found.uncertainty == pytest.approx(0.5 * (0.2 + 0.4) * MS)
    assert abs(found.offset - 1.5 * MS) <= found.uncertainty * (1 + 1e-9)
    assert found.offset == pytest.approx((1.5 + 0.5 * (0.4 - 0.2)) * MS)
    behind = ps.align(serving_trace(planted=-0.7 * MS))
    assert behind.offset == pytest.approx((-0.7 + 0.1) * MS)
    # nothing to pair: host stamps are taken as they are
    bare = ps.ProgramTrace(ops=serving_trace().ops)
    assert ps.align(bare) == ps.Alignment()
    assert ps.align(bare).uncertainty is None


def test_a_program_that_ends_after_its_fetch_closed_reads_inconsistent():
    trace = serving_trace()
    for span in trace.program_spans:
        if span.name == "decode.fetch":
            span.duration -= 1.0 * MS          # closes before the run ends
    found = ps.align(trace)
    assert not found.consistent
    assert found.uncertainty == pytest.approx(0.5 * (1.0 - 0.6) * MS)


def test_idle_gaps_name_the_innermost_span_on_the_aligned_clock():
    trace = serving_trace(account=0.35)
    named = ps.idle_by_span(trace)
    gaps = ps.idle_gaps(trace)
    assert len(gaps) == 5            # the trace's two edges are not gaps
    total = sum(b - a for a, b in gaps)
    assert sum(named.values()) == pytest.approx(total)
    # ... which are the idle seconds the accepted breakdown names too
    assert sum(v for _, v in reducers.breakdown(trace)["idle_gaps"]) \
        == pytest.approx(total)
    # pieces that lie inside a gap keep their length whatever the clock:
    assert named["ds:decode.account"] == pytest.approx(5 * 0.35 * MS)
    assert named["ds:decode.prep"] == pytest.approx(5 * 0.2 * MS)
    assert named["stamp"] == pytest.approx(5 * 0.25 * MS)
    assert named["ds:step.sweep"] == pytest.approx(5 * 0.05 * MS)
    # the host was inside ds:step but in none of its phases for 0.1 ms
    assert named["ds:step"] == pytest.approx(5 * 0.1 * MS)
    # the harness's own span shows only around the program's
    assert named["engine_step.decode"] == pytest.approx(5 * 0.04 * MS)
    # the clock's middle puts host stamps 0.1 ms early.  The readback
    # (the fetch's tail at a gap's start: 0.4 0.6 0.5 0.45 0.7 less that
    # 0.1) and the launch (the next fetch's head at its end: 0.3 0.25 0.4
    # 0.2 0.35 less dispatch's 0.15, plus 0.1) are cut by a gap's edge:
    # named only where longer than the 0.3 ms uncertainty
    assert named["ds:decode.fetch"] == pytest.approx(
        (0.5 + 0.4 + 0.35 + 0.6 + 0.35) * MS)
    # dispatch's 0.15 ms lies whole inside a gap, but nearer than 0.3 ms
    # to its end where the launch was short: three times of five
    assert named["ds:decode.dispatch"] == pytest.approx(2 * 0.15 * MS)
    assert named[ps.AMBIGUOUS] == pytest.approx(
        (0.3 + 0.25 + 0.2 + 0.15 + 0.3 + 3 * 0.15) * MS)
    assert ps.NO_SPAN in named
    assert named[ps.AMBIGUOUS] < 0.25 * total


def test_without_an_alignment_every_piece_is_named():
    trace = serving_trace(planted=0.0)
    for span in trace.program_spans:       # no dispatch spans: no pairing
        if span.name == "decode.dispatch":
            span.name, span.text = "decode.send", "ds:decode.send"
    named = ps.idle_by_span(trace)
    assert ps.AMBIGUOUS not in named
    assert named["ds:decode.fetch"] == pytest.approx(
        (sum(READBACK[:5]) + sum(l - 0.15 for l in LAUNCH[1:])) * MS)


def test_idle_per_step_in_the_spans_of_a_list():
    trace = serving_trace(account=0.35)
    host = ps.idle_ms_in_program_spans(
        trace, {"spans": span_probe.HOST_SPANS}, CTX)
    assert host == pytest.approx(5 * (0.05 + 0.05 + 0.2 + 0.35) / 6)
    sync = ps.idle_ms_in_program_spans(
        trace, {"spans": span_probe.SYNC_SPANS}, CTX)
    named = ps.idle_by_span(trace)
    assert sync == pytest.approx(
        1e3 * (named["ds:decode.fetch"]
               + named.get("ds:decode.dispatch", 0.0)) / 6)
    assert ps.idle_ms_in_program_spans(
        trace, {"spans": ["no.such.*"]}, CTX) == 0.0
    # a trace of a program without spans: nothing to read
    parent = ps.ProgramTrace(ops=trace.ops, modules=trace.modules,
                             spans=trace.spans)
    assert ps.idle_ms_in_program_spans(
        parent, {"spans": ["*"]}, CTX) is None


def training_trace(planted=0.8 * MS):
    """Five steps of 10 ms back to back from 0.3 ms on, two in flight: the
    host dispatches a step (0.5 ms of ``train_batch``), then waits for the
    step two behind it, which returns 0.25 ms after that step has ended;
    the fence returns 0.4 ms after the last."""
    ops, modules, harness, program = [], [], [], []
    now = 0.0                                    # ms, the device's clock

    def host(table, name, a, b):
        kind, bare = name.split(":", 1)
        table.append(ps.Span(bare, name, a * MS + planted, (b - a) * MS))

    for k in range(5):
        modules.append(Event(f"jit_train_step({k})", "jit_train_step(1)",
                             (0.3 + 10 * k) * MS, 10 * MS))
        ops.append(Event("fusion", "%fusion.1 = f32[8]{0} fusion(%x)",
                         (0.3 + 10 * k) * MS, 10 * MS))
        host(program, "ds:train_batch", now, now + 0.5)
        host(program, "ds:dispatch", now + 0.1, now + 0.4)
        ended = 0.3 + 10 * (k - 1) + 0.25 if k >= 2 else 0.0
        host(harness, "bench:wait_step", now + 0.5, max(now + 0.52, ended))
        now = max(now + 0.52, ended)
    host(harness, "bench:fence", now, 50.3 + 0.4)
    return ps.ProgramTrace(ops={0: ops}, modules={0: modules},
                           spans=harness, program_spans=program)


def test_training_alignment_uses_the_harnesss_waits_two_steps_behind():
    found = ps.align(training_trace())
    # the first step runs 0.2 ms after its dispatch opens (later steps wait
    # for the one before); the k-th wait returns 0.25 ms after step k-2
    # ended, the fence 0.4 ms after the last
    assert found.consistent
    assert found.uncertainty == pytest.approx(0.5 * (0.2 + 0.25) * MS)
    assert found.offset == pytest.approx((0.8 + 0.5 * (0.25 - 0.2)) * MS)
    assert ps.program_span_ms(training_trace(), {"span": "train_batch"},
                              {}) == pytest.approx(0.5)


def test_breakdown_carries_the_alignment_and_the_accepted_device_ops():
    trace = serving_trace()
    top = ps.breakdown(trace)
    assert top["device_ops"] == reducers.breakdown(trace)["device_ops"]
    assert top["clock_uncertainty_us"] == pytest.approx(300.0)
    assert top["clock_offset_us"] == pytest.approx(1600.0)
    assert top["clock_consistent"] is True
    assert [name for name, _ in top["idle_gaps"]][0] == "ds:decode.fetch"
    assert ps.breakdown(ps.ProgramTrace()) is None


@pytest.fixture(scope="module")
def recorded():
    return xplane.read(FIXTURE), ps.read(FIXTURE)


ACCEPTED = [
    ("idle_percent", {}),
    ("busy_ms_per_step", {}),
    ("op_ms_per_step", {"pattern": r"^%convolution_tanh_fusion"}),
    ("kernel_roofline", {"pattern": r"^%conv\w+_fusion",
                         "calls_per_layer": 1, "flops": "f", "bytes": "b"}),
    ("module_ms", {"pattern": "^jit_probe_step", "reduce": "median"}),
    ("module_roofline", {"pattern": "^jit_probe_step", "reduce": "median",
                         "flops": "f", "bytes": "b"}),
    ("collective_ms_per_step", {}),
    ("collective_exposed_ms_per_step", {}),
]


@pytest.mark.parametrize("name,args", ACCEPTED, ids=[n for n, _ in ACCEPTED])
def test_accepted_reducers_read_the_same_with_the_new_reader(recorded, name,
                                                             args):
    """The recorded v5e trace and the hand-made one give every accepted
    reducer the same number through ``program_spans.read`` /
    ``ProgramTrace`` as through ``xplane.read`` / ``Trace``."""
    old, new = recorded
    ctx = {"steps": 4, "window_s": 0.02, "device_kind": "TPU v5 lite",
           "counts": {"f": 2 * 1024 ** 3, "b": 3 * 1024 * 1024 * 2}}
    reducer = reducers.REDUCERS[name]
    assert reducer(new, args, ctx) == reducer(old, args, ctx)
    made = serving_trace()
    plain = xplane.Trace(ops=made.ops, async_ops=made.async_ops,
                         modules=made.modules, spans=made.spans)
    args = dict(args, pattern=args.get("pattern", "").replace(
        "jit_probe_step", "jit_decode").replace(
        r"^%convolution_tanh_fusion", "fusion").replace(
        r"^%conv\w+_fusion", "fusion"))
    assert reducer(made, args, ctx) == reducer(plain, args, ctx)


def test_recorded_trace_has_no_program_span_and_new_readers_say_nothing(
        recorded):
    old, new = recorded
    assert new.program_spans == [] and new.spans == old.spans == []
    assert new.ops == old.ops and new.modules == old.modules
    for name, reducer in ps.REDUCERS.items():
        args = {"span": "step", "of": "step", "spans": ["*"]}
        assert reducer(new, args, {"steps": 4}) is None, name
    assert ps.breakdown(new)["idle_gaps"] == \
        reducers.breakdown(old)["idle_gaps"]
    assert "clock_uncertainty_us" not in ps.breakdown(new)


def test_probed_metrics_are_written_as_the_manifest_would_hold_them():
    """Each entry of ``PROBED`` holds a ``per_layer`` entry's keys and a
    reader, under the accepted manifest's rules."""
    from .test_bench_manifest import NAME, SOURCES, UNIT

    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    accepted = {m["name"] for m in bench["per_layer"]}
    assert len(span_probe.PROBED) == 9
    new_layers = set()
    for name, spec in span_probe.PROBED.items():
        assert set(spec) == {"unit", "better", "source", "layer", "moves",
                             "workloads", "reader"}
        assert NAME.match(name) and UNIT.match(spec["unit"])
        assert name not in accepted                # new, not a second copy
        assert spec["better"] == "lower" and spec["source"] in SOURCES
        assert set(spec["workloads"]) <= reports[spec["moves"]]
        new_layers |= {spec["layer"]} - layers
        reader = spec["reader"]
        assert reader["kind"] in ("counter", "trace")
        if reader["kind"] == "trace":
            assert reader["reducer"] in ps.REDUCERS
    assert new_layers == {
        "training host path (runtime/engine.py train_batch)"}


def test_probe_reads_a_tiny_serving_cell():
    """On the CPU there is no device plane, so the readings that need the
    device's operations are left out and the spans' are there."""
    spec = _tiny.serve_spec({"served_logit_gap": 1.0})
    line, trace = span_probe.probe_serve(spec, 11, 1.0, False,
                                         jax.devices()[:1])
    got = line["metrics"]
    assert {"decode_prep_ms", "decode_fetch_ms", "prefill_step_share",
            "prefill_padding_pct", "trace_lower_s",
            "step_traces"} <= set(got)
    assert "serve_idle_host_ms" not in got
    assert got["step_traces"]["value"] == 1
    assert got["trace_lower_s"]["value"] > 0
    assert 0 < got["decode_prep_ms"]["value"] < got["decode_fetch_ms"][
        "value"] + 50
    assert 0 <= got["prefill_step_share"]["value"] < 100
    assert 0 < got["prefill_padding_pct"]["value"] < 100
    assert line["compiled_in_window"] == 0
    assert line["iterations"] == len(
        [s for s in trace.program_spans if s.name == "step"])
    table = span_probe.span_medians_ms(trace)
    assert table["decode"][1] == table["decode.fetch"][1] > 0
    whole, nothing = span_probe.probe_serve(spec, 11, 0.5, True,
                                            jax.devices()[:1])
    assert nothing is None and "metrics" not in whole
    assert whole["serve_tokens_per_s"] > 0


def test_probe_reads_a_tiny_training_cell():
    spec = _tiny.train_spec({})
    line, trace = span_probe.probe_train(spec, 12, 0.5, False,
                                         jax.devices()[:1])
    got = line["metrics"]
    assert set(got) == {"train_host_ms", "trace_lower_s", "step_traces"}
    assert got["train_host_ms"]["value"] > 0
    assert got["step_traces"]["value"] >= 1
    assert line["compiled_in_window"] == 0
    table = span_probe.span_medians_ms(trace)
    assert table["train_batch"][1] == spec["traffic"]["trace_steps"]
    for phase in ("batch_fetch", "pack", "device_put", "dispatch"):
        assert table[phase][1] == table["train_batch"][1]
