"""A device trace read by the program's scopes (``benchmarks/trace/
scopes.py``, ``benchmarks/scope_probe.py``): on hand-made events, and on a
small recorded trace of two programs with its ``program_scopes()`` as JSON.

The recording (a v5e chip, PR 38; ``scopes_2prog.xplane.pb`` +
``scopes_2prog.json`` beside ``probe_1chip.xplane.pb``): ``decode`` and
``prefill_8`` are ONE function compiled under two names with its two scope
words swapped, over 512 and 256 rows (two programs that differ in their
metadata alone are one executable to the runtime, and the trace names
every run after the first) — embed, a ``fori_loop`` of two steps over
``layer_0`` ⊃ (tanh of a 1024² product under ``attention``, the same under
``mlp``), ``lm_head``, ``sample`` — so both modules hold the same
instruction names under different scopes; runs in the order decode,
prefill_8, decode, decode, prefill_8.
"""

import json
import os

import jax
import pytest

from benchmarks import common, scope_probe
from benchmarks.trace import reducers, scopes, xplane
from benchmarks.trace.xplane import Event, Trace

from . import _tiny

FIXTURES = os.path.join(common.HERE, "trace", "fixtures")
US = 1e-6


def op(name, opcode, start, duration):
    text = f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)"
    return Event(xplane.op_name(text), text, start * US, duration * US)


def run(module, start, duration):
    return Event(f"{module}(123)", f"{module}(123)", start * US,
                 duration * US)


def serving_trace():
    """decode, prefill_128, decode: every run holds ``fusion.1`` and
    ``fusion.2``; decode's loop holds the two as its children."""
    ops = []
    for start in (0, 200):
        ops += [op("gather.3", "gather", start, 4),
                op("while.9", "while", start + 5, 60),
                op("fusion.1", "fusion", start + 5, 30),
                op("slice-done.4", "slice-done", start + 35, 10),
                op("fusion.2", "fusion", start + 45, 20),
                op("argmax.7", "reduce", start + 70, 6)]
    ops += [op("fusion.1", "fusion", 100, 50),
            op("fusion.2", "fusion", 150, 40)]
    return Trace(ops={0: ops}, modules={0: [
        run("jit_decode", 0, 80), run("jit_prefill_128", 100, 95),
        run("jit_decode", 200, 80)]})


MAPS = {
    "jit_decode": {
        "gather.3": ["embed", ""], "while.9": ["ut_loop", ""],
        "fusion.1": ["ut_loop/layer_3/attention", ""],
        "slice-done.4": ["ut_loop/layer_3/mlp", ""],
        "fusion.2": ["ut_loop/layer_4/mlp", ""],
        "argmax.7": ["sample", ""]},
    "jit_prefill_128": {
        "fusion.1": ["layer_0/mlp", ""], "fusion.2": ["", ""]},
}


def ctx(maps=MAPS, steps=2):
    return {"steps": steps, "scopes": maps}


def test_the_same_instruction_of_two_modules_is_kept_apart():
    trace = serving_trace()
    decode = {"module": "^jit_decode", "per": "run_median"}
    # fusion.1 is attention in decode (30 us a run) and mlp in the prefill
    assert scopes.scope_ms(trace, {**decode, "scope": "attention"},
                           ctx()) == pytest.approx(0.030)
    assert scopes.scope_ms(trace, {**decode, "scope": ["mlp", "moe"]},
                           ctx()) == pytest.approx(0.030)   # the wait + 20
    assert scopes.scope_ms(
        trace, {"module": "^jit_prefill", "scope": "mlp", "per": "step"},
        ctx(steps=1)) == pytest.approx(0.050)
    # a prefill's time is in no reading of decode's
    every = scopes.scope_ms(
        trace, {**decode, "scope": ["embed", "ut_loop", "sample"]}, ctx())
    assert every == pytest.approx(0.070)


def test_a_loop_is_not_counted_beside_its_children():
    trace = serving_trace()
    found = scopes.scoped_events(trace, MAPS)
    assert not any(e.name == "while" for _, _, e, _, _ in found)
    assert len(found) == 2 * 5 + 2
    # ...so the scopes sum to the time the operations took, once
    out = scopes.breakdown_by_scope(trace, MAPS)
    assert out["scoped_s"] + out["unplaced_s"] == pytest.approx(
        (2 * 70 + 90) * US)
    assert out["busy_s"] == pytest.approx((2 * 70 + 90) * US)
    assert not any("while" in name for name, _ in out["device_scopes"])


def test_breakdown_folds_layers_and_buckets_and_sums_the_waits_apart():
    out = scopes.breakdown_by_scope(serving_trace(), MAPS)
    assert dict(out["device_scopes"]) == pytest.approx({
        "jit_decode:ut_loop/layer/attention": 60 * US,
        "jit_decode:ut_loop/layer/mlp": 60 * US,
        "jit_prefill:layer/mlp": 50 * US,
        "jit_decode:sample": 12 * US, "jit_decode:embed": 8 * US})
    assert out["device_scopes"][0][1] >= out["device_scopes"][-1][1]
    assert out["prefetch_wait_s"] == pytest.approx(20 * US)
    assert out["unplaced_s"] == pytest.approx(40 * US)
    assert scopes.breakdown_by_scope(Trace(), MAPS) is None


def test_direction_and_the_two_ways_to_count():
    ops, maps = [], {"jit_train_step": {}}
    for step in range(3):
        t = 1000 * step
        for i, (scope, direction, us) in enumerate([
                ("loss_and_grads/layer_0/attention", "fwd", 10),
                ("loss_and_grads/layer_0/attention", "bwd", 25),
                ("loss_and_grads/grad_flatten", "", 5),
                ("optimizer", "", 7), ("cast_params", "", 3)]):
            ops.append(op(f"fusion.{i}", "fusion", t + 100 * i, us))
            maps["jit_train_step"][f"fusion.{i}"] = [scope, direction]
    trace = Trace(ops={0: ops}, modules={0: [
        run("jit_train_step", 1000 * s, 900) for s in range(3)]})
    step = {"module": "^jit_train_step", "per": "step"}

    def read(**args):
        return scopes.scope_ms(trace, {**step, **args}, ctx(maps, steps=3))

    assert read(scope="loss_and_grads", direction="fwd") == \
        pytest.approx(0.010)
    assert read(scope="loss_and_grads", direction=["bwd", ""]) == \
        pytest.approx(0.030)
    assert read(scope="attention") == pytest.approx(0.035)
    assert read(scope=["optimizer", "cast_params"]) == pytest.approx(0.010)
    assert read(scope="mlm_head") == 0.0          # placed, and nothing there
    assert read(scope="attention", per="run_median") == pytest.approx(0.035)
    assert scopes.unscoped_pct(trace, {}, ctx(maps)) == 0.0


@pytest.mark.parametrize("maps", [None, {}, {"jit_decode": {}},
                                  {"jit_other": MAPS["jit_decode"]}])
def test_without_a_map_every_reading_says_so(maps):
    """A program without the scopes, a harness that hands none over, a map
    of another compile: 100% unscoped, and no ``scope_ms`` at all."""
    trace = serving_trace()
    for spec in scope_probe.PROBED.values():
        reader = spec["reader"]
        got = scopes.REDUCERS[reader["reducer"]](trace, reader["args"],
                                                 ctx(maps))
        assert got == (100.0 if reader["reducer"] == "unscoped_pct"
                       else None)
    assert scopes.unscoped_pct(Trace(), {}, ctx(maps)) is None


def test_unscoped_share_of_a_part_of_the_programs():
    trace = serving_trace()
    assert scopes.unscoped_pct(trace, {"module": "^jit_decode"},
                               ctx()) == 0.0
    assert scopes.unscoped_pct(trace, {"module": "^jit_prefill"},
                               ctx()) == pytest.approx(100 * 40 / 90)
    assert scopes.unscoped_pct(trace, {}, ctx()) == pytest.approx(
        100 * 40 / 230)


# -- the recorded trace -----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    trace = xplane.read(os.path.join(FIXTURES, "scopes_2prog.xplane.pb"))
    with open(os.path.join(FIXTURES, "scopes_2prog.json")) as f:
        return trace, json.load(f)


def test_the_recording_holds_two_programs_with_the_same_instructions(
        recorded):
    trace, maps = recorded
    assert [scopes.module_name(m.text) for m in trace.modules[0]] == [
        "jit_decode", "jit_prefill_8", "jit_decode", "jit_decode",
        "jit_prefill_8"]
    assert set(maps) == {"jit_decode", "jit_prefill_8"}
    # the layers' two products carry the same names in both programs and
    # stand under the OTHER word in each
    both = set(maps["jit_decode"]) & set(maps["jit_prefill_8"])
    words = {name: (maps["jit_decode"][name][0].split("/")[-1],
                    maps["jit_prefill_8"][name][0].split("/")[-1])
             for name in both}
    assert {("attention", "mlp"), ("mlp", "attention")} <= set(
        words.values())
    for module in maps:
        names = {scopes.instruction_name(e.text)
                 for _, m, e, _, _ in scopes.scoped_events(trace, maps)
                 if m == module}
        assert names and names <= set(maps[module])
    assert any(xplane.opcode(e.text) == "while" for e in trace.ops[0])


def test_the_recording_reduces_to_the_sums_of_its_own_events(recorded):
    trace, maps = recorded
    runs = sorted(trace.modules[0], key=lambda m: m.start)
    by_run = {}        # (run, scope word) -> seconds, summed by hand
    for e in trace.ops[0]:
        if xplane.opcode(e.text) == "while":
            continue
        at = next(i for i, r in enumerate(runs)
                  if r.start <= e.start < r.end)
        module = scopes.module_name(runs[at].text)
        scope = maps[module][scopes.instruction_name(e.text)][0]
        for word in scope.split("/"):
            by_run[at, word] = by_run.get((at, word), 0.0) + e.duration
    c = {"steps": 3, "scopes": maps}
    decode = {"module": "^jit_decode", "per": "run_median"}
    words = {word for at, word in by_run if at == 0}
    assert {"attention", "mlp", "embed", "lm_head"} <= words
    for word in words:
        want = sorted(by_run[at, word] for at in (0, 2, 3))[1]
        assert scopes.scope_ms(trace, {**decode, "scope": word}, c) == \
            pytest.approx(1e3 * want, rel=1e-6)
    # the same instructions are the OTHER word in the prefill's runs
    prefill = {"module": "^jit_prefill", "per": "step"}
    assert scopes.scope_ms(trace, {**prefill, "scope": "mlp"},
                           {**c, "steps": 2}) == pytest.approx(
        1e3 * (by_run[1, "mlp"] + by_run[4, "mlp"]) / 2, rel=1e-6)
    assert scopes.unscoped_pct(trace, {}, c) == 0.0
    out = scopes.breakdown_by_scope(trace, maps)
    # containers left out, the scopes sum to the busy time
    assert out["scoped_s"] + out["unplaced_s"] == pytest.approx(
        out["busy_s"], rel=0.01)
    assert out["unplaced_s"] == 0.0
    lines = dict(out["device_scopes"])
    assert {"jit_decode:ut_loop/layer/attention", "jit_decode:lm_head",
            "jit_prefill:ut_loop/layer/mlp"} <= set(lines)
    assert not any("while" in name for name in lines)
    # an empty map over the same trace
    assert scopes.unscoped_pct(trace, {}, {"steps": 3, "scopes": {}}) == 100
    assert scopes.scope_ms(trace, {**decode, "scope": "attention"},
                           {"steps": 3, "scopes": {}}) is None


# -- the probe --------------------------------------------------------------

def test_probed_metrics_are_written_as_the_manifest_would_hold_them():
    from .test_bench_manifest import NAME, SOURCES, UNIT

    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    accepted = {m["name"] for m in bench["per_layer"]}
    assert len(scope_probe.PROBED) == 11
    for name, spec in scope_probe.PROBED.items():
        assert set(spec) == {"unit", "better", "source", "layer", "moves",
                             "workloads", "reader"}
        assert NAME.match(name) and UNIT.match(spec["unit"])
        assert name not in accepted                # new, not a second copy
        assert spec["better"] == "lower"
        assert spec["source"] == "device_trace" and "device_trace" in SOURCES
        assert set(spec["workloads"]) <= reports[spec["moves"]]
        assert spec["layer"] in layers             # no new layer
        assert spec["reader"]["kind"] == "trace"
        assert spec["reader"]["reducer"] in scopes.REDUCERS
    serving = {w["name"] for w in bench["workloads"]
               if w["name"] in reports["serve_tokens_per_s"]}
    assert set(scope_probe.PROBED["decode_ffn_ms"]["workloads"]) == serving
    assert not set(scopes.REDUCERS) & set(reducers.REDUCERS)


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_the_probe_takes_the_maps_of_a_tiny_cell(kind):
    """On the CPU there is no device plane: the readings by scope are left
    out, and the programs' maps, which need no trace, are there whole."""
    spec = (_tiny.train_spec({}) if kind == "train"
            else _tiny.serve_spec({"served_logit_gap": 1.0}))
    line = scope_probe.probe(spec, 13, 0.5, jax.devices()[:1])
    assert not set(scope_probe.PROBED) & set(line["metrics"])
    assert line["scope_map_s"] > 0 and "device_scopes" not in line
    step = "jit_train_step" if kind == "train" else "jit_decode"
    assert line["programs"][step]["instructions"] > 50
    # a tree before PR 38 may have left this program in the compile cache
    assert 0 <= line["programs"][step]["placed"] <= \
        line["programs"][step]["instructions"]
    if kind == "serve":
        assert {"jit_prefill_8", "jit_prefill_16",
                "jit_prefill_32"} <= set(line["programs"])
    assert spec["config"]["engine"]["profiling"] == {"memory_ledger": True}
