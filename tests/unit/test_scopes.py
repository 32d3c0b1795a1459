"""The program's scopes (``telemetry/scopes.py``): the vocabulary stands in
the compiled programs, every instruction that can show in a trace finds its
scope, a scope is metadata only, nothing is built before it is asked for,
and an on-demand device trace is left reduced by scope."""

import contextlib
import json
import os
import time

import jax
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from benchmarks import generators, models, serve
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.telemetry import scopes
from tests.benchmarks import (_tiny, _tiny_deepseek, _tiny_exaone, _tiny_ouro,
                              _tiny_xing)

LEDGER = {"profiling": {"memory_ledger": True}}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A program loaded from the persistent compile cache carries the
    metadata of the tree that compiled it first (the key leaves metadata
    out): these tests read the metadata, so they compile their own."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()

SERVED = {"gpt2": _tiny.serve_spec, "deepseek_v2": _tiny_deepseek.serve_spec,
          "exaone_moe": _tiny_exaone.serve_spec,
          "ouro": _tiny_ouro.serve_spec, "xing": _tiny_xing.serve_spec}


# -- an op_name read as a scope ---------------------------------------------

@pytest.mark.parametrize("op_name,scope,direction", [
    ("jit(step)/loss_and_grads/transpose(jvp(layer_0))/attention/add_any",
     "loss_and_grads/layer_0/attention", "bwd"),
    ("jit(step)/loss_and_grads/jvp(layer_0)/attention/dot_general",
     "loss_and_grads/layer_0/attention", "fwd"),
    ("jit(step)/optimizer/sub", "optimizer", ""),
    # jax.checkpoint writes the outer scopes again before what it recomputes
    ("jit(step)/loss_and_grads/transpose(jvp(layer_0))/loss_and_grads/"
     "jvp(layer_0)/checkpoint/rematted_computation/attention/tanh",
     "loss_and_grads/layer_0/attention", "bwd"),
    # a differentiated operation outside every model scope
    ("jit(train_step)/loss_and_grads/jvp()/mul", "loss_and_grads", "fwd"),
    # helpers' own jits, a loop's structure, an einsum's subscripts
    ("jit(decode)/ut_loop/while/body/closed_call/layer_3/attention/"
     "bqhd,bkhd->bhqk/dot_general", "ut_loop/layer_3/attention", ""),
    ("jit(decode)/embed/jit(_take)/jit(_where)/select_n", "embed", ""),
    # a call instruction carries the stack alone; merged names, the first
    ("jit(train_step)/loss_and_grads/jvp(embed)/jit(_take)",
     "loss_and_grads/embed", "fwd"),
    ("jit(f)/lm_head/dot_general;jit(f)/sample/argmax", "lm_head", ""),
    ("add", "", ""),
])
def test_an_op_name_reads_as_scope_and_direction(op_name, scope, direction):
    assert scopes.scope_of(op_name) == (scope, direction)


def test_a_map_covers_loops_and_branches_but_no_fusion_body():
    text = """HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inside.1 = f32[4]{0} tanh(%p), metadata={op_name="jit(f)/mlp/tanh"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/ut_loop/while/body/layer_0/mlp/tanh"}
  %copy.7 = f32[4]{0} copy(%fusion.1)
  ROOT %t = (s32[], f32[4]{0}) tuple(%arg, %copy.7)
}

%cond (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.2 = pred[] constant(true), metadata={op_name="jit(f)/ut_loop/while/cond/lt"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%a)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %z = s32[] constant(0)
  %init = (s32[], f32[4]{0}) tuple(%z, %copy-done.1)
  %while.3 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/ut_loop/while"}
  %out = f32[4]{0} get-tuple-element(%while.3), index=1
  ROOT %neg.9 = f32[4]{0} negate(%out)
}
"""
    got = scopes.scope_map(text)
    assert scopes.module_name(text) == "jit_f"
    assert "inside.1" not in got and "a" not in got and "t" not in got
    assert got["fusion.1"] == ("ut_loop/layer_0/mlp", "")
    # made by the compiler without metadata: where their consumer stands,
    # through the tuples in between, else where their operand does
    assert got["copy.7"] == ("ut_loop/layer_0/mlp", "")
    assert got["copy-done.1"] == got["copy-start.1"] == ("ut_loop", "")
    assert got["neg.9"] == ("ut_loop", "")
    assert got["while.3"] == ("ut_loop", "")
    assert scopes.placed_share(got) == 1.0
    assert scopes.placed_share(scopes.scope_map(text.replace(
        "metadata={op_name", "metadata={name"))) == 0.0


# -- the training step ------------------------------------------------------

def bert_engine(devices, config=None, data=1):
    """A tiny BERT engine (nothing compiled yet) and one batch of it."""
    spec = _tiny.train_spec({})
    cfg, traffic = spec["config"], spec["traffic"]
    mc, model = cfg["model_config"], models.load(cfg["model"])
    rows = traffic["batch_per_chip"]
    batch = generators.load(traffic["generator"]).make(traffic, mc, 1,
                                                       rows)[0]
    engine, *_ = deepspeed.initialize(
        model=model.build_program_model(mc, traffic),
        config={**cfg["engine"], "train_batch_size": rows, **LEDGER,
                **(config or {})},
        mesh=make_mesh({"data": data}, devices=list(devices[:data])),
        model_parameters=model.init_params(mc, 1))
    return engine, {k: np.asarray(v) for k, v in batch.items()}


def _paths(scope_map):
    return {(scope, direction) for scope, direction in scope_map.values()}


def _names(scope_map):
    return {part for scope, _ in scope_map.values()
            for part in scope.split("/")}


def test_every_instruction_of_the_train_step_finds_its_scope(cpu_devices):
    engine, batch = bert_engine(cpu_devices)
    try:
        engine.aot_compile_train_step(batch)
        step = engine.program_scopes()["jit_train_step"]
    finally:
        engine.close()
    assert len(step) > 300 and scopes.placed_share(step) == 1.0
    assert {"unpack", "loss_and_grads", "grad_flatten", "optimizer",
            "cast_params", "embed", "layer_0", "layer_1", "attention", "mlp",
            "pooler", "mlm_head", "loss"} <= _names(step)
    paths = _paths(step)
    for scope in ("layer_0/attention", "layer_1/mlp", "mlm_head", "loss",
                  "embed"):
        assert {(f"loss_and_grads/{scope}", "fwd"),
                (f"loss_and_grads/{scope}", "bwd")} <= paths
    # what stands outside the differentiated function has no direction
    assert {("optimizer", ""), ("cast_params", ""), ("unpack", ""),
            ("loss_and_grads/grad_flatten", "")} <= paths
    assert not any(direction for scope, direction in paths
                   if not scope.startswith("loss_and_grads"))


def test_a_zero2_step_on_a_mesh_shows_the_gradient_exchange(cpu_devices):
    engine, batch = bert_engine(
        cpu_devices, {"zero_optimization": {"stage": 2}}, data=4)
    try:
        compiled, _ = engine.aot_compile_train_step(batch)
        step = engine.program_scopes()["jit_train_step"]
        text = compiled.as_text()
    finally:
        engine.close()
    assert "grad_exchange" in _names(step)
    exchanged = [name for name, (scope, _) in step.items()
                 if scope.endswith("grad_exchange")]
    # the collective itself stands there, not only the constraint
    assert any(op in line for name in exchanged for op in
               ("reduce-scatter", "all-reduce") for line in text.splitlines()
               if f"%{name} = " in line)
    assert scopes.placed_share(step) == 1.0


# -- the served models ------------------------------------------------------

def decode_program(spec):
    """A serving cell's ``decode`` and shapes to trace it on (as the engine
    would hold them: ``prepare_params`` of the caller's tree)."""
    cfg = spec["config"]
    shapes_of = models.load(cfg["model"])
    serving = shapes_of.build_program_model(
        cfg["model_config"], spec["traffic"]).serving()
    icfg = DeepSpeedInferenceConfig(cfg["engine"])

    def s(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    params = jax.eval_shape(serving.prepare_params, jax.tree_util.tree_map(
        s, shapes_of.param_shapes(cfg["model_config"]),
        is_leaf=lambda x: isinstance(x, tuple)))
    groups, slots = serving.cache_groups(icfg), icfg.max_batch_slots
    caches = tuple(
        s((g.layers, g.num_blocks(icfg), icfg.kv_block_size, row))
        for g in groups for row in g.buffers.values())
    tables = tuple(s((slots, g.table_width(icfg)), np.int32) for g in groups)
    return serving.build_decode(icfg), (
        params, caches, tables, s((slots,), np.int32), s((slots,), np.int32))


@pytest.mark.parametrize("model", sorted(SERVED))
def test_a_served_models_decode_stands_under_the_vocabulary(model):
    fn, args = decode_program(SERVED[model]({}))
    decode = scopes.scope_map(jax.jit(fn).lower(*args).compile())
    names = _names(decode)
    assert {"embed", "layer_0", "attention", "final_norm", "lm_head",
            "sample"} <= names
    assert ("moe" in names) == (model in ("deepseek_v2", "exaone_moe",
                                          "xing"))
    if model == "xing":      # the four streams' mixes, beside the sublayers
        assert {"hc_pre", "hc_post", "hc_merge"} <= names
        assert {"layer_0/hc_pre", "layer_0/attention", "layer_0/hc_post",
                "layer_0/mlp", "layer_2/moe/router",
                "hc_merge"} <= {scope for scope, _ in decode.values()}
    if "moe" in names:
        assert {"router", "experts", "shared_experts", "mlp"} <= names
    else:
        assert "mlp" in names
    layered = {scope for scope, _ in decode.values() if "layer_" in scope}
    assert all(scope.startswith("ut_loop/") for scope in layered) == (
        model == "ouro")
    if model == "ouro":
        assert {"ut_loop", "exit_gate"} <= names
    assert scopes.placed_share(decode) == 1.0
    assert not any(direction for _, direction in decode.values())


class _NoScope(contextlib.ContextDecorator):
    """``jax.named_scope`` switched off: neither a context nor a decorator
    that does anything."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_a_scope_is_metadata_only_in_the_train_step(cpu_devices,
                                                    monkeypatch):
    """No operation added or moved: the lowered step, locations left out,
    is the one the same code gives with every scope switched off."""
    def lowered():
        engine, batch = bert_engine(cpu_devices)
        try:
            return engine.aot_lower_train_step(batch)
        finally:
            engine.close()

    with_scopes = lowered()
    assert "loss_and_grads" in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    without = lowered()
    assert "loss_and_grads" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


@pytest.mark.parametrize("model", sorted(SERVED))
def test_a_scope_is_metadata_only_in_a_served_decode(model, monkeypatch):
    fn, args = decode_program(SERVED[model]({}))
    with_scopes = jax.jit(fn).lower(*args)
    assert "/lm_head/" in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    fn, args = decode_program(SERVED[model]({}))
    without = jax.jit(fn).lower(*args)
    assert "/lm_head/" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


# -- built when asked, and not before ---------------------------------------

@pytest.fixture
def counted(monkeypatch):
    calls = []
    real = scopes.scope_map

    def counting(compiled):
        calls.append(compiled)
        return real(compiled)

    monkeypatch.setattr(scopes, "scope_map", counting)
    return calls


def test_training_builds_no_map_until_it_is_asked(cpu_devices, counted):
    engine, batch = bert_engine(cpu_devices)
    try:
        for _ in range(2):
            jax.block_until_ready(engine.train_batch(iter([batch])))
        assert counted == []
        maps = engine.program_scopes()
        assert "jit_train_step" in maps and len(counted) == len(maps) >= 1
    finally:
        engine.close()


def test_serving_builds_no_map_until_it_is_asked(cpu_devices, counted):
    spec = _tiny.serve_spec({})
    spec["config"]["engine"] = {**spec["config"]["engine"], **LEDGER}
    loop = serve.setup(spec, 5, cpu_devices[:1])   # __init__, prefills, run
    try:
        for _ in range(4):
            loop.step()
        assert counted == []
        maps = loop.engine.program_scopes()
    finally:
        serve.free(loop)
    # every bucket's program under a name of its own
    buckets = spec["config"]["engine"]["inference"]["prefill_buckets"]
    assert set(maps) == {"jit_decode"} | {
        f"jit_prefill_{b}" for b in buckets}
    assert len(counted) == len(maps)
    assert all(scopes.placed_share(m) == 1.0 for m in maps.values())


def test_an_engine_without_the_ledger_has_no_programs_to_map(cpu_devices):
    spec = _tiny.serve_spec({})
    loop = serve.setup(spec, 5, cpu_devices[:1])
    try:
        assert loop.engine.program_scopes() == {}
    finally:
        serve.free(loop)


# -- the operator's trace, left reduced by scope ----------------------------

def test_the_trigger_leaves_its_trace_reduced_by_scope(cpu_devices,
                                                       tmp_path):
    run_dir = tmp_path / "run"
    engine, batch = bert_engine(cpu_devices, {"telemetry": {
        "enabled": True, "run_dir": str(run_dir),
        "device_trace_secs": 0.2}})
    out = run_dir / "device_trace" / "scopes.json"
    try:
        jax.block_until_ready(engine.train_batch(iter([batch])))
        (run_dir / "device_trace.trigger").touch()
        deadline = time.monotonic() + 60
        while not out.exists() and time.monotonic() < deadline:
            jax.block_until_ready(engine.train_batch(iter([batch])))
            engine.telemetry.device_trace.wait(timeout=0.0)
        engine.telemetry.device_trace.wait(timeout=60)
    finally:
        engine.close()
    with open(out) as f:
        record = json.load(f)
    assert os.path.exists(run_dir / "device_trace" / record["trace"])
    assert record["placed_share_of_instructions"]["jit_train_step"] == 1.0
    step = record["programs"]["jit_train_step"]
    assert step["runs"] >= 1 and step["seconds"] > 0
    assert step["unplaced_s"] <= 0.01 * step["seconds"]
    by_scope = {(scope, d): s for scope, d, s in step["by_scope"]}
    assert sum(by_scope.values()) + step["unplaced_s"] == pytest.approx(
        step["seconds"])
    assert by_scope[("loss_and_grads/layer_0/attention", "fwd")] > 0
    assert by_scope[("loss_and_grads/layer_0/attention", "bwd")] > 0
    assert ("optimizer", "") in by_scope
    rows = scopes.largest(record["programs"])
    assert len(rows) == 10 and rows[0][3] >= rows[-1][3]
    assert any(scope == "loss_and_grads/layer/attention"
               for _, scope, _, _ in rows)
    # what FlopsProfiler takes for its device column
    ms = scopes.ms_per_run(step)
    assert ms[("optimizer", "")] == pytest.approx(
        1e3 * by_scope[("optimizer", "")] / step["runs"])


def test_the_flops_profile_prints_device_time_beside_a_scopes_flops(
        cpu_devices):
    """FLOPs by scope and a trace by scope join on the same keys: the
    reference profiler's latency column, from a ``scopes.json``'s entry."""
    from deepspeed_tpu.profiling import FlopsProfiler

    engine, batch = bert_engine(cpu_devices)
    try:
        engine.aot_compile_train_step(batch)
        step = engine.program_scopes()["jit_train_step"]
        profiler = FlopsProfiler(engine)
        assert not profiler.profile_train_step(batch).device_ms_by_scope
        attention = ("loss_and_grads/layer_0/attention", "bwd")
        entry = {"runs": 2, "by_scope": [[*attention, 8e-3],
                                         ["optimizer", "", 2e-3]]}
        profile = profiler.profile_train_step(
            batch, device_ms_by_scope=scopes.ms_per_run(entry))
    finally:
        engine.close()
    rows = profile.by_program_scope()
    # every scope that counts FLOPs is one the compiled step's map knows
    assert {key for key, (flops, _) in rows.items()
            if flops > 1000 and key[0]} <= _paths(step)
    flops, ms = rows[attention]
    assert flops > 0 and ms == pytest.approx(4.0)
    assert rows[("optimizer", "")][1] == pytest.approx(1.0)
    assert rows[("loss_and_grads/layer_0/mlp", "fwd")][1] is None
    lines = []
    profile.print(log=lines.append)
    line = next(x for x in lines
                if x.endswith("loss_and_grads/layer_0/attention.bwd"))
    assert "4.000 ms" in line and "TFLOP/s" in line
    assert f"{flops / 4e-3 / 1e12:7.2f} TFLOP/s" in line
    # a scope with FLOPs and no time in the trace says so, not zero
    assert any("- ms" in x and x.endswith("layer_0/mlp.fwd") for x in lines)
