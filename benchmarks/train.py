"""A training cell: ``deepspeed_tpu.initialize`` + ``engine.train_batch``
on seeded weights and batches.

Set-up builds ONE engine, drives it from the seed through its first three
steps (whose losses, first gradient and weight change are kept for the
check), warms it up, and hands that same engine to the window.  After the
window the engine is freed and the plain reference follows the same three
steps on the same weights and batches; ``correct`` comes from comparing
the two (``check.compare_training``), from every loss in the window being
finite and from nothing having compiled inside the window.
"""

import gc
import math
import time
from collections import deque

import jax
import numpy as np

from . import (check, common, counts, generators, metrics, models, peaks,
               tracing)
from .models import _init
from .reference import follow, ops

CHECK_STEPS = 3
WARMUP_STEPS = 2
EVAL_POSITIONS = 256


def _flat_host(tree):
    return np.concatenate([np.asarray(x).reshape(-1) for x in
                           jax.tree_util.tree_leaves(jax.device_get(tree))])


def _engine_config(spec, global_batch):
    config = dict(spec["config"]["engine"])
    config["train_batch_size"] = global_batch
    # no "seed" here: the program bakes its dropout key into the compiled
    # step, so another key is another program and 130 s of compiling
    # (PERF.md, Open questions); weights and batches carry the seed
    return config


def first_steps(engine, pool, start_flat, sizes, beta1, phases):
    """Drive ``engine`` through its first steps with the window's own call
    and feed; return what the check compares."""
    out = {"losses": []}
    for n in range(CHECK_STEPS):
        loss = engine.train_batch(iter([pool[n]]))
        out["losses"].append(float(jax.device_get(loss)))
        phases.mark(f"step_{n + 1}")
        if n == 0:
            # Adam's first moment after one step is (1 - beta1) g: the
            # gradient exactly as the optimizer got it
            moment = engine.flat.gather_master_unpadded(
                engine.state["opt"].exp_avg)
            out["grad_norms"] = check.leaf_norms_flat(
                moment, sizes) / (1.0 - beta1)
            del moment
            phases.mark("read_first_moment")
    master = engine.flat.gather_master_unpadded(engine.state["master"])
    np.subtract(master, start_flat, out=master)
    out["delta_norms"] = check.leaf_norms_flat(master, sizes)
    phases.mark("read_master")
    return out


def eval_positions(seed, rows, seq):
    """A seeded sample of (row, column) positions of the evaluated rows."""
    rng = np.random.default_rng([int(seed), 5])
    return (rng.integers(0, rows, size=EVAL_POSITIONS),
            rng.integers(0, seq, size=EVAL_POSITIONS))


def program_eval_logits(engine, model, batch, rows, positions):
    """The program's logits with dropout off at the seeded weights, through
    ``engine.eval_batch``: the one reading a lower precision moves at first
    order (the timed step's own numbers carry its dropout masks, which no
    reference can share)."""
    logits = engine.eval_batch(model.eval_inputs(batch, rows))
    picked = logits[positions[0], positions[1]]
    return np.asarray(jax.device_get(picked), np.float32)


def _placement(model, mc, devices):
    """Where the reference's weights and rows live: one chip, or spread
    over the cell's chips (plain jit; XLA places the exchanges) where the
    float32 state does not fit one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(devices) == 1:
        return None, jax.numpy.asarray
    mesh = Mesh(np.array(devices), ("data",))
    n = len(devices)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(
            mesh, P("data") if s[0] % n == 0 and len(s) > 1 else P()),
        model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))
    rows = NamedSharding(mesh, P("data"))
    return shardings, lambda x: jax.device_put(x, rows)


def reference_eval_logits(spec, seed, batch, rows, positions, devices,
                          precision="float32"):
    """The reference's logits at the same positions, no dropout."""
    cfg = spec["config"]
    mc = cfg["model_config"]
    model, ref = models.load_with_reference(cfg["model"])
    shardings, put = _placement(model, mc, devices)
    params = model.init_params(mc, seed, shardings)
    block = {k: put(v) for k, v in model.eval_inputs(batch, rows).items()}
    r, c = (jax.numpy.asarray(p) for p in positions)
    logits = jax.jit(lambda p, b: ref.eval_logits(
        p, b, r, c, mc, ops.MATMULS[precision]))(params, block)
    return np.asarray(jax.device_get(logits), np.float32)


def logit_rms_gap(program, reference):
    """Root-mean-square gap between two sets of logits, against the spread
    of the reference's."""
    return float(np.sqrt(np.mean(np.square(program - reference)))
                 / np.std(reference))


def reference_steps(spec, seed, pool, devices, precision="float32"):
    """The plain reference through the same first steps (own dropout
    masks); ``precision`` "fp8" is the control."""
    cfg, traffic = spec["config"], spec["traffic"]
    mc = cfg["model_config"]
    model, ref = models.load_with_reference(cfg["model"])
    shardings, put = _placement(model, mc, devices)
    rates, mm = model.dropout_rates(mc), ops.MATMULS[precision]

    def block_loss(params, block, key, totals):
        return ref.block_loss(params, block, mc, traffic, key, rates, mm,
                              totals)

    def init():
        return model.init_params(mc, seed, shardings)

    return follow.follow_steps(
        init(), init, pool[:CHECK_STEPS], block_loss, ref.batch_totals,
        cfg["optimizer"], traffic["check_block_rows"] * len(devices),
        jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), 7),
        put)


def named_leaves(cfg):
    """(every leaf's name, the configuration file's ``check`` block: the
    leaves a number leaves out and the leaves that get a number of their
    own, each pattern with its reason)."""
    model = models.load(cfg["model"])
    return (_init.leaf_paths(model.param_shapes(cfg["model_config"])),
            cfg.get("check", {}))


def setup(spec, seed, devices, wrap_engine=None):
    """Weights and batches from the seed, ONE engine, its first steps (kept
    for the check) and its warm-up.  Returns what the window needs."""
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.parallel import make_mesh

    cfg, traffic, chips = spec["config"], spec["traffic"], spec["chips"]
    mc = cfg["model_config"]
    model = models.load(cfg["model"])
    gen = generators.load(traffic["generator"])
    global_batch = traffic["batch_per_chip"] * chips
    phases = common.Phases()
    pool = gen.make(traffic, mc, seed, global_batch)
    phases.mark("batches")
    params = model.init_params(mc, seed)
    sizes = [int(np.prod(x.shape)) for x in
             jax.tree_util.tree_leaves(params)]
    start_flat = _flat_host(params)
    phases.mark("weights")
    engine, *_ = deepspeed.initialize(
        model=model.build_program_model(mc, traffic),
        config=_engine_config(spec, global_batch),
        mesh=make_mesh(cfg["mesh"], devices=list(devices)),
        model_parameters=params)
    del params
    phases.mark("initialize")
    if wrap_engine is not None:
        engine = wrap_engine(engine)
    eval_rows = chips * traffic["eval_rows_per_chip"]
    positions = eval_positions(seed, eval_rows, traffic["seq_len"])
    eval_logits = program_eval_logits(engine, model, pool[0], eval_rows,
                                      positions)
    phases.mark("eval_logits")
    program = first_steps(engine, pool, start_flat, sizes,
                          cfg["optimizer"]["betas"][0], phases)
    del start_flat
    step_seconds = []
    for n in range(WARMUP_STEPS):
        t = time.perf_counter()
        jax.block_until_ready(engine.train_batch(
            iter([pool[(CHECK_STEPS + n) % len(pool)]])))
        step_seconds.append(time.perf_counter() - t)
    phases.mark("warm_up")
    program.update(eval_logits=eval_logits, eval_rows=eval_rows,
                   eval_positions=positions)
    return {"engine": engine, "pool": pool, "program": program,
            "step_seconds": min(step_seconds), "global_batch": global_batch,
            "tokens_per_step": gen.tokens_per_step(traffic, global_batch)}


def free(engine):
    """Drop the program's state and executables before the reference."""
    engine.close()
    del engine
    gc.collect()
    jax.clear_caches()


def run_cell(spec, seed, seconds, trace, t_process, devices,
             wrap_engine=None):
    """One run of one cell; prints the check lines and the result line and
    returns ``correct``."""
    with common.program_log_on_stderr():
        return _run_cell(spec, seed, seconds, trace, t_process, devices,
                         wrap_engine)


def _run_cell(spec, seed, seconds, trace, t_process, devices,
             wrap_engine=None):
    from deepspeed_tpu.runtime.compilation import CompileStats

    cfg, traffic, chips = spec["config"], spec["traffic"], spec["chips"]
    mc = cfg["model_config"]
    model = models.load(cfg["model"])
    stats = CompileStats()
    ready = setup(spec, seed, devices, wrap_engine)
    engine, pool, program = ready["engine"], ready["pool"], ready["program"]
    global_batch = ready["global_batch"]
    tokens_per_step = ready["tokens_per_step"]
    compile_cold_s, misses_setup = stats.cold_secs, stats.misses
    programs_at_open = stats.programs

    # -- the window ------------------------------------------------------
    device_trace = tracing.DeviceTrace(bool(trace))
    traced_steps = int(traffic["trace_steps"]) if trace else 0
    untraced_s = seconds - traced_steps * ready["step_seconds"]
    losses, inflight, steps = [], deque(), 0
    next_batch = CHECK_STEPS + WARMUP_STEPS
    setup_s = time.perf_counter() - t_process
    t_open = time.perf_counter()

    def one_step():
        nonlocal steps
        with tracing.span("train_batch"):
            loss = engine.train_batch(
                iter([pool[(next_batch + steps) % len(pool)]]))
        steps += 1
        losses.append(loss)
        inflight.append(loss)
        if len(inflight) > 2:   # at most two steps ahead of the device
            with tracing.span("wait_step"):
                jax.block_until_ready(inflight.popleft())

    while time.perf_counter() - t_open < untraced_s:
        one_step()
    jax.block_until_ready(losses[-1])
    t_untraced = time.perf_counter() - t_open
    steps_untraced = steps
    if trace:
        device_trace.start()
        for _ in range(traced_steps):
            one_step()
        with tracing.span("fence"):
            jax.block_until_ready(losses[-1])
        device_trace.stop()
    window_s = time.perf_counter() - t_open
    compiled_in_window = stats.programs - programs_at_open
    memory_peak = common.memory_peak_bytes(devices)
    window_losses = [float(x) for x in jax.device_get(losses)]

    # -- free the program, then the reference and the comparison ----------
    del losses, inflight, ready
    free(engine)
    del engine
    t_ref = time.perf_counter()
    reference = reference_steps(spec, seed, pool, devices)
    reference["eval_logits"] = reference_eval_logits(
        spec, seed, pool[0], program["eval_rows"],
        program["eval_positions"], devices)
    reference_s = time.perf_counter() - t_ref
    comparison = check.Comparison(traffic["limits"][cfg["name"]])
    check.compare_training(comparison, program, reference,
                           *named_leaves(cfg))
    comparison.add("eval_logit_gap", logit_rms_gap(
        program["eval_logits"], reference["eval_logits"]))
    bad = [x for x in window_losses if not math.isfinite(x)]
    comparison.require("window_losses_finite", not bad,
                       f"{len(bad)} of {len(window_losses)} not finite")
    comparison.require("no_compile_in_window", compiled_in_window == 0,
                       f"{compiled_in_window} program(s) compiled")

    tokens_per_s = (steps_untraced * tokens_per_step / t_untraced / chips)
    device = common.device_line(devices)
    device["memory_peak_bytes"] = memory_peak
    result = {"train_tokens_per_s": {"value": tokens_per_s,
                                     "unit": "tokens/s/chip"},
              "setup_s": {"value": setup_s, "unit": "s"}}
    breakdown = None
    if trace:
        layers, b, heads, s, d, causal = model.attention_shape(
            mc, traffic, global_batch)
        flops_per_step = model.train_flops_per_step(mc, traffic,
                                                    global_batch)
        run = {
            "trace": device_trace.trace,
            "ctx": {"steps": traced_steps, "window_s": device_trace.window_s,
                    "device_kind": device["kind"],
                    "counts": {
                        "attn_kernel_flops_per_layer":
                            counts.attention_kernel_flops_per_layer(
                                b // chips, heads, s, d, causal),
                        "attn_kernel_bytes_per_layer":
                            counts.attention_kernel_bytes_per_layer(
                                b // chips, heads, s, d)}},
            "counters": {
                "compile_cold_s": compile_cold_s,
                "cache_misses": misses_setup + compiled_in_window,
                "train_mfu": peaks.mfu_percent(
                    tokens_per_s, flops_per_step / tokens_per_step,
                    device["kind"]),
                "hbm_peak_bytes": memory_peak},
            "spans": {}}
        result = metrics.per_layer(spec["per_layer"], run)
        device.update(device_trace.device_fields())
        breakdown = device_trace.breakdown()
    stats.close()
    common.print_result(
        comparison.correct, steps, len(bad), result, device, breakdown,
        window_s=window_s, steps_untraced=steps_untraced,
        reference_s=reference_s, compile_cold_s=compile_cold_s,
        program_first_steps=program["losses"],
        reference_first_steps=reference["losses"],
        window_losses=[window_losses[0], max(window_losses),
                       window_losses[-1]])
    return comparison.correct
