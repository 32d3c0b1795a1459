"""A serving cell: ``InferenceEngine.submit`` / ``step`` under a traffic
source (closed or open loop) on seeded weights and prompts.

The harness stamps every request's tokens itself, after each
``engine.step()``, and counts the tokens produced inside its own window.
After the window the engine is freed; the plain reference then runs once
over a seeded sample of the finished requests, each prompt with its served
tokens, and ``correct`` compares the widest gap by which a served token's
logit lies below the reference's best (greedy tokens only, which is what
the engine serves).
"""

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, common, generators, metrics, models, tracing
from .generators._requests import token_id_range
from .reference import ops

CHECK_REQUESTS = 6


class Loop:
    """The serve loop and its own clock readings."""
    def __init__(self, engine, source, slots, clock=time.perf_counter):
        self.engine, self.source, self.slots = engine, source, slots
        self.clock = clock
        self.live = {}          # request id -> [request, tokens seen, stamp]
        self.finished = []      # (prompt, tokens) of requests served whole
        self.gaps, self.ttfts, self.late = [], [], []
        self.host_gaps, self.occupancy, self.context_tokens = [], [], []
        self.iterations = 0
        self.tokens = 0         # output tokens seen, by the stamps below
        self._returned = None
        self.t0 = clock()

    def submit(self, prompt, answer, due=None):
        now = self.clock()
        with tracing.span("submit"):
            rid = self.engine.submit(prompt, max_new_tokens=answer)
        self.live[rid] = [self.engine.request(rid), 0,
                          now if due is None else self.t0 + due]
        if due is not None:
            self.late.append(now - (self.t0 + due))

    def start(self):
        for prompt, answer in self.source.initial():
            self.submit(prompt, answer)

    def step(self, record=True):
        for due, prompt, answer in self.source.due(self.clock() - self.t0):
            self.submit(prompt, answer, due)
        sched = self.engine.scheduler
        if not (sched.queue_depth or sched.active_count):
            time.sleep(0.0005)   # open loop with nothing due: do not spin
        kind = ("engine_step.prefill"
                if sched.queue_depth and sched.active_count < self.slots
                else "engine_step.decode")
        called = self.clock()
        if record and self._returned is not None:
            self.host_gaps.append(called - self._returned)
        with tracing.span(kind):
            done = self.engine.step()
        now = self._returned = self.clock()
        with tracing.span("stamp"):
            self.iterations += 1
            if record:
                self.occupancy.append(sched.active_count / self.slots)
                self.context_tokens.append(sum(
                    r.context_len for r in sched.slots if r is not None))
            for rid in list(self.live):
                rec = self.live[rid]
                n = len(rec[0].generated)
                if n > rec[1]:
                    self.tokens += n - rec[1]
                    if record:
                        if rec[1] == 0:
                            self.ttfts.append(now - rec[2])
                        else:
                            self.gaps.append((now - rec[2]) / (n - rec[1]))
                    rec[1], rec[2] = n, now
            for request in done:
                self.live.pop(request.request_id, None)
                self.engine.forget(request.request_id)
                if request.finish_reason == "max_new_tokens":
                    self.finished.append((list(request.prompt),
                                          list(request.generated)))
        for prompt, answer in self.source.on_finish(len(done)):
            self.submit(prompt, answer)
        return done


def drive(loop, seconds):
    """Step ``loop`` for ``seconds`` on its clock.  Returns the output
    tokens produced (every token of every iteration inside, whichever
    request it belongs to, counted by the loop's own stamps and not by a
    counter of the program's), the seconds taken and the iterations
    made."""
    tokens, iterations = loop.tokens, loop.iterations
    t0 = loop.clock()
    while loop.clock() - t0 < seconds:
        loop.step()
    return (loop.tokens - tokens, loop.clock() - t0,
            loop.iterations - iterations)


def sample_finished(finished, seed, n=CHECK_REQUESTS):
    """A sample of the finished requests drawn from the seed, with the
    longest in it."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    picked = [longest] + [int(i) for i in rng.permutation(rest)[:n - 1]]
    return [finished[i] for i in picked]


def reference_gaps(spec, seed, sample, precision="float32"):
    """For every served token of ``sample``: how far its logit lies below
    the reference's best at that position (float32 reference, one forward
    per request row).  With ``precision`` "fp8" (the control) the tokens
    judged are the ones the lower precision puts first instead."""
    cfg = spec["config"]
    mc = cfg["model_config"]
    model, ref = models.load_with_reference(cfg["model"])
    # one shape for every run of the cell (one compiled program): rows and
    # positions padded to the most the traffic's pairs can ask for
    pairs = spec["traffic"]["pairs"]
    n_rows = max(len(sample), CHECK_REQUESTS)
    width = min(-(-max(p + a for p, a in pairs) // 128) * 128,
                mc["max_position_embeddings"])
    n_positions = n_rows * max(a for _, a in pairs)
    ids = np.zeros((n_rows, width), np.int32)
    rows, cols, served = [], [], []
    for r, (prompt, tokens) in enumerate(sample):
        ids[r, :len(prompt)] = prompt
        ids[r, len(prompt):len(prompt) + len(tokens)] = tokens
        for k, token in enumerate(tokens):
            rows.append(r)
            cols.append(len(prompt) + k - 1)   # the position that predicts it
            served.append(token)
    n_served = len(served)
    pad = [0] * (n_positions - n_served)
    rows, cols = jnp.asarray(rows + pad), jnp.asarray(cols + pad)
    served = served + pad
    params = model.init_params(mc, seed)

    def logits_of(mm):
        return jax.jit(lambda p, i: ref.position_logits(
            p, i, rows, cols, mc, mm))(params, jnp.asarray(ids))

    logits = logits_of(ops.MATMULS["float32"])
    judged = jnp.asarray(served)
    if precision != "float32":
        judged = jnp.argmax(logits_of(ops.MATMULS[precision]), axis=-1)
    gaps = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, judged[:, None], axis=-1)[:, 0]
    return np.asarray(jax.device_get(gaps), np.float64)[:n_served]


def setup(spec, seed, devices, wrap_engine=None):
    """Weights from the seed, the engine, one warm-up request per prefill
    bucket (which also compiles the decode program), and the traffic
    source started: the loop the window drives."""
    from deepspeed_tpu.inference import InferenceEngine

    cfg, traffic = spec["config"], spec["traffic"]
    mc, icfg = cfg["model_config"], cfg["engine"]["inference"]
    model = models.load(cfg["model"])
    slots = icfg["max_batch_slots"]
    with jax.default_device(devices[0]):
        params = model.init_params(mc, seed)
        engine = InferenceEngine(model.build_program_model(mc, traffic),
                                 params, config=cfg["engine"])
    del params
    if wrap_engine is not None:
        engine = wrap_engine(engine)
    rng = np.random.default_rng([int(seed), 4])
    for bucket in icfg["prefill_buckets"]:
        engine.submit(rng.integers(0, token_id_range(mc), size=bucket - 1),
                      max_new_tokens=2)
    engine.run()
    source = generators.load(traffic["generator"]).make(
        traffic, mc, seed, slots)
    loop = Loop(engine, source, slots)
    loop.start()
    for _ in range(int(traffic.get("warmup_iterations", 0))):
        loop.step(record=False)
    return loop


def free(loop):
    """Drop the program's state and executables before the reference."""
    loop.engine.close()
    loop.engine = None
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

    cfg, traffic = spec["config"], spec["traffic"]
    mc = cfg["model_config"]
    model = models.load(cfg["model"])
    stats = CompileStats()
    loop = setup(spec, seed, devices, wrap_engine)
    engine, source = loop.engine, loop.source
    compile_cold_s, misses_setup = stats.cold_secs, stats.misses
    programs_at_open = stats.programs

    # -- the window ------------------------------------------------------
    device_trace = tracing.DeviceTrace(bool(trace))
    traced_s = float(traffic.get("trace_seconds", 3.0)) if trace else 0.0
    finished_before = len(loop.finished)
    setup_s = time.perf_counter() - t_process
    t_open = loop.t0 = time.perf_counter()   # arrivals count from here
    tokens_untraced, t_untraced, _ = drive(loop, seconds - traced_s)
    iterations_traced = 0
    if trace:
        device_trace.start()
        _, _, iterations_traced = drive(loop, traced_s)
        live_context = statistics.fmean(
            loop.context_tokens[-iterations_traced:])
        device_trace.stop()
    window_s = time.perf_counter() - t_open
    compiled_in_window = stats.programs - programs_at_open
    memory_peak = common.memory_peak_bytes(devices)
    finished_in_window = len(loop.finished) - finished_before
    sample = sample_finished(loop.finished, seed)

    # -- free the program, then the reference and the comparison ----------
    del engine
    free(loop)
    comparison = check.Comparison(traffic["limits"][cfg["name"]])
    t_ref = time.perf_counter()
    comparison.require("requests_finished", bool(sample),
                       f"{finished_in_window} in the window")
    if sample:
        gaps = reference_gaps(spec, seed, sample)
        print(f"check served_tokens_compared {len(gaps)} in "
              f"{len(sample)} requests, longest "
              f"{max(len(p) + len(t) for p, t in sample)} tokens", flush=True)
        comparison.add("served_logit_gap", float(gaps.max()))
    reference_s = time.perf_counter() - t_ref
    comparison.require("no_compile_in_window", compiled_in_window == 0,
                       f"{compiled_in_window} program(s) compiled")

    tokens_per_s = tokens_untraced / t_untraced / len(devices)
    device = common.device_line(devices)
    device["memory_peak_bytes"] = memory_peak
    result = {"serve_tokens_per_s": {"value": tokens_per_s,
                                     "unit": "tokens/s/chip"},
              "setup_s": {"value": setup_s, "unit": "s"}}
    if source.open_loop and loop.ttfts:
        result["ttft_p95_ms"] = {
            "value": 1e3 * common.quantile(loop.ttfts, 0.95), "unit": "ms"}
        result["tpot_p50_ms"] = {
            "value": 1e3 * common.quantile(loop.gaps, 0.5), "unit": "ms"}
    breakdown = None
    if trace:
        run = {
            "trace": device_trace.trace,
            "ctx": {"steps": max(iterations_traced, 1),
                    "window_s": device_trace.window_s,
                    "device_kind": device["kind"],
                    "counts": {"decode_bytes_per_step":
                               model.decode_bytes_per_step(
                                   mc, live_context)}},
            "counters": {
                "compile_cold_s": compile_cold_s,
                "cache_misses": misses_setup + compiled_in_window,
                "slot_occupancy": 100.0 * statistics.fmean(loop.occupancy),
                "hbm_peak_bytes": memory_peak,
                "serve_tokens_per_s": tokens_per_s},
            "spans": {"token_gap": loop.gaps, "ttft": loop.ttfts,
                      "host_gap": loop.host_gaps,
                      "generator_late": loop.late}}
        result = metrics.per_layer(spec["per_layer"], run)
        device.update(device_trace.device_fields())
        breakdown = device_trace.breakdown()
    stats.close()
    common.print_result(
        comparison.correct, finished_in_window, 0, result, device, breakdown,
        window_s=window_s, iterations=loop.iterations,
        reference_s=reference_s, compile_cold_s=compile_cold_s,
        tokens_in_window=tokens_untraced,
        ttft_p50_ms=1e3 * (common.quantile(loop.ttfts, 0.5) or 0.0),
        tpot_p50_ms=1e3 * (common.quantile(loop.gaps, 0.5) or 0.0),
        tpot_p95_ms=1e3 * (common.quantile(loop.gaps, 0.95) or 0.0))
    return comparison.correct
