"""Per-scope WALL-time attribution for one engine training step.

The flops profiler (``flops_profiler/profiler.py``) accounts FLOPs by
jaxpr scope; this module accounts *wall seconds* by sub-program, which is
what finding a throughput leak needs (reference analog: the per-module
latency columns of ``profiling/flops_profiler/profiler.py:143``, which
the torch reference collects via module hooks — impossible under one
fused XLA program, so here the step is re-timed as its natural
sub-programs instead).

Measurement rules: every timing boundary is a host round-trip
(``device_get`` of a scalar, which cannot return before the work that
produces it has run; ``chip_smoke.py`` checks on each run that it and
``block_until_ready`` read the same step time); small sub-programs
iterate inside ONE jit via ``lax.scan`` with results folded into the
carry so XLA cannot hoist the work and per-dispatch cost does not
dominate; ``steps >= 5`` after ``warmup >= 2`` for the big programs.
"""

import contextlib
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["timed_loop", "timed_scan", "wall_breakdown",
           "model_scope_breakdown", "grad_fold", "StepLatencyRing"]


class StepLatencyRing:
    """Fixed-size ring of recent per-step wall latencies (beat-to-beat
    intervals of the engine's step loop).

    The always-on counterpart of :func:`wall_breakdown`: O(1) host work
    per step, no device access, safe on the step critical path.  The
    resilience watchdog dumps :meth:`summary` in its hang post-mortem so
    "was the job slowing down before it wedged?" is answerable from the
    crash log alone.  Appends are GIL-atomic; the watchdog thread reads
    without locking.
    """

    def __init__(self, capacity=64):
        self._buf = deque(maxlen=int(capacity))
        self.total_steps = 0
        self._last_beat = None

    def record(self, seconds):
        self._buf.append(float(seconds))
        self.total_steps += 1

    def beat(self):
        """One completed step, interval-tracked by the ring itself — for
        engines running WITHOUT the watchdog (whose own ``beat`` feeds
        this ring when it is armed).  O(1) host work, no device access."""
        now = time.monotonic()
        if self._last_beat is not None:
            self.record(now - self._last_beat)
        self._last_beat = now

    def pause(self):
        """Forget the last beat so a known-long gap (rollback restore,
        synchronous save) is not recorded as a step latency."""
        self._last_beat = None

    def recent(self):
        return list(self._buf)

    def latency_snapshot(self):
        """Summary dict for telemetry export (``comm/latency/*`` gauges
        + the per-rank skew exchange): last/mean/p50/p95/max seconds over
        the ring, plus counts.  All-host arithmetic on already-recorded
        floats — exporting this must ride the ``steps_per_print``
        cadence (dslint DSH205 guards that statically)."""
        vals = self.recent()
        if not vals:
            return {"n": 0, "steps": self.total_steps, "last": 0.0,
                    "mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0}
        arr = np.asarray(vals)
        return {"n": int(arr.size), "steps": self.total_steps,
                "last": float(arr[-1]), "mean": float(arr.mean()),
                "p50": float(np.median(arr)),
                "p95": float(np.percentile(arr, 95)),
                "max": float(arr.max())}

    def summary(self):
        snap = self.latency_snapshot()
        if not snap["n"]:
            return "no completed steps recorded"
        return (f"last={snap['last']:.3f}s mean={snap['mean']:.3f}s "
                f"p50={snap['p50']:.3f}s max={snap['max']:.3f}s "
                f"over {snap['n']} of {snap['steps']} step(s)")


def _fence(x):
    """Host round-trip on one scalar derived from ``x`` (tree or array)."""
    leaf = jax.tree_util.tree_leaves(x)[0]
    val = np.asarray(jax.device_get(leaf)).ravel()
    if val.size:
        assert np.isfinite(np.float64(val[0])), "profiled value not finite"
    return val


def timed_loop(call, steps=10, warmup=3):
    """Mean seconds per ``call()`` for dispatch-per-step programs.

    Two-point scheme: the window is fenced by a host round-trip, so a
    single window of N calls reads ``N·t + overhead``.  Timing N and 2N
    calls and differencing cancels the constant overhead exactly."""
    out = None
    for _ in range(warmup):
        out = call()
    if out is not None:  # warmup=0: nothing to fence yet
        _fence(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = call()
    _fence(out)
    t1 = time.perf_counter()
    for _ in range(2 * steps):
        out = call()
    _fence(out)
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / steps


def timed_scan(fn, operands, steps=10, warmup=2, mesh=None):
    """Mean seconds per ``fn(operands, i)`` iterated INSIDE one jitted
    ``lax.scan`` (for sub-programs small enough that dispatch latency
    would otherwise dominate).  ``fn(operands, i) -> scalar``; the scalar
    folds into the carry so XLA cannot hoist or elide iterations.

    ``operands`` (any pytree of arrays) MUST carry every large array the
    scope touches — a closure-captured ``jax.Array`` becomes a jit
    CONSTANT, and embedding model-sized constants stalls XLA's compile
    (observed: GPT-2-medium params as closure constants never finished).

    Two-point scheme: each fenced window costs one dispatch + host fetch
    round-trip; timing an N-iteration and a 2N-iteration scan and
    differencing cancels it exactly."""

    def make(length):
        @jax.jit
        def run(ops):
            def body(carry, i):
                # the carry perturbs every floating operand: without this
                # data dependence XLA hoists an i-independent body out of
                # the scan and the probe measures nothing (observed: all
                # GEMM probes read 0 ms).  1e-30 underflows to zero in
                # the actual arithmetic, so values are unchanged.
                eps = carry * jnp.float32(1e-30)
                poked = jax.tree_util.tree_map(
                    lambda a: a + eps.astype(a.dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, ops)
                return carry + fn(poked, i).astype(jnp.float32), None

            total, _ = jax.lax.scan(body, jnp.float32(0.0),
                                    jnp.arange(length, dtype=jnp.uint32))
            return total

        return run

    run_n, run_2n = make(steps), make(2 * steps)
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        _fence(run_n(operands))   # compile
        _fence(run_2n(operands))  # compile
        for _ in range(warmup):
            _fence(run_n(operands))
            _fence(run_2n(operands))
        t_n = min_wall(lambda: _fence(run_n(operands)), 2)
        t_2n = min_wall(lambda: _fence(run_2n(operands)), 2)
    return max(t_2n - t_n, 1e-9) / steps


def min_wall(thunk, reps):
    """Best-of-``reps`` wall seconds of ``thunk()`` (min filters host
    jitter, which is strictly additive)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        thunk()
        best = min(best, time.perf_counter() - t0)
    return best


def grad_fold(grads):
    """Fold EVERY grad leaf into one scalar — XLA dead-code-eliminates
    unused backward outputs, so touching a single leaf would let it prune
    most of the backward pass and fake a speedup."""
    return sum(jnp.sum(g.astype(jnp.float32))
               for g in jax.tree_util.tree_leaves(grads))


def wall_breakdown(engine, batch, steps=10, warmup=3, scan_steps=6):
    """Wall-time attribution of ``engine``'s training step.

    Returns a dict of mean milliseconds:

    - ``train_step``: the full fused step via ``engine.train_batch``
      (fwd + bwd + grad flatten + optimizer + param cast)
    - ``fwd``: forward loss only, train=True (dropout live), scanned in
      one jit
    - ``fwd_bwd``: forward + backward (grads folded, no flatten/update),
      scanned in one jit
    - ``bwd_derived``: ``fwd_bwd − fwd``
    - ``cast_params``: master→module-dtype cast program
    - ``opt_flatten_derived``: ``train_step − fwd_bwd − cast_params``
      (grad flatten + optimizer update + residual step overhead)

    The engine's state advances by ``steps + warmup`` optimizer steps
    (donated buffers); profile a scratch engine, not a training run.
    """
    sharded = engine._shard_batch(batch)
    params = engine._forward_params()
    extra = engine._extra_kwargs()
    base_rng = engine._next_rng()

    # sub-programs FIRST: train_batch donates the master/opt/param buffers,
    # which would delete the arrays referenced by the scan operands below
    out = {}
    ops = (params, sharded, base_rng)

    def fwd(o, i):
        p, b, r = o
        return engine._loss_fn(p, b, rng=jax.random.fold_in(r, i),
                               train=True, **extra)

    out["fwd"] = timed_scan(fwd, ops, scan_steps, mesh=engine.mesh) * 1e3

    def fwd_bwd(o, i):
        p, b, r = o
        ri = jax.random.fold_in(r, i)
        loss, grads = jax.value_and_grad(
            lambda pp: engine._loss_fn(pp, b, rng=ri, train=True,
                                       **extra))(p)
        # small non-zero factor: XLA may fold a literal 0·x and then DCE
        # the whole backward
        return loss + 1e-30 * grad_fold(grads)

    out["fwd_bwd"] = timed_scan(fwd_bwd, ops, scan_steps,
                                mesh=engine.mesh) * 1e3
    out["bwd_derived"] = out["fwd_bwd"] - out["fwd"]

    if engine.zero_stage < 3 and engine._cast_params_fn is not None:
        master = engine.state["master"]
        with engine.mesh:
            out["cast_params"] = timed_loop(
                lambda: engine._cast_params_fn(master), steps, warmup) * 1e3
        del master
    else:
        out["cast_params"] = 0.0

    out["train_step"] = timed_loop(
        lambda: engine.train_batch(iter([batch])), steps, warmup) * 1e3
    out["opt_flatten_derived"] = (out["train_step"] - out["fwd_bwd"]
                                  - out["cast_params"])
    return out


def model_scope_breakdown(engine, scopes, steps=6, warmup=2):
    """Wall seconds for arbitrary model sub-scopes.

    ``scopes`` maps name -> ``fn(params, i) -> scalar`` (i = iteration
    index, for rng folding; any other arrays the scope needs must ride in
    closures over HOST data or in ``params`` — see ``timed_scan`` on jit
    constants).  Each scope is timed as fwd AND fwd+bwd (value_and_grad
    with every grad leaf folded), scanned inside one jit.  Returns
    ``{name: {"fwd": ms, "fwd_bwd": ms}}``.  Differences between nested
    scopes attribute wall time to the enclosing computation (e.g.
    ``full_loss − hidden`` = LM head + loss)."""
    params = engine._forward_params()
    out = {}
    for name, fn in scopes.items():
        fwd_ms = timed_scan(lambda p, i, fn=fn: fn(p, i), params, steps,
                            warmup, mesh=engine.mesh) * 1e3

        def fb(p, i, fn=fn):
            loss, grads = jax.value_and_grad(lambda pp: fn(pp, i))(p)
            return loss + 1e-30 * grad_fold(grads)

        fb_ms = timed_scan(fb, params, steps, warmup, mesh=engine.mesh) * 1e3
        out[name] = {"fwd": fwd_ms, "fwd_bwd": fb_ms}
    return out
