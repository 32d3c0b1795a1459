"""Persistent XLA compile-cache wiring.

At offload scale compiles dominate process start, and every fresh
process — bench reruns, ``--max-restarts`` respawns after a watchdog exit
85, ``auto_resume`` restarts — pays them again for byte-identical
programs.  JAX ships a persistent compile cache keyed on the lowered
module + compile options (and on the cache directory's own path, so a
directory that moves never hits); this module decides where it lives.

One rule, for every process of the framework (training and serving
engines, ``benchmarks.run``, ``chip_smoke.py``, the test harness):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax uses it natively and
  this module sets no directory in code;
- where it is not, the cache is ``<checkout>/.jax_cache`` — a fixed path
  next to the package, never one derived from the working directory, a
  run directory, a pid or the time — unless the config names an explicit
  ``compilation.cache_dir``, the one override;
- ``compilation.cache: false`` leaves this module's hands off entirely.

A child process resolves the same directory by the same rule, so nothing
is exported to it; ``launcher/launch.py --compile-cache-dir`` sets the
variable for its children from the jax-free side.
"""

import os
import sys
import threading
import time

from ...utils.logging import logger

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def default_cache_dir():
    """``<checkout>/.jax_cache``: the directory that holds the
    ``deepspeed_tpu`` package, whatever the working directory."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def active_cache_dir():
    """Directory of this process's compile cache as jax sees it (the
    environment variable is the default of the jax option), else where
    the rule above would put it.  The flash-attention block tuner keeps
    its winners beside the compiled programs they belong to."""
    import jax

    return jax.config.jax_compilation_cache_dir or default_cache_dir()


def configure_persistent_cache(config):
    """Apply the ``"compilation"`` block to this process's jax config.

    Returns the active cache directory, or None when ``cache`` is false.
    Idempotent; call before the first jit compile (the engines call it
    first thing in their constructors).
    """
    import jax

    if not config.cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        logger.debug("compile cache: JAX_COMPILATION_CACHE_DIR=%s", env_dir)
        return env_dir
    cache_dir = (os.path.abspath(config.cache_dir) if config.cache_dir
                 else default_cache_dir())
    if jax.config.jax_compilation_cache_dir == cache_dir:
        return cache_dir  # an earlier engine (or the test harness) set it
    try:
        # non-fatal by design: this runs on EVERY engine construction,
        # and a read-only checkout must degrade to uncached compilation,
        # not fail deepspeed.initialize.  Loud single error, not a
        # silent pass (dslint DSE5xx contract).
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        logger.error("persistent XLA compile cache unavailable at %s "
                     "(%s); continuing with uncached compilation",
                     cache_dir, e)
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      int(config.min_entry_size_bytes))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(config.min_compile_secs))
    logger.info("persistent XLA compile cache at %s (min entry "
                "%d bytes, min compile %.3gs)", cache_dir,
                config.min_entry_size_bytes, config.min_compile_secs)
    return cache_dir


# jax.monitoring event names this subsystem consumes (stable across the
# supported jax range; see _src/compiler.py / _src/compilation_cache.py)
EVENT_CACHE_HIT = "/jax/compilation_cache/cache_hits"
EVENT_CACHE_MISS = "/jax/compilation_cache/cache_misses"
DURATION_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
DURATION_TRACE = "/jax/core/compile/jaxpr_trace_duration"
DURATION_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
DURATION_CACHE_RETRIEVAL = (
    "/jax/compilation_cache/cache_retrieval_time_sec")


# jax's listener registry is process-global with no unregister API
# across the supported range, so ONE listener pair fans out to the
# live CompileStats instances (same pattern as telemetry_bridge.py) —
# repeated construct/close cycles must not accumulate dead closures in
# jax's registry, each re-walked on every compile event forever.
_stats_lock = threading.Lock()
_stats_sinks = []
_stats_installed = False


def _stats_on_event(event, **kw):
    with _stats_lock:
        sinks = list(_stats_sinks)
    for s in sinks:
        s._on_event(event)


def _stats_on_duration(event, duration, fun_name=None, **kw):
    with _stats_lock:
        sinks = list(_stats_sinks)
    for s in sinks:
        s._on_duration(event, duration, fun_name)


class CompileStats:
    """Host-only compile accounting off ``jax.monitoring`` listeners.

    ``cold_secs`` is the compile-request wall actually paid this
    process — a full backend compile on a cache miss, collapsing to the
    cache-load wall on a hit (jax's backend-compile duration event wraps
    the whole compile-or-get-cached call); ``warm_secs`` isolates the
    retrieval time of the hits.  A fully warm process therefore shows
    ``cold_secs`` collapsed to ~``warm_secs`` with ``hits == programs``
    — the cold/warm receipt the bench JSON records.  ``by_program`` splits
    ``cold_secs`` by the jitted function's name (``train_step``,
    ``decode``, ``prefill``, ...), which jax passes with the event.

    What a compile request costs BEFORE the backend — and all that a
    retrace which then hits the persistent cache costs — is tracing the
    Python function to a jaxpr and lowering that to an MLIR module:
    ``trace_secs`` and ``lower_secs``.  A jitted function called inside
    another is traced inside its caller's trace and reports its own
    duration too; ``trace_secs`` counts such seconds once (the outermost
    trace's), ``trace_secs_by_program`` gives each function's own
    seconds, callees included, under its bare name (``train_step``) and
    ``traces_by_program`` how often it was traced: more than once for
    one program is a retrace.
    """

    def __init__(self):
        global _stats_installed
        self.hits = 0
        self.misses = 0
        self.cold_secs = 0.0
        self.warm_secs = 0.0
        self.programs = 0
        self.by_program = {}
        self.trace_secs = 0.0
        self.lower_secs = 0.0
        self.traces_by_program = {}
        self.trace_secs_by_program = {}
        self._open_traces = []   # (ended at, seconds) of outermost traces
        import jax.monitoring as monitoring

        with _stats_lock:
            _stats_sinks.append(self)
            if _stats_installed:
                return
            _stats_installed = True
        monitoring.register_event_listener(_stats_on_event)
        monitoring.register_event_duration_secs_listener(_stats_on_duration)

    def _on_event(self, event):
        if event == EVENT_CACHE_HIT:
            self.hits += 1
        elif event == EVENT_CACHE_MISS:
            self.misses += 1

    def _on_duration(self, event, duration, fun_name=None):
        if event == DURATION_BACKEND_COMPILE:
            self.cold_secs += float(duration)
            self.programs += 1
            self.by_program[fun_name] = (
                self.by_program.get(fun_name, 0.0) + float(duration))
        elif event == DURATION_CACHE_RETRIEVAL:
            self.warm_secs += float(duration)
        elif event == DURATION_LOWER:
            self.lower_secs += float(duration)
        elif event == DURATION_TRACE:
            self._on_trace(float(duration), fun_name)

    def _on_trace(self, duration, fun_name):
        self.traces_by_program[fun_name] = (
            self.traces_by_program.get(fun_name, 0) + 1)
        self.trace_secs_by_program[fun_name] = (
            self.trace_secs_by_program.get(fun_name, 0.0) + duration)
        # the event comes as a trace ends: traces that ended after this
        # one began ran inside it, and their seconds are in its own
        now = time.perf_counter()
        began = now - duration
        while self._open_traces and self._open_traces[-1][0] > began:
            self.trace_secs -= self._open_traces.pop()[1]
        self._open_traces.append((now, duration))
        self.trace_secs += duration

    def close(self):
        """Stop counting; the first close logs what was counted."""
        with _stats_lock:
            if self not in _stats_sinks:
                return
            _stats_sinks.remove(self)
        logger.info("compile stats: %s", self.summary())

    def summary(self):
        """The counters on one line, the attention kernels' trace cache
        (``flash_attention.trace_stats``) beside them where the process
        has loaded the kernels."""
        line = (f"{self.programs} programs, cache {self.hits} hits / "
                f"{self.misses} misses, compile {self.cold_secs:.2f} s "
                f"(retrieval {self.warm_secs:.2f}), trace "
                f"{self.trace_secs:.2f} s, lower {self.lower_secs:.2f} s")
        kernels = sys.modules.get(
            "deepspeed_tpu.ops.transformer.flash_attention")
        if kernels is not None:
            traces = kernels.trace_stats()
            line += (f"; flash_attention {traces['geometries_traced']} "
                     f"geometries traced, {traces['calls_from_cache']} "
                     "calls from the cache")
        return line

    def as_dict(self):
        return {"compile_cache_hits": self.hits,
                "compile_cache_misses": self.misses,
                "compile_seconds_cold": round(self.cold_secs, 3),
                "compile_seconds_warm": round(self.warm_secs, 3),
                "compile_programs": self.programs}
