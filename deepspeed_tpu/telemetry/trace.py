"""Step tracing: program spans + on-demand device traces.

:func:`program_span` is what ``TelemetryManager.span`` returns: a
``jax.profiler.TraceAnnotation`` named ``ds:<name>`` — the program's host
phases on the timeline of ANY profiler session, beside the device's
operations — and, with a :class:`StepTracer`, the same span in its file.

Two complementary tools behind it:

- :class:`StepTracer` — host-side phase spans (batch fetch, dispatch,
  the one batched ``device_get``, checkpoint snapshot/commit, rollback
  restore) written in the Chrome Trace Event "JSON Array Format" that
  chrome://tracing and Perfetto load directly.  Events stream to disk as
  they complete — the format tolerates a missing ``]``, so a crashed or
  preempted run's trace is still loadable.  Span cost is two
  ``time.perf_counter()`` calls and one dict append: no device access,
  no syncs, safe on the step critical path.

- :class:`DeviceTraceTrigger` — on-demand ``jax.profiler`` device traces
  with a **bounded duration**.  A TPU profile is far too heavy to leave
  on, but the interesting step is never the one you planned for: touch
  the trigger file (``<run_dir>/device_trace.trigger``) — or send
  ``SIGUSR2`` when the engine could install the handler — and the next
  :meth:`poll` starts ``jax.profiler.start_trace`` into the run dir,
  stopping automatically after ``max_secs``.  Polling is one
  ``os.path.exists`` per step (only when tracing is configured).  When
  it stops a trace the engine handed it its ``program_scopes`` for, the
  trace is reduced by the program's own scopes (:mod:`.scopes`) on a
  worker thread: ``<run_dir>/device_trace/scopes.json`` and one log line
  of the ten largest.
"""

import json
import os
import threading
import time

import jax

from ..utils.logging import logger

TRACE_FILE_PREFIX = "trace-"
TRACE_FILE_SUFFIX = ".json"
DEVICE_TRACE_TRIGGER_FILE = "device_trace.trigger"
DEVICE_TRACE_DIR = "device_trace"
# every program span's name in a jax.profiler capture starts with this
PROGRAM_SPAN_PREFIX = "ds:"


def trace_filename(rank):
    return f"{TRACE_FILE_PREFIX}rank{rank}{TRACE_FILE_SUFFIX}"


def program_span(tracer, name, args):
    """One program span, two sinks.  Always a
    ``jax.profiler.TraceAnnotation`` named ``ds:<name>``: it lands on the
    host plane of whatever profiler session is running (the benchmark's
    ``--trace 1``, an on-demand device trace, a TensorBoard capture), on
    the clock the device planes share, and costs under a microsecond
    when no session runs.  With a ``tracer`` (``telemetry.trace`` on),
    also one complete event in its Chrome-trace file."""
    annotation = jax.profiler.TraceAnnotation(PROGRAM_SPAN_PREFIX + name,
                                              **args)
    if tracer is None:
        return annotation
    return _Span(tracer, name, args, annotation)


class _Span:
    """The annotation and one complete ("ph": "X") StepTracer event."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer, name, args, annotation):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._t0 = 0.0
        self._annotation = annotation

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracer._record(self._name, self._t0, time.perf_counter(),
                             self._args)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


class StepTracer:
    """Streams Chrome trace events for one process to
    ``<run_dir>/trace-rank<k>.json``.

    Thread-safe (checkpoint-writer spans land from their own threads,
    tagged with that thread's id so Perfetto draws them on separate
    tracks).  ``max_events`` bounds file growth on long runs: past it the
    tracer drops new spans and says so once.
    """

    def __init__(self, run_dir, rank=0, max_events=200000):
        self.rank = rank
        self.max_events = int(max_events)
        # RLock: the preemption handler's flush may interrupt a frame
        # already holding this lock on the main thread
        self._lock = threading.RLock()
        self._count = 0
        self._dropped = 0
        self._clock0 = time.perf_counter()
        os.makedirs(str(run_dir), exist_ok=True)
        self.path = os.path.join(str(run_dir), trace_filename(rank))
        self._f = open(self.path, "w", encoding="utf-8")
        self._f.write("[\n")
        # process metadata so merged multi-rank traces label their tracks
        self._meta("process_name", {"name": f"rank {rank}"})

    def _meta(self, name, args):
        self._write({"name": name, "ph": "M", "pid": self.rank,
                     "tid": threading.get_ident() % 2**31, "args": args})

    def _write(self, event):
        try:
            self._f.write(json.dumps(event) + ",\n")
        except (OSError, ValueError) as e:
            logger.error("step tracer %s failed (%s); disabling",
                         self.path, e)
            self._f = None

    def _record(self, name, t0, t1, args):
        with self._lock:
            if self._f is None:
                return
            if self._count >= self.max_events:
                self._dropped += 1
                if self._dropped == 1:
                    logger.warning(
                        "step tracer hit max_events=%d; dropping further "
                        "spans (raise telemetry.trace_max_events)",
                        self.max_events)
                return
            self._count += 1
            event = {"name": name, "ph": "X", "pid": self.rank,
                     "tid": threading.get_ident() % 2**31,
                     "ts": (t0 - self._clock0) * 1e6,
                     "dur": (t1 - t0) * 1e6}
            if args:
                event["args"] = args
            self._write(event)

    def span(self, name, **args):
        """``with tracer.span("dispatch", step=n): ...``"""
        return program_span(self, name, args)

    def instant(self, name, **args):
        """Zero-duration marker (anomalies, rollbacks, commits)."""
        now = time.perf_counter()
        self._record(name, now, now, args)

    def complete(self, name, t0, t1, **args):
        """Record an already-finished span (``perf_counter`` endpoints).
        For spans observed post-hoc — e.g. compile durations reported by
        jax.monitoring listeners after the compile returned — where a
        ``with span():`` block never existed."""
        self._record(name, t0, t1, args)

    def flush(self):
        with self._lock:
            if self._f is not None:
                try:
                    self._f.flush()
                except OSError as e:
                    logger.error("step tracer flush failed: %s", e)
                    self._f = None

    def close(self):
        with self._lock:
            if self._f is None:
                return
            try:
                # the trailing comma is legal in the JSON Array Format;
                # close the array anyway so strict json.load works too
                self._f.write("{}]\n")
                self._f.flush()
                self._f.close()
            except (OSError, ValueError) as e:
                logger.warning("step tracer close failed: %s", e)
            self._f = None


class DeviceTraceTrigger:
    """Trigger-file-gated, duration-bounded ``jax.profiler`` traces.

    ``poll(step)`` is called once per completed engine step:

    - trigger file present and no trace running → start a device trace
      into ``<run_dir>/device_trace/`` and delete the trigger (one
      touch, one trace);
    - trace running for more than ``max_secs`` → stop it and, where the
      caller gave ``program_scopes`` (an engine's method of that name),
      reduce it by scope into ``device_trace/scopes.json``.

    Everything is best-effort with loud logging: profiling must never
    take training down.
    """

    # stat the trigger file only every Nth poll: run dirs often live on
    # network filesystems (GCS-fuse/NFS) where a per-step stat would put
    # a network round-trip on the hot path; a few steps of trigger
    # latency is irrelevant for a human-touched file.  Deadline checks
    # (stopping an ACTIVE trace) still run every poll — they are a
    # time.monotonic compare, no I/O.
    CHECK_EVERY = 10

    def __init__(self, run_dir, trigger_path=None, max_secs=10.0,
                 check_every=CHECK_EVERY):
        self.run_dir = str(run_dir)
        self.trigger_path = trigger_path or os.path.join(
            self.run_dir, DEVICE_TRACE_TRIGGER_FILE)
        self.out_dir = os.path.join(self.run_dir, DEVICE_TRACE_DIR)
        self.max_secs = float(max_secs)
        self.check_every = max(1, int(check_every))
        self._polls = 0
        self._deadline = None
        self._signal_flag = False
        self._reducer = None

    def request(self):
        """Programmatic trigger (e.g. from a SIGUSR2 handler)."""
        self._signal_flag = True

    @property
    def active(self):
        return self._deadline is not None

    def poll(self, step=None, program_scopes=None):
        """Start/stop the device trace as the trigger + deadline dictate;
        returns True while a trace is running.  ``program_scopes``: a
        callable that gives the engine's scope maps, called (off this
        thread) only when a trace has just stopped."""
        if self._deadline is not None:
            if time.monotonic() >= self._deadline:
                self._stop(step)
                if program_scopes is not None:
                    self._reducer = threading.Thread(
                        target=self._reduce_by_scope,
                        args=(program_scopes,), daemon=True,
                        name="device-trace-scopes")
                    self._reducer.start()
            return self._deadline is not None
        self._polls += 1
        if not self._signal_flag and self._polls % self.check_every:
            return False
        if self._signal_flag or os.path.exists(self.trigger_path):
            self._signal_flag = False
            try:
                os.remove(self.trigger_path)
            except OSError:
                # signal-triggered, or a concurrent rank won the unlink;
                # either way the trace itself still starts
                logger.info("device trace trigger file already gone")
            self._start(step)
        return self._deadline is not None

    def _start(self, step):
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            jax.profiler.start_trace(self.out_dir)
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            logger.error("device trace start failed: %s", e)
            return
        self._deadline = time.monotonic() + self.max_secs
        logger.info("device trace started at step %s into %s (max %.1fs)",
                    step, self.out_dir, self.max_secs)

    def _stop(self, step):
        try:
            jax.profiler.stop_trace()
            logger.info("device trace stopped at step %s; load %s in "
                        "Perfetto/TensorBoard", step, self.out_dir)
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            logger.error("device trace stop failed: %s", e)
        self._deadline = None

    def _reduce_by_scope(self, program_scopes):
        """The stopped trace by the program's scopes: ``scopes.json``
        beside it and the ten largest in the log.  The one place the
        program reads an ``.xplane.pb``."""
        from . import scopes

        try:
            reduced = scopes.write_scopes_json(self.out_dir,
                                               program_scopes())
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            logger.error("device trace by scope failed: %s", e)
            return
        if reduced is None:
            logger.warning("device trace by scope: no .xplane.pb under %s",
                           self.out_dir)
            return
        busy = sum(p["seconds"] for p in reduced.values())
        unplaced = sum(p["unplaced_s"] for p in reduced.values())
        logger.info(
            "device trace by scope (%s; %.3f s of operations, %.1f%% in no "
            "scope): %s", os.path.join(self.out_dir, "scopes.json"), busy,
            100.0 * unplaced / busy if busy else 0.0,
            ", ".join(f"{module}:{scope or '-'}{'.' + d if d else ''} "
                      f"{seconds:.4f}"
                      for module, scope, d, seconds in
                      scopes.largest(reduced)))

    def wait(self, timeout=None):
        """Join the reduction of the last stopped trace, if one runs."""
        if self._reducer is not None:
            self._reducer.join(timeout)

    def close(self):
        if self._deadline is not None:
            self._stop(None)
        self.wait(timeout=60.0)
