"""The ``--trace 1`` side of a run: a ``jax.profiler`` trace of the last
part of the window, with the harness's own host spans in it."""

import shutil
import tempfile
import time

import jax

from .trace import reducers, xplane


def span(name, **kw):
    """A host span on the profiler's clock (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name, **kw)


class DeviceTrace:
    """``start()`` … ``stop()`` around the traced part; ``window_s`` is the
    host-clock length between the two, ``trace`` the parsed file."""

    def __init__(self, enabled):
        self.enabled = enabled
        self.trace = None
        self.window_s = None
        self._dir = None

    def start(self):
        if not self.enabled:
            return
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the harness spans are TraceMes
        options.host_tracer_level = 1
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._t0 = time.perf_counter()

    def stop(self):
        """Call after the traced work is fenced."""
        if not self.enabled:
            return
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        try:
            self.trace = xplane.read(xplane.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def device_fields(self):
        """``busy_s`` and ``window_s`` for the result line's ``device``."""
        if self.trace is None:
            return {}
        busy = reducers.busy_seconds(self.trace)
        return {"busy_s": busy if busy is not None else 0.0,
                "window_s": self.window_s}

    def breakdown(self):
        return None if self.trace is None else reducers.breakdown(self.trace)
