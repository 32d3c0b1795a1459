"""Wall-clock + throughput timers.

TPU analog of the reference's ``deepspeed/utils/timer.py``:
- ``SynchronizedWallClockTimer`` (reference ``:19-94``) — named timers whose
  start/stop fence outstanding device work.  The reference calls
  ``torch.cuda.synchronize()``; here the fence is draining the async XLA
  dispatch queue (``jax.block_until_ready`` has to be applied by callers on
  their live arrays; as a global fence we submit and block on a trivial
  computation, which orders after previously enqueued work on that device).
- ``ThroughputTimer`` (reference ``:97-163``) — samples/sec with warmup skip.
"""

import time

from .logging import log_dist, logger


def device_fence():
    """Block until previously dispatched device computations complete.

    A host round-trip (``device_get`` of a freshly dispatched
    computation): a fetched result cannot exist until everything queued
    before it (per-device dispatch is in order) has run.
    """
    try:
        import jax
        import jax.numpy as jnp

        jax.device_get(jnp.zeros(()) + 0)
    except Exception:  # dslint: disable=DSE502 -- best-effort fence; timers still run without a backend
        pass


class SynchronizedWallClockTimer:
    """Named timers with device fencing, matching the reference API."""

    class Timer:
        def __init__(self, name):
            self.name_ = name
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.time()

        def start(self, sync=True):
            assert not self.started_, f"{self.name_} timer has already been started"
            if sync:
                device_fence()
            self.start_time = time.time()
            self.started_ = True

        def stop(self, sync=True):
            assert self.started_, "timer is not started"
            if sync:
                device_fence()
            self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started_ = self.started_
            if self.started_:
                self.stop()
            elapsed_ = self.elapsed_
            if reset:
                self.reset()
            if started_:
                self.start()
            return elapsed_

        def mean(self, count):
            return self.elapsed(reset=False) / max(count, 1)

    def __init__(self):
        self.timers = {}

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        """Aggregate allocation stats over ALL local devices (summing —
        on a multi-chip host, device 0 alone understates the footprint by
        the local device count).  Shared implementation:
        ``profiling.memory.device_memory_summary``."""
        try:
            from ..profiling.memory import (device_memory_summary,
                                            format_memory_summary)

            return format_memory_summary(device_memory_summary())
        except Exception:
            return "mem stats unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        """Log named timers; ``ranks`` filters to those process indices
        (None = all, matching ``log_dist``) and ``memory_breakdown``
        appends the cross-device memory summary — both kwargs existed in
        the reference signature and were silently ignored here."""
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        if memory_breakdown:
            string += " | " + self.memory_usage()
        log_dist(string, ranks=ranks)


class ThroughputTimer:
    """Samples/sec with warm-up skipping (reference ``timer.py:97-163``)."""

    def __init__(self, batch_size, num_workers, start_step=2, steps_per_output=50, monitor_memory=False, logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = max(batch_size, 1)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.counted_steps = 0
        self._window_anchor = None
        self._window_anchor_step = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        if not self.initialized:
            # the fence is a tiny program of its own: compile it with the
            # first step's programs, not when the first measured window
            # opens ``start_step`` steps in (a compile in steady state)
            device_fence()
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            if self._window_anchor is None:
                # first measured window opens here, fenced so queued warmup
                # work is not billed to it
                device_fence()
                self._window_anchor = time.time()
                self._window_anchor_step = self.global_step_count
            self.start_time = time.time()

    def stop(self, report_speed=True):
        """Fencing is a host round-trip, so it happens only on reporting
        steps; durations are measured over whole fenced *windows* (time
        between consecutive fenced stops ÷ steps in between) — unfenced
        per-step times would only measure async dispatch."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        self.global_step_count += 1
        if self.start_time > 0:
            if (self.global_step_count % self.steps_per_output == 0
                    and self._window_anchor is not None):
                device_fence()
                now = time.time()
                window_steps = self.global_step_count - self._window_anchor_step
                window_time = now - self._window_anchor
                self.total_elapsed_time += window_time
                self.counted_steps += window_steps
                self._window_anchor = now
                self._window_anchor_step = self.global_step_count
                if report_speed and window_steps > 0 and window_time > 0:
                    avg = self.avg_samples_per_sec()
                    # before any counted window the running average is 0.0
                    # (not -inf); printing "RunningAvgSamplesPerSec=0.00"
                    # would be as misleading, so the field is omitted
                    avg_part = (f"RunningAvgSamplesPerSec={avg:.2f}, "
                                if avg > 0 else "")
                    self.logging(
                        f"{self.__class__.__name__}: epoch={self.epoch_count}/"
                        f"micro_step={self.micro_step_count}/"
                        f"global_step={self.global_step_count}, "
                        f"{avg_part}"
                        f"CurrSamplesPerSec={self.batch_size * self.num_workers * window_steps / window_time:.2f}"
                    )

    def avg_samples_per_sec(self):
        if self.counted_steps > 0 and self.total_elapsed_time > 0:
            samples_per_step = self.batch_size * self.num_workers
            avg_time_per_step = self.total_elapsed_time / self.counted_steps
            return samples_per_step / avg_time_per_step
        # no counted window yet: 0.0, not the reference's float("-inf") —
        # callers format this into logs and "-inf samples/sec" is noise
        return 0.0
