"""Serving observability plane: request-lifecycle tracing, SLO/goodput
accounting, and continuous-batching efficiency receipts.

Three layers, all riding the engine's EXISTING sync structure (the
serve loop's next-token ``device_get`` stays the only per-iteration
host sync — the device_get-counting test pins this with the full plane
armed):

1. **Request-lifecycle tracing.**  A trace id is minted once at submit
   (``ServingFrontend.submit`` for fleet serving, ``engine.submit`` for
   a bare engine) and threaded through admission, prefill, first token,
   the decode windows, requeue, and the terminal state.  The id
   survives ``Request.reset_for_requeue``, so a replica-death re-serve
   is ONE joined trace across replicas in the event stream.  Every
   phase record is a schema-versioned EVENT_SERVING event carrying
   ``trace``/``schema``/``t_mono`` (monotonic clock — orderable within
   a process, joinable by the doctor).

2. **Batching/KV efficiency metrics**, sampled ONLY at the
   steps_per_print cadence: batch-slot occupancy, token-budget
   utilization, padding-waste fraction per prefill bucket, the
   ``BlockAllocator`` pool occupancy + high-water mark, queue depth and
   admission-wait histograms.  Per-iteration bookkeeping is O(active)
   host arithmetic folded into loops the engine already runs.

3. **SLO + goodput.**  The ``inference.slo`` block (``ttft_ms``,
   ``per_token_ms``) defines what counts: *goodput* is tokens from
   SLO-meeting fetches vs raw throughput, attainment is the met
   fraction.  The high-rate per-token stream feeds the O(1) P²
   streaming quantile estimator (``telemetry.registry.quantiles``) —
   the algorithm-R reservoir histogram stays for the low-rate
   admission-wait stream.  With no SLO configured every token counts
   as good (goodput == raw throughput, attainment 1.0).

The cadence exporter :meth:`ServingObservability.export_serving_window`
is registered in dslint's DSH205 skew-export table: calling it from a
driver loop OUTSIDE a ``steps_per_print`` guard is a static lint error,
same contract as the latency/fingerprint exchanges.
"""

import itertools
import os
import time

from ..telemetry import events as TEL

# version stamp every serving phase record carries; bump when a kind's
# payload shape changes (the golden-schema test pins the current table)
SERVING_TRACE_SCHEMA_VERSION = 1

# kind -> required payload keys for the schema-versioned lifecycle
# records (on TOP of EVENT_SERVING's baseline ``kind`` key).  The
# golden-schema test validates emitted records against this table, so a
# dropped key is a test failure, not a silently-thinned artifact.
SERVING_PHASE_KEYS = {
    "submit": ("trace", "request", "schema", "t_mono", "queue_depth"),
    "admit": ("trace", "request", "schema", "t_mono", "wait_seconds",
              "prompt_tokens", "bucket", "blocks", "slot", "queue_depth"),
    "first_token": ("trace", "request", "schema", "t_mono",
                    "ttft_seconds", "prefill_seconds", "bucket"),
    "decode_window": ("schema", "t_mono", "iterations", "tokens",
                      "active_traces", "batch_occupancy",
                      "token_budget_utilization", "kv_used_blocks",
                      "kv_used_peak"),
    "slo": ("schema", "t_mono", "window_tokens", "goodput_tokens",
            "slo_attainment", "goodput_tokens_per_second",
            "tokens_per_second"),
    "finish": ("trace", "request", "schema", "t_mono", "reason",
               "generated_tokens", "latency_seconds"),
    "deadline": ("trace", "request", "schema", "t_mono",
                 "generated_tokens"),
    "requeue": ("trace", "request", "schema", "t_mono", "replica",
                "requeues", "backoff_secs"),
    "shed": ("trace", "request", "schema", "t_mono", "queue_depth",
             "max_queue_depth"),
}

_TRACE_COUNTER = itertools.count()


def mint_trace_id():
    """A process-unique lifecycle trace id.  Minted ONCE per request at
    submit; requeues and replica hops reuse it (that is the point)."""
    return f"trace-{os.getpid()}-{next(_TRACE_COUNTER)}"


class ServingObservability:
    """Per-engine serving observability state.

    Constructed unconditionally by the engine (every method is cheap
    host arithmetic and internally no-ops event/metric emission when
    telemetry is disabled).  The engine calls three hooks:

    - :meth:`note_prefill` — after the prefill's first-token fetch;
    - :meth:`note_decode` — after the decode iteration's batched fetch
      (sums on scalars the loop already holds; O(active) only with
      telemetry on);
    - :meth:`export_serving_window` — ONLY from the steps_per_print
      cadence block (DSH205-registered).
    """

    def __init__(self, engine):
        self.engine = engine
        self.telemetry = engine.telemetry
        icfg = engine.inference_config
        self.icfg = icfg
        self._slo_ttft = icfg.slo_ttft_ms / 1e3       # 0 = disabled
        self._slo_tok = icfg.slo_per_token_ms / 1e3   # 0 = disabled
        # padding waste per prefill bucket: prompt tokens vs padded
        # width actually computed (cumulative over the run)
        self._bucket_prompt = {b: 0 for b in icfg.prefill_buckets}
        self._bucket_padded = {b: 0 for b in icfg.prefill_buckets}
        # decode-window accumulators (reset at every cadence export)
        self._win_start = time.monotonic()
        self._win_iterations = 0
        self._win_tokens = 0
        self._win_good_tokens = 0
        self._win_active_sum = 0
        self._win_reserved_sum = 0
        self._win_live_blocks = [0] * len(self.engine.cache_groups)
        self._win_traces = set()
        # run-cumulative accumulators (the bench receipt)
        self._run_start = self._win_start
        self._cum_iterations = 0
        self._cum_tokens = 0
        self._cum_good_tokens = 0
        self._cum_active_sum = 0
        self._cum_reserved_sum = 0

    # -- helpers --------------------------------------------------------
    def _emit(self, kind, **data):
        if self.telemetry.enabled:
            self.telemetry.emit(
                TEL.EVENT_SERVING, step=self.engine.decode_iterations,
                kind=kind, schema=SERVING_TRACE_SCHEMA_VERSION,
                t_mono=time.monotonic(), **data)

    def slo_enabled(self):
        return bool(self._slo_ttft or self._slo_tok)

    # -- lifecycle hooks ------------------------------------------------
    def note_submit(self, request, queue_depth):
        """Submit-time phase record — the trace's first event."""
        self._emit("submit", trace=request.trace_id,
                   request=request.request_id, queue_depth=queue_depth)

    def note_prefill(self, request, now, prefill_seconds):
        """Post-prefill accounting: the admit + first_token phase
        records, the admission-wait histogram, the bucket padding-waste
        accumulators, and the TTFT leg of the SLO (the TTFT is not a
        per-token observation: ``serving/per_token_seconds`` holds the
        gaps between a request's tokens alone)."""
        sched = self.engine.scheduler
        wait = (request.admitted_at - request.submitted
                if request.admitted_at is not None else 0.0)
        ttft = now - request.submitted
        self._bucket_prompt[request.bucket] += len(request.prompt)
        self._bucket_padded[request.bucket] += request.bucket
        self._cum_tokens += 1
        self._win_tokens += 1
        good = not self._slo_ttft or ttft <= self._slo_ttft
        if good:
            self._cum_good_tokens += 1
            self._win_good_tokens += 1
        self._win_traces.add(request.trace_id)
        if not self.telemetry.enabled:
            return
        self._emit("admit", trace=request.trace_id,
                   request=request.request_id, wait_seconds=wait,
                   prompt_tokens=len(request.prompt),
                   bucket=request.bucket, blocks=len(request.blocks),
                   slot=request.slot, queue_depth=sched.queue_depth)
        self._emit("first_token", trace=request.trace_id,
                   request=request.request_id, ttft_seconds=ttft,
                   prefill_seconds=prefill_seconds, bucket=request.bucket)
        self.telemetry.counter("serving/admitted").inc()
        self.telemetry.histogram(
            "serving/admission_wait_seconds").observe(wait)

    def note_decode(self, before, gaps, live_blocks):
        """Per-iteration accounting on already-fetched scalars: window
        occupancy/budget sums, the KV blocks the iteration read in a
        layer of each cache group (``live_blocks``, a count a group) and
        the per-token SLO leg, judged on
        ``gaps`` — for each request of ``before`` the seconds since ITS
        previous token.  With telemetry on, also the per-token P²
        observations: O(active) host arithmetic, zero syncs; with it
        off nothing walks the requests."""
        n = len(before)
        self._win_iterations += 1
        self._cum_iterations += 1
        self._win_tokens += n
        self._cum_tokens += n
        self._win_active_sum += n
        self._cum_active_sum += n
        self._win_live_blocks = [a + b for a, b in zip(
            self._win_live_blocks, live_blocks)]
        reserved = self.engine.scheduler.reserved_tokens()
        self._win_reserved_sum += reserved
        self._cum_reserved_sum += reserved
        good = (sum(gap <= self._slo_tok for gap in gaps)
                if self._slo_tok else n)
        self._win_good_tokens += good
        self._cum_good_tokens += good
        if not self.telemetry.enabled:
            return
        q = self.telemetry.quantiles("serving/per_token_seconds")
        for request, gap in zip(before, gaps):
            self._win_traces.add(request.trace_id)
            q.observe(gap)

    def note_finish(self, request):
        self._emit(
            "finish", trace=request.trace_id, request=request.request_id,
            reason=request.finish_reason,
            generated_tokens=len(request.generated),
            latency_seconds=(request.finished_at - request.submitted
                             if request.finished_at is not None else None),
            queue_depth=self.engine.scheduler.queue_depth)
        if self.telemetry.enabled:
            self.telemetry.counter("serving/finished").inc()

    def note_deadline(self, request):
        self._emit("deadline", trace=request.trace_id,
                   request=request.request_id,
                   generated_tokens=len(request.generated),
                   queue_depth=self.engine.scheduler.queue_depth)
        if self.telemetry.enabled:
            self.telemetry.counter("serving/deadline_expired").inc()

    # -- padding waste --------------------------------------------------
    def padding_waste_by_bucket(self):
        """bucket -> wasted fraction of prefill compute (padded width
        beyond the prompt), cumulative over the run; buckets never used
        report None."""
        out = {}
        for b in self.icfg.prefill_buckets:
            padded = self._bucket_padded[b]
            out[b] = (1.0 - self._bucket_prompt[b] / padded
                      if padded else None)
        return out

    def padding_waste_fraction(self):
        padded = sum(self._bucket_padded.values())
        if not padded:
            return None
        return 1.0 - sum(self._bucket_prompt.values()) / padded

    # -- the cadence exporter (DSH205: print-cadence only) --------------
    def export_serving_window(self):
        """Close the current decode window: emit the ``decode_window``
        + ``slo`` phase records, set the occupancy/goodput gauges, and
        reset the window accumulators.  Callable ONLY from inside a
        ``steps_per_print`` guard — dslint's DSH205 skew-export table
        enforces this statically, same as the latency exchange."""
        if not self.telemetry.enabled:
            self._reset_window()
            return
        now = time.monotonic()
        window = max(now - self._win_start, 1e-9)
        icfg = self.icfg
        iters = self._win_iterations
        occupancy = (self._win_active_sum
                     / (iters * icfg.max_batch_slots) if iters else 0.0)
        budget_util = (self._win_reserved_sum
                       / (iters * icfg.token_budget) if iters else 0.0)
        allocator = self.engine.allocator
        self._emit("decode_window", iterations=iters,
                   tokens=self._win_tokens,
                   active_traces=sorted(self._win_traces),
                   batch_occupancy=occupancy,
                   token_budget_utilization=budget_util,
                   kv_used_blocks=allocator.used_blocks,
                   kv_used_peak=allocator.used_peak)
        attainment = (self._win_good_tokens / self._win_tokens
                      if self._win_tokens else 1.0)
        self._emit("slo", window_tokens=self._win_tokens,
                   goodput_tokens=self._win_good_tokens,
                   slo_attainment=attainment,
                   goodput_tokens_per_second=self._win_good_tokens / window,
                   tokens_per_second=self._win_tokens / window)
        gauge = self.telemetry.gauge
        gauge("serving/batch_occupancy").set(occupancy)
        gauge("serving/token_budget_utilization").set(budget_util)
        gauge("serving/kv_used_blocks").set(float(allocator.used_blocks))
        gauge("serving/kv_used_peak").set(float(allocator.used_peak))
        # blocks the paged decode kernel walked over the blocks a
        # full-table gather reads (slots x max_blocks_per_seq): the
        # share of the reservation that is live context
        gauge("serving/kv_live_block_share").set(
            self._win_live_blocks[0]
            / (iters * icfg.max_batch_slots * icfg.max_blocks_per_seq)
            if iters else 0.0)
        # the same live blocks as bytes: of each of the model's buffers
        # (GPT-2: serving/k_cache_live_bytes and v_cache's; a latent-
        # attention model: serving/latent_cache_live_bytes), and of each
        # cache group whole — serving/<group>_cache_live_bytes where the
        # group follows the context, serving/<group>_cache_bytes where a
        # request holds a fixed ring (K-EXAONE: full_cache_live_bytes,
        # window_cache_bytes)
        block_bytes = self.engine.cache_block_bytes
        for group, blocks in zip(self.engine.cache_groups,
                                 self._win_live_blocks):
            live = blocks / iters if iters else 0.0
            for name in group.buffers:
                gauge(f"serving/{name}_live_bytes").set(
                    block_bytes[name] * live)
            gauge(f"serving/{group.name}_cache_"
                  + ("live_bytes" if group.pages is None else "bytes")).set(
                sum(block_bytes[name] for name in group.buffers) * live)
        gauge("serving/slo_attainment").set(attainment)
        gauge("serving/goodput_tokens_per_second").set(
            self._win_good_tokens / window)
        waste = self.padding_waste_fraction()
        if waste is not None:
            gauge("serving/padding_waste_fraction").set(waste)
        self._reset_window(now)

    def _reset_window(self, now=None):
        self._win_start = now if now is not None else time.monotonic()
        self._win_iterations = 0
        self._win_tokens = 0
        self._win_good_tokens = 0
        self._win_active_sum = 0
        self._win_reserved_sum = 0
        self._win_live_blocks = [0] * len(self.engine.cache_groups)
        self._win_traces = set()

    # -- the receipt ----------------------------------------------------
    def receipt(self):
        """Run-cumulative occupancy/SLO receipt — merged into
        ``engine.serving_receipt()``, which the dryrun leg quotes."""
        icfg = self.icfg
        iters = self._cum_iterations
        wall = max(time.monotonic() - self._run_start, 1e-9)
        return {
            "batch_occupancy_mean": (
                self._cum_active_sum / (iters * icfg.max_batch_slots)
                if iters else None),
            "token_budget_utilization": (
                self._cum_reserved_sum / (iters * icfg.token_budget)
                if iters else None),
            "kv_block_occupancy_peak": (
                self.engine.allocator.used_peak
                / self.engine.allocator.capacity),
            "padding_waste_fraction": self.padding_waste_fraction(),
            "goodput_tokens": self._cum_good_tokens,
            "goodput_tokens_per_second": self._cum_good_tokens / wall,
            "slo_attainment": (self._cum_good_tokens / self._cum_tokens
                               if self._cum_tokens else 1.0),
            "slo_enabled": self.slo_enabled(),
        }
