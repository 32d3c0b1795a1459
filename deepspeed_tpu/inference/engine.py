"""InferenceEngine: continuous-batching serving over a paged cache,
priced and verified by the training-side toolchain.

The engine owns scheduling, blocks, donation and the ``ds:`` spans; the
served model (``inference/model.py`` has the interface) says what cache
buffers a layer keeps and builds the programs over them.

Program split (all shapes static, all programs ledgered):

- ``serve_prefill_<bucket>`` — one per declared prefill bucket, compiled
  on first use; cache buffers donated.
- ``serve_decode`` — ONE fixed-width program for the whole serve; cache
  buffers donated, so the per-token append is one in-place scatter a
  layer and buffer that XLA aliases onto the input allocation, and the paged
  attention kernel reads the live blocks of that same buffer
  (``engine.verify_programs()`` proves the ``input_output_alias``
  materialized — DSP601; a silently-copied cache is the classic decode
  perf bug).

Observability rides the training machinery unchanged: the
MemoryLedger/CommLedger AOT hook records every serve program's memory
analysis + HLO walk at compile time, the ProgramDumper lands
``<run_dir>/programs/serve_*.{hlo,json}`` sidecars for the offline
verifier, decode iterations feed a StepLatencyRing for the attribution
doctor, and EVENT-stream telemetry narrates admissions / finishes /
queue depth.

The serve loop keeps ONE program in flight.  A program's sampled tokens
stay on the device as the next decode's input (the decode's output
array, with each prefill's first token put into its lane by the prefill
program itself), the block tables live there too and are sent again only
when a slot's grant changes, and the host reads a program's outputs only
after the NEXT decode has been enqueued: the device runs iteration k
while the host reads iteration k-1, books it and prepares k+1.  The one
batched ``device_get`` a ``step()`` makes is its ONLY host sync —
telemetry and the health plane add zero (the device_get-counting tests
pin this).  What follows for a caller is in :meth:`InferenceEngine.step`.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..module_inject.replace_module import cast_weights
from ..parallel.mesh import current_platform
from ..profiling.comm import CommLedger, SERVE_DECODE_PROGRAM
from ..profiling.memory import MemoryLedger
from ..profiling.step_profiler import StepLatencyRing
from ..runtime import constants as C
from ..runtime.compilation import (DeepSpeedCompilationConfig,
                                   configure_persistent_cache)
from ..telemetry import events as TEL
from ..telemetry.config import DeepSpeedTelemetryConfig
from ..telemetry.manager import TelemetryManager
from ..utils.logging import logger
from .config import DeepSpeedInferenceConfig
from .kv_cache import NULL_BLOCK, BlockAllocator, init_cache_buffers
from .observability import ServingObservability, mint_trace_id
from .scheduler import ContinuousBatchScheduler, Request

# one string shared with the step pricer (profiling/comm.py), so the
# live receipts and the offline doctor name the same step program
DECODE_PROGRAM = SERVE_DECODE_PROGRAM


def prefill_program_name(bucket):
    return f"serve_prefill_{int(bucket)}"


class _Enqueued:
    """One enqueued program whose outputs the host has not read: the
    device ``out`` dict, the ``(request, block grant)`` each lane was
    dispatched for, the clock at its enqueue and, for a decode, the KV
    blocks it reads in a layer of each cache group (None marks a
    prefill)."""

    __slots__ = ("out", "lanes", "enqueued_at", "live_blocks")

    def __init__(self, out, lanes, enqueued_at, live_blocks=None):
        self.out = out
        self.lanes = lanes
        self.enqueued_at = enqueued_at
        self.live_blocks = live_blocks

    @property
    def is_decode(self):
        return self.live_blocks is not None


class InferenceEngine:
    """Serve a model with continuous batching.

    ``model`` exposes ``.config`` (``max_position_embeddings``) and
    ``.serving()``, its side of the served-model interface
    (``inference/model.py``): :class:`~deepspeed_tpu.models.gpt2.
    GPT2LMHeadTPU`, :class:`~deepspeed_tpu.models.deepseek_v2.
    DeepseekV2ForServing`.  ``params`` is its parameter pytree (for an HF
    Flax GPT-2 checkpoint see :meth:`from_hf_gpt2`); leaves already in
    the serving dtype are taken as they are, not copied, and
    ``engine.params`` is what ``serving.prepare_params`` made of it, once
    (:meth:`_prepare_params`).  ``config`` is
    the usual DeepSpeed config dict; the ``inference`` block is
    DSC4xx-schema-validated like every other section.
    """

    def __init__(self, model, params, config=None):
        param_dict = dict(config or {})
        self._validate_config(param_dict)
        self.inference_config = DeepSpeedInferenceConfig(param_dict)
        icfg = self.inference_config
        # persistent compile cache BEFORE the first jit, by the same rule
        # as the training engine: the decode program of a 36-layer model
        # compiles for tens of seconds, and a respawned replica must not
        # pay it again
        self._compile_cache_dir = configure_persistent_cache(
            DeepSpeedCompilationConfig(param_dict))
        self.model = model
        mc = model.config
        assert mc.max_position_embeddings >= icfg.max_seq_len, (
            f"inference.max_seq_len ({icfg.max_seq_len}) exceeds the "
            f"model's max_position_embeddings "
            f"({mc.max_position_embeddings})")
        self.steps_per_print = int(param_dict.get(
            C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        serving = self.serving = model.serving()
        if icfg.weights_dtype == "bfloat16":
            params = cast_weights(params, jnp.bfloat16)
        self.params = self._prepare_params(jax.device_put(params))
        cache_dtype = (jnp.bfloat16 if icfg.weights_dtype == "bfloat16"
                       else jnp.float32)
        if current_platform() == "tpu":
            # a geometry the decode kernel cannot tile fails here, at
            # construction, never by a silent second path
            serving.check_tpu_geometry(icfg)
        # a pool, its buffers and a block table for every cache group the
        # model names (one, for a model whose layers all cache the whole
        # context); the programs take the buffers as one flat tuple in
        # cache_buffers' order and the tables as a tuple, one a group
        groups = self.cache_groups = tuple(serving.cache_groups(icfg))
        buffers = serving.cache_buffers(icfg)
        assert list(buffers) == [n for g in groups for n in g.buffers]
        self.allocators = [BlockAllocator(g.num_blocks(icfg), g.pages)
                           for g in groups]
        self._caches = tuple(
            cache for g in groups for cache in init_cache_buffers(
                g.layers, g.num_blocks(icfg), icfg.kv_block_size,
                tuple(g.buffers.values()), dtype=g.dtype or cache_dtype))
        # bytes one live block holds in each buffer (the live-bytes gauges)
        self.cache_block_bytes = {
            name: g.layers * icfg.kv_block_size * row
            * jnp.dtype(g.dtype or cache_dtype).itemsize
            for g in groups for name, row in g.buffers.items()}
        # the first group's pool is the one the occupancy gauges follow
        self.allocator = self.allocators[0]
        self.scheduler = ContinuousBatchScheduler(icfg, self.allocators)

        # -- telemetry + ledgers (the training engine's wiring, reused) --
        self.telemetry_config = DeepSpeedTelemetryConfig(param_dict)
        self.telemetry = TelemetryManager(self.telemetry_config,
                                          rank=jax.process_index())
        from ..profiling.config import DeepSpeedProfilingConfig

        profiling_config = DeepSpeedProfilingConfig(param_dict)
        tel_on = self.telemetry.enabled
        comm_on = profiling_config.comm_ledger_enabled(tel_on)
        mem_on = profiling_config.memory_ledger_enabled(tel_on)
        self.comm_ledger = CommLedger(
            enabled=comm_on, telemetry=self.telemetry,
            mesh_axes={"data": 1})
        self.comm_ledger.overlap_context_fn = self.program_verify_context
        dump_on = profiling_config.program_dump_enabled(comm_on)
        self.memory_ledger = MemoryLedger(
            enabled=mem_on or comm_on or dump_on,
            telemetry=self.telemetry, comm_ledger=self.comm_ledger,
            record_memory=mem_on)
        if dump_on and self.telemetry.run_dir:
            from ..profiling.verify import ProgramDumper

            self.memory_ledger.dumper = ProgramDumper(
                self.telemetry.run_dir, rank=jax.process_index(),
                context_fn=self.program_verify_context,
                donation_fn=lambda name: self._donation_specs.get(name))

        # -- compiled programs (argument 1, the tuple of cache buffers,
        # donated everywhere) ---------------------------------------------
        self._donation_specs = {DECODE_PROGRAM: (1,)}
        self._decode = self.memory_ledger.wrap(
            DECODE_PROGRAM,
            jax.jit(serving.build_decode(icfg), donate_argnums=(1,)))
        self._prefills = {}
        for bucket in icfg.prefill_buckets:
            name = prefill_program_name(bucket)
            self._donation_specs[name] = (1,)
            prefill = serving.build_prefill(icfg, bucket)
            # a device trace names a compiled program after its function:
            # jit_prefill_<bucket>, so that the buckets' runs (and their
            # instructions, which share names) can be told apart
            prefill.__name__ = prefill.__qualname__ = f"prefill_{bucket}"
            self._prefills[bucket] = self.memory_ledger.wrap(
                name, jax.jit(prefill, donate_argnums=(1,)))
        # the decode program's host tables, kept between iterations: a
        # slot's row changes only when its request does
        self._tables = [np.full(
            (icfg.max_batch_slots, g.table_width(icfg)), NULL_BLOCK,
            np.int32) for g in groups]
        self._table_owner = [None] * icfg.max_batch_slots
        # ...and their copy on the device, made anew when a row changed
        self._tables_dev = None
        # the next decode's input tokens, on the device: the last
        # decode's output with the prefills' first tokens put into their
        # lanes by the prefill programs themselves
        self._next_tokens = jnp.zeros((icfg.max_batch_slots,), jnp.int32)
        # programs enqueued whose outputs the host has not read (one
        # program deep: a step reads everything enqueued before its own
        # decode), and the weight fingerprint enqueued with them
        self._unread = []
        self._fingerprint_dev = None
        self._last_read_at = 0.0
        # the scalar counters the model's decode program last reported
        self.model_counters = {}

        self._step_latencies = StepLatencyRing()
        self._driver_latencies = StepLatencyRing()
        # the clock of every per-token stamp (a test puts its own here)
        self._clock = time.monotonic
        self.decode_iterations = 0
        # of those, the iterations enqueued while the previous one's
        # outputs were still unread (serving/enqueued_ahead_share)
        self.decodes_enqueued_ahead = 0
        self._sampled_decodes = self._sampled_ahead = 0
        # the serving observability plane: lifecycle tracing, occupancy
        # windows, SLO/goodput accounting.  Always constructed — every
        # hook is host arithmetic that no-ops emission when telemetry
        # is off, and the bench receipt needs the accumulators either way
        self.observability = ServingObservability(self)
        self.generated_tokens = 0
        self._results = {}
        self._next_request_id = 0
        self._health = None          # ServingHealth, via attach_health
        self._pending_fingerprint = None
        self._draining = False
        self._closed = False
        if self.telemetry.enabled:
            self.telemetry.emit(TEL.EVENT_RUN_START, world_size=1,
                                mode="serving", **{
                                    "max_batch_slots": icfg.max_batch_slots,
                                    "kv_blocks": icfg.kv_blocks,
                                    "prefill_buckets": list(
                                        icfg.prefill_buckets)})
        logger.info(
            "InferenceEngine: %s, %d layers, %d slots, blocks of %d tokens: "
            "%s; prefill buckets %s, weights %s",
            type(model).__name__, serving.num_layers, icfg.max_batch_slots,
            icfg.kv_block_size,
            "; ".join(
                f"{g.name} ({g.layers} layers, {g.num_blocks(icfg)} blocks, "
                + ("the whole context" if g.pages is None
                   else f"{g.pages} a request") + ") "
                + " + ".join(f"{n}[{w}]" for n, w in g.buffers.items())
                for g in groups),
            list(icfg.prefill_buckets), icfg.weights_dtype)

    def _prepare_params(self, params):
        """The tree every program is handed: what the served model makes
        of its weights ONCE (``serving.prepare_params``,
        ``inference/model.py``), so that nothing which depends on the
        weights alone is computed inside a program call.  Whatever
        replaces the engine's weights goes through here.  What it did is
        one log line and ``self.prepared_params`` (the
        ``serving/prepared_param_*`` gauges): the leaves of the result
        that are not the caller's, and their bytes."""
        t0 = time.perf_counter()
        prepared = self.serving.prepare_params(params)

        def apart(tree, other):
            ids = {id(leaf) for leaf in jax.tree_util.tree_leaves(other)}
            return [leaf for leaf in jax.tree_util.tree_leaves(tree)
                    if id(leaf) not in ids]

        made, dropped = apart(prepared, params), apart(params, prepared)
        jax.block_until_ready(made)
        self.prepared_params = {
            "leaves": len(made), "bytes": sum(x.nbytes for x in made)}
        logger.info(
            "InferenceEngine: prepare_params: %d leaves prepared, %d bytes "
            "in, %d bytes out, %.3f s", len(made),
            sum(x.nbytes for x in dropped), self.prepared_params["bytes"],
            time.perf_counter() - t0)
        return prepared

    @staticmethod
    def _validate_config(param_dict):
        from ..tools.dslint.schema import validate_config_dict

        strict = bool(param_dict.get(C.STRICT_CONFIG,
                                     C.STRICT_CONFIG_DEFAULT))
        issues = validate_config_dict(param_dict)
        for issue in issues:
            logger.warning(f"InferenceEngine config: {issue.message}")
        if strict and issues:
            raise ValueError(
                "strict_config: rejected unknown configuration keys: "
                + "; ".join(i.message for i in issues))

    @classmethod
    def from_hf_gpt2(cls, hf_params, model_config, config=None):
        """Serve an HF Flax GPT-2 checkpoint: weight surgery through
        ``module_inject`` (fused-layer injection + embedding remap),
        then the standard constructor (which applies the configured
        serve dtype)."""
        from ..models.gpt2 import GPT2LMHeadTPU
        from ..module_inject import ingest_gpt2_model

        params = ingest_gpt2_model(hf_params)
        model = GPT2LMHeadTPU(model_config)
        return cls(model, params, config=config)

    # ------------------------------------------------------------------
    # request front-end
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, request_id=None,
               deadline_ms=None, trace_id=None):
        """Queue one generation request; returns its id.  Rejects (by
        raising) prompts longer than the largest prefill bucket and
        requests whose worst case exceeds ``max_seq_len`` — at
        SUBMISSION, never mid-serve.  ``deadline_ms`` overrides the
        configured ``inference.request_deadline_ms`` for this request
        (0 = no deadline).  ``trace_id`` joins this request into an
        existing lifecycle trace (a routing front-end mints one before
        the shed decision); None mints a fresh one here."""
        if self._draining:
            raise RuntimeError(
                "InferenceEngine is draining (close()/SIGTERM): "
                "admission is stopped; route this request elsewhere")
        if request_id is None:
            request_id = f"req-{self._next_request_id}"
            self._next_request_id += 1
        minted_here = trace_id is None
        if minted_here:
            trace_id = mint_trace_id()
        ms = (deadline_ms if deadline_ms is not None
              else self.inference_config.request_deadline_ms)
        request = Request(
            request_id, prompt,
            max_new_tokens if max_new_tokens is not None
            else self.inference_config.max_new_tokens,
            deadline_at=(time.monotonic() + ms / 1000.0 if ms else None),
            trace_id=trace_id)
        self.scheduler.submit(request)
        self._results[request_id] = request
        if minted_here:
            # a front-end that minted the trace already emitted the
            # submit record (before its shed decision); bare-engine
            # submits start the trace here
            self.observability.note_submit(request,
                                           self.scheduler.queue_depth)
        return request_id

    def resubmit(self, request):
        """Admit a router-requeued :class:`Request` (already through
        ``reset_for_requeue``): same validation as :meth:`submit`, but
        the request object — and with it the id, the original prompt,
        and the requeue count — survives the replica hop."""
        if self._draining:
            raise RuntimeError(
                "InferenceEngine is draining (close()/SIGTERM): "
                "admission is stopped; route this request elsewhere")
        self.scheduler.submit(request)
        self._results[request.request_id] = request
        return request.request_id

    def request(self, request_id):
        """The live :class:`Request` behind an id (None if unknown) —
        the front-end's handle for harvest/requeue decisions."""
        return self._results.get(request_id)

    def forget(self, request_id):
        """Drop a request from this engine's result map (the front-end
        moved it to another replica; leaving it here would double-count
        it in this engine's receipts)."""
        self._results.pop(request_id, None)

    # ------------------------------------------------------------------
    # the serve loop
    # ------------------------------------------------------------------
    def _enqueue_prefill(self, request):
        """Enqueue the admitted request's prefill.  Nothing is read here:
        the program also puts its first token into the request's lane of
        the next decode's input, on the device, and the host reads it in
        the step's one fetch."""
        span = self.telemetry.span
        with span("prefill", bucket=request.bucket,
                  prompt_tokens=len(request.prompt)):
            t_pre = self._clock()
            with span("prefill.prep"):
                # fresh arrays: a program that has not run yet may still
                # read its host arguments (the CPU backend aliases them)
                ids = np.zeros((1, request.bucket), np.int32)
                ids[0, :len(request.prompt)] = request.prompt
                tables = self.scheduler.block_tables(request)
            with span("prefill.dispatch"):
                out, self._caches, self._next_tokens = self._prefills[
                    request.bucket](
                        self.params, self._caches, ids,
                        np.int32(len(request.prompt)), tables,
                        self._next_tokens, np.int32(request.slot))
            with span("prefill.fetch"):
                # kept so that a `prefill` still holds its four phases
                # (trace readers pair them by count); the token comes
                # with the step's one fetch
                pass
            with span("prefill.account"):
                request.dispatched = 1
                self._unread.append(_Enqueued(
                    out, [(request, request.grants)], t_pre))

    def _emit_finish(self, request):
        self.observability.note_finish(request)

    def _emit_deadline(self, request):
        self.observability.note_deadline(request)

    def _decode_once(self, active):
        """Enqueue one continuous-batch decode iteration over the
        ``active`` slots the scheduler advances, THEN read what was
        enqueued before it: the previous decode's tokens and counters,
        the first tokens of this step's prefills and, on the cadence
        iterations with a health plane attached, the re-computed
        weight-fingerprint scalar — ONE batched ``device_get``, the
        step's only host sync (the zero-added-syncs tests count them).  The device runs this
        iteration while the host reads, accounts and prepares the next:
        its input tokens are the previous programs' outputs and never
        leave the device."""
        icfg = self.inference_config
        sched = self.scheduler
        span = self.telemetry.span
        # counted before the span opens: an annotation's arguments are
        # fixed at its start
        live_blocks = tuple(sched.live_blocks(g)
                            for g in range(len(self.cache_groups)))
        in_flight = int(any(p.is_decode for p in self._unread))
        with span("decode", active=active, live_blocks=live_blocks[0],
                  in_flight=in_flight, **{
                      f"live_blocks_{g.name}": n
                      for g, n in zip(self.cache_groups, live_blocks)}):
            with span("decode.prep"):
                t_prep = self._clock()
                tables, owner = self._tables, self._table_owner
                ctx_lens = np.zeros((icfg.max_batch_slots,), np.int32)
                lanes = []
                for slot, request in enumerate(sched.slots):
                    grant = (request.grants if sched.decodes_next(request)
                             else None)
                    if grant is not owner[slot]:
                        # a block grant (a tuple made at admission) stays
                        # as it is until the request finishes, so a row is
                        # rewritten only when the slot's grant changes; a
                        # freed slot, and one whose request has all its
                        # tokens dispatched, goes back to the null block
                        for g, table in enumerate(tables):
                            table[slot] = (
                                NULL_BLOCK if grant is None else
                                sched.block_table_row(request, g))
                        owner[slot] = grant
                        self._tables_dev = None
                    if grant is None:
                        continue
                    # position of the token being decoded: the context
                    # dispatched so far - 1 (the last dispatched token is
                    # the decode input, on the device already)
                    ctx_lens[slot] = (len(request.prompt)
                                      + request.dispatched - 1)
                    lanes.append((request, grant))
                if self._tables_dev is None:
                    # a COPY goes to the device: the host rows are written
                    # again while programs that read the old ones wait
                    self._tables_dev = jax.device_put(
                        tuple(table.copy() for table in tables))
                if self._health is not None:
                    # liveness tick for ENTERING this iteration
                    # (throttled O(1) publish; a wedged decode never
                    # refreshes it again)
                    self._health.beat(self.decode_iterations + 1)
                    if ((self.decode_iterations + 1)
                            % self.steps_per_print == 0):
                        self._fingerprint_dev = \
                            self._health.fingerprint_device()
                t0 = self._clock()
                self._driver_latencies.record(t0 - t_prep)
            with span("decode.dispatch"):
                # returns when the positions are copied and the program
                # is enqueued, not when it has run
                out_dev, self._caches = self._decode(
                    self.params, self._caches, self._tables_dev, ctx_lens,
                    self._next_tokens)
            self._next_tokens = out_dev["tokens"]
            for request, _ in lanes:
                request.dispatched += 1
            self.decode_iterations += 1
            self.decodes_enqueued_ahead += in_flight
            before, self._unread = self._unread, [
                _Enqueued(out_dev, lanes, t0, live_blocks)]
            self._read(before, "decode.fetch", "decode.account")

    def _read(self, unread, fetch_span, account_span):
        """Read the outputs of the ``unread`` programs (and the weight
        fingerprint, when one was enqueued) in ONE ``device_get`` and
        book them.  A lane is booked only while its request still holds
        the block grant it was dispatched under: one that was finished
        (the token decoded past an EOS), expired, aborted or requeued
        while its program was in flight is dropped, never booked to the
        slot's next owner."""
        span = self.telemetry.span
        fp_dev, self._fingerprint_dev = self._fingerprint_dev, None
        outs = ()
        with span(fetch_span):
            if unread or fp_dev is not None:
                outs, fingerprint = jax.device_get(
                    ([program.out for program in unread], fp_dev))
        with span(account_span):
            if fp_dev is not None:
                self._pending_fingerprint = int(fingerprint)
            now = self._clock()
            for program, out in zip(unread, outs):
                lanes = [request for request, grant in program.lanes
                         if request.grants is grant]
                if not program.is_decode:
                    for request in lanes:
                        # the TTFT is first_token_at - submitted;
                        # step_times holds the gaps BETWEEN a request's
                        # tokens only
                        request.first_token_at = request.last_token_at = now
                        request.generated.append(int(out["tokens"]))
                        self.generated_tokens += 1
                        # admit + first_token phase records,
                        # admission-wait histogram, TTFT SLO leg, bucket
                        # padding-waste accumulators
                        self.observability.note_prefill(
                            request, now, now - program.enqueued_at)
                    continue
                # the tokens, and whatever scalar counters the model's
                # program reported in the same fetch
                self.model_counters = dict(out)
                next_tokens = self.model_counters.pop("tokens")
                # an iteration's time: read to read while the pipe is
                # full, enqueue to read when it was empty
                self._step_latencies.record(
                    now - max(program.enqueued_at, self._last_read_at))
                gaps = []
                for request in lanes:
                    request.generated.append(int(next_tokens[request.slot]))
                    # the gap since THIS request's previous token: a
                    # neighbour's prefill between two of its tokens is
                    # in it
                    gap = now - request.last_token_at
                    gaps.append(gap)
                    request.step_times.append(gap)
                    request.last_token_at = now
                    self.generated_tokens += 1
                # host arithmetic on the scalars this loop already holds
                # (occupancy window sums, the per-token SLO leg; with
                # telemetry on, the P² per-token observations) — no
                # device syncs
                self.observability.note_decode(lanes, gaps,
                                               program.live_blocks)
            self._last_read_at = now

    def _flush(self):
        """Read the program in flight, if any: the pipe drains (no slot
        is left to advance, ``run()``, ``drain()``, ``close()``)."""
        if self._unread:
            unread, self._unread = self._unread, []
            self._read(unread, "step.fetch", "step.account")

    def _sample_telemetry(self):
        """Print-cadence sampling: queue/occupancy gauges, one
        EVENT_SERVING queue record, and the attribution gauges — all
        host arithmetic on already-fetched scalars, zero device syncs."""
        if not self.telemetry.enabled:
            return
        sched = self.scheduler
        self.telemetry.gauge("serving/queue_depth").set(
            float(sched.queue_depth))
        self.telemetry.gauge("serving/active_slots").set(
            float(sched.active_count))
        self.telemetry.gauge("serving/free_blocks").set(
            float(self.allocator.free_blocks))
        self.telemetry.gauge("serving/generated_tokens").set(
            float(self.generated_tokens))
        for key, value in self.prepared_params.items():
            self.telemetry.gauge(f"serving/prepared_param_{key}").set(
                float(value))
        for key, value in self.model_counters.items():
            self.telemetry.gauge(f"serving/{key}").set(float(value))
        decodes = self.decode_iterations - self._sampled_decodes
        if decodes:
            # the share of this window's decode iterations that were
            # enqueued while the previous one's outputs were still
            # unread: 1.0 with the pipe full, lower where it drained
            self.telemetry.gauge("serving/enqueued_ahead_share").set(
                (self.decodes_enqueued_ahead - self._sampled_ahead)
                / decodes)
            self._sampled_decodes = self.decode_iterations
            self._sampled_ahead = self.decodes_enqueued_ahead
        self.telemetry.emit(
            TEL.EVENT_SERVING, step=self.decode_iterations, kind="queue",
            queue_depth=sched.queue_depth, active=sched.active_count,
            free_blocks=self.allocator.free_blocks,
            reserved_tokens=sched.reserved_tokens())
        # close the observability decode window: decode_window + slo
        # phase records, occupancy/goodput gauges (DSH205: this call is
        # only legal here, inside the steps_per_print cadence)
        self.observability.export_serving_window()
        # the same comm/latency snapshot the training engine publishes:
        # it is the measured side the offline doctor reconciles against
        snap = self._step_latencies.latency_snapshot()
        if snap["n"]:
            from ..profiling import comm as comm_prof

            for key in ("last", "mean", "p50", "p95", "max"):
                self.telemetry.gauge(
                    f"comm/latency/{key}_secs").set(snap[key])
            self.telemetry.emit(TEL.EVENT_COMM, step=self.decode_iterations,
                                kind=comm_prof.KIND_LATENCY, **snap)
        receipt = self.attribution_receipt()
        if receipt is not None:
            self.telemetry.gauge(
                "serving/attribution/predicted_step_seconds").set(
                    float(receipt["predicted_step_seconds"]))
            if receipt["measured_step_seconds"] is not None:
                self.telemetry.emit(TEL.EVENT_ATTRIBUTION,
                                    step=self.decode_iterations, **receipt)

    def _sample_integrity(self):
        """Print-cadence health sample: hand the fingerprint scalar the
        batched decode fetch already transferred to the health plane —
        publish, fleet read, majority vote (dslint DSH205 pins the
        publish/read APIs to this cadence statically).  Raises
        :class:`~deepspeed_tpu.resilience.constants.FleetIntegrityError`
        (respawnable exit 87) when the vote convicts a replica."""
        if self._health is None or self._pending_fingerprint is None:
            return
        fingerprint, self._pending_fingerprint = \
            self._pending_fingerprint, None
        self._health.note_weight_fingerprint(fingerprint)

    def _sweep_finished(self, finished):
        for request in self.scheduler.sweep_finished(
                self.inference_config.eos_token_id):
            self._emit_finish(request)
            finished.append(request)
        return finished

    def step(self):
        """One engine iteration, one program ahead of the host: expire
        deadlines, admit from the queue (each admission's prefill is
        enqueued at once), enqueue the decode that advances every slot
        with tokens left to ask for, and only then read what was
        enqueued BEFORE that decode — the previous decode's tokens and
        this step's first tokens — in the step's one host sync; last,
        recycle the slots those tokens finished.  Returns the requests
        finished during this iteration, each with its whole
        ``generated``.

        So a decode's tokens reach the host one enqueue late; a request
        is returned by the ``step()`` that READ its last token, the one
        after the step that enqueued it; and an EOS is seen one
        iteration late: the token decoded past it is dropped here, its
        cache row inside the request's own block grant."""
        span = self.telemetry.span
        with span("step"):
            sched = self.scheduler
            with span("step.sweep"):
                finished = sched.sweep_deadlines()
                for request in finished:
                    self._emit_deadline(request)
                self._sweep_finished(finished)
            while not self._draining:
                # one span per try_admit call; the call that admits nothing
                # closes the loop
                with span("step.admit"):
                    request = sched.try_admit()
                if request is None:
                    break
                try:
                    self._enqueue_prefill(request)
                except BaseException:
                    # a prefill that raises after admission must not strand
                    # the slot + block grant it was just handed (the
                    # blocks-conserved invariant): release everything and
                    # surface the fault
                    sched.abort(request)
                    raise
            # a request whose tokens are all dispatched (max_new_tokens=1:
            # by its prefill alone) is not advanced again: its slot parks
            # until the read below, or the next step's, finishes it
            active = sched.decoding_count
            if active:
                self._decode_once(active)
            else:
                self._flush()
            with span("step.sweep"):
                self._sweep_finished(finished)
            if (self.decode_iterations
                    and self.decode_iterations % self.steps_per_print == 0):
                with span("step.sample"):
                    self._sample_telemetry()
                    self._sample_integrity()
                    # the operator's on-demand device trace (touch
                    # <run_dir>/device_trace.trigger), as train_batch polls it
                    self.telemetry.poll_device_trace(self.decode_iterations,
                                                     self.program_scopes)
            return finished

    def run(self):
        """Drain the queue: iterate until every submitted request has
        finished; returns ``{request_id: result dict}`` (tokens, finish
        reason, TTFT, per-token p50/p99)."""
        while not self.scheduler.idle():
            self.step()
        # nothing stays unread (requests aborted from outside can leave
        # the scheduler idle over a program in flight)
        self._flush()
        self._sweep_finished([])
        self._sample_telemetry()
        return {rid: r.result() for rid, r in self._results.items()}

    # ------------------------------------------------------------------
    # receipts (the training engine's surface, serving programs)
    # ------------------------------------------------------------------
    def serving_receipt(self):
        """Aggregate serve metrics over every finished request."""
        finished = [r for r in self._results.values()
                    if r.state == "finished"]
        lats = sorted(t for r in finished for t in r.step_times)
        ttfts = sorted(r.first_token_at - r.submitted for r in finished
                       if r.first_token_at is not None)

        def pct(vals, p):
            if not vals:
                return None
            return float(vals[min(len(vals) - 1, int(p * len(vals)))])

        wall = None
        if finished:
            start = min(r.submitted for r in finished)
            end = max(r.finished_at for r in finished)
            wall = max(end - start, 1e-9)
        receipt = {
            "requests": len(finished),
            "generated_tokens": self.generated_tokens,
            "decode_iterations": self.decode_iterations,
            "per_token_p50_seconds": pct(lats, 0.50),
            "per_token_p99_seconds": pct(lats, 0.99),
            "ttft_p50_seconds": pct(ttfts, 0.50),
            "tokens_per_second_per_chip": (
                self.generated_tokens / wall if wall else None),
            "programs_compiled": len(self.memory_ledger.entries()),
        }
        # occupancy/SLO/goodput receipt (observability plane); goodput
        # is re-based onto the same wall clock as the throughput
        # headline so the two are directly comparable
        obs = self.observability.receipt()
        receipt.update(obs)
        receipt["goodput_tokens_per_second"] = (
            obs["goodput_tokens"] / wall if wall else None)
        return receipt

    def comm_receipt(self):
        """Collective receipt for ONE decode iteration (count/payload/
        wire from the compile-time HLO walk); None until decode has
        compiled or with the ledger off."""
        return self.comm_ledger.step_entry(1, prefer=DECODE_PROGRAM)

    def overlap_receipt(self):
        """Static exposed-wire verdict for the decode program; None
        until it has an overlap summary."""
        return self.comm_ledger.step_overlap(1, prefer=DECODE_PROGRAM)

    def attribution_receipt(self):
        """Reconciled per-decode-iteration budget (compute / exposed
        wire / host driver vs the measured p50) — the serving phase
        table ``python -m deepspeed_tpu.profiling.doctor`` renders."""
        from ..profiling import attribution as attr_prof

        if not self.comm_ledger.enabled:
            return None
        vals = self._driver_latencies.recent()
        budget = attr_prof.step_budget(
            self.comm_ledger.overlap_entries(), 1, prefer=DECODE_PROGRAM,
            driver_seconds=float(min(vals)) if vals else 0.0)
        if budget is None:
            return None
        snap = self._step_latencies.latency_snapshot()
        return attr_prof.reconcile(budget,
                                   snap["p50"] if snap["n"] else None)

    def program_verify_context(self):
        """Mesh/parameter/donation context for the DSP6xx verifier and
        the ``programs/`` sidecars (single-replica serving: a 1-wide
        data axis, no master, no declared host stream)."""
        leaves = jax.tree_util.tree_leaves(self.params)
        return {
            "mesh_axes": {"data": 1},
            "data_axis": "data",
            "param_bytes": int(sum(
                np.prod(l.shape) * l.dtype.itemsize for l in leaves)),
            "master_provenance": None,
            "host_state_wire_bytes": None,
            "host_stream_schedule": None,
            "collective_schedule": None,
            "device_kind": getattr(jax.devices()[0], "device_kind", ""),
            # declared sharding (profiling/sharding, DSS8xx): single-
            # replica serving declares everything replicated on a
            # 1-wide data axis — weights as the params family, the
            # paged cache buffers as kv_cache — so the decode program's
            # residency still gets a priced receipt
            "declared_sharding": self._declared_sharding(leaves),
        }

    def _declared_sharding(self, param_leaves):
        from ..profiling import sharding as sharding_prof
        try:
            mesh_axes = {"data": 1}
            families = {
                "params": sharding_prof.build_declared_family(
                    (int(np.prod(l.shape)) * l.dtype.itemsize, [], 1)
                    for l in param_leaves),
                "kv_cache": sharding_prof.build_declared_family(
                    (int(np.prod(c.shape)) * c.dtype.itemsize, [], 1)
                    for c in self._caches),
            }
            return {"tag": "serve|data1", "mesh_axes": mesh_axes,
                    "families": families}
        except Exception as e:
            logger.debug("declared_sharding unavailable: %s", e)
            return None

    def verify_programs(self):
        """DSP6xx pass over every compiled serve program — the KV-cache
        donation must materialize as ``input_output_alias`` on the
        decode program (DSP601) or this returns a violation."""
        from ..profiling.verify import verify_engine_programs

        return verify_engine_programs(self)

    def program_scopes(self):
        """``{module name as a device trace prints it ("jit_decode",
        "jit_prefill_<bucket>"): {instruction: (scope path, direction)}}``
        of every serve program compiled so far (``telemetry/scopes.py``).
        Built when asked, never at construction or in ``step``; empty
        where the ledger keeps no program (``profiling.memory_ledger``,
        on with telemetry)."""
        from ..telemetry import scopes

        return scopes.program_scopes(self.memory_ledger.compiled_programs())

    # ------------------------------------------------------------------
    # resilience plane (inference/resilience.py)
    # ------------------------------------------------------------------
    def attach_health(self, health):
        """Arm the serving health plane (heartbeats per decode
        iteration + weight-fingerprint consensus on the print cadence)
        and start its peer monitor.  Zero added per-token host syncs:
        the fingerprint rides the decode loop's existing next-token
        fetch."""
        self._health = health
        health.start()
        return health

    def drain(self, deadline_secs=None):
        """Stop admission and finish the in-flight decodes up to a
        bounded deadline (``DS_TERM_DRAIN_DEADLINE_SECS`` contract;
        ``<= 0`` drains unbounded).  Queued-but-unadmitted requests
        stay queued — a router requeues them onto surviving replicas;
        this engine only owes the sequences already holding KV state.
        Returns the requests that finished during the drain."""
        from .resilience import drain_deadline_secs

        self._draining = True
        if deadline_secs is None:
            deadline_secs = drain_deadline_secs()
        deadline = (time.monotonic() + float(deadline_secs)
                    if deadline_secs and float(deadline_secs) > 0
                    else None)
        if self.telemetry.enabled:
            self.telemetry.emit(
                TEL.EVENT_SERVING, step=self.decode_iterations,
                kind="drain", active=self.scheduler.active_count,
                queued=self.scheduler.queue_depth,
                deadline_secs=(float(deadline_secs)
                               if deadline is not None else None))
        drained = []
        while self.scheduler.active_count:
            if deadline is not None and time.monotonic() >= deadline:
                logger.warning(
                    "serving drain hit the %.1fs deadline with %d "
                    "request(s) still decoding; abandoning them "
                    "(the router re-serves anything undelivered)",
                    float(deadline_secs), self.scheduler.active_count)
                break
            drained.extend(self.step())
        # the program in flight is read before the drain returns, at the
        # deadline too
        self._flush()
        return self._sweep_finished(drained)

    def close(self, reason="serve_done"):
        """Shut the engine down respawnably: stop admission, drain the
        in-flight decodes up to the bounded deadline, stop the health
        plane, flush + close telemetry.  Idempotent (the SIGTERM
        handler and a normal exit path may both call it)."""
        if self._closed:
            return
        self._closed = True
        if self.scheduler.active_count:
            self.drain()
        self._draining = True
        self._flush()
        if self._health is not None:
            self._health.stop()
        # TelemetryManager.close emits the EVENT_RUN_END itself
        self.telemetry.close(reason=reason)
