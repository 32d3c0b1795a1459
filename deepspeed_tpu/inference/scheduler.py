"""Continuous-batching scheduler: Orca-style iteration-level admission
over the paged KV cache.

Pure host bookkeeping — the scheduler decides WHO runs; the engine
dispatches the compiled programs.  Per engine iteration:

1. finished slots (generation cap or EOS) release their blocks and free
   their slot — mid-batch, without draining the other sequences;
2. queued requests admit in FIFO order while a slot is free, the token
   budget holds, and the allocator can grant the request's WHOLE
   worst-case block span (prefill bucket ∪ prompt+generation cap) —
   allocation is all-at-admission, so decode can never hit
   out-of-blocks;
3. every active slot whose DISPATCHED tokens are short of its cap
   advances one token through the fixed-shape decode program.  The
   engine keeps one program in flight, so the host's ``generated`` lags
   what the device was asked for by one enqueue: ``Request.dispatched``
   counts the tokens asked for, and a request that has reached
   ``max_new_tokens`` by that count is not dispatched again — its slot
   parks at the null block, as a free slot does, until its last token
   has been read and the sweep recycles it — so no write ever leaves
   the block grant.

A model whose layers cache different spans has a pool a cache group
(``inference/kv_cache.py``): the request's grant holds one block list a
group, all granted at admission or none, all released together.

The token budget is the Orca admission knob: the sum of each active
request's worst case (prompt + remaining generation) stays under
``inference.token_budget``, bounding both cache pressure and
per-iteration latency under load.
"""

import time
from collections import deque

import numpy as np

from .kv_cache import NULL_BLOCK

# request lifecycle
QUEUED = "queued"
ACTIVE = "active"
FINISHED = "finished"

# finish reasons
REASON_EOS = "eos"
REASON_LENGTH = "max_new_tokens"
REASON_DEADLINE = "deadline"


class Request:
    """One generation request and its measured lifecycle.

    Timing fields are host wall-clock (``time.monotonic``): ``submitted``
    at entry, ``first_token_at`` when prefill emits (TTFT), ``step_times``
    one per generated token AFTER the first: the gap since this request's
    previous token (``last_token_at``), so an iteration spent behind a
    neighbour's prefill shows (the per-token latency record the serving
    bench quotes p50/p99 from; the TTFT is not in it).  ``deadline_at``
    is an absolute monotonic expiry (None = no deadline): the
    scheduler's deadline sweep finishes an expired request with
    ``reason="deadline"`` and the partial tokens it generated so far.
    ``dispatched`` counts the tokens whose programs have been enqueued
    (the prefill's and one a decode), ``generated`` holds those the host
    has read: ``dispatched - len(generated)`` are in flight.  ``grants``
    is the block grant, one list of block ids a cache group (empty while
    the request holds none); ``blocks`` the first group's."""

    __slots__ = ("request_id", "prompt", "max_new_tokens", "state",
                 "generated", "dispatched", "grants", "slot", "bucket",
                 "submitted", "first_token_at", "last_token_at",
                 "finished_at", "finish_reason", "step_times",
                 "deadline_at", "requeues", "trace_id", "admitted_at",
                 "_cached_summary")

    def __init__(self, request_id, prompt, max_new_tokens,
                 deadline_at=None, trace_id=None):
        assert len(prompt) > 0, "empty prompt"
        self.request_id = request_id
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.state = QUEUED
        self.generated = []
        self.dispatched = 0
        self.grants = ()
        self.slot = None
        self.bucket = None
        self.submitted = time.monotonic()
        self.first_token_at = None
        self.last_token_at = None
        self.finished_at = None
        self.finish_reason = None
        self.step_times = []
        self.deadline_at = deadline_at
        self.requeues = 0
        # the lifecycle trace id: minted once at submit and PRESERVED
        # across reset_for_requeue, so a replica-death re-serve joins
        # into one trace in the event stream
        self.trace_id = trace_id
        self.admitted_at = None
        self._cached_summary = None

    def reset_for_requeue(self):
        """Return the request to a pristine QUEUED state for re-serving
        on another replica after its original replica died.  The KV
        cache died with the replica, so everything derived from serving
        — generated tokens, block grant, slot/bucket assignment, timing
        — is discarded; prefill recomputes it all, and greedy decode
        determinism makes the re-served tokens bit-identical.  The
        block list is just CLEARED, never released: the grant belonged
        to the dead replica's allocator (a live allocator must never be
        handed another pool's block ids — the leak class the
        blocks-conserved invariant test pins)."""
        assert self.state != FINISHED, (
            f"request {self.request_id!r} already finished; a completed "
            "result is never re-served (exactly-once)")
        self.state = QUEUED
        self.generated = []
        self.dispatched = 0
        self.grants = ()
        self.slot = None
        self.bucket = None
        self.first_token_at = None
        self.last_token_at = None
        self.finished_at = None
        self.finish_reason = None
        self.step_times = []
        self.requeues += 1
        self.admitted_at = None
        self._cached_summary = None

    @property
    def blocks(self):
        """The first cache group's block ids ([] while none are held)."""
        return self.grants[0] if self.grants else []

    @property
    def context_len(self):
        return len(self.prompt) + len(self.generated)

    def worst_case_tokens(self):
        return len(self.prompt) + self.max_new_tokens

    def result(self):
        """The request's latency summary.  Computed once and cached when
        the request is FINISHED (``step_times`` only grows while ACTIVE,
        so the cache can never go stale; ``reset_for_requeue``
        invalidates it) — report-cadence sampling of a large in-flight
        set used to re-sort ``step_times`` on every call."""
        if self._cached_summary is not None:
            return self._cached_summary
        lat = sorted(self.step_times)

        def pct(p):
            if not lat:
                return None
            return lat[min(len(lat) - 1, int(p * len(lat)))]

        summary = {
            "request_id": self.request_id,
            "trace_id": self.trace_id,
            "tokens": list(self.generated),
            "finish_reason": self.finish_reason,
            "requeues": self.requeues,
            "ttft_seconds": (self.first_token_at - self.submitted
                             if self.first_token_at is not None else None),
            "admission_wait_seconds": (
                self.admitted_at - self.submitted
                if self.admitted_at is not None else None),
            "latency_seconds": (self.finished_at - self.submitted
                                if self.finished_at is not None else None),
            "per_token_p50_seconds": pct(0.50),
            "per_token_p99_seconds": pct(0.99),
        }
        if self.state == FINISHED:
            self._cached_summary = summary
        return summary


class ContinuousBatchScheduler:
    """Slot/block/budget bookkeeping for one
    :class:`~deepspeed_tpu.inference.engine.InferenceEngine`."""

    def __init__(self, icfg, allocator):
        """``allocator``: the pool, or one pool a cache group (a list)."""
        self.icfg = icfg
        self.allocators = (list(allocator)
                           if isinstance(allocator, (list, tuple))
                           else [allocator])
        self.allocator = self.allocators[0]
        self.waiting = deque()
        self.slots = [None] * icfg.max_batch_slots
        self.admitted_total = 0
        self.finished_total = 0

    # -- state views ---------------------------------------------------
    @property
    def queue_depth(self):
        return len(self.waiting)

    def active_requests(self):
        return [r for r in self.slots if r is not None]

    @property
    def active_count(self):
        return sum(1 for r in self.slots if r is not None)

    def reserved_tokens(self):
        """Worst-case token debt of the active set (the budget term)."""
        return sum(r.worst_case_tokens() for r in self.slots
                   if r is not None)

    @staticmethod
    def decodes_next(request):
        """Whether the next decode advances this slot: it holds a
        request whose dispatched tokens (in flight included) are short
        of its cap."""
        return (request is not None
                and request.dispatched < request.max_new_tokens)

    @property
    def decoding_count(self):
        """Slots the next decode advances."""
        return sum(1 for r in self.slots if self.decodes_next(r))

    def live_blocks(self, group=0):
        """KV blocks the next decode iteration reads in one layer of cache
        group ``group``: for each slot it advances, the blocks holding its
        context and the token being decoded, ``ceil((prompt + dispatched)
        / block_size)`` — what the paged kernel walks, of the ``slots *
        max_blocks_per_seq`` a full-table gather would — or, in a group
        of fixed spans, the slot's whole ring."""
        pages = self.allocators[group].pages_per_request
        if pages is not None:
            return pages * self.decoding_count
        bs = self.icfg.kv_block_size
        return sum(-(-(len(r.prompt) + r.dispatched) // bs)
                   for r in self.slots if self.decodes_next(r))

    def idle(self):
        return not self.waiting and self.active_count == 0

    # -- admission ------------------------------------------------------
    def submit(self, request):
        icfg = self.icfg
        assert not request.grants and request.slot is None, (
            f"request {request.request_id!r} submitted while still "
            "holding a block grant/slot — a requeued request must go "
            "through reset_for_requeue() first (a stale grant would be "
            "silently overwritten at admission and leak from its pool)")
        if request.worst_case_tokens() > icfg.max_seq_len:
            raise ValueError(
                f"request {request.request_id!r}: prompt "
                f"({len(request.prompt)}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds inference.max_seq_len "
                f"({icfg.max_seq_len})")
        if request.worst_case_tokens() > icfg.token_budget:
            # try_admit() can NEVER seat this request — even an empty
            # batch leaves the budget short — and FIFO admission means
            # it would park at the queue head starving everything
            # behind it forever.  Loud at submit time, not a hang
            raise ValueError(
                f"request {request.request_id!r}: prompt "
                f"({len(request.prompt)}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds "
                f"inference.token_budget ({icfg.token_budget}); this "
                "request could never be admitted (raise token_budget "
                "or shorten the request)")
        icfg.bucket_for(len(request.prompt))  # reject over-long prompts
        self.waiting.append(request)

    def _blocks_needed(self, request, bucket):
        bs = self.icfg.kv_block_size
        span = max(bucket, request.worst_case_tokens())
        return -(-span // bs)  # ceil

    def _grant(self, request, bucket):
        """The request's blocks of every cache group — its worst-case
        context, or the group's fixed span — or None, with nothing taken,
        when any pool cannot cover its part."""
        grants = []
        for allocator in self.allocators:
            blocks = allocator.allocate(
                allocator.pages_per_request
                or self._blocks_needed(request, bucket))
            if blocks is None:
                self._release(grants)
                return None
            grants.append(blocks)
        return tuple(grants)

    def _release(self, grants):
        for allocator, blocks in zip(self.allocators, grants):
            allocator.release(blocks)

    def try_admit(self):
        """Admit the queue head if a slot, the token budget, and the
        block pool all allow it; None otherwise (FIFO — no overtaking,
        so admission latency stays predictable under load)."""
        if not self.waiting:
            return None
        free_slots = [i for i, r in enumerate(self.slots) if r is None]
        if not free_slots:
            return None
        request = self.waiting[0]
        if self.reserved_tokens() + request.worst_case_tokens() \
                > self.icfg.token_budget:
            return None
        bucket = self.icfg.bucket_for(len(request.prompt))
        grants = self._grant(request, bucket)
        if grants is None:
            return None
        try:
            self.waiting.popleft()
            request.state = ACTIVE
            request.slot = free_slots[0]
            request.bucket = bucket
            request.grants = grants
            request.admitted_at = time.monotonic()
            self.slots[request.slot] = request
            self.admitted_total += 1
        except BaseException:
            # every early exit past the allocator grant MUST return the
            # blocks to the pool — a raise here would otherwise strand
            # the grant forever (the allocator has no owner to reclaim
            # from; the blocks-conserved invariant test pins this)
            self._release(grants)
            if request.slot is not None \
                    and self.slots[request.slot] is request:
                self.slots[request.slot] = None
            request.grants = ()
            request.slot = None
            request.bucket = None
            if request.state == ACTIVE:
                request.state = QUEUED
            raise
        return request

    def block_table_row(self, request, group=0):
        """The request's block table in cache group ``group``, padded with
        the null block to the group's fixed width: ``max_blocks_per_seq``,
        or a ring's pages."""
        width = (self.allocators[group].pages_per_request
                 or self.icfg.max_blocks_per_seq)
        row = list(request.grants[group])[:width]
        return row + [NULL_BLOCK] * (width - len(row))

    def block_tables(self, request):
        """The request's table row in every cache group, as the int32
        arrays a prefill program takes (fresh: the program may read its
        host arguments after this returns)."""
        return tuple(np.array(self.block_table_row(request, g), np.int32)
                     for g in range(len(self.allocators)))

    # -- recycling ------------------------------------------------------
    def finish(self, request, reason):
        """Release the request's slot and blocks mid-batch (the
        continuous-batching move: siblings keep decoding)."""
        assert self.slots[request.slot] is request
        self.slots[request.slot] = None
        self._release(request.grants)
        request.grants = ()
        request.state = FINISHED
        request.finish_reason = reason
        request.finished_at = time.monotonic()
        self.finished_total += 1

    def _finish_queued(self, request, reason):
        """Finish a request that never got a slot (expired while
        waiting): no blocks or slot to release, just the lifecycle
        bookkeeping."""
        request.state = FINISHED
        request.finish_reason = reason
        request.finished_at = time.monotonic()
        self.finished_total += 1

    def abort(self, request):
        """Forcibly release whatever the request holds — slot, block
        grant, queue position — WITHOUT finishing it (state returns to
        QUEUED, generated tokens are dropped by the caller's
        ``reset_for_requeue``).  The failure-recovery primitive: a
        prefill that raised after admission, or a replica front-end
        reclaiming a dead engine's in-flight work, must leave the
        allocator conserved (free == initial on idle) or every fault
        permanently shrinks the KV pool."""
        if request.state == ACTIVE:
            assert self.slots[request.slot] is request
            self.slots[request.slot] = None
            self._release(request.grants)
        elif request.state == QUEUED:
            try:
                self.waiting.remove(request)
            except ValueError:
                pass
        request.grants = ()
        request.slot = None
        request.bucket = None
        request.state = QUEUED
        request.dispatched = 0
        request.admitted_at = None

    def sweep_finished(self, eos_token_id):
        """Mark every slot that hit its cap or emitted EOS; returns the
        finished requests."""
        done = []
        for request in list(self.slots):
            if request is None:
                continue
            if (eos_token_id >= 0 and request.generated
                    and request.generated[-1] == eos_token_id):
                self.finish(request, REASON_EOS)
                done.append(request)
            elif len(request.generated) >= request.max_new_tokens:
                self.finish(request, REASON_LENGTH)
                done.append(request)
        return done

    def sweep_deadlines(self, now=None):
        """Finish every request — active OR still queued — whose
        wall-clock deadline has passed, with ``reason="deadline"`` and
        whatever tokens it generated so far.  Active slots and their
        block grants recycle mid-batch exactly like an EOS finish, so
        the queue head behind a stuck-slow batch gets the freed
        capacity the very next admission pass."""
        now = time.monotonic() if now is None else now
        done = []
        for request in list(self.slots):
            if request is None or request.deadline_at is None:
                continue
            if now >= request.deadline_at:
                self.finish(request, REASON_DEADLINE)
                done.append(request)
        for request in [r for r in self.waiting
                        if r.deadline_at is not None
                        and now >= r.deadline_at]:
            self.waiting.remove(request)
            self._finish_queued(request, REASON_DEADLINE)
            done.append(request)
        return done
