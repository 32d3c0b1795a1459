"""Multi-replica serving front-end (inference/frontend.py) and the
request-level robustness satellites:

- per-request deadlines: expiry mid-batch recycles the slot and block
  grant, partial tokens come back with ``reason="deadline"``;
- bounded admission: load shedding at ``max_queue_depth`` (typed
  :class:`ServingOverloadError`) and graceful degradation past
  ``degrade_queue_depth``;
- dead-replica requeue: in-flight requests reset and re-served on a
  survivor with BIT-IDENTICAL tokens (greedy determinism), exactly
  once — pinned by the kill-at-every-step-k sweep;
- the blocks-conserved invariant: after every scheduler exercise —
  including admission paths that RAISE — aborting everything returns
  the allocator to its initial free count.  A leaked grant is a
  permanently shrunk KV pool.
"""

import pytest

from deepspeed_tpu.inference import (BlockAllocator,
                                     ContinuousBatchScheduler,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine, Request,
                                     ServingFrontend,
                                     ServingOverloadError,
                                     reference_generate)
from deepspeed_tpu.inference.scheduler import (ACTIVE, FINISHED, QUEUED,
                                               REASON_DEADLINE,
                                               REASON_LENGTH)

from .test_inference import (seeded_prompts, serve_config, tiny_model,
                             model_and_params)  # noqa: F401 — fixture


def _drain_and_check_conserved(sched, alloc, initial_free):
    """The blocks-conserved invariant: abort every request the
    scheduler still tracks and the allocator must be exactly back at
    its initial free count — any shortfall is a leaked grant."""
    for request in list(sched.slots):
        if request is not None:
            sched.abort(request)
    for request in list(sched.waiting):
        sched.abort(request)
    assert alloc.free_blocks == initial_free, (
        f"block leak: {initial_free - alloc.free_blocks} block(s) never "
        "returned to the pool")


# ---------------------------------------------------------------------------
# scheduler satellites: deadlines + exception-safe admission
# ---------------------------------------------------------------------------

class TestSchedulerDeadlines:
    @pytest.fixture(autouse=True)
    def conserved(self):
        """Every test in this class ends with the invariant check."""
        self._made = []
        yield
        for sched, alloc, initial in self._made:
            _drain_and_check_conserved(sched, alloc, initial)

    def make(self, **overrides):
        icfg = DeepSpeedInferenceConfig(serve_config(**overrides))
        alloc = BlockAllocator(icfg.kv_blocks)
        sched = ContinuousBatchScheduler(icfg, alloc)
        self._made.append((sched, alloc, alloc.free_blocks))
        return sched, alloc

    def test_active_deadline_recycles_slot_and_blocks(self):
        sched, alloc = self.make()
        r = Request("r", [1] * 8, 8, deadline_at=100.0)
        sched.submit(r)
        assert sched.try_admit() is r
        r.generated = [5, 6]                      # two tokens in
        free_mid = alloc.free_blocks
        done = sched.sweep_deadlines(now=99.0)    # not yet
        assert done == [] and r.state == ACTIVE
        done = sched.sweep_deadlines(now=100.0)   # expired
        assert done == [r]
        assert r.state == FINISHED
        assert r.finish_reason == REASON_DEADLINE
        assert r.generated == [5, 6]              # partial tokens kept
        assert alloc.free_blocks > free_mid       # grant recycled
        assert sched.slots == [None] * len(sched.slots)

    def test_slot_reuse_after_deadline(self):
        # the freed slot must seat the queue head the very next pass
        sched, _ = self.make(max_batch_slots=1)
        doomed = Request("doomed", [1] * 8, 8, deadline_at=10.0)
        waiting = Request("waiting", [1] * 8, 4)
        sched.submit(doomed)
        sched.submit(waiting)
        assert sched.try_admit() is doomed
        assert sched.try_admit() is None          # the only slot is busy
        sched.sweep_deadlines(now=10.0)
        again = sched.try_admit()
        assert again is waiting and again.slot == 0

    def test_queued_request_expires_without_ever_running(self):
        sched, _ = self.make(max_batch_slots=1)
        hog = Request("hog", [1] * 8, 8)
        late = Request("late", [1] * 8, 4, deadline_at=5.0)
        sched.submit(hog)
        sched.submit(late)
        assert sched.try_admit() is hog
        done = sched.sweep_deadlines(now=6.0)
        assert done == [late]
        assert late.state == FINISHED
        assert late.finish_reason == REASON_DEADLINE
        assert late.generated == [] and late.blocks == []
        assert sched.queue_depth == 0

    def test_no_deadline_never_expires(self):
        sched, _ = self.make()
        r = Request("r", [1] * 8, 4)              # deadline_at=None
        sched.submit(r)
        sched.try_admit()
        assert sched.sweep_deadlines(now=1e12) == []

    def test_try_admit_exception_returns_the_grant(self):
        """A raise during post-allocate bookkeeping must release the
        fresh grant — the allocator has no owner to reclaim from."""
        sched, alloc = self.make()
        r = Request("r", [1] * 8, 4)
        sched.submit(r)
        free_before = alloc.free_blocks

        class Detonating(list):
            def __setitem__(self, i, v):
                raise RuntimeError("chaos: bookkeeping blew up")

        sched.slots = Detonating(sched.slots)
        with pytest.raises(RuntimeError, match="bookkeeping"):
            sched.try_admit()
        sched.slots = [None] * sched.icfg.max_batch_slots
        assert alloc.free_blocks == free_before   # grant came back
        assert r.blocks == [] and r.slot is None
        assert r.state == QUEUED

    def test_abort_releases_active_and_queued(self):
        sched, alloc = self.make()
        a = Request("a", [1] * 8, 4)
        b = Request("b", [1] * 8, 4)
        sched.submit(a)
        sched.submit(b)
        sched.try_admit()
        free_mid = alloc.free_blocks
        sched.abort(a)                            # active: slot + blocks
        assert alloc.free_blocks > free_mid
        assert sched.slots[0] is None
        sched.abort(b)                            # queued: just dequeued
        assert sched.queue_depth == 0
        assert a.state == QUEUED and b.state == QUEUED

    def test_submit_rejects_stale_grant(self):
        sched, _ = self.make()
        r = Request("r", [1] * 8, 4)
        sched.submit(r)
        sched.try_admit()
        with pytest.raises(AssertionError, match="reset_for_requeue"):
            sched.submit(r)                       # still holds blocks

    def test_reset_for_requeue_refuses_finished(self):
        sched, _ = self.make()
        r = Request("r", [1] * 8, 4)
        sched.submit(r)
        sched.try_admit()
        r.generated = [1, 2, 3, 4]
        sched.finish(r, REASON_LENGTH)
        with pytest.raises(AssertionError, match="exactly-once"):
            r.reset_for_requeue()

    def test_reset_for_requeue_clears_but_never_releases(self):
        # the grant belonged to the DEAD replica's allocator: the block
        # list is cleared, not released into this pool
        sched, alloc = self.make()
        r = Request("r", [1] * 8, 4)
        sched.submit(r)
        sched.try_admit()
        r.generated = [9]
        foreign = list(r.blocks)
        sched.abort(r)                            # the dead engine's abort
        r.reset_for_requeue()
        assert r.blocks == [] and r.generated == []
        assert r.requeues == 1
        assert r.state == QUEUED
        assert foreign                            # (the ids existed)


# ---------------------------------------------------------------------------
# engine-level deadline + prefill-abort
# ---------------------------------------------------------------------------

class TestEngineDeadlines:
    def test_deadline_result_carries_partial_tokens(self,
                                                    model_and_params):
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config())
        prompt = seeded_prompts(1, seed=41)[0]
        fast = engine.submit(prompt, max_new_tokens=8, request_id="fast")
        doomed = engine.submit(prompt, max_new_tokens=8,
                               request_id="doomed", deadline_ms=1)
        engine.step()                             # admit both, decode once
        import time as _t

        _t.sleep(0.01)                            # let the deadline lapse
        results = engine.run()
        assert results["doomed"]["finish_reason"] == REASON_DEADLINE
        assert len(results["doomed"]["tokens"]) < 8      # partial
        assert results["fast"]["finish_reason"] == REASON_LENGTH
        assert results["fast"]["tokens"] == reference_generate(
            model, params, prompt, 8)
        assert engine.allocator.free_blocks \
            == engine.inference_config.kv_blocks - 1
        engine.close()
        assert fast and doomed

    def test_config_deadline_applies_to_every_request(self,
                                                      model_and_params):
        model, params = model_and_params
        engine = InferenceEngine(
            model, params, config=serve_config(request_deadline_ms=1))
        rid = engine.submit(seeded_prompts(1, seed=42)[0],
                            max_new_tokens=8)
        import time as _t

        engine.step()
        _t.sleep(0.01)
        out = engine.run()[rid]
        assert out["finish_reason"] == REASON_DEADLINE
        engine.close()

    def test_prefill_raise_aborts_cleanly(self, model_and_params):
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config())
        initial_free = engine.allocator.free_blocks
        engine.submit(seeded_prompts(1, seed=43)[0], max_new_tokens=4,
                      request_id="r")

        def exploding_prefill(*a, **k):
            raise RuntimeError("chaos: prefill died")

        real = dict(engine._prefills)
        engine._prefills = {b: exploding_prefill for b in real}
        with pytest.raises(RuntimeError, match="prefill died"):
            engine.step()
        assert engine.allocator.free_blocks == initial_free
        assert engine.scheduler.slots \
            == [None] * engine.inference_config.max_batch_slots
        # the engine recovers once the fault clears: the aborted request
        # is gone from the queue (the router owns the retry), new ones run
        engine._prefills = real
        rid = engine.submit(seeded_prompts(1, seed=44)[0],
                            max_new_tokens=4)
        assert len(engine.run()[rid]["tokens"]) == 4
        engine.close()


# ---------------------------------------------------------------------------
# front-end: shedding, degradation, requeue, exactly-once
# ---------------------------------------------------------------------------

def _fleet(model_and_params, n=2, **cfg_overrides):
    model, params = model_and_params
    return [InferenceEngine(model, params,
                            config=serve_config(**cfg_overrides))
            for _ in range(n)]


class TestServingFrontend:
    def test_round_robin_completion_and_parity(self, model_and_params):
        model, params = model_and_params
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(6, seed=51)
        rids = [fe.submit(p, max_new_tokens=4) for p in prompts]
        results = fe.run()
        assert set(results) == set(rids)
        for rid, p in zip(rids, prompts):
            assert results[rid]["tokens"] == reference_generate(
                model, params, p, 4)
        # both replicas actually served
        assert all(e.generated_tokens > 0 for e in replicas)
        for e in replicas:
            e.close()

    def test_shed_at_max_queue_depth(self, model_and_params):
        replicas = _fleet(model_and_params, n=1, max_queue_depth=2)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(3, seed=52)
        fe.submit(prompts[0], max_new_tokens=2)
        fe.submit(prompts[1], max_new_tokens=2)
        with pytest.raises(ServingOverloadError) as err:
            fe.submit(prompts[2], max_new_tokens=2)
        assert err.value.queue_depth == 2
        assert err.value.max_queue_depth == 2
        assert fe.shed_total == 1
        results = fe.run()                 # the admitted two still finish
        assert len(results) == 2
        assert fe.resilience_receipt()["shed_requests"] == 1
        replicas[0].close()

    def test_degrade_caps_generation_under_pressure(self,
                                                    model_and_params):
        replicas = _fleet(model_and_params, n=1, max_queue_depth=8,
                          degrade_queue_depth=1,
                          degraded_max_new_tokens=2)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(3, seed=53)
        a = fe.submit(prompts[0], max_new_tokens=6)   # depth 0: full cap
        b = fe.submit(prompts[1], max_new_tokens=6)   # depth 1: capped
        c = fe.submit(prompts[2], max_new_tokens=1)   # already under cap
        assert fe.degraded_total == 1
        results = fe.run()
        assert len(results[a]["tokens"]) == 6
        assert len(results[b]["tokens"]) == 2
        assert len(results[c]["tokens"]) == 1
        replicas[0].close()

    def test_dead_replica_requeues_with_parity(self, model_and_params):
        model, params = model_and_params
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(6, seed=54)
        rids = [fe.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(2):
            fe.step()                      # both replicas mid-decode
        moved = fe.mark_dead(0)
        assert moved, "replica 0 should have owned in-flight work"
        results = fe.run()
        assert set(results) == set(rids)   # nothing lost, nothing doubled
        for rid, p in zip(rids, prompts):
            assert results[rid]["tokens"] == reference_generate(
                model, params, p, 6), (
                f"requeued request {rid} lost greedy determinism")
        receipt = fe.resilience_receipt()
        assert receipt["requeued_requests"] == len(moved)
        assert receipt["dead_replicas"] == 1
        assert receipt["recovery_latency_seconds"] is not None
        # the dead replica's allocator stayed conserved: its aborts
        # released every grant back to ITS pool
        assert replicas[0].allocator.free_blocks \
            == replicas[0].inference_config.kv_blocks - 1
        for e in replicas:
            e.close()

    def test_dead_replicas_program_in_flight_is_read_and_dropped(
            self, model_and_params):
        """A replica dies with a decode enqueued and unread: its
        requests move on, and when the dead engine is closed the
        program is read (nothing stays unread) and its tokens are
        booked to nobody — the requests live on another replica now."""
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        for p in seeded_prompts(4, seed=57):
            fe.submit(p, max_new_tokens=6)
        for _ in range(2):
            fe.step()
        dead = replicas[0]
        assert dead._unread, "replica 0 should have a program in flight"
        booked = dead.generated_tokens
        assert fe.mark_dead(0)
        dead.close()
        assert dead._unread == [] and dead.generated_tokens == booked
        assert dead.allocator.free_blocks == dead.allocator.capacity
        assert len(fe.run()) == 4
        replicas[1].close()

    def test_replica_that_raises_mid_step_is_evicted(self,
                                                     model_and_params):
        model, params = model_and_params
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(4, seed=55)
        rids = [fe.submit(p, max_new_tokens=4) for p in prompts]
        fe.step()

        def explode():
            raise RuntimeError("chaos: replica wedged")

        replicas[0].step = explode
        results = fe.run()
        assert set(results) == set(rids)
        assert fe.live_replicas() == [1]
        for rid, p in zip(rids, prompts):
            assert results[rid]["tokens"] == reference_generate(
                model, params, p, 4)
        for e in replicas:
            e.close()

    def test_finished_results_survive_the_death_unrecomputed(
            self, model_and_params):
        # a result the dead replica already materialized is DELIVERED,
        # never re-served (exactly-once)
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        prompts = seeded_prompts(2, seed=56)
        rids = [fe.submit(p, max_new_tokens=2) for p in prompts]
        while not all(fe.replicas[fe._owner[r]].request(r).state
                      == FINISHED for r in rids if r in fe._owner):
            fe.step()
            if not fe._owner:
                break
        dead_tokens = {rid: list(fe.results().get(rid, {}).get("tokens",
                                                               []))
                       for rid in rids}
        fe.mark_dead(0)
        assert fe.requeued_total == 0      # nothing was in flight
        results = fe.run() if (fe._owner or fe._backlog) else fe.results()
        assert set(results) == set(rids)
        for rid in rids:
            if dead_tokens[rid]:
                assert results[rid]["tokens"] == dead_tokens[rid]
        for e in replicas:
            e.close()

    def test_no_live_replicas_is_loud(self, model_and_params):
        replicas = _fleet(model_and_params, n=1)
        fe = ServingFrontend(replicas)
        fe.mark_dead(0)
        with pytest.raises(RuntimeError, match="no live replicas"):
            fe.submit(seeded_prompts(1, seed=57)[0], max_new_tokens=2)
        replicas[0].close()

    def test_deadline_counted_in_receipt(self, model_and_params):
        replicas = _fleet(model_and_params, n=1)
        fe = ServingFrontend(replicas)
        import time as _t

        fe.submit(seeded_prompts(1, seed=58)[0], max_new_tokens=8,
                  deadline_ms=1)
        fe.step()
        _t.sleep(0.01)
        fe.run()
        assert fe.resilience_receipt()["deadline_expired"] == 1
        replicas[0].close()


# ---------------------------------------------------------------------------
# the kill-at-every-step-k determinism sweep (satellite 4)
# ---------------------------------------------------------------------------

def test_kill_at_every_step_k_is_token_identical(model_and_params):
    """For EVERY step index k, killing replica 0 after k front-end
    iterations and requeuing its in-flight work onto the survivor
    yields the complete result set with tokens BIT-IDENTICAL to the
    uninterrupted reference — the greedy-determinism property the whole
    requeue design rests on."""
    model, params = model_and_params
    prompts = seeded_prompts(4, seed=61)
    reference = {i: reference_generate(model, params, p, 4)
                 for i, p in enumerate(prompts)}
    # enough iterations that the sweep crosses admission, prefill, and
    # every request's full decode on the victim
    for k in range(6):
        replicas = _fleet(model_and_params)
        fe = ServingFrontend(replicas)
        rids = [fe.submit(p, max_new_tokens=4, request_id=f"k{k}-r{i}")
                for i, p in enumerate(prompts)]
        for _ in range(k):
            fe.step()
        fe.mark_dead(0)
        results = fe.run()
        assert set(results) == set(rids), f"k={k}: lost/duplicated work"
        for i, rid in enumerate(rids):
            assert results[rid]["tokens"] == reference[i], (
                f"k={k}: request {rid} diverged after requeue")
        for e in replicas:
            e.close()
