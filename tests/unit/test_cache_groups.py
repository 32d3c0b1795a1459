"""Cache groups in the scheduler: a pool a group, a request's blocks of
every group granted together at admission and released together, a window
group's grant the same whatever the request's length (host bookkeeping
alone: nothing here touches a device)."""

import pytest

from deepspeed_tpu.inference import (BlockAllocator,
                                     ContinuousBatchScheduler,
                                     DeepSpeedInferenceConfig, NULL_BLOCK,
                                     Request)
from deepspeed_tpu.inference.kv_cache import CacheGroup


def make(slots=3, ring=3, full_blocks=64):
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 8, "kv_blocks": full_blocks,
        "max_batch_slots": slots, "max_seq_len": 64,
        "prefill_buckets": [8, 16, 32], "token_budget": 512}})
    groups = [CacheGroup("full", 1, {"k": 16, "v": 16}),
              CacheGroup("window", 4, {"wk": 16, "wv": 16}, pages=ring)]
    pools = [BlockAllocator(g.num_blocks(icfg), g.pages) for g in groups]
    return ContinuousBatchScheduler(icfg, pools), pools, groups, icfg


def conserved(pools):
    return all(p.free_blocks == p.capacity for p in pools)


def test_a_groups_pool_and_table_follow_its_span():
    _, pools, groups, icfg = make()
    assert [g.num_blocks(icfg) for g in groups] == [64, 3 * 3 + 1]
    assert [g.table_width(icfg) for g in groups] == [8, 3]
    assert [p.capacity for p in pools] == [63, 9]
    assert [p.pages_per_request for p in pools] == [None, 3]


@pytest.mark.parametrize("prompt,answer", [(3, 2), (8, 8), (30, 34)])
def test_a_window_groups_grant_does_not_depend_on_the_request(prompt,
                                                               answer):
    sched, pools, _, _ = make()
    sched.submit(Request("r", [1] * prompt, answer))
    r = sched.try_admit()
    full, window = r.grants
    assert len(window) == 3
    assert len(full) == max(-(-(prompt + answer) // 8), r.bucket // 8)
    assert r.blocks is full
    assert NULL_BLOCK not in full + window
    row = sched.block_table_row(r, 1)
    assert row == window and len(sched.block_table_row(r)) == 8
    sched.finish(r, "max_new_tokens")
    assert r.grants == () and r.blocks == [] and conserved(pools)


def test_blocks_of_both_pools_are_conserved_over_a_requests_lives():
    sched, pools, _, _ = make()
    for i in range(5):
        sched.submit(Request(f"r{i}", [1] * (5 + 6 * i), 8))
    admitted = [sched.try_admit() for _ in range(3)]
    assert all(admitted) and sched.try_admit() is None      # no slot
    assert pools[1].free_blocks == 0
    assert pools[0].used_blocks == sum(len(r.grants[0]) for r in admitted)
    # finish one: both of its grants come back, the next one is admitted
    sched.finish(admitted[0], "max_new_tokens")
    assert pools[1].free_blocks == 3
    fourth = sched.try_admit()
    assert fourth.request_id == "r3" and len(fourth.grants[1]) == 3
    # abort one mid-flight and requeue it: nothing strands, and its next
    # grant is fresh in both pools
    victim = admitted[1]
    stale = victim.grants
    sched.abort(victim)
    assert victim.grants == () and pools[1].free_blocks == 3
    victim.reset_for_requeue()
    sched.submit(victim)
    last = sched.try_admit()
    assert last.request_id == "r4"
    assert sched.try_admit() is None
    for r in list(sched.active_requests()):
        sched.finish(r, "max_new_tokens")
    again = sched.try_admit()
    assert again is victim and again.grants is not stale
    sched.finish(again, "max_new_tokens")
    assert conserved(pools) and sched.idle()


def test_a_grant_is_whole_or_nothing():
    """The window pool is spent (a slot's ring taken from outside): the
    full pool's part of the grant goes back, the request stays queued."""
    sched, pools, _, _ = make()
    taken = pools[1].allocate(7)             # 2 of 9 left: no ring of 3
    sched.submit(Request("r", [1] * 10, 6))
    assert sched.try_admit() is None
    assert pools[0].free_blocks == pools[0].capacity
    assert sched.queue_depth == 1
    pools[1].release(taken)
    assert sched.try_admit().request_id == "r"


def test_live_blocks_by_group():
    sched, _, _, _ = make()
    assert sched.live_blocks(0) == sched.live_blocks(1) == 0
    for slot, (n_prompt, n_dispatched) in enumerate([(1, 0), (8, 1)]):
        request = Request(f"r{slot}", list(range(n_prompt)), 16)
        request.dispatched = n_dispatched
        sched.slots[slot] = request
    # the full layer's pages follow the context; a window layer reads
    # each advancing slot's whole ring
    assert sched.live_blocks(0) == 1 + 2
    assert sched.live_blocks(1) == 2 * 3
    sched.slots[1].dispatched = 16          # parked: read by no decode
    assert sched.live_blocks(0) == 1 and sched.live_blocks(1) == 3


def test_one_pool_is_the_scheduler_every_other_model_has():
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 8, "kv_blocks": 16, "max_batch_slots": 2,
        "max_seq_len": 32, "prefill_buckets": [8], "token_budget": 64}})
    pool = BlockAllocator(icfg.kv_blocks)
    sched = ContinuousBatchScheduler(icfg, pool)
    assert sched.allocator is pool and sched.allocators == [pool]
    sched.submit(Request("r", [1] * 4, 4))
    r = sched.try_admit()
    assert r.grants == (r.blocks,) and len(r.blocks) == 1
