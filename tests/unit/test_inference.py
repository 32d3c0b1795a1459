"""Serving subsystem tests: paged KV cache, continuous-batching
scheduler, and the InferenceEngine's acceptance guarantees —

- greedy decode through the cache is TOKEN-IDENTICAL to the naive
  one-request-at-a-time full-forward reference over staggered requests;
- the KV-cache donation materializes as ``input_output_alias`` on the
  decode program (``verify_programs()`` clean);
- the whole serve compiles at most ``len(prefill_buckets) + 1``
  programs (the bounded-retrace contract);
- serving telemetry + ledgers add ZERO host syncs over the serve loop's
  own next-token fetches.
"""

import json
import os

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import (NULL_BLOCK, BlockAllocator,
                                     ContinuousBatchScheduler,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine, Request,
                                     reference_generate)
from deepspeed_tpu.inference.scheduler import REASON_EOS, REASON_LENGTH
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadTPU

VOCAB = 256


def tiny_model():
    cfg = GPT2Config(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=64,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    return GPT2LMHeadTPU(cfg)


def serve_config(**inference_overrides):
    inf = {"kv_block_size": 8, "kv_blocks": 64, "max_batch_slots": 4,
           "max_seq_len": 64, "prefill_buckets": [8, 16, 32],
           "token_budget": 256, "max_new_tokens": 8}
    inf.update(inference_overrides)
    return {"inference": inf, "steps_per_print": 4}


def seeded_prompts(n, seed=42, lo=3, hi=30):
    rng = np.random.RandomState(seed)
    return [list(int(t) for t in rng.randint(0, VOCAB,
                                             size=rng.randint(lo, hi)))
            for _ in range(n)]


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_model()
    return model, model.init(jax.random.PRNGKey(0))


def count_device_gets(engine, prompts, monkeypatch, max_new_tokens):
    """Serve ``prompts`` step by step and return the number of
    ``jax.device_get`` calls made, after asserting that no ``step()`` —
    one that admits and prefills included — made more than one."""
    counts = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        counts["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    try:
        for i, p in enumerate(prompts):
            engine.submit(p, max_new_tokens=max_new_tokens,
                          request_id=f"r{i}")
        admitting_steps = 0
        while not engine.scheduler.idle():
            gets, admitted = counts["n"], engine.scheduler.admitted_total
            engine.step()
            assert counts["n"] - gets <= 1, "a step synced twice"
            admitting_steps += engine.scheduler.admitted_total > admitted
        assert admitting_steps >= 2     # prefills mid-run, not only first
        engine.run()
    finally:
        monkeypatch.setattr(jax, "device_get", real_get)
    engine.close()
    return counts["n"]


# ---------------------------------------------------------------- config
class TestInferenceConfig:
    def test_defaults(self):
        icfg = DeepSpeedInferenceConfig({})
        assert icfg.kv_block_size == 16
        assert icfg.max_seq_len % icfg.kv_block_size == 0
        assert icfg.prefill_buckets == tuple(sorted(icfg.prefill_buckets))
        assert icfg.max_blocks_per_seq \
            == icfg.max_seq_len // icfg.kv_block_size

    def test_bucket_for(self):
        icfg = DeepSpeedInferenceConfig(serve_config())
        assert icfg.bucket_for(1) == 8
        assert icfg.bucket_for(8) == 8
        assert icfg.bucket_for(9) == 16
        assert icfg.bucket_for(32) == 32
        with pytest.raises(ValueError):
            icfg.bucket_for(33)

    @pytest.mark.parametrize("bad", [
        {"max_seq_len": 60},               # not a multiple of block size
        {"prefill_buckets": [12]},         # bucket not block-aligned
        {"prefill_buckets": [128]},        # bucket beyond max_seq_len
        {"kv_blocks": 1},                  # only the null block
        {"weights_dtype": "float16"},      # unsupported serve dtype
    ])
    def test_invalid_rejected(self, bad):
        with pytest.raises((AssertionError, ValueError)):
            DeepSpeedInferenceConfig(serve_config(**bad))


# ------------------------------------------------------------- kv blocks
class TestBlockAllocator:
    def test_never_hands_out_null_block(self):
        alloc = BlockAllocator(8)
        got = alloc.allocate(7)
        assert got is not None and NULL_BLOCK not in got
        assert alloc.free_blocks == 0

    def test_no_partial_grant(self):
        alloc = BlockAllocator(4)
        assert alloc.allocate(5) is None
        assert alloc.free_blocks == 3  # nothing leaked by the refusal

    def test_release_recycles(self):
        alloc = BlockAllocator(4)
        got = alloc.allocate(3)
        alloc.release(got)
        assert alloc.free_blocks == 3
        assert alloc.allocate(3) is not None


# ------------------------------------------------------------- scheduler
class TestScheduler:
    def make(self, **overrides):
        icfg = DeepSpeedInferenceConfig(serve_config(**overrides))
        alloc = BlockAllocator(icfg.kv_blocks)
        return ContinuousBatchScheduler(icfg, alloc), alloc

    def test_submit_rejects_overflow(self):
        sched, _ = self.make()
        with pytest.raises(ValueError):
            # worst case exceeds max_seq_len
            sched.submit(Request("r", list(range(32)), 64))
        with pytest.raises(ValueError):
            # prompt exceeds the largest prefill bucket
            sched.submit(Request("r", list(range(40)), 2))

    def test_submit_rejects_budget_overflow_at_submit_time(self):
        """A request whose worst case exceeds the TOKEN BUDGET (not
        just max_seq_len) can never be admitted: FIFO admission would
        park it at the queue head and starve everything behind it
        forever.  Loud ValueError at submit, not a silent hang."""
        sched, _ = self.make(token_budget=32)
        with pytest.raises(ValueError, match="token_budget"):
            sched.submit(Request("r", [1] * 16, 32))   # worst 48 > 32
        assert sched.queue_depth == 0                  # nothing parked
        # exactly at the budget: queues and admits normally
        sched.submit(Request("ok", [1] * 16, 16))      # worst 32 == 32
        ok = sched.try_admit()
        assert ok is not None and ok.request_id == "ok"

    def test_fifo_admission_and_token_budget(self):
        sched, _ = self.make(token_budget=24)
        sched.submit(Request("a", [1] * 10, 8))   # worst case 18
        sched.submit(Request("b", [1] * 10, 8))   # would push to 36 > 24
        a = sched.try_admit()
        assert a is not None and a.request_id == "a"
        assert sched.try_admit() is None           # budget defers b
        assert sched.queue_depth == 1
        sched.finish(a, REASON_LENGTH)             # debt released...
        b = sched.try_admit()
        assert b is not None and b.request_id == "b"  # ...b admits

    def test_slot_recycling_mid_batch(self):
        sched, alloc = self.make()
        reqs = [Request(f"r{i}", [1] * 8, 4) for i in range(4)]
        for r in reqs:
            sched.submit(r)
        admitted = [sched.try_admit() for _ in range(4)]
        assert all(admitted) and sched.active_count == 4
        free_before = alloc.free_blocks
        sched.finish(admitted[1], REASON_EOS)      # middle slot finishes
        assert sched.active_count == 3
        assert alloc.free_blocks > free_before     # blocks came back
        assert sched.slots[admitted[1].slot] is None
        late = Request("late", [1] * 8, 4)
        sched.submit(late)
        again = sched.try_admit()                  # recycled slot reused
        assert again is late and again.slot == admitted[1].slot

    def test_block_table_row_padded_with_null(self):
        sched, _ = self.make()
        sched.submit(Request("r", [1] * 8, 4))
        r = sched.try_admit()
        row = sched.block_table_row(r)
        assert len(row) == sched.icfg.max_blocks_per_seq
        assert row[:len(r.blocks)] == r.blocks
        assert all(b == NULL_BLOCK for b in row[len(r.blocks):])

    def test_allocation_covers_worst_case(self):
        # bucket 16 but prompt+max_new = 10+20=30 -> 4 blocks of 8
        sched, _ = self.make()
        sched.submit(Request("r", [1] * 10, 20))
        r = sched.try_admit()
        assert len(r.blocks) == 4


# ---------------------------------------------------------------- engine
class TestInferenceEngine:
    def test_continuous_batching_token_parity(self, model_and_params):
        """THE acceptance test: 8 staggered seeded requests through the
        continuous batch are token-identical to the naive
        one-request-at-a-time full-forward reference."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config())
        prompts = seeded_prompts(8)
        # stagger: half up front, the rest submitted mid-serve so they
        # join a batch whose siblings are mid-generation
        for i, p in enumerate(prompts[:4]):
            engine.submit(p, max_new_tokens=8, request_id=f"r{i}")
        for _ in range(3):
            engine.step()
        for i, p in enumerate(prompts[4:], start=4):
            engine.submit(p, max_new_tokens=8, request_id=f"r{i}")
        results = engine.run()
        for i, p in enumerate(prompts):
            ref = reference_generate(model, params, p, 8)
            got = results[f"r{i}"]["tokens"]
            assert got == ref, (f"request r{i} (prompt len {len(p)}): "
                                f"cached decode {got} != reference {ref}")
            assert results[f"r{i}"]["finish_reason"] == REASON_LENGTH
        engine.close()

    def test_eos_stops_generation(self, model_and_params):
        model, params = model_and_params
        prompt = seeded_prompts(1, seed=7)[0]
        ref = reference_generate(model, params, prompt, 8)
        eos = ref[2]  # force an EOS hit mid-generation
        engine = InferenceEngine(model, params,
                                 config=serve_config(eos_token_id=eos))
        rid = engine.submit(prompt, max_new_tokens=8)
        out = engine.run()[rid]
        assert out["tokens"] == reference_generate(model, params, prompt,
                                                   8, eos_token_id=eos)
        assert out["finish_reason"] == REASON_EOS
        assert len(out["tokens"]) < 8
        engine.close()

    def test_kv_cache_donation_materializes(self, model_and_params,
                                            tmp_path):
        """DSP601/DSP603: the decode program's donated cache args must
        materialize as input_output_alias entries — a silently-copied
        KV cache is the bug this gate exists for."""
        model, params = model_and_params
        config = serve_config()
        config["telemetry"] = {"enabled": True, "run_dir": str(tmp_path)}
        engine = InferenceEngine(model, params, config=config)
        for i, p in enumerate(seeded_prompts(4, seed=3)):
            engine.submit(p, max_new_tokens=4, request_id=f"r{i}")
        engine.run()
        report = engine.verify_programs()
        assert report is not None
        assert report["programs_checked"] >= 2  # decode + >=1 prefill
        assert report["errors"] == 0, report["diagnostics"]
        assert report["violations"] == 0, report["diagnostics"]
        # and explicitly: the alias is in the decode HLO header
        compiled = engine.memory_ledger.compiled_programs()["serve_decode"]
        assert "input_output_alias" in compiled.as_text().split("\n", 1)[0]
        # the dumper landed offline-verifiable sidecars for every program
        dumped = sorted(os.listdir(tmp_path / "programs"))
        assert "serve_decode.hlo" in dumped
        assert any(f.startswith("serve_prefill_") and f.endswith(".json")
                   for f in dumped)
        engine.close()

    def test_bounded_retraces_compile_counter(self, model_and_params):
        """The whole serve compiles at most len(prefill_buckets) + 1
        programs, however many requests and lengths flow through — and a
        SECOND wave of new lengths adds zero."""
        model, params = model_and_params
        config = serve_config()
        config["profiling"] = {"memory_ledger": True}
        engine = InferenceEngine(model, params, config=config)
        limit = len(engine.inference_config.prefill_buckets) + 1
        # first wave deliberately covers every declared bucket
        lens = [5, 12, 30, 7, 14, 25]
        rng = np.random.RandomState(11)
        for i, n in enumerate(lens):
            prompt = [int(t) for t in rng.randint(0, VOCAB, size=n)]
            engine.submit(prompt, max_new_tokens=4, request_id=f"a{i}")
        engine.run()
        first_wave = set(engine.memory_ledger.entries())
        assert 0 < len(first_wave) <= limit, first_wave
        for i, p in enumerate(seeded_prompts(6, seed=12, lo=3, hi=31)):
            engine.submit(p, max_new_tokens=4, request_id=f"b{i}")
        engine.run()
        assert set(engine.memory_ledger.entries()) == first_wave
        engine.close()

    def test_zero_added_host_syncs(self, model_and_params, tmp_path,
                                   monkeypatch):
        """Serving observability (telemetry + both ledgers + program
        dumper, print cadence every iteration) rides the serve loop's
        own next-token fetches: the jax.device_get count is IDENTICAL
        with it all on and all off — and no ``step()`` makes more than
        ONE, the steps that admit (two slots for six requests: prefills
        all along the run) included."""
        model, params = model_and_params
        prompts = seeded_prompts(6, seed=5)

        def count_gets(config):
            config["inference"]["max_batch_slots"] = 2
            engine = InferenceEngine(model, params, config=config)
            return count_device_gets(
                engine, prompts, monkeypatch, max_new_tokens=4)

        base_cfg = serve_config()
        base_cfg["steps_per_print"] = 1
        base = count_gets(base_cfg)
        # the FULL observability plane armed: lifecycle tracing +
        # occupancy/goodput windows ride automatically with telemetry,
        # and the slo block arms the per-token conformance legs — all
        # of it host arithmetic over values the loop already fetched
        tel_cfg = serve_config(slo={"ttft_ms": 100, "per_token_ms": 50})
        tel_cfg["steps_per_print"] = 1
        tel_cfg["telemetry"] = {"enabled": True,
                                "run_dir": str(tmp_path / "t")}
        tel = count_gets(tel_cfg)
        assert base > 0
        assert tel == base, (f"serving observability added host syncs: "
                             f"{tel} device_get calls vs {base} baseline")

    def test_per_token_latency_is_the_gap_a_request_sees(
            self, model_and_params):
        """With an injected clock (a prefill program costs 1 s, a decode
        0.1 s, charged where the program is enqueued): a request whose
        neighbour is prefilled between two of its tokens reports the
        1.1 s gap, not the decode call's 0.1 s; the TTFT is not among the
        per-token latencies; and the per-token SLO leg judges each token
        by its own gap.  A token is stamped when the host reads it, one
        enqueue after its own: the neighbour's prefill lies between the
        reads of ``first``'s second and third tokens, and the last token
        is read by a step that enqueues nothing (the clock stands)."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            slo={"ttft_ms": 0, "per_token_ms": 500}))
        now = [50.0]
        engine._clock = lambda: now[0]

        def costing(program, seconds):
            def run(*args):
                now[0] += seconds
                return program(*args)
            return run

        engine._decode = costing(engine._decode, 0.1)
        for bucket in list(engine._prefills):
            engine._prefills[bucket] = costing(engine._prefills[bucket],
                                               1.0)
        first = engine.request(engine.submit([3, 4, 5], max_new_tokens=6))
        engine.step()
        engine.step()
        second = engine.request(engine.submit([6, 7, 8, 9],
                                              max_new_tokens=3))
        engine.run()
        assert first.step_times == pytest.approx([0.1, 1.1, 0.1, 0.1, 0.0])
        assert second.step_times == pytest.approx([0.1, 0.1])
        for request in (first, second):
            assert len(request.step_times) == len(request.generated) - 1
        assert first.result()["per_token_p99_seconds"] == pytest.approx(1.1)
        assert first.result()["per_token_p50_seconds"] == pytest.approx(0.1)
        receipt = engine.serving_receipt()
        assert receipt["per_token_p99_seconds"] == pytest.approx(1.1)
        # nine tokens in all; the one that waited behind the prefill
        # misses the 500 ms per-token SLO, its neighbour's does not
        assert receipt["goodput_tokens"] == 8
        assert receipt["slo_attainment"] == pytest.approx(8 / 9)
        engine.close()

    def test_serving_events_and_receipt(self, model_and_params, tmp_path):
        model, params = model_and_params
        config = serve_config()
        config["steps_per_print"] = 2
        config["telemetry"] = {"enabled": True, "run_dir": str(tmp_path)}
        engine = InferenceEngine(model, params, config=config)
        for i, p in enumerate(seeded_prompts(4, seed=9)):
            engine.submit(p, max_new_tokens=6, request_id=f"r{i}")
        engine.run()
        receipt = engine.serving_receipt()
        assert receipt["requests"] == 4
        assert receipt["generated_tokens"] == 4 * 6
        assert receipt["per_token_p50_seconds"] > 0
        assert receipt["per_token_p99_seconds"] \
            >= receipt["per_token_p50_seconds"]
        assert receipt["ttft_p50_seconds"] > 0
        assert receipt["tokens_per_second_per_chip"] > 0
        # the decode comm/attribution receipts resolve to serve_decode
        assert engine.comm_receipt()["program"] == "serve_decode"
        attribution = engine.attribution_receipt()
        assert attribution["program"] == "serve_decode"
        assert attribution["measured_step_seconds"] > 0
        assert set(attribution["phases"]) == {
            "compute", "exposed_collective", "host_stream", "driver",
            "unexplained"}
        engine.close()
        events = [json.loads(line) for line in
                  open(tmp_path / "events-rank0.jsonl")]
        kinds = {e["data"].get("kind") for e in events
                 if e["type"] == "serving"}
        assert {"admit", "finish", "queue"} <= kinds
        assert any(e["type"] == "attribution" for e in events)
        # the offline doctor reconstructs the SAME phase table from the
        # run dir alone: serve_decode priced as the step program, with a
        # measured side from the comm/latency snapshots
        from deepspeed_tpu.profiling.doctor import doctor_run_dir

        verdict = doctor_run_dir(str(tmp_path))
        assert verdict["budget"]["program"] == "serve_decode"
        assert verdict["ranks"], "doctor found no measured latency"
        rank0 = verdict["ranks"]["rank0"]
        assert rank0["measured_step_seconds"] > 0
        assert set(rank0["phases"]) == {
            "compute", "exposed_collective", "host_stream", "driver",
            "unexplained"}

    def test_bf16_weight_ingestion(self, model_and_params):
        import jax.numpy as jnp

        model, params = model_and_params
        engine = InferenceEngine(
            model, params, config=serve_config(weights_dtype="bfloat16"))
        leaves = jax.tree_util.tree_leaves(engine.params)
        assert all(l.dtype == jnp.bfloat16 for l in leaves)
        assert engine._caches[0].dtype == jnp.bfloat16
        rid = engine.submit(seeded_prompts(1, seed=2)[0],
                            max_new_tokens=4)
        out = engine.run()[rid]
        assert len(out["tokens"]) == 4
        assert all(0 <= t < VOCAB for t in out["tokens"])
        engine.close()

    def test_strict_config_rejects_unknown_keys(self, model_and_params):
        model, params = model_and_params
        config = serve_config()
        config["inference"]["kv_block_sise"] = 8  # typo
        config["strict_config"] = True
        with pytest.raises(ValueError, match="kv_block_sise"):
            InferenceEngine(model, params, config=config)


# ------------------------------------------------- one program in flight
def recording(program, calls):
    """``program`` with the positional arguments of every call kept."""
    def run(*args):
        calls.append(args)
        return program(*args)
    return run


def all_blocks_free(engine):
    return engine.allocator.free_blocks == engine.allocator.capacity


class TestOneProgramInFlight:
    """The pipelined serve loop: a program's tokens stay on the device as
    the next decode's input and are read one enqueue late."""

    @pytest.mark.parametrize("slots,caps", [
        (2, [1, 2, 5, 8, 3, 4, 8, 1]),      # recycled; caps of 1 and 2
        (3, [8, 7, 6, 5, 4, 3, 2]),
        (1, [3, 1, 4]),                     # one slot: every edge in turn
    ], ids=["2-slots", "3-slots", "1-slot"])
    def test_tokens_match_the_reference_with_slots_recycled(
            self, model_and_params, slots, caps):
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            max_batch_slots=slots))
        prompts = seeded_prompts(len(caps), seed=17 + slots)
        for i, (p, cap) in enumerate(zip(prompts, caps)):
            engine.submit(p, max_new_tokens=cap, request_id=f"r{i}")
        results = engine.run()
        for i, (p, cap) in enumerate(zip(prompts, caps)):
            assert results[f"r{i}"]["tokens"] == reference_generate(
                model, params, p, cap), f"r{i}"
            assert results[f"r{i}"]["finish_reason"] == REASON_LENGTH
        assert engine.generated_tokens == sum(caps)
        assert all_blocks_free(engine) and engine._unread == []
        engine.close()

    @pytest.mark.parametrize("eos_at", [0, 2], ids=["prefill-token",
                                                    "third-token"])
    def test_eos_is_seen_one_iteration_late_and_the_overshoot_dropped(
            self, model_and_params, eos_at):
        """The decode after the program that produced EOS (a decode, or
        the prefill itself) is already enqueued when the host sees it:
        its token is dropped, the request ends AT the EOS, and the blocks
        come back."""
        model, params = model_and_params
        prompts = seeded_prompts(2, seed=7)
        refs = [reference_generate(model, params, p, 8) for p in prompts]
        eos = refs[0][eos_at]
        engine = InferenceEngine(model, params,
                                 config=serve_config(eos_token_id=eos))
        calls = []
        engine._decode = recording(engine._decode, calls)
        rids = [engine.submit(p, max_new_tokens=8) for p in prompts]
        first = engine.request(rids[0])
        out = engine.run()
        for rid, p in zip(rids, prompts):
            want = reference_generate(model, params, p, 8, eos_token_id=eos)
            assert out[rid]["tokens"] == want
        assert out[rids[0]]["finish_reason"] == REASON_EOS
        assert out[rids[0]]["tokens"][-1] == eos
        n = len(out[rids[0]]["tokens"])
        assert n < 8
        # one decode went past the EOS (its position was handed to the
        # program), inside the grant; its token was booked to nobody
        positions = [int(np.asarray(c[3])[0]) for c in calls]
        assert len(prompts[0]) + n - 1 in positions
        assert first.dispatched == n + 1
        assert engine.generated_tokens == sum(
            len(r["tokens"]) for r in out.values())
        assert all_blocks_free(engine)
        engine.close()

    def test_a_request_at_its_cap_is_never_dispatched_again(
            self, model_and_params):
        """The positions handed to ``decode``: each request's run from
        its prompt's length to one short of its worst case, then 0 over a
        null table row (the slot parks) while its last token is read."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            max_batch_slots=2))
        calls = []
        engine._decode = recording(engine._decode, calls)
        prompts = seeded_prompts(2, seed=23, lo=4, hi=12)
        caps = [3, 6]
        for i, (p, cap) in enumerate(zip(prompts, caps)):
            engine.submit(p, max_new_tokens=cap, request_id=f"r{i}")
        engine.run()
        for slot, (p, cap) in enumerate(zip(prompts, caps)):
            handed = [int(np.asarray(c[3])[slot]) for c in calls]
            live = [x for x in handed if x]
            assert live == list(range(len(p), len(p) + cap - 1))
            assert max(live) < len(p) + cap - 1 < 64
            # parked: position 0 AND the null block in every table entry
            parked = [c for c, x in zip(calls, handed) if not x]
            assert parked or cap == max(caps)
            for c in parked:    # c[2]: one table a cache group
                assert (np.asarray(c[2][0])[slot] == NULL_BLOCK).all()
        assert len(calls) == max(caps) - 1
        engine.close()

    @pytest.mark.parametrize("how", ["deadline", "abort", "requeue"])
    def test_a_lane_in_flight_is_never_booked_to_the_slots_next_owner(
            self, model_and_params, how):
        """One slot: ``a`` leaves it with a program in flight and ``b``
        (or ``a`` itself, requeued) takes the slot — and with it the
        freed blocks — in the very next step."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            max_batch_slots=1))
        pa, pb = seeded_prompts(2, seed=29)
        a = engine.request(engine.submit(pa, max_new_tokens=8,
                                         request_id="a"))
        b = engine.request(engine.submit(pb, max_new_tokens=5,
                                         request_id="b"))
        for _ in range(3):
            engine.step()
        assert engine._unread and a.dispatched == len(a.generated) + 1
        held = list(a.generated)
        if how == "deadline":
            a.deadline_at = 0.0                 # long past
        else:
            engine.scheduler.abort(a)
            if how == "requeue":
                a.reset_for_requeue()
                engine.resubmit(a)
        engine.step()
        assert engine.scheduler.slots[0] is b and b.dispatched == 2
        if how == "deadline":
            assert a.finish_reason == "deadline" and a.generated == held
        engine.run()
        assert b.generated == reference_generate(model, params, pb, 5)
        if how == "requeue":
            assert a.generated == reference_generate(model, params, pa, 8)
            assert a.requeues == 1
        served = [r for r in (a, b) if r.state == "finished"]
        assert engine.generated_tokens >= sum(
            len(r.generated) for r in served)
        assert all_blocks_free(engine) and engine._unread == []
        engine.close()

    @pytest.mark.parametrize("leave", ["run", "drain", "drain_deadline",
                                       "close", "idle_then_resume"])
    def test_nothing_is_left_unread(self, model_and_params, leave):
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config())
        prompts = seeded_prompts(3, seed=37)
        requests = [engine.request(engine.submit(p, max_new_tokens=6))
                    for p in prompts]
        for _ in range(2):
            engine.step()
        assert engine._unread                   # a program in flight
        if leave == "drain":
            done = engine.drain(deadline_secs=0)        # unbounded
            assert sorted(r.request_id for r in done) == sorted(
                r.request_id for r in requests)
        elif leave == "drain_deadline":
            engine.drain(deadline_secs=1e-9)    # abandons them at once
            # what was in flight was read all the same
            assert all(r.dispatched == len(r.generated) for r in requests)
        elif leave == "close":
            engine.close()
        else:
            engine.run()
        assert engine._unread == []
        if leave != "drain_deadline":
            for p, r in zip(prompts, requests):
                assert r.generated == reference_generate(model, params, p, 6)
            assert all_blocks_free(engine)
        if leave == "idle_then_resume":
            for _ in range(3):
                assert engine.step() == []      # idle steps: nothing to do
            ahead, iterations = (engine.decodes_enqueued_ahead,
                                 engine.decode_iterations)
            late = seeded_prompts(1, seed=41)[0]
            rid = engine.submit(late, max_new_tokens=4)
            assert engine.run()[rid]["tokens"] == reference_generate(
                model, params, late, 4)
            # the pipe was empty: the first decode had nothing ahead of it
            assert engine.decode_iterations - iterations == 3
            assert engine.decodes_enqueued_ahead - ahead == 2
        engine.close()
        assert engine._unread == []

    def test_host_tables_are_not_written_under_an_unread_program(
            self, model_and_params):
        """What a program was handed is what it reads, whenever it runs:
        the tables on the device share no memory with the host rows the
        loop keeps writing, and every array handed over still holds what
        it held at its enqueue after the serve has moved on."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            max_batch_slots=2))
        handed = []

        def keeping(program, which):
            def run(*args):
                for i in which:
                    # the tables come as a tuple, one a cache group
                    for arg in (args[i] if isinstance(args[i], tuple)
                                else (args[i],)):
                        assert not np.shares_memory(np.asarray(arg),
                                                    engine._tables[0])
                        handed.append((arg, np.array(arg, copy=True)))
                return program(*args)
            return run

        engine._decode = keeping(engine._decode, (2, 3))
        for bucket in list(engine._prefills):
            engine._prefills[bucket] = keeping(engine._prefills[bucket],
                                               (2, 4))
        for i, p in enumerate(seeded_prompts(5, seed=43)):
            engine.submit(p, max_new_tokens=3 + i)
        engine.run()
        assert len(handed) > 20
        for array, then in handed:
            np.testing.assert_array_equal(np.asarray(array), then)
        engine.close()

    def test_decode_is_enqueued_before_the_last_ones_tokens_are_read(
            self, model_and_params, monkeypatch):
        """On a clock that only the programs move: ``decode`` k is
        entered before the read that returns decode k-1's tokens, every
        step reads once, and the host's ``generated`` runs one token
        behind what was dispatched while the pipe is full."""
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config())
        clock, events = [100.0], []
        engine._clock = lambda: clock[0]
        decode, real_get = engine._decode, jax.device_get

        def entered(*args):
            clock[0] += 0.01
            events.append("decode")
            return decode(*args)

        def reading(x):
            events.append("read")
            return real_get(x)

        engine._decode = entered
        monkeypatch.setattr(jax, "device_get", reading)
        request = engine.request(engine.submit([5, 6, 7], max_new_tokens=6))
        for k in range(1, 6):
            engine.step()
            assert events == ["decode", "read"] * k
            assert request.dispatched == k + 1
            assert len(request.generated) == k
        assert engine.step() == [request]       # the pipe drains: a read
        assert events == ["decode", "read"] * 5 + ["read"]
        monkeypatch.setattr(jax, "device_get", real_get)
        assert request.generated == reference_generate(
            model, params, [5, 6, 7], 6)
        # stamped when read: the first token one decode call after the
        # prefill's enqueue, the others a decode call apart, the last by
        # the step that enqueued nothing
        assert request.first_token_at == pytest.approx(100.01)
        assert request.step_times == pytest.approx(
            [0.01, 0.01, 0.01, 0.01, 0.0])
        engine.close()

    def test_a_prefill_that_raises_releases_slot_and_grant(
            self, model_and_params):
        model, params = model_and_params
        engine = InferenceEngine(model, params, config=serve_config(
            max_batch_slots=2))
        ok = engine.request(engine.submit([1, 2, 3], max_new_tokens=5))
        engine.step()
        engine.step()
        real = dict(engine._prefills)

        def exploding(*args):
            raise RuntimeError("injected prefill fault")

        engine._prefills = {b: exploding for b in real}
        engine.submit([4, 5, 6, 7], max_new_tokens=5, request_id="bad")
        with pytest.raises(RuntimeError, match="injected"):
            engine.step()
        assert engine.scheduler.active_count == 1
        engine._prefills = real
        engine.scheduler.waiting.clear()
        engine.run()
        # the neighbour's program in flight was not lost to the fault
        assert ok.generated == reference_generate(model, params,
                                                  [1, 2, 3], 5)
        assert all_blocks_free(engine)
        engine.close()

    def test_enqueued_ahead_share_gauge_reads_one_over_a_busy_run(
            self, model_and_params, tmp_path):
        model, params = model_and_params
        config = serve_config(max_batch_slots=2)
        config["steps_per_print"] = 3
        config["telemetry"] = {"enabled": True, "run_dir": str(tmp_path)}
        engine = InferenceEngine(model, params, config=config)
        # caps staggered so that the two slots never finish together
        # (that would drain the pipe, as an idle engine does)
        for p, cap in zip(seeded_prompts(6, seed=47), [9, 4, 6, 7, 5, 8]):
            engine.submit(p, max_new_tokens=cap)
        gauge = engine.telemetry.registry.gauge(
            "serving/enqueued_ahead_share")
        seen = []
        while not engine.scheduler.idle():
            engine.step()
            if engine.decode_iterations % 3 == 0:
                seen.append(gauge.value)
        # the first window holds the first decode, which had nothing
        # ahead of it; every later one is full
        assert seen[0] == pytest.approx(2 / 3)
        assert len(seen) > 4 and set(seen[1:]) == {1.0}, seen
        assert engine.decodes_enqueued_ahead == engine.decode_iterations - 1
        engine.close()
