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
        with it all on and all off."""
        model, params = model_and_params
        prompts = seeded_prompts(4, seed=5)

        def count_gets(config):
            engine = InferenceEngine(model, params, config=config)
            counts = {"n": 0}
            real_get = jax.device_get

            def counting_get(x):
                counts["n"] += 1
                return real_get(x)

            monkeypatch.setattr(jax, "device_get", counting_get)
            try:
                for i, p in enumerate(prompts):
                    engine.submit(p, max_new_tokens=4,
                                  request_id=f"r{i}")
                engine.run()
            finally:
                monkeypatch.setattr(jax, "device_get", real_get)
            engine.close()
            return counts["n"]

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
        0.1 s): a request whose neighbour is prefilled between two of its
        tokens reports the 1.1 s gap, not the decode call's 0.1 s; the
        TTFT is not among the per-token latencies; and the per-token SLO
        leg judges each token by its own gap."""
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
        assert first.step_times == pytest.approx([0.1, 0.1, 1.1, 0.1, 0.1])
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
