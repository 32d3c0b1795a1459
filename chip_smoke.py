#!/usr/bin/env python
"""Chip smoke: the system's main paths, once, on the attached TPU.

    python chip_smoke.py                  # one chip: train + serve
    python chip_smoke.py --phase offload  # one chip: ZeRO-Offload alone
    python chip_smoke.py --chips 4        # four chips: ZeRO-2/3 data parallel

One process, which imports jax itself and starts no child.  It fails
(non-zero exit, no result line) when jax finds no TPU.  Every phase goes
through the entry points a user calls — ``deepspeed.initialize`` +
``engine.train_batch``, ``InferenceEngine.submit``/``step`` — at the full
width of a model the repo supports, with seeded random weights, and checks
what comes out by the repo's own means.  A phase that fails raises: there
is no ``except`` that records the error and carries on.

Each phase prints one JSON line (name, seconds, compile seconds, what it
checked); the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The framework's log lines go to stderr.  This is a bring-up check and the
quickest proof that the system still starts on the chip — its seconds are
set-up observations, not benchmark metrics.

The phase functions take their sizes as arguments so that
``tests/unit/test_chip_smoke.py`` can walk the same code at a tiny size on
the CPU; the defaults are the real sizes and ``main`` passes nothing else.
"""

import argparse
import gc
import importlib.metadata
import json
import logging
import math
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# dp=N against dp=1 on the same global batches: the tolerance of
# __graft_entry__._assert_loss_parity (a mean-vs-sum collective bug scales
# the loss by the dp degree, orders of magnitude outside this band)
PARITY_RTOL = 1e-3
PARITY_ATOL = 1e-4
# offload against device-resident at step 1: the same forward on the same
# initial parameters, so the losses agree to well within bf16's 2^-8
OFFLOAD_RTOL = 2.0 ** -8


def emit(record):
    print(json.dumps(record), flush=True)
    return record


def device_line():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_hbm_bytes(devices):
    """Process-lifetime peak per device, or None where the backend keeps
    no such statistic (the CPU test mesh)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


class _GeometryLog(logging.Handler):
    """Collects the flash kernel's one-per-shape geometry lines."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("flash_attention geometry:"):
            self.lines.append(msg[len("flash_attention geometry: "):])

    def drain(self):
        lines, self.lines = self.lines, []
        return lines


def _compile_delta(stats, before):
    after = stats.as_dict()
    return {"compile_seconds": round(after["compile_seconds_cold"]
                                     - before["compile_seconds_cold"], 3),
            "cache_hits": after["compile_cache_hits"]
            - before["compile_cache_hits"],
            "cache_misses": after["compile_cache_misses"]
            - before["compile_cache_misses"]}


def _free():
    """After an engine's last reference is gone: collect its cycles and
    drop its executables before the next engine is built (two co-resident
    engines do not fit the chip)."""
    import jax

    gc.collect()
    jax.clear_caches()


def _loss(value):
    import jax

    v = float(jax.device_get(value))
    if not math.isfinite(v):
        raise AssertionError(f"loss is not finite: {v}")
    return v


def _step_program_text(engine):
    return engine.memory_ledger.compiled_programs()["train_step"].as_text()


def _train(name, model, config, mesh, batches, order, stats, geometry,
           expect_kernel, fence_check=False):
    """``len(order)`` steps of ``engine.train_batch`` over ``batches`` in
    ``order``.  Checks: every loss finite; each batch that repeats has a
    lower loss the last time than the first; the Pallas kernel is in the
    compiled step; nothing compiles after the first step."""
    import jax

    import deepspeed_tpu as deepspeed

    t0 = time.perf_counter()
    before = stats.as_dict()
    engine, *_ = deepspeed.initialize(model=model, config=config, mesh=mesh)
    losses = [_loss(engine.train_batch(iter([batches[order[0]]])))]
    first_step_seconds = time.perf_counter() - t0
    programs_after_first = stats.programs
    compile_receipt = _compile_delta(stats, before)
    step_seconds = []
    for i in order[1:]:
        t1 = time.perf_counter()
        losses.append(_loss(engine.train_batch(iter([batches[i]]))))
        step_seconds.append(time.perf_counter() - t1)
    compiled_later = stats.programs - programs_after_first
    if compiled_later:
        raise AssertionError(
            f"{name}: {compiled_later} program(s) compiled after the first "
            f"step ({stats.by_program})")
    for b in set(order):
        seen = [loss for i, loss in zip(order, losses) if i == b]
        if len(seen) > 1 and not seen[-1] < seen[0]:
            raise AssertionError(
                f"{name}: loss on repeated batch {b} did not fall: {seen}")
    has_kernel = "tpu_custom_call" in _step_program_text(engine)
    if has_kernel != expect_kernel:
        raise AssertionError(
            f"{name}: Pallas kernel in the compiled step: {has_kernel}, "
            f"expected {expect_kernel} — attention dispatch went the "
            "other way")
    record = {"phase": name, "steps": len(order),
              "losses": [round(v, 4) for v in losses],
              "pallas_kernel_in_step": has_kernel,
              "flash_geometries": geometry.drain(),
              "compiled_after_first_step": compiled_later,
              "first_step_seconds": round(first_step_seconds, 3),
              "step_seconds_median": round(
                  statistics.median(step_seconds), 4),
              **compile_receipt}
    if fence_check:
        # the same step timed to block_until_ready and to device_get of
        # the loss: equal where block_until_ready fences, as every timing
        # in this repo assumes of device_get
        bur, get = [], []
        batch = batches[order[0]]
        for _ in range(3):
            t1 = time.perf_counter()
            jax.block_until_ready(engine.train_batch(iter([batch])))
            bur.append(time.perf_counter() - t1)
            t1 = time.perf_counter()
            jax.device_get(engine.train_batch(iter([batch])))
            get.append(time.perf_counter() - t1)
        record["fence_check"] = {
            "block_until_ready_seconds": round(statistics.median(bur), 4),
            "device_get_seconds": round(statistics.median(get), 4)}
    record["peak_hbm_bytes"] = peak_hbm_bytes(mesh.devices.flat)
    record["seconds"] = round(time.perf_counter() - t0, 3)
    engine.close()
    del engine
    _free()
    return emit(record)


def train_bert(mesh, seed, stats, geometry, cfg=None, batch=8, seq=512,
               n_pred=80, steps=5, expect_kernel=True):
    """BERT-large pretraining steps in bing_bert's phase-2 shape: seq 512,
    dropout 0.1, ``max_predictions_per_seq``, bf16, Adam."""
    from deepspeed_tpu.models import BertConfig, BertForPreTrainingTPU

    if cfg is None:
        cfg = BertConfig.bert_large(
            max_position_embeddings=512, vocab_size=30528,
            hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
            max_predictions_per_seq=n_pred)
    rng = np.random.default_rng(seed)

    def one_batch():
        ids = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(
            np.int32)
        labels = np.full((batch, seq), -100, np.int32)
        for r in range(batch):  # exactly n_pred labelled positions a row
            pos = rng.permutation(seq)[:n_pred]
            labels[r, pos] = ids[r, pos]
        return {"input_ids": ids,
                "attention_mask": np.ones((batch, seq), np.int32),
                "token_type_ids": np.zeros((batch, seq), np.int32),
                "masked_lm_labels": labels,
                "next_sentence_labels": rng.integers(
                    0, 2, size=(batch,)).astype(np.int32)}

    # lr: with no warm-up the post-LN stack overshoots at lr 1e-4
    # (on the chip the loss went 11.34, 11.45, 13.53 before it fell);
    # 1e-5 descends from the first step, ~0.1 a step, far above the
    # dropout noise of 640 predicted tokens
    config = {"train_batch_size": batch, "steps_per_print": 10 ** 9,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-5}},
              "bf16": {"enabled": True},
              "profiling": {"memory_ledger": True}}
    return _train(
        "train.bert_large", BertForPreTrainingTPU(cfg, compute_dtype=None),
        config, mesh, [one_batch(), one_batch()],
        [i % 2 for i in range(steps)], stats, geometry, expect_kernel,
        fence_check=True)


def train_gpt2(mesh, seed, stats, geometry, cfg=None, batch=4, seq=1024,
               steps=3, expect_kernel=True):
    """GPT-2-medium steps in the ZeRO-2 + Lamb + bf16 configuration of
    the round-5 bench's third row (the causal single-tile kernel)."""
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU

    if cfg is None:
        cfg = GPT2Config.gpt2_medium(
            max_position_embeddings=seq, embd_dropout=0.1, attn_dropout=0.1,
            resid_dropout=0.1)
    rng = np.random.default_rng(seed)
    batches = [{"input_ids": rng.integers(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)}
        for _ in range(2)]
    # Lamb scales each tensor's step to lr * |w|: 5e-3 moves the weights
    # about as far per step as Adam's 1e-4 does at init scale 0.02
    config = {"train_batch_size": batch, "steps_per_print": 10 ** 9,
              "optimizer": {"type": "Lamb", "params": {"lr": 5e-3}},
              "zero_optimization": {"stage": 2},
              "bf16": {"enabled": True},
              "profiling": {"memory_ledger": True}}
    return _train(
        "train.gpt2_medium", GPT2LMHeadTPU(cfg, compute_dtype=None), config,
        mesh, batches, [i % 2 for i in range(steps)], stats, geometry,
        expect_kernel)


def _reference_margin(model, params, prompt, tokens, at):
    """How far below the reference's best logit the served token sits at
    position ``at``, and what two bf16 steps are at that logit's size:
    the logits are bf16, so nearer than that is a tie."""
    import jax.numpy as jnp

    context = list(prompt) + list(tokens[:at])
    logits = np.asarray(model.logits(
        params, jnp.asarray([context], jnp.int32))[0, -1], np.float32)
    best = float(logits.max())
    ulp = 2.0 ** (math.floor(math.log2(abs(best))) - 7)
    return best - float(logits[tokens[at]]), 2 * ulp


def serve(seed, stats, geometry, cfg=None, inference=None,
          prompt_lens=(64, 512, 200, 130, 384, 97, 256, 311), new_tokens=32,
          n_check=2):
    """GPT-2-large behind ``InferenceEngine``: 8 requests through
    ``submit``/``step`` with continuous batching (more requests than
    slots, late arrivals), the tokens of ``n_check`` of them compared
    with ``inference.reference_generate``, and no compile after warm-up."""
    import jax

    from deepspeed_tpu.inference import InferenceEngine, reference_generate
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU

    t0 = time.perf_counter()
    if cfg is None:
        cfg = GPT2Config.gpt2_large(embd_dropout=0.0, attn_dropout=0.0,
                                    resid_dropout=0.0)
    if inference is None:
        # 4 slots x 1024 positions in 64-token blocks, plus the null block
        inference = {"kv_block_size": 64, "kv_blocks": 4 * 16 + 1,
                     "max_batch_slots": 4, "max_seq_len": 1024,
                     "prefill_buckets": [128, 256, 512],
                     "token_budget": 4 * 1024,
                     "max_new_tokens": new_tokens,
                     "weights_dtype": "bfloat16"}
    model = GPT2LMHeadTPU(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    before = stats.as_dict()
    engine = InferenceEngine(model, params, config={
        "inference": inference, "steps_per_print": 10 ** 9})
    rng = np.random.default_rng(seed)

    def prompt(n):
        return [int(t) for t in rng.integers(0, cfg.vocab_size, size=n)]

    # warm-up: one short request per prefill bucket compiles every
    # program the serve can use (one prefill per bucket + the decode)
    t1 = time.perf_counter()
    for bucket in inference["prefill_buckets"]:
        engine.submit(prompt(bucket - 1), max_new_tokens=2)
    engine.run()
    warmup_seconds = time.perf_counter() - t1
    compile_receipt = _compile_delta(stats, before)
    compile_by_program = {
        name: round(sum(seconds for program, seconds
                        in stats.by_program.items()
                        if program.startswith(f"jit({name}")), 3)
        for name in ("decode", "prefill")}  # prefill_<bucket>: all together
    programs_after_warmup = stats.programs

    # the served wave: the first five arrive together (four slots, so one
    # waits), the rest join while the batch is decoding
    prompts = [prompt(n) for n in prompt_lens]
    t1 = time.perf_counter()
    ids = [engine.submit(p) for p in prompts[:5]]
    engine.step()
    engine.step()
    ids += [engine.submit(p) for p in prompts[5:]]
    results = engine.run()
    serve_seconds = time.perf_counter() - t1
    compiled_later = stats.programs - programs_after_warmup
    if compiled_later:
        raise AssertionError(
            f"serve: {compiled_later} program(s) compiled after warm-up "
            f"({stats.by_program})")
    tokens = {rid: results[rid]["tokens"] for rid in ids}
    for rid, toks in tokens.items():
        if len(toks) != new_tokens:
            raise AssertionError(
                f"serve: {rid} returned {len(toks)} tokens, "
                f"asked {new_tokens}")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"serve: {rid} token out of range: {toks}")
    receipt = engine.serving_receipt()

    # parity with the naive full-forward reference on the very weights
    # the engine serves, shortest prompts first: the reference re-runs
    # the whole context per token and retraces per length
    ref_params = engine.params
    t1 = time.perf_counter()
    checked = []
    for idx in sorted(range(len(prompts)),
                      key=lambda i: len(prompts[i]))[:n_check]:
        served = tokens[ids[idx]]
        ref = reference_generate(model, ref_params, prompts[idx], new_tokens)
        entry = {"request": ids[idx], "prompt_tokens": len(prompts[idx]),
                 "tokens_equal": served == ref}
        if served != ref:
            # random weights in bf16 leave ties among 50k logits (on
            # the chip both checked requests met one, the served token
            # exactly one bf16 step under the reference's best), and the
            # engine (padded prefill, paged decode) rounds in another
            # order than the full forward.  A divergence passes only as
            # such a tie, by the reference's own logits; a wrong cache
            # position or mask lands whole logits away.
            at = next(i for i, (a, b) in enumerate(zip(served, ref))
                      if a != b)
            margin, tie = _reference_margin(model, ref_params,
                                            prompts[idx], served, at)
            entry.update(tokens_equal_before=at, margin=round(margin, 5),
                         near_tie_bound=round(tie, 5))
            if margin > tie:
                raise AssertionError(
                    f"serve: {ids[idx]} diverges from reference_generate "
                    f"at token {at} by {margin:.4f} logits (a near-tie "
                    f"is <= {tie:.4f}): served {served} reference {ref}")
        checked.append(entry)
    reference_seconds = time.perf_counter() - t1

    record = {"phase": "serve.gpt2_large", "layers": cfg.num_layers,
              "hidden": cfg.hidden_size, "requests": len(ids),
              "generated_tokens": sum(len(t) for t in tokens.values()),
              "decode_iterations": receipt["decode_iterations"],
              "checked_against_reference": checked,
              "compiled_after_warmup": compiled_later,
              "flash_geometries": geometry.drain(),
              "warmup_seconds": round(warmup_seconds, 3),
              "compile_seconds_by_program": compile_by_program,
              **compile_receipt,
              "serve_seconds": round(serve_seconds, 3),
              "per_token_p50_seconds": round(
                  receipt["per_token_p50_seconds"], 4),
              "ttft_p50_seconds": round(receipt["ttft_p50_seconds"], 4),
              "reference_seconds": round(reference_seconds, 3),
              "peak_hbm_bytes": peak_hbm_bytes(jax.devices()[:1]),
              "seconds": round(time.perf_counter() - t0, 3)}
    engine.close()
    del engine, params
    _free()
    return emit(record)


def _gpt2_large_offload_cfg(seq):
    from deepspeed_tpu.models import GPT2Config

    return GPT2Config.gpt2_large(
        max_position_embeddings=seq, embd_dropout=0.0, attn_dropout=0.0,
        resid_dropout=0.0, remat=True, loss_chunk=256)


def offload(mesh, seed, stats, geometry, cfg=None, batch=4, seq=1024,
            steps=2):
    """GPT-2-large with ``zero_optimization.cpu_offload``: master and
    optimizer buffers in pinned host memory, and the step-1 loss equal to
    the device-resident engine's on the same batch."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import GPT2LMHeadTPU
    from deepspeed_tpu.ops.adam import cpu_adam

    t0 = time.perf_counter()
    if cfg is None:
        cfg = _gpt2_large_offload_cfg(seq)
    rng = np.random.default_rng(seed)
    batch_ = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)}

    def config(cpu_offload):
        return {"train_batch_size": batch, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 2,
                                      "cpu_offload": cpu_offload},
                "bf16": {"enabled": True},
                "profiling": {"memory_ledger": True}}

    # the device-resident engine first: one step is all the comparison
    # needs, and its 9 GB of optimizer state must be gone before the
    # offload engine compiles
    engine, *_ = deepspeed.initialize(model=GPT2LMHeadTPU(cfg),
                                      config=config(False), mesh=mesh)
    resident_loss = _loss(engine.train_batch(iter([batch_])))
    engine.close()
    del engine
    _free()

    before = stats.as_dict()
    t1 = time.perf_counter()
    engine, *_ = deepspeed.initialize(model=GPT2LMHeadTPU(cfg),
                                      config=config(True), mesh=mesh)
    kinds = sorted({leaf.sharding.memory_kind
                    for leaf in jax.tree_util.tree_leaves(
                        (engine.state["master"], engine.state["opt"]))
                    if getattr(leaf, "ndim", 0) >= 1})
    if kinds != ["pinned_host"]:
        raise AssertionError(
            f"offload: master/optimizer buffers live in {kinds}, "
            "expected pinned_host alone")
    losses, step_seconds = [], []
    for _ in range(steps):
        t2 = time.perf_counter()
        losses.append(_loss(engine.train_batch(iter([batch_]))))
        step_seconds.append(time.perf_counter() - t2)
    if abs(losses[0] - resident_loss) > OFFLOAD_RTOL * abs(resident_loss):
        raise AssertionError(
            f"offload: step-1 loss {losses[0]} differs from the "
            f"device-resident engine's {resident_loss} beyond bf16 "
            f"tolerance")
    record = {"phase": "offload.gpt2_large", "steps": steps,
              "losses": [round(v, 4) for v in losses],
              "device_resident_step1_loss": round(resident_loss, 4),
              "state_memory_kinds": kinds,
              "host_state_bytes": int(
                  engine.memory_ledger.host_buffers.total_bytes()),
              "stream_min_bytes": int(engine.offload_stream_min_bytes),
              "host_stream_schedule": engine.host_stream_schedule(),
              # the offload update streams chunks through the device; the
              # g++-built host kernel serves only "optimizer": cpu_adam
              "cpu_adam_kernel_used": "lib" in cpu_adam._lib_cache,
              "first_step_seconds": round(step_seconds[0], 3),
              "later_step_seconds": [round(v, 3) for v in step_seconds[1:]],
              "init_and_first_step_seconds": round(
                  time.perf_counter() - t1 - sum(step_seconds[1:]), 3),
              **_compile_delta(stats, before),
              "flash_geometries": geometry.drain(),
              "peak_hbm_bytes": peak_hbm_bytes(mesh.devices.flat),
              "seconds": round(time.perf_counter() - t0, 3)}
    engine.close()
    del engine
    _free()
    return emit(record)


def _master_shards(engine):
    """(device ids, bytes per shard, total bytes) of the flat fp32 master."""
    master = engine.state["master"]
    shards = master.addressable_shards
    return (sorted({s.device.id for s in shards}),
            [int(s.data.nbytes) for s in shards], int(master.nbytes))


def multichip(n_chips, seed, stats, geometry, cfg=None, batch=4, seq=1024,
              steps=3):
    """ZeRO-2 (``overlap_comm`` at its default) and ZeRO-3 on a
    ``{"data": n_chips}`` mesh against the same global batches on a
    one-device mesh, with the sharded master spread over the chips and
    the collectives in the compiled step."""
    import jax

    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU
    from deepspeed_tpu.parallel import make_mesh

    if cfg is None:
        # dropout off: the kernel seeds its masks by (batch x head) index,
        # which a batch shard numbers from zero
        cfg = GPT2Config.gpt2_medium(
            max_position_embeddings=seq, embd_dropout=0.0, attn_dropout=0.0,
            resid_dropout=0.0)
    rng = np.random.default_rng(seed)
    batches = [{"input_ids": rng.integers(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)}
        for _ in range(steps)]
    records = []
    for stage in (2, 3):
        t0 = time.perf_counter()
        before = stats.as_dict()
        config = {"train_batch_size": batch, "steps_per_print": 10 ** 9,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                  "zero_optimization": {"stage": stage},
                  "bf16": {"enabled": True},
                  "profiling": {"memory_ledger": True}}
        mesh = make_mesh({"data": n_chips})
        engine, *_ = deepspeed.initialize(model=GPT2LMHeadTPU(cfg),
                                          config=config, mesh=mesh)
        losses = [_loss(engine.train_batch(iter([b]))) for b in batches]
        compile_receipt = _compile_delta(stats, before)

        shard_devices, shard_bytes, master_bytes = _master_shards(engine)
        if len(shard_devices) != n_chips:
            raise AssertionError(
                f"zero{stage}: master shards sit on devices "
                f"{shard_devices}, expected {n_chips} distinct")
        if (max(shard_bytes) != min(shard_bytes)
                or sum(shard_bytes) != master_bytes):
            raise AssertionError(
                f"zero{stage}: master shards are not 1/{n_chips} of "
                f"{master_bytes} bytes each: {shard_bytes}")
        device_stats = [d.memory_stats() for d in mesh.devices.flat]
        in_use = ([int(s["bytes_in_use"]) for s in device_stats]
                  if all(device_stats) else None)
        # state replicated instead of sharded would show as a multiple
        if in_use is not None and max(in_use) > 1.5 * min(in_use):
            raise AssertionError(
                f"zero{stage}: device memory is not spread evenly over "
                f"the chips: {in_use}")
        text = _step_program_text(engine)
        collectives = {op: op in text
                       for op in ("reduce-scatter", "all-gather")}
        if not all(collectives.values()):
            raise AssertionError(
                f"zero{stage}: collectives in the compiled step: "
                f"{collectives}")
        record = {"phase": f"chips{n_chips}.zero{stage}",
                  "losses": [round(v, 5) for v in losses],
                  "comm_overlap": engine.comm_overlap_enabled(),
                  "master_shard_devices": shard_devices,
                  "master_shard_bytes": shard_bytes,
                  "bytes_in_use_per_device": in_use,
                  "collectives_in_step": collectives,
                  "pallas_kernel_in_step": "tpu_custom_call" in text,
                  "flash_geometries": geometry.drain(),
                  **compile_receipt,
                  "peak_hbm_bytes": peak_hbm_bytes(mesh.devices.flat)}
        engine.close()
        del engine, text
        _free()

        # the same global batches on one device of the same machine
        engine, *_ = deepspeed.initialize(
            model=GPT2LMHeadTPU(cfg), config=config,
            mesh=make_mesh({"data": 1}))
        ref_losses = [_loss(engine.train_batch(iter([b]))) for b in batches]
        engine.close()
        del engine
        _free()
        for i, (leg, ref) in enumerate(zip(losses, ref_losses)):
            if abs(leg - ref) > PARITY_ATOL + PARITY_RTOL * abs(ref):
                raise AssertionError(
                    f"zero{stage}: dp={n_chips} loss {leg} differs from "
                    f"dp=1 loss {ref} at step {i + 1}")
        record["dp1_losses"] = [round(v, 5) for v in ref_losses]
        record["seconds"] = round(time.perf_counter() - t0, 3)
        records.append(emit(record))
    return records


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--phase", choices=("default", "offload"),
                        default="default",
                        help="default: train + serve; offload: GPT-2-large "
                             "with zero_optimization.cpu_offload, alone")
    parser.add_argument("--chips", type=int, default=1,
                        help="> 1: the ZeRO data-parallel phase on that "
                             "many chips and its one-chip reference, and "
                             "no other phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    found = device_line()
    if found["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU; jax found {found} — nothing "
              "ran", file=sys.stderr)
        return 2
    if args.chips > found["count"]:
        print(f"chip_smoke.py --chips {args.chips}: jax found {found}",
              file=sys.stderr)
        return 2

    from deepspeed_tpu.parallel import make_mesh
    from deepspeed_tpu.runtime.compilation import (
        CompileStats, DeepSpeedCompilationConfig, configure_persistent_cache)
    from deepspeed_tpu.utils.logging import logger

    # stdout carries the JSON lines alone
    for handler in logger.handlers:
        handler.setStream(sys.stderr)
    geometry = _GeometryLog()
    logger.addHandler(geometry)
    cache_dir = configure_persistent_cache(DeepSpeedCompilationConfig({}))
    stats = CompileStats()
    emit({"phase": "env", "jax": jax.__version__,
          "jaxlib": importlib.metadata.version("jaxlib"),
          "libtpu": importlib.metadata.version("libtpu"),
          "device": found, "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start": (
              len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
              else 0)})

    t0 = time.perf_counter()
    if args.chips > 1:
        multichip(args.chips, args.seed, stats, geometry)
    else:
        one_chip = make_mesh({"data": 1})
        if args.phase == "offload":
            offload(one_chip, args.seed, stats, geometry)
        else:
            train_bert(one_chip, args.seed, stats, geometry)
            train_gpt2(one_chip, args.seed, stats, geometry)
            serve(args.seed, stats, geometry)
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 3),
          **stats.as_dict()})
    print(json.dumps({"ok": True, "device": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
