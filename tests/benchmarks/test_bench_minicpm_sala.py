"""MiniCPM-SALA, one pipeline stage (``minicpm_sala_pp8.longdoc_backlog``): its
configuration's file against the published row, the counts by hand, the
traffic's recipe and schedule, and the cell at the tests' size through
``InferenceEngine`` on the CPU (the kernels run through Pallas' interpreter) —
a bucketed prefill, then decode through the pages, the compressed keys and the
float32 state, against the reference's full forward: logits not tokens, for
contexts that stay under, cross and start over ``dense_len``, and the planted
faults the limit has to catch."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, models, serve
from benchmarks.reference import ops

from . import _tiny_minicpm_sala as _tiny

# float32 program at the tiny size: the served token is the reference's best,
# so sound runs read 0; the limit leaves room for a float32 near-tie
LIMITS = {"served_logit_gap": 1e-3}
CELL = "minicpm_sala_pp8.longdoc_backlog"
MM = ops.MATMULS["float32"]


@pytest.fixture(scope="module")
def cell():
    return common.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    model, ref = models.load_with_reference("minicpm_sala")
    return model, ref, model.init_params(_tiny.MODEL, 5)


# -- the configuration's file ------------------------------------------------

_MIXERS = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] \
    + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4 \
    + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3

# the catalog row's ``config`` (openbmb/MiniCPM-SALA config.json)
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": _MIXERS, "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True}


def test_configuration_is_the_published_row_but_for_the_depth(cell):
    cfg = cell["config"]
    mc = cfg["model_config"]
    assert len(_MIXERS) == 32 and _MIXERS.count("minicpm4") == 8
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value and mc[key] == value, key
    # one whole period, a contiguous slice of the published list
    assert cfg["num_hidden_layers"] == mc["num_hidden_layers"] == 4
    assert cfg["mixer_types"] == mc["mixer_types"] == _MIXERS[17:21] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    assert set(mc) - set(PUBLISHED) == {
        "sparse_config", "initializer_range", "weights_dtype",
        "decode_batch_for_counts"}
    assert mc["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assumed = cfg["assumed"]
    for key in ("sparse_config", "topk_counts_forced_blocks",
                "state_float32", "compressed_key_pages"):
        assert "Moves cost" in assumed[key], key
    for key in ("compression", "lightning_decay", "lightning_norms",
                "key_ownership", "residual_scale", "fused_kernels"):
        assert "Moves no cost" in assumed[key], key
    for key in ("dense_switch", "initializer_range", "weights", "serving",
                "decode_batch_for_counts"):
        assert key in assumed
    assert "eight stages" in cfg["deployment"]
    assert "four layers a chip" in cfg["deployment"]
    assert "3.42 GB" in cfg["bytes"]
    assert "idle share larger" in cfg["reduced_notes"]["num_hidden_layers"]
    icfg = cfg["engine"]["inference"]
    assert icfg == {
        "kv_block_size": 64, "kv_blocks": 19457, "max_batch_slots": 64,
        "max_seq_len": 19456,
        "prefill_buckets": [10240, 12288, 14336, 16384],
        "token_budget": 64 * 19456, "max_new_tokens": 3072,
        "weights_dtype": "bfloat16"}
    # every slot's worst case and the null block
    assert icfg["kv_blocks"] == 64 * 19456 // 64 + 1


def test_manifest_gains_the_configuration_the_cell_and_five_metrics(cell):
    bench = json.load(open(common.ROOT + "/BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == "minicpm_sala_pp8"]
    assert entry["source"] == cell["config"]["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["reduced"] == cell["config"]["reduced"]
    assert bench["configs"][-1] is entry
    work = bench["workloads"][-1]
    assert (work["name"], work["config"], work["traffic"], work["chips"]) \
        == (CELL, "minicpm_sala_pp8", "longdoc_backlog", 1)
    for line in [entry["why"], entry["source"], work["why"]] + [
            m["layer"] for m in bench["per_layer"]]:
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    new = ["sparse_select_ms", "sparse_decode_attn_ms",
           "sparse_prefill_attn_ms", "lightning_decode_ms",
           "lightning_prefill_ms"]
    assert [m["name"] for m in bench["per_layer"][-5:]] == new
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    assert set(cell["per_layer"]) == set(new) | {
        "compile_cold_s", "cache_misses", "decode_device_ms",
        "prefill_device_ms", "decode_roofline", "host_prep_ms",
        "slot_occupancy", "tpot_p50_ms.backlog", "tpot_p95_ms.backlog",
        "serve_device_idle", "serve_hbm_peak_gb"}
    # each reader is anchored at its kernel's own name
    from benchmarks import metrics
    specs = metrics.load_all()
    import re
    kernels = ["sparse_block_select", "sparse_paged_decode_attention",
               "sparse_prefill_attention", "lightning_decode_update",
               "lightning_prefill_scan"]
    for name, kernel in zip(new, kernels):
        pattern = specs[name]["reader"]["args"]["pattern"]
        assert specs[name]["reader"]["reducer"] == "op_ms_per_step"
        hits = [k for k in kernels
                if re.search(pattern, f"%{k}.3 = f32[] custom-call()")]
        assert hits == [kernel], name


def test_the_traffic_is_the_recipe_of_its_lengths_file(cell):
    import random
    import statistics
    nd = statistics.NormalDist()

    def quantiles(median, sigma, lo, hi):
        return [int(round(min(max(median * math.exp(
            sigma * nd.inv_cdf((i + 0.5) / 128)), lo), hi)))
            for i in range(128)]

    prompts = quantiles(12288, 0.20, 9216, 16384)
    answers = quantiles(2048, 0.30, 1024, 3072)
    rng = random.Random(SHUFFLE)
    rng.shuffle(answers)
    pairs = [[p, a] for p, a in zip(prompts, answers)]
    rng.shuffle(pairs)
    traffic = cell["traffic"]
    assert traffic["pairs"] == pairs
    assert all(9216 <= p <= 16384 and 1024 <= a <= 3072 and p + a <= 19456
               for p, a in pairs)
    assert traffic["generator"] == "closed_loop"
    assert (traffic["callers"], traffic["warmup_iterations"],
            traffic["trace_seconds"]) == (80, 8, 3.0)
    assert set(traffic["limits"]) == {"minicpm_sala_pp8"}
    # every prompt is past dense_len: the dense path never runs in the cell
    assert min(prompts) > 8192
    buckets = cell["config"]["engine"]["inference"]["prefill_buckets"]
    assert max(prompts) <= max(buckets) and all(b % 2048 == 0
                                                for b in buckets)
    # the reference's float32 logits: 6 rows of the longest answer
    assert 6 * max(a for _, a in pairs) * 73448 * 4 == pytest.approx(
        5.4e9, rel=0.01)


SHUFFLE = 20261040


def test_parameter_tree_is_the_programs(cell):
    model = models.load("minicpm_sala")
    for mc in (_tiny.MODEL, cell["config"]["model_config"]):
        program = model.build_program_model(mc, {})
        assert program.param_shapes() == model.param_shapes(mc)
        leaves = jax.tree_util.tree_leaves(
            model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))
        assert sum(math.prod(s) for s in leaves) == model.param_count(mc)
        assert program.serving().num_layers == mc["num_hidden_layers"]
        assert program.config.residual_scale == pytest.approx(
            1.4 / math.sqrt(32))


def test_seeded_weights_come_in_the_serving_dtype():
    model = models.load("minicpm_sala")
    mc = dict(_tiny.MODEL, weights_dtype="bfloat16")
    a, b = model.init_params(mc, 7), model.init_params(mc, 7)
    other = model.init_params(mc, 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves(a)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert all(bool((x == y).all()) for x, y in zip(
        leaves, jax.tree_util.tree_leaves(b)))
    assert bool((a["embed"] != other["embed"]).any())
    for norm in ("q_norm", "k_norm", "o_norm", "input_norm"):
        assert bool((a["layers"]["layer_1"][norm]["scale"] == 1).all())
    assert "q_norm" not in a["layers"]["layer_0"]


def test_counts_by_hand(cell):
    mc = cell["config"]["model_config"]
    model = models.load("minicpm_sala")
    h, w = 4096, 16384
    mlp = 3 * h * w + 2 * h
    sparse_layer = mlp + h * (4096 + 256 + 256 + 4096) + 4096 * h
    lightning_layer = mlp + 5 * h * 4096 + 3 * 128
    assert model.layer_params(mc, "minicpm4") == sparse_layer == 253_763_584
    assert model.layer_params(mc, "lightning-attn") == lightning_layer \
        == 285_221_248
    total = sparse_layer + 3 * lightning_layer + 2 * 73448 * h + h
    assert model.param_count(mc) == total
    assert round(2 * total / 1e7) == 342                  # 3.42 GB of bf16
    # a token leaves a K and a V row of 256 bf16 values and 1/16 of a
    # compressed key; a request 3 x 32 x 128 x 128 float32 values
    assert model.cache_bytes_per_token(mc) == 2 * 256 * 2 + 256 * 2 / 16 \
        == 1056
    assert model.state_bytes_per_slot(mc) == 3 * 2_097_152
    # pages a (slot, KV head) reads: all under dense_len, 64 past it
    assert model.pages_read(mc, 8192) == (128, 128)
    assert model.pages_read(mc, 8193) == (64, 129)
    assert model.pages_read(mc, 14000) == (64, 219)
    # a step of 64 slots at 14,000 tokens each: the layers' and the head's
    # weights once, every state read and written, 64 pages of K and V and
    # the 875 compressed keys of each slot
    step = model.decode_bytes_per_step(mc, 64 * 14000)
    weights = 2 * (sparse_layer + 3 * lightning_layer + 73448 * h + h)
    assert step == weights + 2 * 64 * 3 * 2_097_152 \
        + 64 * 2 * 256 * (2 * 64 * 64 + 14000 / 16)
    assert weights == pytest.approx(2.82e9, rel=0.01)
    assert step == pytest.approx(3.92e9, rel=0.01)
    # a sweep of the context would read 219 pages, not 64: 0.65 GB more
    swept = 64 * 2 * 256 * 2 * (219 - 64) * 64
    assert swept == pytest.approx(0.65e9, rel=0.01)
    # the states' bytes do not follow the context, the pages' stop at 64
    assert model.decode_bytes_per_step(mc, 64 * 19000) - step \
        == 64 * 2 * 256 * 5000 / 16
    counts = model.counts(mc, 64 * 14000, 64, 16384)
    assert counts["lightning_decode_bytes"] == 2 * 64 * 2_097_152
    assert counts["lightning_decode_flops"] == 5 * 64 * 32 * 128 * 128
    assert counts["sparse_decode_bytes"] == 2 * 64 * (
        2 * 4096 * 256 + 2 * 4096)
    assert counts["sparse_decode_flops"] == 4 * 64 * 32 * 128 * 4096
    assert counts["sparse_select_flops"] == 2 * 64 * 32 * 128 * 874
    assert counts["sparse_prefill_flops"] == 4 * 32 * 128 * (
        16384 * 16385 // 2)
    assert counts["lightning_prefill_flops"] == 32 * 64 * (
        4 * 256 * 256 * 128 + 6 * 256 * 128 * 128)
    assert counts["lightning_prefill_bytes"] == 16384 * 4096 * 10


# -- prefill and decode through both kinds of cache ------------------------------

def _serve(params, requests, model_config=None):
    """Serve ``requests`` [(prompt, answer length)] on the tiny model and
    return each one's tokens."""
    from deepspeed_tpu.inference import InferenceEngine
    model = models.load("minicpm_sala")
    engine = InferenceEngine(
        model.build_program_model(model_config or _tiny.MODEL, {}),
        params, config=_tiny.ENGINE)
    rids = [engine.submit(p, max_new_tokens=n) for p, n in requests]
    while not engine.scheduler.idle() or engine._unread:
        engine.step()
    tokens = [list(engine.request(rid).generated) for rid in rids]
    for allocator in engine.allocators:
        assert allocator.free_blocks == allocator.capacity
    engine.close()
    return tokens


def _gaps(ref, params, requests, served, model_config=None):
    """How far each served token's logit lies below the reference's best."""
    ids = np.zeros((len(requests), 256), np.int32)
    rows, cols = [], []
    for r, ((prompt, _), tokens) in enumerate(zip(requests, served)):
        n, new = len(prompt), len(tokens)
        ids[r, :n] = prompt
        ids[r, n:n + new] = tokens
        rows += [r] * new
        cols += list(range(n - 1, n + new - 1))
    logits = np.asarray(ref.position_logits(
        params, jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(cols),
        model_config or _tiny.MODEL, MM))
    assert logits.std() > 0.3        # not a flat distribution
    tokens = np.concatenate([np.asarray(t) for t in served])
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]


def _requests(lengths=((40, 30), (20, 100), (97, 50), (150, 60), (182, 40),
                       (110, 51))):
    rng = np.random.default_rng(40)
    return [(rng.integers(0, 512, size=n), new) for n, new in lengths]


def test_decode_through_both_caches_matches_the_full_forward(tiny):
    """A bucketed prefill, then decode, three requests side by side at
    different lengths: a context that stays under ``dense_len`` (96), one
    that crosses it while decoding, ones that start over it — just over, and
    with more visible blocks than ``top_k`` from the first token — across
    kernel and block edges (every residue of 16 is decoded) and past six
    visible blocks.  Every served token's logit is the reference's best, by
    its own full forward over the whole sequence."""
    _, ref, params = tiny
    requests = _requests()
    served = _serve(params, requests)
    assert [len(t) for t in served] == [n for _, n in requests]
    gaps = _gaps(ref, params, requests, served)
    assert len(gaps) == 331
    assert gaps.max() <= LIMITS["served_logit_gap"]


def test_the_reference_reads_the_prompts_length_from_the_judged_columns(
        tiny):
    """``position_logits`` is not told where a prompt ends: a row's first
    judged column is its prompt's last position, whatever padding follows
    the list (the harness pads with (0, 0))."""
    _, ref, _ = tiny
    rows = jnp.asarray([0, 0, 0, 2, 2, 0, 0, 0])
    cols = jnp.asarray([0, 1, 2, 120, 121, 0, 0, 0])
    assert np.asarray(ref.prompt_lengths(rows, cols, 4)).tolist() == [
        1, 1, 121, 1]
    rows = jnp.asarray([0, 0, 1, 0, 0])
    cols = jnp.asarray([99, 100, 7, 0, 0])
    assert np.asarray(ref.prompt_lengths(rows, cols, 2)).tolist() == [100, 8]


def test_the_sparse_rule_follows_the_prompts_length(tiny):
    """A prompt of 150 is chosen sparsely from its first row (rows with
    more than six visible blocks read six); one of 90 attends densely until
    the context passes 96."""
    _, ref, params = tiny
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 512, size=200),
                      jnp.int32)
    long_prompt, _ = ref.chosen_blocks(params, ids, 150, _tiny.MODEL)
    short_prompt, margin = ref.chosen_blocks(params, ids, 90, _tiny.MODEL)
    chosen = np.asarray(long_prompt[0]).sum(axis=-1)          # [2, 200]
    assert (chosen[:, 95] == 6).all() and (chosen[:, 199] == 6).all()
    assert (chosen[:, 40] == 3).all()                # blocks 0, 1, 2: all
    dense = np.asarray(short_prompt[0]).sum(axis=-1)
    assert (dense[:, 95] == 6).all() and (dense[:, 96] == 6).all()
    assert (dense[:, 80] == 6).all()                 # six visible: all
    assert (np.asarray(short_prompt[0])[:, 95, :6]).all()
    assert np.isinf(np.asarray(margin)[:96]).all()
    assert np.isfinite(np.asarray(margin)[112:]).any()


FAULTS = {
    # name -> (changes to the PROGRAM's configuration, patches)
    "window_left_out": ({"sparse_config": {"window_size": 1}}, None),
    "block_0_left_out": ({"sparse_config": {"init_blocks": 0}}, None),
    "5_blocks": ({"sparse_config": {"topk": 5}}, None),
    "7_blocks": ({"sparse_config": {"topk": 7}}, None),
    "scores_averaged": ({}, "mean_scores"),
    "another_heads_decay": ({}, "rolled_decay"),
    "depth_from_the_layers_held": ({"mup_denominator": 4}, None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_fail_by_100_times_the_limit(tiny, fault, monkeypatch):
    """The window left out (only the query's own block forced), block 0 left
    out, one block fewer or more than ``top_k``, block scores averaged over
    the overlapping kernels in place of their maximum, the decay of the
    next head, the residual scale from the depth held: each moves a served
    token's logit 0.1 or more below the reference's best.  (Two faults
    cannot move a greedy token that far at this size and are held where they
    show, in ``tests/unit/test_minicpm_sala.py``: the state kept in
    bfloat16, 177 times the limit on the state itself after 61 decode steps,
    and the logits' divisor left out, on the logits.)"""
    from deepspeed_tpu.ops.transformer import (lightning_attention,
                                               sparse_attention)
    _, ref, params = tiny
    changes, patch = FAULTS[fault]
    faulty = dict(_tiny.MODEL, **{k: v for k, v in changes.items()
                                  if k != "sparse_config"})
    faulty["sparse_config"] = dict(_tiny.SPARSE_CONFIG,
                                   **changes.get("sparse_config", {}))
    if patch == "mean_scores":
        def averaged(self, r, blocks):
            need = blocks * self.per_block
            r = jnp.pad(r, [(0, 0)] * (r.ndim - 1)
                        + [(0, max(need - r.shape[-1], 0))])
            by_block = r[..., :need].reshape(
                *r.shape[:-1], blocks, self.per_block)
            lead = jnp.pad(by_block[..., :-1, -1:],
                           [(0, 0)] * (r.ndim - 1) + [(1, 0), (0, 0)])
            return jnp.concatenate([lead, by_block], axis=-1).mean(axis=-1)
        monkeypatch.setattr(sparse_attention.SparseGeometry, "block_scores",
                            averaged)
    elif patch == "rolled_decay":
        slopes = lightning_attention.decay_slopes
        monkeypatch.setattr(lightning_attention, "decay_slopes",
                            lambda heads: np.roll(slopes(heads), 1))
    jax.clear_caches()
    requests = _requests(((150, 60), (110, 51), (182, 40)))
    served = _serve(params, requests, faulty)
    jax.clear_caches()
    gaps = _gaps(ref, params, requests, served)
    assert gaps.max() >= 100 * LIMITS["served_logit_gap"], gaps.max()


def test_the_cell_at_the_tests_size_is_correct_and_the_control_is_not(
        tiny, capsys):
    """The harness's own path: seeded weights and traffic, the closed loop,
    the window, then the float32 reference over a sample of the finished
    requests — ``correct`` true; and the same reference computed in float8
    puts first, somewhere, a token 0.1 or more below the float32 best."""
    import time
    spec = _tiny.serve_spec(LIMITS)
    ok = serve.run_cell(spec, 2 ** 31 + 40, 4.0, 0, time.perf_counter(),
                        jax.devices()[:1])
    out = capsys.readouterr().out
    assert ok, out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] > 20
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # the control by the harness's own comparison, over requests served
    # with the fixture's weights (seed 5)
    _, _, params = tiny
    requests = _requests(((150, 51), (110, 40)))
    sample = [(prompt, tokens) for (prompt, _), tokens in zip(
        requests, _serve(params, requests))]
    gaps = serve.reference_gaps(spec, 5, sample)
    assert len(gaps) == 91 and gaps.max() <= LIMITS["served_logit_gap"]
    low = serve.reference_gaps(spec, 5, sample, "fp8")
    assert (low > 0).sum() > 0
    assert low.max() >= 100 * LIMITS["served_logit_gap"]


def test_the_control_returns_its_argmax_and_nothing_is_left_unjudged(tiny):
    """Under the float8 product ``position_logits`` returns int8 one-hot
    rows, the head taken in blocks of positions: their argmax is the argmax
    of the float8 logits computed whole.  Under float32 it returns the
    logits, of every position: a near-tie between the sixth and the seventh
    block (the choice's margin 0) is judged like any other."""
    _, ref, params = tiny
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (2, 192)),
                      jnp.int32)
    rows = jnp.asarray([0] * 40 + [1] * 30 + [0] * 2)
    cols = jnp.asarray(list(range(120, 160)) + list(range(150, 180)) + [0, 0])
    low = ops.MATMULS["fp8"]
    hot = ref.position_logits(params, ids, rows, cols, _tiny.MODEL, low)
    assert hot.dtype == jnp.int8 and hot.shape == (72, 512)
    assert (np.asarray(hot).sum(axis=-1) == 1).all()
    with jax.default_matmul_precision("highest"):
        x = ref.stream_at(params, ids, rows, cols, _tiny.MODEL, low) \
            / ref.head_scale(_tiny.MODEL)
        whole = low(x, params["lm_head"]["kernel"].astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(hot).argmax(axis=-1),
                                  np.asarray(whole).argmax(axis=-1))
    logits = np.asarray(ref.position_logits(params, ids, rows, cols,
                                            _tiny.MODEL, MM))
    assert logits.dtype == np.float32 and (logits.std(axis=-1) > 0.3).all()
    assert "judge_choice_margin" not in common.load_cell(CELL)["config"][
        "model_config"]


# -- the schedule ----------------------------------------------------------------

@pytest.mark.parametrize("decode_ms,prefill_ms_per_1k", [
    (5.96, 23.7), (5.78, 21.0), (6.14, 26.5)])
def test_the_stored_order_puts_prefills_into_the_traced_seconds(
        cell, decode_ms, prefill_ms_per_1k):
    """The cell's schedule — closed loop, 80 callers over 64 slots in
    progress, the stored order — through ``serve.Loop`` and the engine's own
    ``step`` with empty programs and a stepped clock (a decode 5.96 ms, a
    prefill 303 ms at the mean bucket: my chip run, PR 40; and 3% / 12%
    either way): the traced last 3 s of a 30 s window hold at least four
    prefills, so ``prefill_device_ms`` and the two prefill kernels' metrics
    have something to read, and more than half of the window is prefill."""
    from benchmarks import generators
    from deepspeed_tpu.inference import InferenceEngine
    from .test_bench_ouro import _EmptyPrograms

    class Programs(_EmptyPrograms):
        def cache_buffers(self, icfg):
            return {"k_cache": 1}

    cfg, traffic = cell["config"], cell["traffic"]
    icfg = dict(cfg["engine"]["inference"], weights_dtype="float32")
    engine = InferenceEngine(Programs(), {"w": jnp.zeros((1,))},
                             config={"steps_per_print": 10 ** 9,
                                     "inference": icfg})
    now, batch, log = [0.0], [], []
    step, enqueue = engine.step, engine._enqueue_prefill

    def counted(request):
        batch.append(request.bucket)
        return enqueue(request)

    def timed():
        del batch[:]
        start = now[0]
        done = step()
        now[0] += 1e-3 * (decode_ms + prefill_ms_per_1k * sum(batch) / 1e3)
        log.extend([start] * len(batch))
        return done

    engine._enqueue_prefill, engine.step = counted, timed
    source = generators.load(traffic["generator"]).make(
        traffic, cfg["model_config"], 1, icfg["max_batch_slots"])
    loop = serve.Loop(engine, source, icfg["max_batch_slots"],
                      clock=lambda: now[0])
    loop.start()
    for _ in range(traffic["warmup_iterations"]):
        loop.step(record=False)
    # the first warm-up step admitted every slot's first prompt
    assert len(log) == 64
    opened = now[0]
    tokens, seconds, _ = serve.drive(loop, 30.0 - traffic["trace_seconds"])
    traced_from = now[0]
    serve.drive(loop, traffic["trace_seconds"])
    traced = [t for t in log if traced_from <= t]
    assert len(traced) >= 4
    window = [t for t in log if opened <= t]
    assert 50 <= len(window) <= 72
    assert 3300 < tokens / seconds < 5000
    engine.close()
