"""Xing-4.0 through the harness at a tiny size on the CPU, and its
configuration, traffic, counts and manifest entries by hand.  The manifest's
entries are looked up BY NAME: a cell a later PR appends turns nothing here
red."""

import json
import math
import os
import random
import re
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, metrics, models, serve
from benchmarks.reference import ops

from . import _tiny_xing

# float32 program at the tiny size: sound runs read 0 (the served token is
# the reference's best); the float8 control reads over 1
LIMITS = {"served_logit_gap": 1e-3}
CONFIG, CELL = "xing4_29b_6l", "xing4_29b_6l.docqa_backlog"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl:
# XingChen-AGI/Xing4.0-29B-A4B config.json), key for key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 6, "num_nextn_predict_layers": 0}


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return common.load_cell(CELL)


def _last_line(capsys):
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(out[-1]), out


# -- the configuration's file ------------------------------------------------

def test_configuration_is_the_catalog_row_but_for_the_two_reduced_keys(cell):
    cfg = cell["config"]
    mc = cfg["model_config"]
    assert cfg["reduced"] == sorted(REDUCED) == ["num_hidden_layers",
                                                 "num_nextn_predict_layers"]
    assert set(cfg["reduced_notes"]) == set(REDUCED)
    for key, value in PUBLISHED.items():
        want = REDUCED.get(key, value)
        assert cfg[key] == want and mc[key] == want, key
        assert type(cfg[key]) is type(want), key
    # the published numbers stand beside the cut ones
    assert mc["published_num_hidden_layers"] == 40
    assert mc["published_num_nextn_predict_layers"] == 1
    assert mc["published_n_routed_experts"] == mc["n_routed_experts"] == 64
    # no width, expert count, experts a token or vocabulary in `reduced`; the
    # floors of the guide: both leading dense layers and four expert layers
    assert mc["num_hidden_layers"] - mc["first_k_dense_replace"] == 4
    assert mc["first_expert"] == 0 and mc["decode_batch_for_counts"] == 32
    # every key of the published config is at the top level as it is run
    assert all(mc[k] == cfg[k] for k in cfg if k in mc)
    for item in ("hc_input_norm", "hc_sinkhorn_order", "hc_eps_and_clamp",
                 "hc_in_out", "hc_alpha", "hc_bias", "router_bias",
                 "initializer_range", "judge_routing_margin"):
        assert len(cfg["assumed"][item]) > 40, item
    assert cfg["source"].endswith("XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
                                  "config.json")
    from benchmarks.generators._requests import token_id_range
    assert token_id_range(mc) == 131072


def test_engine_and_traffic_are_what_the_issue_names(cell):
    icfg = cell["config"]["engine"]["inference"]
    traffic = cell["traffic"]
    slots = icfg["max_batch_slots"]
    assert (slots, traffic["callers"]) in ((32, 48), (24, 36))
    assert icfg["kv_block_size"] == 64 and icfg["max_seq_len"] == 13312
    assert icfg["kv_blocks"] == slots * 13312 // 64 + 1
    assert icfg["token_budget"] == slots * 13312
    assert icfg["prefill_buckets"] == [6144, 8192, 10240, 12288]
    assert icfg["max_new_tokens"] == 1024
    assert traffic["generator"] == "closed_loop"
    assert traffic["warmup_iterations"] == 8
    assert traffic["trace_seconds"] == 3.0
    pairs = traffic["pairs"]
    assert len(pairs) == 128
    assert all(6144 <= p <= 12288 and 256 <= a <= 1024 and p + a <= 13312
               for p, a in pairs)
    assert statistics.median(p for p, _ in pairs) == 8192
    assert statistics.median(a for _, a in pairs) == 512
    assert max(p for p, _ in pairs) <= max(icfg["prefill_buckets"])
    assert set(traffic["limits"]) == {CONFIG}
    assert set(traffic["limits"][CONFIG]) == {"served_logit_gap"}
    assert len(traffic["limits_why"]) > 200


def test_the_stored_lengths_are_the_stated_quantiles_in_a_stored_order():
    with open(os.path.join(common.HERE, "traffic", "lengths",
                           "docqa128.json")) as f:
        stored = json.load(f)
    nd = statistics.NormalDist()

    def quantiles(median, sigma, lo, hi):
        return [min(hi, max(lo, round(median * math.exp(
            sigma * nd.inv_cdf((i + 0.5) / 128))))) for i in range(128)]

    prompts = quantiles(8192, 0.25, 6144, 12288)
    answers = quantiles(512, 0.35, 256, 1024)
    rng = random.Random(20261047)
    rng.shuffle(answers)
    pairs = [list(p) for p in zip(prompts, answers)]
    rng.shuffle(pairs)
    assert stored["pairs"] == pairs
    assert "20261047" in stored["lengths"]


# -- the manifest, by name ---------------------------------------------------

def test_manifest_holds_the_configuration_the_cell_and_two_metrics(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    entry, work = configs[CONFIG], cells[CELL]
    assert entry["file"] == "benchmarks/configs/xing4_29b_6l.json"
    assert entry["reduced"] == sorted(REDUCED)
    assert work == {"name": CELL, "config": CONFIG,
                    "traffic": "docqa_backlog", "chips": 1,
                    "why": work["why"]}
    for line in (entry["why"], entry["source"], work["why"]):
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line
    assert CELL in end_to_end["serve_tokens_per_s"]["workloads"]
    files = metrics.load_all()
    for name, kernel in (("hc_pre_mix_ms", "mhc_pre_mix"),
                         ("hc_post_res_mix_ms", "mhc_post_res_mix")):
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["unit"] == "ms"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert len(m["layer"]) <= 200
        reader = files[name]["reader"]
        assert reader["reducer"] == "op_ms_per_step"
        assert kernel in reader["args"]["pattern"]
    for name in ("compile_cold_s", "cache_misses", "decode_device_ms",
                 "prefill_device_ms", "decode_roofline", "host_prep_ms",
                 "slot_occupancy", "tpot_p50_ms.backlog",
                 "tpot_p95_ms.backlog", "serve_device_idle",
                 "serve_hbm_peak_gb", "mla_decode_attn_ms",
                 "mla_prefill_attn_ms", "moe_experts_ms"):
        assert CELL in per_layer[name]["workloads"], name
    # every metric asked of the cell moves a metric the cell reports
    for name, m in per_layer.items():
        if CELL in m["workloads"]:
            assert m["moves"] in ("serve_tokens_per_s", "setup_s"), name


def test_the_two_patterns_read_the_kernels_by_their_instructions_names():
    """Anchored at the instruction's own name: ``mhc_pre_mix`` in an operand
    list or in a fusion that calls it is not the kernel's event."""
    from deepspeed_tpu.ops.transformer import hyper_connection
    files = metrics.load_all()
    pre = re.compile(files["hc_pre_mix_ms"]["reader"]["args"]["pattern"])
    post = re.compile(
        files["hc_post_res_mix_ms"]["reader"]["args"]["pattern"])
    assert hyper_connection.mhc_pre_mix.__name__ == "mhc_pre_mix"
    assert hyper_connection.mhc_post_res_mix.__name__ == "mhc_post_res_mix"
    with open(hyper_connection.__file__) as f:
        source = f.read()
    assert 'name="mhc_pre_mix"' in source
    assert 'name="mhc_post_res_mix"' in source
    event = ("%mhc_pre_mix.7 = (f32[8192,3584]{1,0}, f32[8192,128]{1,0}) "
             "custom-call(f32[8192,14336]{1,0} %fusion.3), "
             "custom_call_target=\"tpu_custom_call\"")
    assert pre.search(event) and not post.search(event)
    other = ("%mhc_post_res_mix.2 = f32[8192,14336]{1,0} custom-call("
             "f32[8192,14336]{1,0} %x, f32[8192,3584]{1,0} %y, "
             "f32[8192,128]{1,0} %get-tuple-element.mhc_pre_mix.7)")
    assert post.search(other) and not pre.search(other)
    assert not pre.search("%fusion.9 = f32[32,3584]{1,0} fusion(%mhc_pre_mix"
                          ".1), kind=kLoop")


# -- the parameter tree and the weights ---------------------------------------

@pytest.mark.parametrize("mc", ["tiny", "cell"])
def test_parameter_tree_is_the_programs(mc, cell):
    mc = (_tiny_xing.MODEL if mc == "tiny"
          else cell["config"]["model_config"])
    model = models.load("xing")
    program = model.build_program_model(mc, {})
    assert model.param_shapes(mc) == program.param_shapes()
    assert program.config.experts_held == mc["n_routed_experts"]
    assert program.config.n_routed_experts == \
        mc["published_n_routed_experts"]
    assert program.config.hc_mult == mc["hc_mult"]


def test_seeded_weights_come_in_the_serving_dtype_with_the_assumed_draws():
    model = models.load("xing")
    mc = dict(_tiny_xing.MODEL, weights_dtype="bfloat16", hc_alpha_mean=0.2,
              hc_alpha_std=0.05, hc_bias_std=0.5, router_bias_std=0.1)
    a, b = model.init_params(mc, 2 ** 31 + 5), model.init_params(
        mc, 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves(a)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    assert all(bool((x == y).all()) for x, y in zip(
        leaves, jax.tree_util.tree_leaves(b)))
    other = jax.tree_util.tree_leaves(model.init_params(mc, 3))
    assert not bool((leaves[0] == other[0]).all())
    layer = a["layers"]["layer_2"]
    kernel = np.asarray(layer["moe"]["experts"]["down"], np.float32)
    assert kernel.std() == pytest.approx(mc["initializer_range"], rel=0.05)
    assert bool((a["final_norm"]["scale"] == 1).all())
    alphas = np.concatenate([np.asarray(l[name]["alpha"], np.float32)
                             for l in a["layers"].values()
                             for name in ("hc_attn", "hc_mlp")])
    assert 0.05 < alphas.min() and alphas.max() < 0.4
    assert alphas.mean() == pytest.approx(0.2, abs=0.04)
    biases = np.concatenate([np.asarray(l[name]["bias"], np.float32)
                             for l in a["layers"].values()
                             for name in ("hc_attn", "hc_mlp")])
    assert biases.std() == pytest.approx(0.5, rel=0.2)
    chosen = np.asarray(layer["moe"]["router"]["bias"], np.float32)
    assert 0.02 < chosen.std() < 0.25 and chosen.any()
    phi = np.asarray(layer["hc_mlp"]["phi"], np.float32)
    assert phi.std() == pytest.approx(mc["initializer_range"], rel=0.1)


# -- the counts, by hand -----------------------------------------------------

def test_counts_by_hand(cell):
    model = models.load("xing")
    mc = cell["config"]["model_config"]
    attention = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
                 + 512 * 32 * 256 + 32 * 128 * 3584 + 2 * 3584)
    assert attention == 28_418_304
    maps = 2 * (4 * 3584 * 24 + 24 + 3)
    assert maps == 688_182
    expert = 3 * 3584 * 1024
    assert expert == 11_010_048
    dense = attention + maps + 3 * 3584 * 9216
    expert_layer = attention + maps + 3584 * 64 + 64 + 65 * expert
    total = 2 * dense + 4 * expert_layer + 2 * 131072 * 3584 + 3584
    assert total == 4_175_877_700
    assert model.param_count(mc) == total
    assert 2 * total == pytest.approx(8.35e9, rel=1e-3)
    # the program's tree holds exactly these
    assert sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))) \
        == total
    reached = 64 * (1 - (1 - 4 / 64) ** 32)
    assert model.held_experts_reached(mc, 32) == pytest.approx(reached)
    assert reached == pytest.approx(55.89, abs=0.01)
    live = 288000
    weights = (2 * dense + 4 * (attention + maps + 3584 * 64 + 64 + expert
                                + reached * expert)
               + 131072 * 3584 + 3584)
    assert model.decode_bytes_per_step(mc, live) == pytest.approx(
        2 * weights + 6 * live * 1152)
    counts = model.counts(mc, live, 32, 8192)
    # one pass over the stream each: 4 x 3584 float32 a token in, u and the
    # 24 map values out, Phi once; in and out, y and the maps
    assert counts["mhc_pre_mix_bytes"] == 4 * (
        8192 * (14336 + 3584 + 24) + 14336 * 24)
    assert counts["mhc_post_res_mix_bytes"] == 4 * 8192 * (
        2 * 14336 + 3584 + 24)
    assert counts["mhc_pre_mix_bytes"] / 8192 == pytest.approx(71_944, abs=1)
    assert counts["mhc_post_res_mix_bytes"] / 8192 == 129_120
    assert counts["mhc_pre_mix_flops"] == 8192 * (2 * 14336 * 24 + 3 * 14336)
    assert counts["mhc_post_res_mix_flops"] == 8192 * 2 * 20 * 3584
    # both are bandwidth-bound by these counts: under 10 FLOP a byte
    assert counts["mhc_pre_mix_flops"] / counts["mhc_pre_mix_bytes"] < 11
    assert counts["mhc_post_res_mix_flops"] \
        / counts["mhc_post_res_mix_bytes"] < 2
    # the latent decode kernel at 32 heads: 2 * 32 * (576 + 512) FLOPs
    # against 1,152 bytes a cached token, a quarter of DeepSeek-V2's 242
    assert counts["mla_decode_flops"] == 2 * 32 * 1088 * live
    assert counts["mla_decode_bytes"] == 1152 * live + 2 * 32 * 32 * 1088
    assert counts["mla_decode_flops"] / (1152 * live) == pytest.approx(
        60.4, abs=0.1)
    assert counts["mla_prefill_flops"] == 32 * 8192 ** 2 * 320
    assert counts["mla_prefill_bytes"] == 32 * 8192 * 2 * 320 * 2
    # an expert layer's two grouped products over 8,192 x 4 pairs: ~512 rows
    # an expert
    assert counts["moe_grouped_flops"] == 2 * 32768 * 3 * 3584 * 1024
    assert counts["moe_grouped_bytes"] == 2 * (
        64 * expert + 32768 * (2 * 3584 + 3 * 1024))
    assert 32768 / 64 == 512
    m = 0.1 * math.log(64) + 1
    assert model.yarn_softmax_scale(mc) == pytest.approx(
        m * m / math.sqrt(192))


# -- reference against the model --------------------------------------------

def test_reference_imports_nothing_of_the_program():
    from benchmarks.reference import xing as ref
    with open(ref.__file__) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if re.match(r"\s*(from|import)\s", line)]
    assert not any("deepspeed_tpu" in line for line in imports)
    assert "from . import ops" in imports


def test_reference_scale_and_frequencies_are_the_programs(cell):
    from benchmarks.reference import xing as ref
    from deepspeed_tpu.models.deepseek_v2 import (yarn_inv_freq,
                                                  yarn_softmax_scale)
    model = models.load("xing")
    for mc in (_tiny_xing.MODEL, cell["config"]["model_config"]):
        config = model.build_program_model(mc, {}).config
        np.testing.assert_allclose(ref.yarn_inv_freq(mc),
                                   yarn_inv_freq(config), rtol=1e-6)
        assert ref.softmax_scale(mc) == pytest.approx(
            yarn_softmax_scale(config))
        assert ref.softmax_scale(mc) == pytest.approx(
            model.yarn_softmax_scale(mc))


def test_routing_margin_by_hand_and_what_is_not_judged():
    from benchmarks.reference import xing as ref
    # the last chosen stands at 0.66 = 0.60 + its bias, the first not chosen
    # at 0.62 = 0.70 - 0.08: the steeper sigmoid of the two is 0.6's
    got = float(ref.choice_margin(
        jnp.asarray([0.66, 0.62]), jnp.asarray([0.60, 0.70]))[()])
    assert got == pytest.approx(0.04 / (0.6 * 0.4))
    mc = _tiny_xing.MODEL
    model = models.load("xing")
    params = model.init_params(mc, 5)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(2, 48)), jnp.int32)
    rows = jnp.repeat(jnp.arange(2), 48)
    cols = jnp.tile(jnp.arange(48), 2)
    mm = ops.MATMULS["float32"]
    logits, margins = ref.logits_and_margins(params, ids, rows, cols, mc, mm)
    margins = np.asarray(margins)
    assert (margins > 0).all() and np.isfinite(margins).all()
    tau = float(np.median(margins))
    judged = np.asarray(ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau), mm))
    narrow = margins < tau
    assert 0 < narrow.sum() < len(narrow)
    assert not judged[narrow].any()                  # flat: gap 0 there
    np.testing.assert_array_equal(judged[~narrow],
                                  np.asarray(logits)[~narrow])
    # the control comes back as its best token, one int8 one-hot row a
    # position, judged where the float32 pass judges
    low = ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau),
        ops.MATMULS["fp8"])
    assert low.dtype == jnp.int8 and low.shape == logits.shape
    assert bool((low.sum(axis=-1) == 1).all())
    moved = np.asarray(jnp.argmax(low, -1) != jnp.argmax(logits, -1))
    assert 0 < moved.sum()
    # no threshold in the configuration: every position is judged
    np.testing.assert_array_equal(
        np.asarray(ref.position_logits(params, ids, rows, cols, mc, mm)),
        np.asarray(logits))


def test_a_row_hands_back_only_the_positions_asked_of_it():
    """``hidden_at`` against the whole ``[rows, seq, hidden]``: any order of
    the positions, rows of unequal numbers within the room a row has, and a
    position past its row's room NaN (no limit passes a NaN)."""
    from benchmarks.reference import xing as ref
    mc = _tiny_xing.MODEL
    params = models.load("xing").init_params(mc, 4)
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, 256, size=(3, 24)), jnp.int32)
    mm = ops.MATMULS["float32"]
    whole, margins = ref.hidden(params, ids, mc, mm)
    rows = jnp.asarray([2, 0, 1, 2, 0, 2, 1, 0, 1])      # three a row
    cols = jnp.asarray([5, 23, 0, 7, 1, 6, 11, 2, 12])
    x, margin = ref.hidden_at(params, ids, rows, cols, mc, mm)
    np.testing.assert_allclose(x, whole[rows, cols], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(margin, margins[rows, cols], rtol=1e-5)
    # four of row 0 where a row has room for three: the fourth is NaN, the
    # others stand
    rows = jnp.asarray([0, 0, 1, 0, 0, 2, 1, 2, 1])
    x, margin = ref.hidden_at(params, ids, rows, cols, mc, mm)
    late = np.asarray([False] * 4 + [True] + [False] * 4)
    assert np.isnan(np.asarray(x)[late]).all()
    assert np.isnan(np.asarray(margin)[late]).all()
    np.testing.assert_allclose(np.asarray(x)[~late],
                               np.asarray(whole[rows, cols])[~late],
                               rtol=1e-5, atol=1e-5)


def test_the_head_in_blocks_is_the_head_whole():
    from benchmarks.reference import xing as ref
    mc = _tiny_xing.MODEL
    params = models.load("xing").init_params(mc, 9)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, size=(1, 24)), jnp.int32)
    rows, cols = jnp.zeros(24, jnp.int32), jnp.arange(24)
    mm = ops.MATMULS["float32"]
    logits, _ = ref.logits_and_margins(params, ids, rows, cols, mc, mm)
    hidden, _ = ref.hidden(params, ids, mc, mm)
    whole = hidden[0] @ params["lm_head"]["kernel"].astype(jnp.float32)
    np.testing.assert_allclose(logits, whole, rtol=1e-5, atol=1e-5)
    assert ref.VOCAB_BLOCKS == 8 and mc["vocab_size"] % 8 == 0


# -- the engine and the cell at tiny size -------------------------------------

def test_engine_serves_the_model_through_its_one_interface(devices):
    spec = _tiny_xing.serve_spec(LIMITS)
    loop = serve.setup(spec, 3, devices)
    engine = loop.engine
    assert type(engine).__name__ == "InferenceEngine"
    buffers = engine.serving.cache_buffers(engine.inference_config)
    assert tuple(buffers) == ("latent_cache",)
    assert [c.shape[-1] for c in engine._caches] == [128]
    for _ in range(120):
        loop.step()
    assert engine.decode_iterations > 100
    counters = engine.model_counters
    assert float(counters["moe_local_assignment_share"]) == 1.0
    assert float(counters["moe_pair_passes"]) == 1.0
    assert float(counters["hc_streams"]) == 4.0
    assert float(counters["hc_res_stochastic_err_max"]) < 1e-4
    sample = serve.sample_finished(loop.finished, 3, n=12)
    serve.free(loop)
    gaps = serve.reference_gaps(spec, 3, sample)
    assert len(gaps) > 50
    assert gaps.max() <= LIMITS["served_logit_gap"]


def test_tiny_cell_is_correct_through_run_cell(devices, capsys):
    spec = _tiny_xing.serve_spec(LIMITS)
    ok = serve.run_cell(spec, 2 ** 31 + 6, 1.5, 0, time.perf_counter(),
                        devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert any(l.startswith("check served_logit_gap") and " ok" in l
               for l in out)
    assert any(l.startswith("check no_compile_in_window ok") for l in out)


@pytest.mark.parametrize("seed", [2, 7])
def test_fp8_control_fails_the_tiny_cells_limit(devices, seed):
    spec = _tiny_xing.serve_spec(LIMITS)
    loop = serve.setup(spec, seed, devices)
    for _ in range(150):
        loop.step()
    sample = serve.sample_finished(loop.finished, seed, n=12)
    serve.free(loop)
    sound = serve.reference_gaps(spec, seed, sample)
    control = serve.reference_gaps(spec, seed, sample, "fp8")
    assert len(sound) > 50
    assert sound.max() <= LIMITS["served_logit_gap"]
    assert control.max() > 100 * LIMITS["served_logit_gap"]
