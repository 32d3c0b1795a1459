"""DeepSeek-V2 through the harness at a tiny size on the CPU: the plain
reference against the program (prefill through a bucket, then absorbed
decode through the paged latent cache), the shares of an expert layer
against the uncut layer, the engine on both model families through its one
interface, the counts by hand, and ``correct`` coming out true for a sound
run and false for the float8 control."""

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, models, serve
from benchmarks.reference import ops

from . import _tiny, _tiny_deepseek

# float32 program at the tiny size: sound runs read 0 (the served token is
# the reference's best); the float8 control reads 0.02-0.2 over seeds 1-9
LIMITS = {"served_logit_gap": 1e-3}
CELL = "deepseek_v2_ep8.repo_backlog"


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


@pytest.fixture(scope="module")
def cell():
    return common.load_cell(CELL)


def _last_line(capsys):
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(out[-1]), out


# -- the configuration's file ------------------------------------------------

PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_attention_heads": 128,
    "num_key_value_heads": 128, "moe_intermediate_size": 1536,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "n_group": 8,
    "topk_group": 3, "routed_scaling_factor": 16, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 163840, "moe_layer_freq": 1,
    "norm_topk_prob": False, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy", "tie_word_embeddings": False}


def test_configuration_holds_the_published_widths_and_states_its_cuts(cell):
    cfg = cell["config"]
    mc = cfg["model_config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and mc[key] == value, key
    assert cfg["rope_scaling"] == mc["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the three cuts, each with the published number beside it
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    for key, held, published in (
            ("num_hidden_layers", 5, ("published_num_hidden_layers", 60)),
            ("n_routed_experts", 20, ("published_n_routed_experts", 160)),
            ("vocab_size", 12800, ("source_vocab_size", 102400))):
        assert cfg[key] == mc[key] == held
        assert mc[published[0]] == published[1]
    # floors of the guide: a whole period and four expert layers, 8 routed
    # experts a layer, an eighth of the vocabulary
    assert mc["num_hidden_layers"] - mc["first_k_dense_replace"] >= 4
    assert mc["n_routed_experts"] == 160 // mc["n_group"]
    assert mc["vocab_size"] * 8 >= 102400
    # every key of the published config is at the top level as it is run
    assert all(mc[k] == cfg[k] for k in cfg if k in mc)
    # token ids are drawn below the held slice
    from benchmarks.generators._requests import token_id_range
    assert token_id_range(mc) == 12800
    icfg = cfg["engine"]["inference"]
    assert icfg["kv_blocks"] == 64 * 12288 // 64 + 1
    assert icfg["token_budget"] == 64 * 12288
    pairs = cell["traffic"]["pairs"]
    assert len(pairs) == 128
    assert all(3072 <= p <= 8192 and 1024 <= a <= 4096 and p + a <= 12288
               for p, a in pairs)
    assert max(p for p, _ in pairs) <= max(icfg["prefill_buckets"])
    assert cell["traffic"]["callers"] == 96
    assert "deepseek_v2_ep8" in cell["traffic"]["limits"]


@pytest.mark.parametrize("mc", ["tiny", "cell"])
def test_parameter_tree_is_the_programs(mc, cell):
    mc = (_tiny_deepseek.MODEL if mc == "tiny"
          else cell["config"]["model_config"])
    model = models.load("deepseek_v2")
    program = model.build_program_model(mc, {})
    assert model.param_shapes(mc) == program.param_shapes()
    assert program.config.experts_held == mc["n_routed_experts"]
    assert program.config.n_routed_experts == mc["published_n_routed_experts"]


def test_seeded_weights_come_in_the_serving_dtype():
    model = models.load("deepseek_v2")
    mc = dict(_tiny_deepseek.MODEL, weights_dtype="bfloat16")
    a, b = model.init_params(mc, 2 ** 31 + 5), model.init_params(mc, 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves(a)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    assert all(bool((x == y).all()) for x, y in zip(
        leaves, jax.tree_util.tree_leaves(b)))
    other = jax.tree_util.tree_leaves(model.init_params(mc, 3))
    assert not bool((leaves[0] == other[0]).all())
    kernel = np.asarray(a["layers"]["layer_1"]["moe"]["experts"]["down"],
                        np.float32)
    assert kernel.std() == pytest.approx(mc["initializer_range"], rel=0.05)
    assert bool((a["final_norm"]["scale"] == 1).all())


# -- the counts, by hand -----------------------------------------------------

def test_counts_by_hand(cell):
    model = models.load("deepseek_v2")
    mc = cell["config"]["model_config"]
    attention = (5120 * 1536 + 1536 + 1536 * 128 * 192 + 5120 * 576 + 512
                 + 512 * 128 * 256 + 128 * 128 * 5120 + 2 * 5120)
    assert attention == pytest.approx(149.2e6, rel=1e-3)
    dense = attention + 3 * 5120 * 12288
    expert_layer = (attention + 5120 * 160 + 3 * 5120 * 3072
                    + 20 * 3 * 5120 * 1536)
    total = dense + 4 * expert_layer + 2 * 12800 * 5120 + 5120
    assert model.param_count(mc) == total
    assert 2 * total == pytest.approx(6.29e9, rel=1e-3)
    # the program's tree holds exactly these
    assert sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))) \
        == total
    reached = 20 * (1 - (1 - 6 / 160) ** 64)
    assert model.held_experts_reached(mc, 64) == pytest.approx(reached)
    assert reached == pytest.approx(18.27, abs=0.01)
    live = 505000
    weights = (dense + 4 * (attention + 5120 * 160 + 3 * 5120 * 3072
                            + reached * 3 * 5120 * 1536)
               + 12800 * 5120 + 5120)
    assert model.decode_bytes_per_step(mc, live) == pytest.approx(
        2 * weights + 5 * live * 1152)
    # the decode kernel: 2 * 128 * (576 + 512) FLOPs against 1,152 bytes a
    # cached token is the v5e's ridge
    flops = model.mla_decode_flops(mc, live)
    moved = model.mla_decode_bytes(mc, live, 64)
    assert flops == 2 * 128 * 1088 * live
    assert moved == 1152 * live + 2 * 64 * 128 * 1088
    assert flops / (1152 * live) == pytest.approx(241.8, abs=0.1)
    # the prefill kernel, causal: half of 2 s^2 (192 + 128) a head
    assert model.mla_prefill_flops(mc, 8192) == 128 * 8192 ** 2 * 320
    assert model.mla_prefill_bytes(mc, 8192) == 128 * 8192 * 2 * 320 * 2
    m = 0.1 * 0.707 * math.log(40) + 1
    assert model.yarn_softmax_scale(mc) == pytest.approx(
        m * m / math.sqrt(192))


# -- reference against the model --------------------------------------------

def test_reference_scale_and_frequencies_are_the_programs():
    from benchmarks.reference import deepseek_v2 as ref
    from deepspeed_tpu.models.deepseek_v2 import (yarn_inv_freq,
                                                  yarn_softmax_scale)
    model = models.load("deepseek_v2")
    for mc in (_tiny_deepseek.MODEL,
               common.load_cell(CELL)["config"]["model_config"]):
        config = model.build_program_model(mc, {}).config
        np.testing.assert_allclose(ref.yarn_inv_freq(mc),
                                   yarn_inv_freq(config), rtol=1e-6)
        assert ref.softmax_scale(mc) == pytest.approx(
            yarn_softmax_scale(config))
        assert ref.softmax_scale(mc) == pytest.approx(
            model.yarn_softmax_scale(mc))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold a group of four experts each; what all of them
    compute alike (the shared expert) counted once, their parts add up to
    the reference's layer with all sixteen experts — and each share is
    what the program's layer gives when told that it holds that group."""
    from benchmarks.reference import deepseek_v2 as ref
    model = models.load("deepseek_v2")
    whole_cfg = dict(_tiny_deepseek.MODEL, n_routed_experts=16)
    whole = model.init_params(whole_cfg, 11)["layers"]["layer_1"]["moe"]
    z = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    mm = ops.MATMULS["float32"]
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.experts_layer(whole, z, whole_cfg, mm)
        shared = ref.gated_mlp(
            whole["shared"]["gate_up"]["kernel"],
            whole["shared"]["down"]["kernel"], z, mm)
        total = jnp.zeros_like(uncut)
        for first in (0, 4, 8, 12):
            cfg = dict(_tiny_deepseek.MODEL, first_expert=first)
            share = dict(whole, experts=jax.tree_util.tree_map(
                lambda w: w[first:first + 4], whole["experts"]))
            part, _ = ref.experts_layer(share, z, cfg, mm)
            total = total + part - shared
            serving = model.build_program_model(cfg, {}).serving()
            got, counts = serving._mlp(
                {"moe": share}, z, jnp.float32, jnp.ones((24,), bool),
                (16, 128, 128))
            np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                       rtol=2e-4, atol=2e-5)
            assert int(counts.sum()) == 24 * 3
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(uncut), rtol=2e-4, atol=2e-5)
    # the shares differ: no share is the whole
    assert float(jnp.abs(part - uncut).max()) > 1e-3


@pytest.mark.parametrize("prompt_len", [5, 16, 27])
def test_decode_through_the_latent_cache_matches_the_full_forward(
        prompt_len):
    """A bucketed prefill (expanded path), then absorbed decode through the
    paged cache: every served token's logit is the reference's best, by the
    reference's own full forward over the whole sequence."""
    from deepspeed_tpu.inference import InferenceEngine
    mc = _tiny_deepseek.MODEL
    model, ref = models.load_with_reference("deepseek_v2")
    params = model.init_params(mc, 5)
    engine = InferenceEngine(model.build_program_model(mc, {}), params,
                             config=_tiny_deepseek.ENGINE)
    prompt = np.random.default_rng(prompt_len).integers(
        0, 256, size=prompt_len)
    rid = engine.submit(prompt, max_new_tokens=14)
    tokens = engine.run()[rid]["tokens"]
    assert len(tokens) == 14
    ids = np.zeros((1, 64), np.int32)
    ids[0, :prompt_len] = prompt
    ids[0, prompt_len:prompt_len + 14] = tokens
    cols = jnp.arange(prompt_len - 1, prompt_len + 13)
    logits = np.asarray(ref.position_logits(
        params, jnp.asarray(ids), jnp.zeros(14, jnp.int32), cols, mc,
        ops.MATMULS["float32"]))
    assert logits.std() > 0.5        # not a flat distribution
    served = logits[np.arange(14), tokens]
    np.testing.assert_allclose(served, logits.max(axis=-1), atol=1e-3)
    assert tokens == logits.argmax(axis=-1).tolist()


def test_routing_margin_worked_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, 3 experts chosen; this
    chip holds group 1 (experts 2 and 3)."""
    from benchmarks.reference import deepseek_v2 as ref
    cfg = {"first_expert": 2, "n_routed_experts": 2}

    def margin(scores, kept, groups, experts):
        log = jnp.log(jnp.asarray(scores).reshape(1, 4, 2))
        return float(ref.held_margin(
            log, jnp.asarray([kept]), jnp.log(jnp.asarray([groups])),
            jnp.log(jnp.asarray([experts])), cfg)[0])

    # groups by best expert: .30 (g0) .25 (g1) .20 (g2) .02 (g3): g0, g1
    # kept; chosen .30 .25 .06, first not chosen .02
    scores = [.30, .02, .06, .25, .20, .19, .02, .01]
    kept = [True, True, False, False]
    got = margin(scores, kept, [.25, .20], [.06, .02])
    # the held group is kept: two other groups could swap (.25 / .20); the
    # held expert .06 is chosen and falls out below .02; .25 stands far
    assert got == pytest.approx(min(math.log(.25 / .20), math.log(.06 / .02)))
    # the held group left out: its best expert .20 against the last kept .25
    cfg = {"first_expert": 4, "n_routed_experts": 2}
    assert margin(scores, kept, [.25, .20], [.06, .02]) == pytest.approx(
        math.log(.25 / .20))
    # a held group far from every edge: nothing of it can change
    cfg = {"first_expert": 6, "n_routed_experts": 2}
    assert margin(scores, kept, [.25, .20], [.06, .02]) == pytest.approx(
        math.log(.25 / .02))
    # an expert not chosen comes in above the last chosen
    cfg = {"first_expert": 0, "n_routed_experts": 2}
    assert margin(scores, kept, [.25, .20], [.06, .02]) == pytest.approx(
        math.log(.25 / .20))
    scores[1] = .055
    assert margin(scores, kept, [.25, .20], [.06, .055]) == pytest.approx(
        math.log(.06 / .055))


def test_positions_with_a_narrow_routing_margin_are_not_judged():
    from benchmarks.reference import deepseek_v2 as ref
    mc = _tiny_deepseek.MODEL
    model = models.load("deepseek_v2")
    params = model.init_params(mc, 5)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(2, 48)), jnp.int32)
    rows = jnp.repeat(jnp.arange(2), 48)
    cols = jnp.tile(jnp.arange(48), 2)
    mm = ops.MATMULS["float32"]
    logits, margins = ref.logits_and_margins(params, ids, rows, cols, mc, mm)
    margins = np.asarray(margins)
    assert (margins > 0).all() and np.isfinite(margins).any()
    tau = float(np.median(margins[np.isfinite(margins)]))
    judged = np.asarray(ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau), mm))
    narrow = margins < tau
    assert 0 < narrow.sum() < len(narrow)
    assert not judged[narrow].any()                  # flat: gap 0 there
    np.testing.assert_array_equal(judged[~narrow],
                                  np.asarray(logits)[~narrow])
    # the margins are the float32 pass's: the control's logits come back
    # whole, so that its tokens are judged where the float32 pass judges
    low = np.asarray(ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau),
        ops.MATMULS["fp8"]))
    assert low[narrow].any() and low.std(axis=-1).min() > 0
    # no threshold in the configuration: every position is judged
    np.testing.assert_array_equal(
        np.asarray(ref.position_logits(params, ids, rows, cols, mc, mm)),
        np.asarray(logits))


# -- the engine, both families through one interface -------------------------

@pytest.mark.parametrize("family", ["gpt2", "deepseek_v2"])
def test_engine_serves_both_families_through_one_interface(family, devices):
    spec = (_tiny.serve_spec(LIMITS) if family == "gpt2"
            else _tiny_deepseek.serve_spec(LIMITS))
    loop = serve.setup(spec, 3, devices)
    engine = loop.engine
    assert type(engine).__name__ == "InferenceEngine"
    buffers = engine.serving.cache_buffers(engine.inference_config)
    assert tuple(engine.cache_block_bytes) == tuple(buffers)
    assert [c.shape[-1] for c in engine._caches] == list(buffers.values())
    for _ in range(120):
        loop.step()
    assert engine.decode_iterations > 100
    if family == "deepseek_v2":
        # the expert layers' counters came back in the decode fetch
        share = float(engine.model_counters["moe_local_assignment_share"])
        peak = float(engine.model_counters["moe_expert_load_max_over_mean"])
        assert 0.0 <= share <= 1.0 and peak >= 1.0
    else:
        assert engine.model_counters == {}
    sample = serve.sample_finished(loop.finished, 3, n=12)
    serve.free(loop)
    gaps = serve.reference_gaps(spec, 3, sample)
    assert len(gaps) > 50
    assert gaps.max() <= LIMITS["served_logit_gap"]


def test_decode_fetches_once_an_iteration_with_the_counters_in_it(
        devices, monkeypatch):
    spec = _tiny_deepseek.serve_spec(LIMITS)
    loop = serve.setup(spec, 4, devices)
    for _ in range(5):
        loop.step()                 # past the admissions
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    before = loop.engine.decode_iterations
    prefills = loop.engine.scheduler.admitted_total
    for _ in range(10):
        loop.step()
    iterations = loop.engine.decode_iterations - before
    admitted = loop.engine.scheduler.admitted_total - prefills
    monkeypatch.setattr(jax, "device_get", real)
    assert iterations == 10 and len(calls) == iterations + admitted
    serve.free(loop)


def test_live_bytes_gauges_are_named_after_the_models_buffers(devices,
                                                              tmp_path):
    from deepspeed_tpu.inference import InferenceEngine
    mc = _tiny_deepseek.MODEL
    model = models.load("deepseek_v2")
    config = dict(_tiny_deepseek.ENGINE, steps_per_print=4, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "t"})
    engine = InferenceEngine(model.build_program_model(mc, {}),
                             model.init_params(mc, 1), config=config)
    engine.submit(list(range(1, 12)), max_new_tokens=10)
    engine.run()
    gauge = engine.telemetry.gauge
    assert gauge("serving/latent_cache_live_bytes").value > 0
    # one live block of 3 layers x 8 tokens x 128 stored values x 4 bytes
    assert gauge("serving/latent_cache_live_bytes").value % (
        3 * 8 * 128 * 4 / 4) == 0
    assert 0 < gauge("serving/moe_local_assignment_share").value <= 1
    assert gauge("serving/moe_expert_load_max_over_mean").value >= 1
    assert gauge("serving/kv_live_block_share").value > 0
    engine.close()


# -- the cell at tiny size ---------------------------------------------------

def test_tiny_cell_is_correct_and_the_fp8_control_is_not(devices, capsys):
    spec = _tiny_deepseek.serve_spec(LIMITS)
    ok = serve.run_cell(spec, 6, 1.5, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert any(l.startswith("check served_logit_gap") and " ok" in l
               for l in out)
    assert any(l.startswith("check no_compile_in_window ok") for l in out)


@pytest.mark.parametrize("seed", [2, 7])
def test_fp8_control_fails_the_tiny_cells_limit(devices, seed):
    spec = _tiny_deepseek.serve_spec(LIMITS)
    loop = serve.setup(spec, seed, devices)
    for _ in range(150):
        loop.step()
    sample = serve.sample_finished(loop.finished, seed, n=12)
    serve.free(loop)
    sound = serve.reference_gaps(spec, seed, sample)
    control = serve.reference_gaps(spec, seed, sample, "fp8")
    assert len(sound) > 50
    assert sound.max() <= LIMITS["served_logit_gap"]
    assert control.max() > 10 * LIMITS["served_logit_gap"]


def test_layer_metric_files_match_the_kernels_names():
    """The three new metrics read the device-visible names the program
    gives its kernels."""
    from benchmarks import metrics
    from deepspeed_tpu.models import deepseek_v2, expert_shard
    from deepspeed_tpu.ops.transformer import (grouped_matmul,
                                               mla_paged_attention)
    files = metrics.load_all()
    patterns = {n: files[n]["reader"]["args"]["pattern"] for n in (
        "mla_decode_attn_ms", "mla_prefill_attn_ms", "moe_experts_ms")}
    assert patterns["mla_decode_attn_ms"] == \
        mla_paged_attention.mla_paged_decode_attention.__name__
    assert patterns["moe_experts_ms"] == \
        grouped_matmul.moe_grouped_matmul.__name__
    with open(deepseek_v2.__file__) as f:
        assert f'name="{patterns["mla_prefill_attn_ms"]}"' in f.read()
    assert expert_shard.moe_grouped_matmul is \
        grouped_matmul.moe_grouped_matmul
    for name in patterns:
        assert files[name]["reader"]["reducer"] == "op_ms_per_step"
        assert files[name]["moves"] == "serve_tokens_per_s"
