"""The K-EXAONE share (``k_exaone_ep8.reason_backlog``): its configuration's
file, the counts by hand, the plain reference against the program's layer
shares, and the cell at the tests' size through ``InferenceEngine`` on the CPU
(the kernels run through Pallas' interpreter) — prefill then decode through
BOTH caches against the reference's full forward, logits not tokens, with the
planted faults the limit has to catch."""

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

from . import _tiny_exaone

# float32 program at the tiny size: sound runs read 0 (the served token is
# the reference's best)
LIMITS = {"served_logit_gap": 1e-3}
CELL = "k_exaone_ep8.reason_backlog"
MM = ops.MATMULS["float32"]


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


@pytest.fixture(scope="module")
def cell():
    return common.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    model, ref = models.load_with_reference("exaone_moe")
    return model, ref, model.init_params(_tiny_exaone.MODEL, 5)


def _last_line(capsys):
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(out[-1]), out


# -- the configuration's file ------------------------------------------------

PUBLISHED = {
    "hidden_size": 6144, "intermediate_size": 18432, "head_dim": 128,
    "num_attention_heads": 64, "num_key_value_heads": 8,
    "moe_intermediate_size": 2048, "num_shared_experts": 1,
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "sliding_window": 128,
    "max_position_embeddings": 262144, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "hidden_act": "silu",
    "model_type": "exaone_moe", "tie_word_embeddings": False,
    "sliding_window_pattern": "LLLG"}


def test_configuration_holds_the_published_widths_and_states_its_cuts(cell):
    cfg = cell["config"]
    mc = cfg["model_config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and mc[key] == value, key
    assert cfg["rope_parameters"] == mc["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default"}
    # the per-layer lists are the published ones whole; the first five
    # entries are the layers run: the dense window layer, then W W F W
    window, full = "sliding_attention", "full_attention"
    assert cfg["layer_types"] == mc["layer_types"] == \
        [window, window, window, full] * 12
    assert cfg["sliding_windows"] == [128, 128, 128, 0] * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    model = models.load("exaone_moe")
    assert model.layer_kinds(mc) == [
        (window, "dense"), (window, "sparse"), (window, "sparse"),
        (full, "sparse"), (window, "sparse")]
    # the four cuts, each with the published number beside it
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert set(cfg["reduced_notes"]) == set(cfg["reduced"])
    for key, held, published in (
            ("num_hidden_layers", 5, ("published_num_hidden_layers", 48)),
            ("num_experts", 16, ("published_num_experts", 128)),
            ("vocab_size", 19200, ("source_vocab_size", 153600)),
            ("num_nextn_predict_layers", 0,
             ("published_num_nextn_predict_layers", 1))):
        assert cfg[key] == mc[key] == held
        assert mc[published[0]] == published[1]
    # every assumption is stated with its reason
    for key in ("qk_norm", "rotary_on_window_layers_only", "pre_norm",
                "selection_bias", "judge_routing_margin"):
        assert len(cfg["assumed"][key]) > 40
    # floors of the guide: the dense layer and a whole period of expert
    # layers, 8 routed experts a layer, an eighth of the vocabulary
    assert mc["num_hidden_layers"] - mc["first_k_dense_replace"] >= 4
    assert mc["num_experts"] * 8 == mc["published_num_experts"]
    assert mc["vocab_size"] * 8 == 153600
    assert all(mc[k] == cfg[k] for k in cfg if k in mc)
    from benchmarks.generators._requests import token_id_range
    assert token_id_range(mc) == 19200
    icfg = cfg["engine"]["inference"]
    assert icfg["kv_blocks"] == 64 * 12288 // 64 + 1
    assert icfg["token_budget"] == 64 * 12288
    assert icfg["prefill_buckets"] == [3072, 4096, 5120, 6144]
    pairs = cell["traffic"]["pairs"]
    assert len(pairs) == 128
    assert all(2048 <= p <= 6144 and 2048 <= a <= 6144 and p + a <= 12288
               for p, a in pairs)
    padded = [min(b for b in icfg["prefill_buckets"] if b >= p)
              for p, _ in pairs]
    assert 1 - sum(p for p, _ in pairs) / sum(padded) <= 0.20
    assert cell["traffic"]["callers"] == 96
    assert cell["traffic"]["warmup_iterations"] == 8
    assert "k_exaone_ep8" in cell["traffic"]["limits"]
    assert "bytes" in cfg and "deployment" in cfg


def test_the_traffic_is_the_recipe_of_its_lengths_file():
    import random
    import statistics
    nd = statistics.NormalDist()

    def quantiles(sigma):
        return [int(round(min(max(4096 * math.exp(
            sigma * nd.inv_cdf((i + 0.5) / 128)), 2048), 6144)))
            for i in range(128)]

    prompts, answers = quantiles(0.30), quantiles(0.35)
    rng = random.Random(20260929)
    rng.shuffle(answers)
    pairs = [[p, a] for p, a in zip(prompts, answers)]
    rng.shuffle(pairs)
    assert common.load_traffic("reason_backlog")["pairs"] == pairs


@pytest.mark.parametrize("mc", ["tiny", "cell"])
def test_parameter_tree_is_the_programs(mc, cell):
    mc = _tiny_exaone.MODEL if mc == "tiny" else cell["config"][
        "model_config"]
    model = models.load("exaone_moe")
    program = model.build_program_model(mc, {})
    assert program.param_shapes() == model.param_shapes(mc)
    leaves = jax.tree_util.tree_leaves(
        model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in leaves) == model.param_count(mc)


def test_seeded_weights_come_in_the_serving_dtype_with_a_zero_bias():
    model = models.load("exaone_moe")
    mc = dict(_tiny_exaone.MODEL, weights_dtype="bfloat16")
    a, b = model.init_params(mc, 7), model.init_params(mc, 7)
    other = model.init_params(mc, 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves(a)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    assert all(bool((x == y).all()) for x, y in zip(
        leaves, jax.tree_util.tree_leaves(b)))
    assert bool((a["embed"] != other["embed"]).any())
    router = a["layers"]["layer_1"]["moe"]["router"]
    assert not bool(router["bias"].any()) and bool(router["kernel"].any())
    assert bool((a["layers"]["layer_0"]["q_norm"]["scale"] == 1).all())


def test_counts_by_hand(cell):
    mc = cell["config"]["model_config"]
    model = models.load("exaone_moe")
    h = 6144
    attention = h * (8192 + 2 * 1024) + 2 * 128 + 8192 * h + 2 * h
    assert model._attention_params(mc) == attention == 113_258_752
    expert = 3 * h * 2048
    sparse = h * 128 + 128 + expert + 16 * expert
    total = (5 * attention + 3 * h * 18432 + 4 * sparse
             + 2 * 19200 * h + h)
    assert model.param_count(mc) == total == 3_712_028_416
    # 64 tokens x 8 of 128: 1 - (15/16)^64 of the 16 held are reached
    reached = 16 * (1 - (1 - 8 / 128) ** 64)
    assert model.held_experts_reached(mc, 64) == pytest.approx(reached)
    assert 15.7 < reached < 15.8
    live = 64 * 5000
    weights = (5 * attention + 3 * h * 18432
               + 4 * (h * 128 + 128 + expert + reached * expert)
               + 19200 * h + h)
    cached = (live + 4 * 64 * 128) * 2 * 1024 * 2
    assert model.decode_bytes_per_step(mc, live) == pytest.approx(
        2 * weights + cached)
    # 7.1 GB of weights beside 1.3 GB of the full layer's K/V and 0.13 GB
    # of the four windows'
    assert 7.0e9 < 2 * weights < 7.2e9
    assert live * 4096 == pytest.approx(1.31e9, rel=0.01)
    assert 4 * 64 * 128 * 4096 == pytest.approx(0.134e9, rel=0.01)
    # a context shorter than the window reads no more than it holds
    assert model.cached_tokens_read(mc, 100, 64) == (100, 100)
    c = model.counts(mc, live, 64, 4096)
    assert c["gqa_decode_flops"] == 4 * 64 * 128 * live
    assert c["gqa_decode_bytes"] == 2 * (2 * 1024 * live + 2 * 64 * 8192)
    assert c["window_decode_flops"] == 4 * 64 * 128 * 64 * 128
    assert c["window_decode_bytes"] == 2 * (2 * 1024 * 64 * 128
                                            + 2 * 64 * 8192)
    assert c["gqa_prefill_flops"] == 4 * 64 * 128 * (4096 * 4097 // 2)
    # a position sees min(t + 1, 128) keys
    assert c["window_prefill_flops"] == 4 * 64 * 128 * sum(
        min(t + 1, 128) for t in range(4096))
    assert c["gqa_prefill_bytes"] == c["window_prefill_bytes"] == \
        2 * 4096 * (2 * 8192 + 2 * 1024)
    # 0.56 TFLOP of attention a 4096-prompt in the full layer, 0.017 a
    # window layer: whole-context windows would be 4 x the full layer's
    assert c["gqa_prefill_flops"] / c["window_prefill_flops"] > 16


# -- the reference against the program's pieces -------------------------------

def test_rotation_and_norms_are_the_programs(tiny):
    from deepspeed_tpu.models import exaone_moe as program
    model, ref, _ = tiny
    mc = _tiny_exaone.MODEL
    config = model.build_program_model(mc, {}).config
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 4, 128))
    np.testing.assert_allclose(
        np.asarray(program.rotate(x, jnp.arange(24), config)),
        np.asarray(ref.rotate(x, mc)), rtol=1e-5, atol=1e-5)
    # position 0 is not turned; the pairs are (c, c + 64)
    np.testing.assert_array_equal(np.asarray(ref.rotate(x, mc))[0],
                                  np.asarray(x)[0])
    one = jnp.zeros((2, 1, 128)).at[:, 0, 3].set(1.0)
    turned = np.asarray(ref.rotate(one, mc))[1, 0]
    assert set(np.nonzero(turned)[0]) == {3, 67}
    assert turned[3] == pytest.approx(math.cos(1e6 ** (-6 / 128)))


def test_the_router_is_sigmoid_with_a_bias_that_chooses_and_does_not_weigh():
    from deepspeed_tpu.models import expert_shard
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    kw = dict(n_group=1, topk_group=1, top_k=3, scaling=2.5,
              scoring="sigmoid", renormalise=True)
    scores = np.asarray(jax.nn.sigmoid(z @ kernel))
    w0, ids0 = expert_shard.route(z, kernel, bias=jnp.zeros(16), **kw)
    # the three best sigmoid scores, renormalised over the three, x 2.5
    np.testing.assert_array_equal(np.sort(np.asarray(ids0), axis=1),
                                  np.sort(np.argsort(-scores, 1)[:, :3], 1))
    np.testing.assert_allclose(np.asarray(w0).sum(axis=1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(scores, np.asarray(ids0), axis=1)
    np.testing.assert_allclose(
        np.asarray(w0), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-6)
    # a bias on expert 5 brings it into every token's choice, and the
    # weights are still the chosen experts' own scores, renormalised
    bias = jnp.zeros(16).at[5].set(10.0)
    w1, ids1 = expert_shard.route(z, kernel, bias=bias, **kw)
    assert (np.asarray(ids1) == 5).any(axis=1).all()
    assert not (np.asarray(ids0) == 5).any(axis=1).all()
    picked = np.take_along_axis(scores, np.asarray(ids1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w1).sum(axis=1), 2.5, rtol=1e-6)


def test_the_reference_routes_as_the_program_does(tiny):
    from deepspeed_tpu.models import expert_shard
    model, ref, params = tiny
    mc = _tiny_exaone.MODEL
    moe = dict(params["layers"]["layer_2"]["moe"])
    moe["router"] = dict(moe["router"], bias=jnp.asarray(
        np.random.default_rng(1).standard_normal(16) * 0.2, jnp.float32))
    z = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    with jax.default_matmul_precision("highest"):
        w_ref, ids_ref, _ = ref.route(moe, z, mc)
    w, ids = expert_shard.route(
        z, moe["router"]["kernel"], n_group=1, topk_group=1, top_k=3,
        scaling=2.5, scoring="sigmoid", bias=moe["router"]["bias"],
        renormalise=True)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-5)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The deployment's shares (four of 4 experts each at the tests' size,
    eight of 16 in the cell): the held experts' parts, each with the
    normaliser over ALL the chosen, add up to the uncut layer, the shared
    expert counted once; and the program's layer gives each share."""
    model, ref = models.load_with_reference("exaone_moe")
    whole_cfg = dict(_tiny_exaone.MODEL, num_experts=16)
    whole = model.init_params(whole_cfg, 11)["layers"]["layer_1"]["moe"]
    z = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.experts_layer(whole, z, whole_cfg, MM)
        shared = ref.gated_mlp(whole["shared"]["gate_up"]["kernel"],
                               whole["shared"]["down"]["kernel"], z, MM)
        total = jnp.zeros_like(uncut)
        nowhere = []
        for first in (0, 4, 8, 12):
            cfg = dict(_tiny_exaone.MODEL, first_expert=first)
            share = dict(whole, experts=jax.tree_util.tree_map(
                lambda w: w[first:first + 4], whole["experts"]))
            part, _ = ref.experts_layer(share, z, cfg, MM)
            total = total + part - shared
            serving = model.build_program_model(cfg, {}).serving()
            got, (counts, none) = serving._mlp(
                {"moe": share}, z, jnp.float32, jnp.ones((24,), bool),
                (16, 128, 128))
            np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                       rtol=2e-4, atol=2e-5)
            assert int(counts.sum()) == 24 * 3
            nowhere.append(float(none))
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(uncut), rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(part - uncut).max()) > 1e-3
    # some tokens have no expert on some chip, none has none anywhere
    assert max(nowhere) > 0 and all(0 <= n < 1 for n in nowhere)


def test_routing_margin_worked_by_hand():
    """6 experts, 2 chosen; this chip holds experts 2 and 3.  Margins are
    differences of logits of the choice values."""
    from benchmarks.reference import exaone_moe as ref
    cfg = {"first_expert": 2, "num_experts": 2}

    def logit(c):
        return math.log(c / (1 - c))

    def margin(choice):
        c = jnp.asarray([choice], jnp.float32)
        top = jnp.sort(c, axis=1)[:, ::-1]
        return float(ref.held_margin(c, top[:, 1], top[:, 2], cfg)[0])

    # a held expert is the last chosen: it falls out below the third
    assert margin([0.9, 0.1, 0.6, 0.2, 0.5, 0.3]) == pytest.approx(
        logit(0.6) - logit(0.5), rel=1e-4)
    # a held expert is the first not chosen: it comes in above the second
    assert margin([0.9, 0.6, 0.5, 0.1, 0.2, 0.3]) == pytest.approx(
        logit(0.6) - logit(0.5), rel=1e-4)
    # the edge is between two experts held elsewhere, a hair apart: the
    # held ones are far from it, and the position is judged
    far = margin([0.9, 0.6, 0.2, 0.1, 0.5999, 0.3])
    assert far == pytest.approx(logit(0.6) - logit(0.2), rel=1e-4)
    # both held experts chosen: the nearer one's distance to the third
    assert margin([0.5, 0.1, 0.9, 0.8, 0.2, 0.3]) == pytest.approx(
        logit(0.8) - logit(0.5), rel=1e-4)


def test_positions_with_a_narrow_routing_margin_are_not_judged(tiny):
    model, ref, params = tiny
    mc = _tiny_exaone.MODEL
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(2, 32)), jnp.int32)
    rows = jnp.repeat(jnp.arange(2), 32)
    cols = jnp.tile(jnp.arange(32), 2)
    logits, margins = ref.logits_and_margins(params, ids, rows, cols, mc, MM)
    margins = np.asarray(margins)
    assert (margins > 0).all()
    tau = float(np.median(margins[np.isfinite(margins)]))
    judged = np.asarray(ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau), MM))
    narrow = margins < tau
    assert 0 < narrow.sum() < len(narrow)
    assert not judged[narrow].any()
    np.testing.assert_array_equal(judged[~narrow],
                                  np.asarray(logits)[~narrow])
    low = np.asarray(ref.position_logits(
        params, ids, rows, cols, dict(mc, judge_routing_margin=tau),
        ops.MATMULS["fp8"]))
    assert low[narrow].any() and low.std(axis=-1).min() > 0


# -- prefill and decode through both caches ------------------------------------

def _serve(params, requests, model_config=None, engine_config=None):
    """Serve ``requests`` [(prompt, answer length)] on the tiny model and
    return each one's tokens."""
    from deepspeed_tpu.inference import InferenceEngine
    model = models.load("exaone_moe")
    engine = InferenceEngine(
        model.build_program_model(model_config or _tiny_exaone.MODEL, {}),
        params, config=engine_config or _tiny_exaone.ENGINE)
    rids = [engine.submit(p, max_new_tokens=n) for p, n in requests]
    results = engine.run()
    assert all(a.free_blocks == a.capacity for a in engine.allocators)
    engine.close()
    return [results[rid]["tokens"] for rid in rids]


def _gaps(ref, params, requests, served, model_config=None):
    """How far each served token's logit lies below the reference's best,
    and the reference's logits."""
    mc = model_config or _tiny_exaone.MODEL
    out = []
    for (prompt, _), tokens in zip(requests, served):
        n, new = len(prompt), len(tokens)
        ids = np.zeros((1, 96), np.int32)
        ids[0, :n] = prompt
        ids[0, n:n + new] = tokens
        logits = np.asarray(ref.position_logits(
            params, jnp.asarray(ids), jnp.zeros(new, jnp.int32),
            jnp.arange(n - 1, n + new - 1), mc, MM))
        assert logits.std() > 0.5        # not a flat distribution
        out.append(logits.max(axis=-1) - logits[np.arange(new), tokens])
    return np.concatenate(out)


def _requests():
    rng = np.random.default_rng(31)
    # a prompt shorter than the window (12) and than a page; one padded to
    # its bucket (37 in 48: the ring holds the keys before 37, not before
    # 48); one that fills its bucket; every answer runs past the window and
    # past the ring's wrap (3 pages of 8)
    return [(rng.integers(0, 256, size=n), new)
            for n, new in ((5, 40), (37, 40), (32, 36), (20, 40))]


def test_decode_through_both_caches_matches_the_full_forward(tiny):
    """A bucketed prefill, then decode through the full layer's pages and
    the window layers' rings, four requests side by side: every served
    token's logit is the reference's best, by the reference's own full
    forward over the whole sequence."""
    _, ref, params = tiny
    requests = _requests()
    served = _serve(params, requests)
    assert [len(t) for t in served] == [n for _, n in requests]
    gaps = _gaps(ref, params, requests, served)
    assert len(gaps) == 156
    assert gaps.max() <= LIMITS["served_logit_gap"]


def _skip_qk_norm(monkeypatch):
    from deepspeed_tpu.models import exaone_moe as program
    real = program.rms_norm
    monkeypatch.setattr(program, "rms_norm", lambda p, x, eps: (
        x if p["scale"].shape == (128,) else real(p, x, eps)))


def _rotate_the_full_layer(monkeypatch):
    from deepspeed_tpu.models import exaone_moe as program
    real = program.ExaoneMoeServing._qkv
    monkeypatch.setattr(
        program.ExaoneMoeServing, "_qkv",
        lambda self, lp, u, positions, window: real(
            self, lp, u, positions, True))


def _normalise_over_the_held_experts(monkeypatch):
    from deepspeed_tpu.models import expert_shard
    real = expert_shard.route

    def route(x, kernel, **kw):
        weights, ids = real(x, kernel, **kw)
        held = ids < 4                      # first_expert 0, 4 held
        total = jnp.where(held, weights, 0.0).sum(axis=-1, keepdims=True)
        return weights * 2.5 / jnp.maximum(total, 1e-9), ids

    monkeypatch.setattr(expert_shard, "route", route)


@pytest.mark.parametrize("fault", [
    "window_of_11", "window_of_13", "skip_qk_norm", "rotate_the_full_layer",
    "normalise_over_the_held_experts"])
def test_planted_faults_fail_the_limit(tiny, fault, monkeypatch):
    """A program that reads one window key too few or too many, skips the
    QK-norm, rotates the full layer or normalises over the held experts
    only serves tokens whose logits lie below the reference's best by far
    more than the limit."""
    _, ref, params = tiny
    program_config = None
    if fault.startswith("window_of_"):
        program_config = dict(_tiny_exaone.MODEL,
                              sliding_window=int(fault[-2:]))
    else:
        {"skip_qk_norm": _skip_qk_norm,
         "rotate_the_full_layer": _rotate_the_full_layer,
         "normalise_over_the_held_experts":
             _normalise_over_the_held_experts}[fault](monkeypatch)
    requests = _requests()
    served = _serve(params, requests, program_config)
    gaps = _gaps(ref, params, requests, served)
    assert gaps.max() > 100 * LIMITS["served_logit_gap"]


# -- the engine over two cache groups -------------------------------------------

def test_the_engine_keeps_a_pool_and_a_table_a_cache_group(devices):
    spec = _tiny_exaone.serve_spec(LIMITS)
    loop = serve.setup(spec, 3, devices)
    engine = loop.engine
    icfg = engine.inference_config
    assert [g.name for g in engine.cache_groups] == ["full", "window"]
    assert [g.layers for g in engine.cache_groups] == [1, 4]
    assert [g.pages for g in engine.cache_groups] == [None, 3]
    buffers = engine.serving.cache_buffers(icfg)
    assert list(buffers) == ["full_k_cache", "full_v_cache",
                             "window_k_cache", "window_v_cache"]
    assert tuple(engine.cache_block_bytes) == tuple(buffers)
    # [layers of the group, the group's blocks, block, row]
    assert [c.shape for c in engine._caches] == \
        [(1, 49, 8, 256)] * 2 + [(4, 4 * 3 + 1, 8, 256)] * 2
    assert [t.shape for t in engine._tables] == [(4, 12), (4, 3)]
    for _ in range(120):
        loop.step()
    assert engine.decode_iterations > 100
    counters = engine.model_counters
    assert 0.0 <= float(counters["moe_local_assignment_share"]) <= 1.0
    assert float(counters["moe_expert_load_max_over_mean"]) >= 1.0
    assert 0.0 <= float(counters["moe_tokens_without_local_expert"]) <= 1.0
    # every active request holds its ring whatever its length
    for request in engine.scheduler.active_requests():
        full, window = request.grants
        assert len(window) == 3 and len(full) >= -(-len(request.prompt) // 8)
    sample = serve.sample_finished(loop.finished, 3, n=12)
    serve.free(loop)
    gaps = serve.reference_gaps(spec, 3, sample)
    assert len(gaps) > 50
    assert gaps.max() <= LIMITS["served_logit_gap"]


def test_spans_and_gauges_name_both_cache_groups(devices, tmp_path):
    from deepspeed_tpu.inference import InferenceEngine
    mc = _tiny_exaone.MODEL
    model = models.load("exaone_moe")
    config = dict(_tiny_exaone.ENGINE, steps_per_print=4, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "t"})
    engine = InferenceEngine(model.build_program_model(mc, {}),
                             model.init_params(mc, 1), config=config)
    spans = []
    real = engine.telemetry.span

    def recording(name, **args):
        spans.append((name, args))
        return real(name, **args)

    engine.telemetry.span = recording
    engine.submit(list(range(1, 12)), max_new_tokens=14)
    engine.submit(list(range(1, 30)), max_new_tokens=14)
    engine.run()
    decodes = [args for name, args in spans if name == "decode"]
    assert len(decodes) == 13
    # 11 + 1 and 29 + 1 tokens in pages of 8: 2 + 4 pages of the full
    # layer; two rings of 3 in a window layer
    assert decodes[0]["live_blocks"] == decodes[0]["live_blocks_full"] == 6
    assert decodes[0]["live_blocks_window"] == 6
    # the last decode: 11 + 13 and 29 + 13 tokens
    assert decodes[-1]["live_blocks_full"] == 3 + 6
    gauge = engine.telemetry.gauge
    # one live block: 1 full layer x 8 tokens x 256 values x 4 B, K and V
    assert gauge("serving/full_cache_live_bytes").value % (
        2 * 8 * 256 * 4) == 0
    assert gauge("serving/full_cache_live_bytes").value > 0
    assert gauge("serving/full_cache_live_bytes").value == \
        2 * gauge("serving/full_k_cache_live_bytes").value
    # two slots' rings in 4 window layers, K and V
    assert gauge("serving/window_cache_bytes").value == \
        2 * 3 * 2 * 4 * 8 * 256 * 4
    assert 0 < gauge("serving/moe_local_assignment_share").value <= 1
    assert gauge("serving/moe_expert_load_max_over_mean").value >= 1
    assert 0 <= gauge("serving/moe_tokens_without_local_expert").value < 1
    assert gauge("serving/kv_live_block_share").value > 0
    engine.close()


# -- the cell at tiny size ---------------------------------------------------

def test_tiny_cell_is_correct(devices, capsys):
    spec = _tiny_exaone.serve_spec(LIMITS)
    ok = serve.run_cell(spec, 6, 2.0, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert any(l.startswith("check served_logit_gap") and " ok" in l
               for l in out)
    assert any(l.startswith("check no_compile_in_window ok") for l in out)


@pytest.mark.parametrize("seed", [2, 7])
def test_fp8_control_fails_the_tiny_cells_limit(devices, seed):
    spec = _tiny_exaone.serve_spec(LIMITS)
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
    from deepspeed_tpu.models import exaone_moe
    from deepspeed_tpu.ops.transformer import paged_attention
    files = metrics.load_all()
    patterns = {n: files[n]["reader"]["args"]["pattern"] for n in (
        "gqa_full_decode_attn_ms", "gqa_window_decode_attn_ms",
        "gqa_prefill_attn_ms")}
    assert patterns["gqa_prefill_attn_ms"] == \
        "(gqa|window)_prefill_attention"
    with open(paged_attention.__file__) as f:
        source = f.read()
    assert f'"{patterns["gqa_full_decode_attn_ms"]}"' in source
    assert f'"{patterns["gqa_window_decode_attn_ms"]}"' in source
    # neither pattern matches the other kernel, nor GPT-2's
    import re
    assert not re.search(patterns["gqa_full_decode_attn_ms"],
                         "window_paged_decode_attention")
    with open(exaone_moe.__file__) as f:
        source = f.read()
    assert '"window_prefill_attention"' in source
    assert '"gqa_prefill_attention"' in source
    for name in patterns:
        assert files[name]["reader"]["reducer"] == "op_ms_per_step"
        assert files[name]["moves"] == "serve_tokens_per_s"


def test_manifest_lines_fit_the_drivers_200_characters():
    """Every `why`, `layer` and `source` of BENCHMARK.json is one printable
    line of 1 to 200 characters (the driver refuses the file otherwise: a
    243-character `why` on this configuration was refused once)."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = (manifest["configs"] + manifest["workloads"]
               + manifest["end_to_end"] + manifest["per_layer"])
    lines = [(e["name"], key, e[key]) for e in entries
             for key in ("why", "layer", "source") if key in e]
    assert any(name == "k_exaone_ep8" and key == "why"
               for name, key, _ in lines)
    for name, key, text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, key)
