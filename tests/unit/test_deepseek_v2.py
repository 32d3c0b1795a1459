"""The pieces DeepSeek-V2's serving path is made of, each against a closed
form or a case worked by hand (CPU, small widths)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.kv_cache import cache_bytes, kv_cache_bytes
from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU
from deepspeed_tpu.models import expert_shard
from deepspeed_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                              DeepseekV2ForServing, rotate,
                                              yarn_inv_freq,
                                              yarn_softmax_scale)
from deepspeed_tpu.models.layers import gated_silu_mlp, rms_norm
from deepspeed_tpu.module_inject.replace_module import cast_weights


def test_yarn_frequencies_against_the_closed_form():
    c = DeepseekV2Config()          # the published rope_scaling
    inv = yarn_inv_freq(c)
    assert inv.shape == (32,)
    j = np.arange(32)
    plain = 10000.0 ** (-2.0 * j / 64)
    # correction dimensions of 32 and 1 rotations over 4096 positions
    def dim(r):
        return 64 * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, plain / 40 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    # fast dimensions are left alone, slow ones divided by the factor
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert yarn_softmax_scale(c) == pytest.approx(m * m / math.sqrt(192))


def test_rotation_turns_interleaved_pairs_and_keeps_dot_products():
    c = DeepseekV2Config(qk_rope_head_dim=8, rope_scaling={
        "type": "yarn", "factor": 1, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1.0, "mscale_all_dim": 1.0,
        "original_max_position_embeddings": 64})
    x = jnp.arange(8, dtype=jnp.float32)[None] + 1.0
    pos = jnp.asarray([3])
    got = np.asarray(rotate(x, pos, c))[0]
    inv = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    cos, sin = np.cos(3 * inv), np.sin(3 * inv)
    even, odd = np.arange(1, 9, 2.0), np.arange(2, 10, 2.0)
    # pairs (x0, x1), (x2, x3), ... turn; first halves then second halves
    np.testing.assert_allclose(got[:4], even * cos - odd * sin, rtol=1e-5)
    np.testing.assert_allclose(got[4:], odd * cos + even * sin, rtol=1e-5)
    # q.k depends on the distance alone
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 8))
    def dot(pq, pk):
        return float(jnp.sum(rotate(q, jnp.asarray([pq]), c)
                             * rotate(k, jnp.asarray([pk]), c)))
    assert dot(5, 2) == pytest.approx(dot(40, 37), rel=1e-4)
    # one rotation serves every head of a token
    per_head = rotate(jnp.stack([x, 2 * x], axis=1), pos, c)
    np.testing.assert_allclose(np.asarray(per_head[0, 1]), 2 * got,
                               rtol=1e-5)


def test_group_limited_routing_worked_by_hand():
    # 8 experts in 4 groups of 2, keep 2 groups, choose 3
    scores = jnp.asarray([
        # g0        g1        g2        g3
        [.30, .01, .02, .25, .20, .19, .02, .01],
        [.05, .05, .40, .01, .10, .30, .05, .04]])
    weights, ids = expert_shard.group_limited_topk(scores, 4, 2, 3)
    # token 0: groups by best expert .30 .25 .20 .02 -> g0, g1; of their
    # experts (.30 .01 .02 .25) the best three — .20 and .19 of g2 are
    # larger than .02 but their group is out
    assert ids[0].tolist() == [0, 3, 2]
    np.testing.assert_allclose(weights[0], [.30, .25, .02])
    # token 1: groups .05 .40 .30 .05 -> g1, g2: .40, .30, .10
    assert ids[1].tolist() == [2, 5, 4]
    np.testing.assert_allclose(weights[1], [.40, .30, .10])


def _experts(held, hidden, width, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"gate_up": 0.3 * jax.random.normal(
                keys[0], (held, hidden, 2 * width)),
            "down": 0.3 * jax.random.normal(keys[1], (held, width, hidden))}


def _expert_ffn(experts, e, x):
    return gated_silu_mlp({"gate_up": {"kernel": experts["gate_up"][e]},
                           "down": {"kernel": experts["down"][e]}}, x)


@pytest.mark.parametrize("tokens", [5, 40])
def test_no_token_is_dropped_when_every_token_picks_one_expert(tokens):
    """GShard capacity routing would drop all but ``capacity`` of them."""
    held, hidden, width, top_k = 4, 16, 8, 2
    experts = _experts(held, hidden, width)
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, hidden))
    ids = jnp.broadcast_to(jnp.asarray([2, 9]), (tokens, top_k))  # 9: away
    weights = jnp.full((tokens, top_k), 0.5)
    y, counts = expert_shard.held_experts_ffn(
        x, weights, ids, jnp.ones((tokens,), bool), experts,
        first_expert=0, interpret=True, tiling=(16, 128, 128))
    np.testing.assert_allclose(np.asarray(y),
                               0.5 * np.asarray(_expert_ffn(experts, 2, x)),
                               rtol=1e-4, atol=1e-5)
    assert counts.tolist() == [0, 0, tokens, 0, tokens]
    share, peak = expert_shard.load_counters(counts)
    assert float(share) == pytest.approx(0.5)
    assert float(peak) == pytest.approx(4.0)


def test_padding_rows_are_routed_nowhere_and_counted_nowhere():
    experts = _experts(2, 16, 8)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 16))
    ids = jnp.asarray([[0, 1]] * 6)
    valid = jnp.arange(6) < 4
    y, counts = expert_shard.held_experts_ffn(
        x, jnp.ones((6, 2)), ids, valid, experts, first_expert=0,
        interpret=True, tiling=(16, 128, 128))
    assert counts.tolist() == [4, 4, 0]
    assert not np.asarray(y[4:]).any()
    want = _expert_ffn(experts, 0, x) + _expert_ffn(experts, 1, x)
    np.testing.assert_allclose(np.asarray(y[:4]), np.asarray(want[:4]),
                               rtol=1e-4, atol=1e-5)


def test_rms_norm_is_fp32_inside():
    x = jnp.asarray([[3.0, 4.0]], jnp.bfloat16)
    y = rms_norm({"scale": jnp.asarray([1.0, 2.0])}, x, eps=0.0)
    rms = math.sqrt(12.5)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               [[3 / rms, 8 / rms]], rtol=1e-2)


def test_weights_already_in_the_serving_dtype_are_not_copied():
    tree = {"a": jnp.ones((4, 4), jnp.bfloat16),
            "b": jnp.ones((4,), jnp.float32), "ids": jnp.arange(3)}
    out = cast_weights(tree, jnp.bfloat16)
    assert out["a"] is tree["a"] and out["ids"] is tree["ids"]
    assert out["b"].dtype == jnp.bfloat16


def test_both_models_describe_their_cache_through_one_interface():
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 8, "kv_blocks": 9, "max_seq_len": 32,
        "prefill_buckets": [8]}})
    gpt2 = GPT2LMHeadTPU(GPT2Config(vocab_size=64, hidden_size=32,
                                    num_layers=2, num_heads=4)).serving()
    assert gpt2.cache_buffers(icfg) == {"k_cache": 32, "v_cache": 32}
    mla = DeepseekV2ForServing(DeepseekV2Config()).serving()
    # 512 + 64 stored in whole lane tiles
    assert mla.cache_buffers(icfg) == {"latent_cache": 640}
    assert mla.num_layers == 60 and gpt2.num_layers == 2
    for serving in (gpt2, mla):
        assert serving.build_decode(icfg).__name__ == "decode"
        assert serving.build_prefill(icfg, 8).__name__ == "prefill"
    # the footprint of any set of buffers; K+V is a case of it
    assert cache_bytes(5, 12289, 64, (640,), jnp.bfloat16) == 5033574400
    assert kv_cache_bytes(2, 9, 8, 4, 8) == cache_bytes(2, 9, 8, (32, 32))
    with pytest.raises(ValueError, match="cannot tile"):
        DeepseekV2ForServing(DeepseekV2Config(
            kv_lora_rank=48)).serving().check_tpu_geometry(icfg)
    mla.check_tpu_geometry(icfg)


# ------------------------------- the interface's tokens stay on the device
def _tiny_expert_model(family):
    """16 routed experts, 4 held, 3 a token: DeepSeek-V2's softmax router
    over 4 groups or K-EXAONE's sigmoid one."""
    if family == "exaone_moe":
        from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                     ExaoneMoeForServing)
        return ExaoneMoeForServing(ExaoneMoeConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=128,
            intermediate_size=128, moe_intermediate_size=32, num_experts=16,
            experts_held=4, num_experts_per_tok=3, sliding_window=8,
            max_position_embeddings=2560))
    return DeepseekV2ForServing(DeepseekV2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=48,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=16, experts_held=4, n_shared_experts=1,
        num_experts_per_tok=3, n_group=4, topk_group=2,
        routed_scaling_factor=4.0, max_position_embeddings=2560,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 64}))


def _tiny_served(family):
    """A tiny model of ``family`` with seeded weights and the engine
    config it is served under."""
    if family == "gpt2":
        model = GPT2LMHeadTPU(GPT2Config(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_position_embeddings=64, embd_dropout=0.0, attn_dropout=0.0,
            resid_dropout=0.0))
        params = model.init(jax.random.PRNGKey(0))
    else:
        model = _tiny_expert_model(family)
        leaves, tree = jax.tree_util.tree_flatten(
            model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
        params = jax.tree_util.tree_unflatten(tree, [
            jnp.ones(shape) if len(shape) == 1
            else 0.2 * jax.random.normal(key, shape)
            for key, shape in zip(keys, leaves)])
    config = {"steps_per_print": 10 ** 9, "inference": {
        "kv_block_size": 8, "kv_blocks": 33, "max_batch_slots": 3,
        "max_seq_len": 64, "prefill_buckets": [16, 32], "token_budget": 192,
        "max_new_tokens": 16, "weights_dtype": "float32"}}
    return model, params, config


@pytest.mark.parametrize("family", ["gpt2", "deepseek_v2"])
def test_tokens_reach_the_next_decode_without_leaving_the_device(
        family, monkeypatch):
    """Both models' side of the interface: a prefill puts its first token
    into lane ``slot`` of the next decode's input and leaves the other
    lanes alone; a decode's ``tokens`` argument IS the last program's
    output array, never a host copy; positions and tables are what the
    host sends.  And the engine, whatever the model: one ``device_get`` a
    step, admissions included, with the model's counters in it."""
    from deepspeed_tpu.inference import InferenceEngine
    model, params, config = _tiny_served(family)
    engine = InferenceEngine(model, params, config=config)
    produced, gets = [], []

    def watched(program, decode):
        def run(*args):
            tokens = args[4] if decode else args[5]
            assert isinstance(tokens, jax.Array)
            if produced:
                assert tokens is produced[-1]
            result = program(*args)
            if decode:
                (tables,) = args[2]             # one a cache group
                assert isinstance(tables, jax.Array)
                assert isinstance(args[3], np.ndarray)      # positions
                produced.append(result[0]["tokens"])
            else:
                out, _, merged = result
                slot, before = int(args[6]), np.asarray(tokens)
                want = before.copy()
                want[slot] = int(out["tokens"])
                np.testing.assert_array_equal(np.asarray(merged), want)
                produced.append(merged)
            return result
        return run

    engine._decode = watched(engine._decode, True)
    for bucket in list(engine._prefills):
        engine._prefills[bucket] = watched(engine._prefills[bucket], False)
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or real_get(x))
    rng = np.random.default_rng(3)
    for n, cap in [(5, 6), (12, 4), (20, 7), (9, 5), (3, 6)]:
        engine.submit(rng.integers(0, 256, size=n), max_new_tokens=cap)
    steps = 0
    while not engine.scheduler.idle():
        before = len(gets)
        engine.step()
        steps += 1
        assert len(gets) - before <= 1
        if family == "deepseek_v2" and engine.decode_iterations >= 2:
            share = float(engine.model_counters[
                "moe_local_assignment_share"])
            assert 0.0 <= share <= 1.0
            assert float(engine.model_counters[
                "moe_expert_load_max_over_mean"]) >= 1.0
    monkeypatch.setattr(jax, "device_get", real_get)
    assert engine.scheduler.admitted_total == 5 and len(gets) <= steps
    if family == "gpt2":
        assert engine.model_counters == {}
    assert engine.generated_tokens == 6 + 4 + 7 + 5 + 6
    engine.close()


@pytest.mark.parametrize("family", ["deepseek_v2", "exaone_moe"])
def test_one_pass_over_the_pairs_at_uniform_routing(family, tmp_path):
    """Seeded weights route near uniformly, so the held pairs fit the
    capacity of one pass in every expert layer of every decode step: the
    counter in the decode's fetch and its gauge read 1.0."""
    from deepspeed_tpu.inference import InferenceEngine
    model, params, config = _tiny_served(family)
    config = dict(config, steps_per_print=2, telemetry={
        "enabled": True, "run_dir": str(tmp_path)})
    engine = InferenceEngine(model, params, config=config)
    rng = np.random.default_rng(5)
    for n in (5, 12, 9):
        engine.submit(rng.integers(0, 256, size=n), max_new_tokens=6)
    seen = []
    while not engine.scheduler.idle():
        engine.step()
        if "moe_pair_passes" in engine.model_counters:
            seen.append(float(engine.model_counters["moe_pair_passes"]))
    assert len(seen) >= 4 and set(seen) == {1.0}
    assert engine.telemetry.gauge("serving/moe_pair_passes").value == 1.0
    engine.close()


# ------------------------- weights are prepared once, outside every program
def test_models_with_nothing_to_prepare_hand_their_tree_back():
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                 ExaoneMoeForServing)
    tree = {"a": jnp.ones((2, 2))}
    assert ExaoneMoeForServing(ExaoneMoeConfig()).serving().prepare_params(
        tree) is tree
    model, params, config = _tiny_served("gpt2")
    assert model.serving().prepare_params(tree) is tree
    engine = InferenceEngine(model, params, config=config)
    assert engine.prepared_params == {"leaves": 0, "bytes": 0}
    for mine, theirs in zip(jax.tree_util.tree_leaves(engine.params),
                            jax.tree_util.tree_leaves(params)):
        assert mine is theirs
    engine.close()


def test_prepare_params_splits_kv_b_exactly_and_keeps_every_other_leaf():
    model, params, _ = _tiny_served("deepseek_v2")
    c = model.config
    prepared = model.serving().prepare_params(params)
    assert params["layers"]["layer_0"]["kv_b"]["kernel"].shape == (48, 128)
    for name, lp in prepared["layers"].items():
        theirs = params["layers"][name]
        assert "kv_b" not in lp
        # what the programs sliced out of kv_b in every call, until PR 32:
        # [latent, heads, nope | value], the halves interleaved per head
        w = np.asarray(theirs["kv_b"]["kernel"]).reshape(
            c.kv_lora_rank, c.num_attention_heads, -1)
        assert lp["w_uk"].shape == (4, 16, 48)      # [heads, nope, latent]
        assert lp["w_uv"].shape == (4, 48, 16)      # [heads, latent, value]
        np.testing.assert_array_equal(
            np.asarray(lp["w_uk"]), w[..., :16].transpose(1, 2, 0))
        np.testing.assert_array_equal(
            np.asarray(lp["w_uv"]), w[..., 16:].transpose(1, 0, 2))
        assert lp["o"]["kernel"] is theirs["o"]["kernel"]
        assert lp["kv_a"] is theirs["kv_a"]
    assert prepared["embed"] is params["embed"]
    assert "w_uk" not in params["layers"]["layer_0"]    # the caller's: whole


class _SplitsInsideEveryCall:
    """DeepSeek-V2 as it was served until PR 32: the programs are handed the
    caller's tree and split ``kv_b`` themselves, in every call."""

    def __init__(self, model):
        self.config = model.config
        self._serving = model.serving()

    def serving(self):
        return self

    def __getattr__(self, name):
        return getattr(self._serving, name)

    def prepare_params(self, params):
        return params

    def _inside(self, program):
        def run(params, *args):
            return program(self._serving.prepare_params(params), *args)
        run.__name__ = program.__name__
        return run

    def build_prefill(self, icfg, bucket_len):
        return self._inside(self._serving.build_prefill(icfg, bucket_len))

    def build_decode(self, icfg):
        return self._inside(self._serving.build_decode(icfg))


def test_prepared_tree_serves_what_the_split_inside_the_programs_served(
        monkeypatch):
    """Prefill and decode through the engine, slots recycled: the same
    tokens and the same latent cache (every row of a layer past the first
    is a function of the layers before it, ``W_UK`` and ``W_UV`` among
    them) as programs that split ``kv_b`` in every call.  The split is
    exact and the products are the same; the order in which a dot sums
    its contraction follows the operands' layout, so float32's last bits
    differ: rows of a few units, 48 to 64 terms a sum, three layers
    (4e-6 seen).  ``prepare_params`` runs once, at construction, and never
    from ``step()``."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.deepseek_v2 import DeepseekV2Serving
    model, params, config = _tiny_served("deepseek_v2")
    calls = []
    real = DeepseekV2Serving.prepare_params
    monkeypatch.setattr(
        DeepseekV2Serving, "prepare_params",
        lambda self, tree: calls.append(1) or real(self, tree))
    engine = InferenceEngine(model, params, config=config)
    assert calls == [1]
    n_layers = model.config.num_hidden_layers
    assert engine.prepared_params == {
        "leaves": 2 * n_layers,
        "bytes": n_layers * params["layers"]["layer_0"]["kv_b"][
            "kernel"].nbytes}
    assert "kv_b" not in engine.params["layers"]["layer_0"]
    monkeypatch.setattr(DeepseekV2Serving, "prepare_params", real)
    before = InferenceEngine(_SplitsInsideEveryCall(model), params,
                             config=config)
    assert before.prepared_params == {"leaves": 0, "bytes": 0}
    rng = np.random.default_rng(11)
    prompts = [(rng.integers(0, 256, size=n), cap)
               for n, cap in [(5, 9), (14, 6), (27, 12), (9, 8), (3, 10)]]
    served = []
    for e in (engine, before):
        ids = [e.submit(p, max_new_tokens=cap) for p, cap in prompts]
        results = e.run()
        served.append([results[i]["tokens"] for i in ids])
    assert calls == [1]
    assert served[0] == served[1]
    assert [len(t) for t in served[0]] == [9, 6, 12, 8, 10]
    (mine,), (theirs,) = engine._caches, before._caches
    assert np.asarray(mine[1:]).any()
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                               rtol=0, atol=3e-5)
    engine.close()
    before.close()
