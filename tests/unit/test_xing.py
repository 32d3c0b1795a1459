"""Xing-4.0 served at a tiny size on the CPU (float32 program, limit 1e-3):
a bucketed prefill, then absorbed decode through the paged latent cache,
against the plain reference's full forward (``benchmarks/reference/xing.py``);
every expert held; the counters and scopes; and ten planted faults, each of
which the comparison must read two orders above the sound program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import models
from benchmarks.reference import ops
from benchmarks.reference import xing as reference
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models import expert_shard, xing
from deepspeed_tpu.ops.transformer import hyper_connection as hc
from tests.benchmarks import _tiny_xing

MC = _tiny_xing.MODEL
LIMIT = 1e-3
ANSWER = 14


@pytest.fixture(scope="module")
def weights():
    model = models.load("xing")
    return model, model.init_params(MC, 5)


def _serve(weights, prompt_len, seed=None):
    """``(prompt, served tokens, engine)`` of one request through a new
    engine."""
    model, params = weights
    engine = InferenceEngine(model.build_program_model(MC, {}), params,
                             config=_tiny_xing.ENGINE)
    prompt = np.random.default_rng(seed or prompt_len).integers(
        0, MC["vocab_size"], size=prompt_len)
    rid = engine.submit(prompt, max_new_tokens=ANSWER)
    return prompt, engine.run()[rid]["tokens"], engine


def _served_gap(weights, prompt, tokens):
    """How far each served token's logit lies below the reference's best at
    its position (the harness's comparison), and the logits."""
    n = len(prompt)
    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = prompt
    ids[0, n:n + len(tokens)] = tokens
    cols = jnp.arange(n - 1, n + len(tokens) - 1)
    logits = np.asarray(reference.position_logits(
        weights[1], jnp.asarray(ids), jnp.zeros(len(tokens), jnp.int32),
        cols, MC, ops.MATMULS["float32"]))
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens], \
        logits


@pytest.mark.parametrize("prompt_len", [5, 9, 16, 27])
def test_decode_through_the_latent_cache_matches_the_full_forward(
        weights, prompt_len):
    prompt, tokens, engine = _serve(weights, prompt_len)
    engine.close()
    assert len(tokens) == ANSWER
    gaps, logits = _served_gap(weights, prompt, tokens)
    assert logits.std() > 0.5        # not a flat distribution
    assert gaps.max() <= LIMIT
    assert tokens == logits.argmax(axis=-1).tolist()


def test_parameter_tree_and_what_prepare_params_makes_of_it(weights):
    model, params = weights
    program = model.build_program_model(MC, {})
    assert model.param_shapes(MC) == program.param_shapes()
    c = program.config
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.mhc_h_res_clamp) \
        == (4, 20, 1e-6, (-30.0, 30.0))
    assert c.experts_held == c.n_routed_experts == 8 and c.first_expert == 0
    prepared = program.serving().prepare_params(params)
    lp = prepared["layers"]["layer_2"]
    assert "kv_b" not in lp and lp["w_uk"].shape == (4, 16, 48)
    for name in xing.SUBLAYERS:
        assert set(lp[name]) == {"phi_t", "affine"}
        assert lp[name]["phi_t"].shape == (48, 4 * 128)
        assert lp[name]["phi_t"].dtype == jnp.float32
    # what is not prepared is the caller's leaf itself
    assert prepared["embed"] is params["embed"]
    assert lp["moe"]["router"]["bias"] is \
        params["layers"]["layer_2"]["moe"]["router"]["bias"]


def test_every_expert_is_held_one_pass_and_no_token_is_without_one(weights):
    _, params = weights
    model = models.load("xing")
    serving = model.build_program_model(MC, {}).serving()
    moe = params["layers"]["layer_2"]["moe"]
    z = jax.random.normal(jax.random.PRNGKey(1), (48, MC["hidden_size"]))
    valid = jnp.arange(48) < 40
    y, counts = serving._mlp({"moe": moe}, z, jnp.float32, valid,
                             (16, 128, 128))
    top_k = MC["num_experts_per_tok"]
    # every pair of a valid token fell to a held expert: none left for the
    # last group, the experts held elsewhere
    assert counts.shape == (9,) and int(counts[-1]) == 0
    assert int(counts.sum()) == 40 * top_k
    share, peak = expert_shard.load_counters(counts)
    assert float(share) == 1.0 and float(peak) >= 1.0
    assert expert_shard.pair_capacity(48 * top_k, 8, 8, 16) == 48 * top_k
    assert int(expert_shard.pair_passes(counts, 48 * top_k, 8, 16)) == 1
    weights_, ids = expert_shard.route(
        z, moe["router"]["kernel"], n_group=1, topk_group=1, top_k=top_k,
        scaling=2.0, scoring="sigmoid", bias=moe["router"]["bias"],
        renormalise=True)
    assert float(expert_shard.tokens_without_held_expert(
        ids, valid, 0, 8)) == 0.0
    np.testing.assert_allclose(weights_.sum(axis=-1), 2.0, rtol=1e-5)
    # the layer is the reference's, rows that are padding apart
    want, _ = reference.experts_layer(moe, z, MC, ops.MATMULS["float32"])
    np.testing.assert_allclose(np.asarray(y)[:40], np.asarray(want)[:40],
                               rtol=2e-4, atol=2e-5)


def test_counters_come_back_in_the_decode_fetch(weights, tmp_path):
    model, params = weights
    config = dict(_tiny_xing.ENGINE, steps_per_print=4, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "t"})
    engine = InferenceEngine(model.build_program_model(MC, {}), params,
                             config=config)
    engine.submit(list(range(1, 12)), max_new_tokens=10)
    engine.run()
    counters = {k: float(v) for k, v in engine.model_counters.items()}
    assert set(counters) == {
        "moe_local_assignment_share", "moe_expert_load_max_over_mean",
        "moe_pair_passes", "hc_streams", "hc_res_stochastic_err_max",
        "hc_pre_mass_mean"}
    assert counters["moe_local_assignment_share"] == 1.0
    assert counters["moe_pair_passes"] == 1.0
    assert counters["hc_streams"] == 4.0
    assert 0.0 <= counters["hc_res_stochastic_err_max"] < 1e-4
    assert 0.0 < counters["hc_pre_mass_mean"] < 4.0
    gauge = engine.telemetry.gauge
    assert gauge("serving/hc_streams").value == 4.0
    assert gauge("serving/moe_local_assignment_share").value == 1.0
    assert gauge("serving/latent_cache_live_bytes").value > 0
    assert gauge("serving/prepared_param_leaves").value == 4 * (2 + 4)
    engine.close()


def test_both_forms_of_the_mixes_serve_the_same_tokens(weights, monkeypatch):
    """A program whose tokens fill whole tiles takes the kernels, another
    the plain forms: at the tiny size every program is the second kind, and
    with a tile of 8 tokens (through the interpreter) the prefill of 32
    rows is the first while the decode step of 4 stays the second."""
    taken = []

    def counted(name):
        real = getattr(hc, name)

        def call(x, *args, **kw):
            taken.append((name, x.shape[0], kw.get("tile")))
            return real(x, *args, **kw)
        monkeypatch.setattr(xing.hc, name, call)

    for name in ("mhc_pre_mix", "mhc_post_res_mix", "mhc_pre_mix_xla",
                 "mhc_post_res_mix_xla"):
        counted(name)

    def forms(tile):
        monkeypatch.setattr(xing.XingServing, "MIX_TILE", tile)
        taken.clear()
        _, tokens, engine = _serve(weights, 21)
        engine.close()
        return tokens, set(taken)

    plain, calls = forms(128)
    assert calls == {(name, rows, None) for rows in (32, 4)
                     for name in ("mhc_pre_mix_xla", "mhc_post_res_mix_xla")}
    kernels, calls = forms(8)
    assert calls == {("mhc_pre_mix", 32, 8), ("mhc_post_res_mix", 32, 8),
                     ("mhc_pre_mix_xla", 4, None),
                     ("mhc_post_res_mix_xla", 4, None)}
    assert kernels == plain


def test_a_width_the_kernels_cannot_tile_is_refused_at_construction():
    model = models.load("xing")
    serving = model.build_program_model(
        dict(MC, hidden_size=192, kv_lora_rank=128), {}).serving()

    class Icfg:
        kv_block_size = 8

    with pytest.raises(ValueError, match="hyper_connection cannot tile"):
        serving.check_tpu_geometry(Icfg)


# -- planted faults -----------------------------------------------------------

def _on_maps(change):
    """The mixes with ``change(maps) -> maps`` between them."""
    def mixes(self, tokens, real=xing.XingServing._mixes):
        pre, post = real(self, tokens)

        def faulty_pre(x, packed):
            u, maps = pre(x, packed)
            return u, change(maps)
        return faulty_pre, post
    return mixes


def _groups(maps):
    return maps[:, :48].reshape(-1, 6, 8)


def _regroup(groups, maps):
    return jnp.concatenate([groups.reshape(-1, 48), maps[:, 48:]], axis=1)


def _half_post(maps):                       # 2 sigma as sigma
    g = _groups(maps)
    return _regroup(g.at[:, 1].multiply(0.5), maps)


def _transposed(maps):                      # H_res[j, i] for H_res[i, j]
    g = _groups(maps)
    res = jnp.swapaxes(g[:, 2:, :4], 1, 2)
    return _regroup(g.at[:, 2:, :4].set(res), maps)


def _rows_only(self, tokens, real=xing.XingServing._mixes):
    """exp(R) with its rows normalised and its columns never."""
    c = self.config
    pre, post = real(self, tokens)

    def faulty_pre(x, packed):
        u, maps = pre(x, packed)
        _, raw = hc.mhc_pre_mix_xla(
            x, packed, n=4, eps=c.rms_norm_eps, sinkhorn_iters=0,
            sinkhorn_eps=c.hc_eps, clamp=c.mhc_h_res_clamp)
        g, r = _groups(maps), _groups(raw)[:, 2:, :4]
        rows = r / (r.sum(axis=2, keepdims=True) + c.hc_eps)
        return u, _regroup(g.at[:, 2:, :4].set(rows), maps)
    return faulty_pre, post


def _plain_sum(self, tokens, real=xing.XingServing._mixes):
    pre, post = real(self, tokens)

    def faulty_pre(x, packed):
        _, maps = pre(x, packed)
        return x.reshape(x.shape[0], 4, -1).sum(axis=1), maps
    return faulty_pre, post


def _static_maps(self, params, real=xing.XingServing.prepare_params):
    """``x^ Phi`` dropped: the maps are their biases', the same for every
    token."""
    prepared = real(self, params)
    for lp in prepared["layers"].values():
        for name in xing.SUBLAYERS:
            lp[name] = dict(lp[name],
                            phi_t=jnp.zeros_like(lp[name]["phi_t"]))
    return prepared


def _route(**changed):
    def route(x, kernel, real=expert_shard.route, **kw):
        return real(x, kernel, **{**kw, **changed})
    return route


def _bias_weighs(x, kernel, *, n_group, topk_group, top_k, scaling, scoring,
                 bias, renormalise):
    """The weights taken from ``score + bias``, the values the choice was
    made on."""
    scores = expert_shard.router_scores(x, kernel, scoring) \
        + bias.astype(jnp.float32)
    weights, ids = jax.lax.top_k(scores, top_k)
    return scaling * weights / weights.sum(axis=-1, keepdims=True), ids


class _Unnormalised(xing.XingConfig):
    """``H_res`` as ``exp(R)``: no Sinkhorn iteration run."""

    def __init__(self, **kw):
        super().__init__(**dict(kw, hc_sinkhorn_iters=0))


FAULTS = {
    "maps_static_x_phi_dropped": ("prepare_params", _static_maps),
    "post_sigma_for_two_sigma": ("_mixes", _on_maps(_half_post)),
    "res_unnormalised_exp": ("config", _Unnormalised),
    "res_rows_only": ("_mixes", _rows_only),
    "res_transposed": ("_mixes", _on_maps(_transposed)),
    "u_plain_sum": ("_mixes", _plain_sum),
    "router_not_renormalised": ("route", _route(renormalise=False)),
    "router_scaling_missing": ("route", _route(scaling=1.0)),
    "router_bias_weighs": ("route", _bias_weighs),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_two_orders_above_the_limit(
        weights, fault, monkeypatch):
    """The sound program reads under 1e-3 (the tests above, same weights and
    prompts); with the fault some served token's logit lies more than 0.1
    under the reference's best within three requests of 14 tokens."""
    where, planted = FAULTS[fault]
    if where == "config":
        monkeypatch.setattr(xing, "XingConfig", planted)
    elif where == "route":
        monkeypatch.setattr(xing.expert_shard, "route", planted)
    else:
        monkeypatch.setattr(xing.XingServing, where, planted)
    reading = 0.0
    for prompt_len in (27, 16, 9):
        prompt, tokens, engine = _serve(weights, prompt_len)
        engine.close()
        reading = max(reading, _served_gap(weights, prompt, tokens)[0].max())
        if reading > 0.1:
            break
    assert reading > 0.1, (fault, reading)


def test_the_streams_merged_by_their_mean_read_before_the_final_norm(
        weights):
    """The final RMSNorm takes a factor out, so this fault cannot show in a
    logit: it is read where it is planted.  The program's merge is the
    reference's sum; a mean is 3/4 of the sum's size away."""
    _, params = weights
    serving = models.load("xing").build_program_model(MC, {}).serving()
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 4, MC["hidden_size"]))
    merged = serving._merged(x.reshape(6, -1))
    np.testing.assert_allclose(merged, x.sum(axis=1), rtol=1e-6, atol=1e-6)
    mean = x.mean(axis=1)
    reading = float(jnp.abs(mean - merged).max() / jnp.abs(merged).max())
    assert reading > 0.1
    # and the reference sums too: its final norm sees the same vector
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, MC["vocab_size"], size=(1, 8)), jnp.int32)
    hidden, _ = reference.hidden(params, ids, MC, ops.MATMULS["float32"])
    assert hidden.shape == (1, 8, MC["hidden_size"])
    assert float(jnp.abs(jnp.mean(jnp.square(hidden), axis=-1) - 1.0).max()) \
        < 1e-3
