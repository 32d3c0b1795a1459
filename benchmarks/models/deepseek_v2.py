"""DeepSeek-V2 (deepseek-ai/DeepSeek-V2 ``config.json`` and modelling code):
multi-head latent attention, RMSNorm, gated-SiLU MLPs, group-limited routed
experts beside shared ones, an untied head.  The configuration gives one
chip's share of an expert-parallel deployment: ``n_routed_experts`` counts
the experts HELD here (``first_expert`` onward), ``published_n_routed_experts``
the ones the router scores; ``vocab_size`` the rows of the vocabulary held."""

import math

import jax
import jax.numpy as jnp

from . import _init

REFERENCE = "benchmarks.reference.deepseek_v2"

# what a token caches in a layer, logically: [c_kv ; k_r] in bfloat16
LATENT_ROW_BYTES = 2


def _kernel(i, o):
    return {"kernel": (i, o)}


def _mlp(h, width):
    return {"gate_up": _kernel(h, 2 * width), "down": _kernel(width, h)}


def is_expert_layer(cfg, n):
    return n >= cfg["first_k_dense_replace"]


def param_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    w, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]

    def layer(n):
        out = {"input_norm": {"scale": (h,)},
               "q_a": _kernel(h, cfg["q_lora_rank"]),
               "q_a_norm": {"scale": (cfg["q_lora_rank"],)},
               "q_b": _kernel(cfg["q_lora_rank"], heads * (nope + rope)),
               "kv_a": _kernel(h, cfg["kv_lora_rank"] + rope),
               "kv_a_norm": {"scale": (cfg["kv_lora_rank"],)},
               "kv_b": _kernel(cfg["kv_lora_rank"], heads * (nope + v)),
               "o": _kernel(heads * v, h),
               "post_norm": {"scale": (h,)}}
        if is_expert_layer(cfg, n):
            out["moe"] = {
                "router": _kernel(h, cfg["published_n_routed_experts"]),
                "shared": _mlp(h, cfg["n_shared_experts"] * w),
                "experts": {"gate_up": (held, h, 2 * w),
                            "down": (held, w, h)}}
        else:
            out["mlp"] = _mlp(h, cfg["intermediate_size"])
        return out

    return {"embed": (cfg["vocab_size"], h),
            "layers": {f"layer_{n}": layer(n)
                       for n in range(cfg["num_hidden_layers"])},
            "final_norm": {"scale": (h,)},
            "lm_head": _kernel(h, cfg["vocab_size"])}


def init_params(cfg, seed, out_shardings=None):
    """Seeded weights in the serving dtype, made leaf by leaf on the
    device: N(0, initializer_range) drawn in float32 and rounded to
    ``weights_dtype`` (bfloat16: the program is handed these leaves as they
    are, and the reference reads the same rounded values), ones for the
    norm scales.  One leaf's float32 draw at a time: the whole tree in
    float32 would be twice the weights beside the program."""
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    std, key = cfg["initializer_range"], _init.seed_key(seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def draw(shape):
        return jax.jit(lambda k: (std * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype))

    out = []
    for index, (path, shape) in enumerate(leaves):
        if path[-1].key == "scale":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append(draw(shape)(jax.random.fold_in(key, index)))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                  DeepseekV2ForServing)

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "n_shared_experts",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta",
            "rope_scaling", "max_position_embeddings", "initializer_range",
            "first_expert")
    return DeepseekV2ForServing(DeepseekV2Config(
        n_routed_experts=cfg["published_n_routed_experts"],
        experts_held=cfg["n_routed_experts"], **{k: cfg[k] for k in keys}))


# -- counts (the yardstick's own; nothing of the program's) ----------------

def _attention_params(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"]
            + cfg["q_lora_rank"] * heads * (nope + rope)
            + h * (cfg["kv_lora_rank"] + rope) + cfg["kv_lora_rank"]
            + cfg["kv_lora_rank"] * heads * (nope + v)
            + heads * v * h + 2 * h)


def _mlp_params(cfg, width):
    return 3 * cfg["hidden_size"] * width


def held_experts_reached(cfg, batch):
    """Distinct held experts a batch of ``batch`` tokens reaches when every
    token's ``num_experts_per_tok`` choices are uniform over the published
    experts: ``held * (1 - (1 - k / E) ** batch)``."""
    k, e = cfg["num_experts_per_tok"], cfg["published_n_routed_experts"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - k / e) ** batch)


def param_count(cfg):
    """Every parameter this chip holds."""
    h, n = cfg["hidden_size"], 0
    for layer in range(cfg["num_hidden_layers"]):
        n += _attention_params(cfg)
        if is_expert_layer(cfg, layer):
            w = cfg["moe_intermediate_size"]
            n += (h * cfg["published_n_routed_experts"]
                  + _mlp_params(cfg, cfg["n_shared_experts"] * w)
                  + cfg["n_routed_experts"] * _mlp_params(cfg, w))
        else:
            n += _mlp_params(cfg, cfg["intermediate_size"])
    return n + 2 * cfg["vocab_size"] * h + h


def decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must read
    (``decode_roofline``'s count): every weight a step uses, once — the
    attention projections, the dense MLP, the router and the shared
    experts of every layer, the head (the embedding is read by row, not
    counted), and of the routed experts only as many as a full batch of
    ``decode_batch_for_counts`` tokens reaches under uniform routing
    (:func:`held_experts_reached`: 18.3 of the 20 held at 64 tokens; an
    expert no token chose is not read) — plus the logical latent row,
    ``kv_lora_rank + qk_rope_head_dim`` values (1,152 B), of every live
    token in every layer, whatever padding the cache stores it with."""
    h, weights = cfg["hidden_size"], 0.0
    reached = held_experts_reached(cfg, cfg["decode_batch_for_counts"])
    for layer in range(cfg["num_hidden_layers"]):
        weights += _attention_params(cfg)
        if is_expert_layer(cfg, layer):
            w = cfg["moe_intermediate_size"]
            weights += (h * cfg["published_n_routed_experts"]
                        + _mlp_params(cfg, cfg["n_shared_experts"] * w)
                        + reached * _mlp_params(cfg, w))
        else:
            weights += _mlp_params(cfg, cfg["intermediate_size"])
    weights += cfg["vocab_size"] * h + h
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    cache = (cfg["num_hidden_layers"] * live_context_tokens * row
             * LATENT_ROW_BYTES)
    return weights * dtype_bytes + cache


def mla_decode_flops(cfg, live_context_tokens):
    """The absorbed decode kernel's matrix work for ONE layer of one step:
    per head and cached token a score over the whole latent row and a
    value sum over the latent part."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (2 * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"])
            * live_context_tokens)


def mla_decode_bytes(cfg, live_context_tokens, slots):
    """Lower bound of that kernel's HBM traffic for one layer: the logical
    latent row of every live token once, the absorbed queries in and the
    latent values out."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    per_slot = cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"])
    return LATENT_ROW_BYTES * (live_context_tokens * row + slots * per_slot)


def mla_prefill_flops(cfg, seq):
    """The expanded prefill kernel's matrix work for one layer of one
    request of ``seq`` (bucket) positions: causal, so half of q.k over
    nope + rope and of p.v over the value width."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * seq * seq * (qk + cfg["v_head_dim"])


def mla_prefill_bytes(cfg, seq, dtype_bytes=2):
    """Lower bound of its HBM traffic: q and k (nope + rope wide), v and
    the output (value wide), each touched once."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (cfg["num_attention_heads"] * seq * 2 * (qk + cfg["v_head_dim"])
            * dtype_bytes)


def yarn_softmax_scale(cfg):
    """``(nope + rope)^-1/2 * m^2`` with ``m = 0.1 * mscale_all_dim *
    ln(factor) + 1`` (the closed form the tests hold both sides to)."""
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return m * m / math.sqrt(cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])
