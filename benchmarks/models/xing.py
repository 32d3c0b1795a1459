"""Xing-4.0 (XingChen-AGI/Xing4.0-29B-A4B ``config.json``, ``model_type``
``xing4_0``): DeepSeek-V2's multi-head latent attention, sigmoid-scored
top-4 experts beside a shared one (all 64 held: ``ep_size`` 1), an untied
head — on a residual path of ``hc_mult`` = 4 streams mixed around every
sublayer by Sinkhorn-normalised maps (mHC).  ``n_routed_experts`` counts the
experts HELD here (``first_expert`` onward), ``published_n_routed_experts``
the ones the router scores: the same 64."""

import jax
import jax.numpy as jnp

from . import _init
# the attention, the gated MLPs and the experts' reach are DeepSeek-V2's, at
# this configuration's numbers: its table helpers and counts, not a copy
from .deepseek_v2 import (_attention_params, _kernel, _mlp, _mlp_params,
                          held_experts_reached, is_expert_layer,
                          yarn_softmax_scale)

REFERENCE = "benchmarks.reference.xing"

# what a token caches in a layer, logically: [c_kv ; k_r] in bfloat16
LATENT_ROW_BYTES = 2
STREAM_BYTES = 4        # the residual streams, the maps and F's output


def map_values(cfg):
    """Values of one sublayer's three maps: ``2n + n^2``."""
    return 2 * cfg["hc_mult"] + cfg["hc_mult"] ** 2


def param_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    w, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    routed = cfg["published_n_routed_experts"]

    def maps():
        return {"phi": (cfg["hc_mult"] * h, map_values(cfg)),
                "bias": (map_values(cfg),), "alpha": (3,)}

    def layer(n):
        out = {"hc_attn": maps(), "hc_mlp": maps(),
               "input_norm": {"scale": (h,)},
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
                "router": {"kernel": (h, routed), "bias": (routed,)},
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
    norm scales.  The leaves the published config gives no scale for are
    drawn as the configuration file's ``assumed`` says, so that the maps
    differ by token and the bias chooses: a sublayer's ``alpha`` from
    N(``hc_alpha_mean``, ``hc_alpha_std``), its ``bias`` from N(0,
    ``hc_bias_std``), the router's selection ``bias`` from N(0,
    ``router_bias_std``).  One leaf's float32 draw at a time."""
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = _init.seed_key(seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def draw(shape, mean, std):
        return jax.jit(lambda k: (mean + std * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype))

    def moments(path):
        leaf, owner = path[-1].key, path[-2].key if len(path) > 1 else ""
        if leaf == "alpha":
            return cfg["hc_alpha_mean"], cfg["hc_alpha_std"]
        if leaf == "bias":
            return 0.0, cfg["router_bias_std" if owner == "router"
                            else "hc_bias_std"]
        return 0.0, cfg["initializer_range"]

    out = []
    for index, (path, shape) in enumerate(leaves):
        if path[-1].key == "scale":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append(draw(shape, *moments(path))(
                jax.random.fold_in(key, index)))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.xing import XingConfig, XingForServing

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "n_shared_experts",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "norm_topk_prob", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta",
            "rope_scaling", "max_position_embeddings", "initializer_range",
            "first_expert")
    return XingForServing(XingConfig(
        n_routed_experts=cfg["published_n_routed_experts"],
        experts_held=cfg["n_routed_experts"], **{k: cfg[k] for k in keys}))


# -- counts (the yardstick's own; nothing of the program's) ----------------

def _connection_params(cfg):
    """Both sublayers' ``Phi``, ``b`` and three ``alpha``."""
    values = map_values(cfg)
    return 2 * (cfg["hc_mult"] * cfg["hidden_size"] * values + values + 3)


def _layer_params(cfg, layer, routed_experts):
    """Parameters of one layer with ``routed_experts`` of its routed experts
    counted (all held: what the chip stores; the ones a step reaches: what
    it reads)."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = _attention_params(cfg) + _connection_params(cfg)
    if not is_expert_layer(cfg, layer):
        return n + _mlp_params(cfg, cfg["intermediate_size"])
    e = cfg["published_n_routed_experts"]
    return (n + h * e + e + _mlp_params(cfg, cfg["n_shared_experts"] * w)
            + routed_experts * _mlp_params(cfg, w))


def param_count(cfg):
    """Every parameter this chip holds."""
    h = cfg["hidden_size"]
    return (sum(_layer_params(cfg, layer, cfg["n_routed_experts"])
                for layer in range(cfg["num_hidden_layers"]))
            + 2 * cfg["vocab_size"] * h + h)


def decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must read
    (``decode_roofline``'s count): every weight a step uses, once — the
    attention projections, the maps' ``Phi``, the dense MLPs, the router and
    the shared expert of every layer, the head (the embedding is read by
    row, not counted), and of the routed experts only as many as a full
    batch of ``decode_batch_for_counts`` tokens reaches under uniform
    routing (:func:`held_experts_reached`: 55.9 of the 64 at 32 tokens; an
    expert no token chose is not read) — plus the logical latent row,
    ``kv_lora_rank + qk_rope_head_dim`` values (1,152 B), of every live
    token in every layer, whatever padding the cache stores it with.  The
    streams of a step's few rows are not counted."""
    h = cfg["hidden_size"]
    reached = held_experts_reached(cfg, cfg["decode_batch_for_counts"])
    weights = cfg["vocab_size"] * h + h + sum(
        _layer_params(cfg, layer, reached)
        for layer in range(cfg["num_hidden_layers"]))
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    cache = (cfg["num_hidden_layers"] * live_context_tokens * row
             * LATENT_ROW_BYTES)
    return weights * dtype_bytes + cache


def counts(cfg, live_context_tokens, slots, seq):
    """FLOPs and lower-bound HBM bytes of ONE call of each kernel this
    configuration runs: the two mixes over ``seq`` tokens (a prefill bucket;
    a decode step's are the same functions of ``slots``), the latent decode
    kernel of one layer over ``live_context_tokens`` cached tokens in
    ``slots`` slots, the expanded prefill kernel of one request of ``seq``
    positions, and one expert layer's two grouped products over ``seq``
    tokens' pairs.

    - ``mhc_pre_mix``: the stream read once (``n C`` float32 a token),
      ``u`` (``C``) and the ``2n + n^2`` map values written, ``Phi`` read
      once; its matrix work is ``x^ Phi`` and the mix.
    - ``mhc_post_res_mix``: the stream read and written, ``y`` and the map
      values read; ``n^2 + n`` multiply-adds a value of ``C``.
    - the latent kernels: ``benchmarks/models/deepseek_v2.py``'s counts at
      this configuration's heads.
    - the grouped products: ``seq * top_k`` rows through ``[h, 2w]`` and
      ``[w, h]``; every held expert's weights read once, the rows in and out
      once in bfloat16."""
    h, n = cfg["hidden_size"], cfg["hc_mult"]
    values = map_values(cfg)
    heads = cfg["num_attention_heads"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    w, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    pairs = seq * k
    return {
        "mhc_pre_mix_flops": seq * (2 * n * h * values + 3 * n * h),
        "mhc_pre_mix_bytes": STREAM_BYTES * (
            seq * (n * h + h + values) + n * h * values),
        "mhc_post_res_mix_flops": seq * 2 * (n * n + n) * h,
        "mhc_post_res_mix_bytes": STREAM_BYTES * seq * (
            2 * n * h + h + values),
        "mla_decode_flops": (2 * heads * (row + cfg["kv_lora_rank"])
                             * live_context_tokens),
        "mla_decode_bytes": LATENT_ROW_BYTES * (
            live_context_tokens * row
            + slots * heads * (row + cfg["kv_lora_rank"])),
        "mla_prefill_flops": heads * seq * seq * (qk + cfg["v_head_dim"]),
        "mla_prefill_bytes": (heads * seq * 2 * (qk + cfg["v_head_dim"])
                              * LATENT_ROW_BYTES),
        "moe_grouped_flops": 2 * pairs * 3 * h * w,
        "moe_grouped_bytes": 2 * (
            cfg["n_routed_experts"] * 3 * h * w + pairs * (2 * h + 3 * w)),
    }
