"""K-EXAONE-236B-A23B (LGAI-EXAONE ``config.json``, ``model_type``
``exaone_moe``): sliding-window layers (128 keys) beside full-attention
ones over 64 query / 8 KV heads of 128, RMSNorm on each head's q and k,
rotary positions on the window layers, gated-SiLU MLPs, an untied head, a
sigmoid top-8-of-128 expert layer beside a shared expert.  The configuration
gives one chip's share of an expert-parallel deployment: ``num_experts``
counts the experts HELD here (``first_expert`` onward),
``published_num_experts`` the ones the router scores; ``vocab_size`` the rows
of the vocabulary held; ``layer_types`` and ``mlp_layer_types`` are the
published lists whole, of which the first ``num_hidden_layers`` entries run."""

import jax
import jax.numpy as jnp

from . import _init

REFERENCE = "benchmarks.reference.exaone_moe"

CACHE_BYTES = 2          # a cached value, bfloat16
WINDOW, FULL = "sliding_attention", "full_attention"


def _kernel(i, o):
    return {"kernel": (i, o)}


def _mlp(h, width):
    return {"gate_up": _kernel(h, 2 * width), "down": _kernel(width, h)}


def layer_kinds(cfg):
    """[(attention kind, MLP kind)] of the layers that run."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]))


def widths(cfg):
    """(query width, K-or-V row width) of a layer's projections."""
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d


def param_shapes(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q_width, kv_row = widths(cfg)
    w, held = cfg["moe_intermediate_size"], cfg["num_experts"]

    def layer(mlp_kind):
        out = {"input_norm": {"scale": (h,)},
               "qkv": _kernel(h, q_width + 2 * kv_row),
               "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
               "o": _kernel(q_width, h),
               "post_norm": {"scale": (h,)}}
        if mlp_kind == "sparse":
            out["moe"] = {
                "router": {"kernel": (h, cfg["published_num_experts"]),
                           "bias": (cfg["published_num_experts"],)},
                "shared": _mlp(h, cfg["num_shared_experts"] * w),
                "experts": {"gate_up": (held, h, 2 * w),
                            "down": (held, w, h)}}
        else:
            out["mlp"] = _mlp(h, cfg["intermediate_size"])
        return out

    return {"embed": (cfg["vocab_size"], h),
            "layers": {f"layer_{n}": layer(mlp_kind)
                       for n, (_, mlp_kind) in enumerate(layer_kinds(cfg))},
            "final_norm": {"scale": (h,)},
            "lm_head": _kernel(h, cfg["vocab_size"])}


def init_params(cfg, seed, out_shardings=None):
    """Seeded weights in the serving dtype, made leaf by leaf on the
    device: N(0, initializer_range) drawn in float32 and rounded to
    ``weights_dtype`` (bfloat16: the program is handed these leaves as they
    are, and the reference reads the same rounded values), ones for the
    norm scales, zeros for the router's selection bias.  One leaf's float32
    draw at a time: the whole tree in float32 would be twice the weights
    beside the program."""
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
        elif path[-1].key == "bias":
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append(draw(shape)(jax.random.fold_in(key, index)))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                 ExaoneMoeForServing)

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "num_shared_experts", "layer_types",
            "sliding_window", "mlp_layer_types", "n_group", "topk_group",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "max_position_embeddings", "initializer_range", "first_expert")
    return ExaoneMoeForServing(ExaoneMoeConfig(
        num_experts=cfg["published_num_experts"],
        experts_held=cfg["num_experts"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        **{k: cfg[k] for k in keys}))


# -- counts (the yardstick's own; nothing of the program's) ----------------

def _attention_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q_width, kv_row = widths(cfg)
    return h * (q_width + 2 * kv_row) + 2 * d + q_width * h + 2 * h


def _mlp_params(cfg, width):
    return 3 * cfg["hidden_size"] * width


def _layer_params(cfg, mlp_kind, routed_experts):
    """Parameters of one layer with ``routed_experts`` of its routed
    experts counted (all held: what the chip stores; the ones a step
    reaches: what it reads)."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = _attention_params(cfg)
    if mlp_kind != "sparse":
        return n + _mlp_params(cfg, cfg["intermediate_size"])
    e = cfg["published_num_experts"]
    return (n + h * e + e + _mlp_params(cfg, cfg["num_shared_experts"] * w)
            + routed_experts * _mlp_params(cfg, w))


def held_experts_reached(cfg, batch):
    """Distinct held experts a batch of ``batch`` tokens reaches when every
    token's ``num_experts_per_tok`` choices are uniform over the published
    experts: ``held * (1 - (1 - k / E) ** batch)``."""
    k, e = cfg["num_experts_per_tok"], cfg["published_num_experts"]
    return cfg["num_experts"] * (1.0 - (1.0 - k / e) ** batch)


def param_count(cfg):
    """Every parameter this chip holds."""
    h = cfg["hidden_size"]
    return (sum(_layer_params(cfg, mlp_kind, cfg["num_experts"])
                for _, mlp_kind in layer_kinds(cfg))
            + 2 * cfg["vocab_size"] * h + h)


def cached_tokens_read(cfg, live_context_tokens, slots):
    """(tokens a full layer's decode reads, tokens a window layer's reads)
    in one step, as lower bounds: every live token; the window's keys of
    every slot, no more than its context holds."""
    return live_context_tokens, min(slots * cfg["sliding_window"],
                                    live_context_tokens)


def decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must read
    (``decode_roofline``'s count): every weight a step uses, once — the
    attention projections, the dense MLP, the router and the shared expert
    of every layer, the head (the embedding is read by row, not counted),
    and of the routed experts only as many as a full batch of
    ``decode_batch_for_counts`` tokens reaches under uniform routing
    (:func:`held_experts_reached`: 15.7 of the 16 held at 64 tokens) — plus
    the K and V rows (``2 x 8 x 128`` values) of every live token in every
    full layer and of ``slots x 128`` tokens in every window layer."""
    h = cfg["hidden_size"]
    slots = cfg["decode_batch_for_counts"]
    reached = held_experts_reached(cfg, slots)
    full, window = cached_tokens_read(cfg, live_context_tokens, slots)
    weights = cfg["vocab_size"] * h + h
    cached = 0.0
    for kind, mlp_kind in layer_kinds(cfg):
        weights += _layer_params(cfg, mlp_kind, reached)
        cached += window if kind == WINDOW else full
    return (weights * dtype_bytes
            + cached * 2 * widths(cfg)[1] * CACHE_BYTES)


def counts(cfg, live_context_tokens, slots, seq):
    """FLOPs and lower-bound HBM bytes of ONE call (one layer) of each new
    kernel: the two decode calls of a step over ``live_context_tokens``
    cached tokens in ``slots`` slots, the two prefill calls of a request of
    ``seq`` (bucket) positions.  Per query head and visible key a score
    over ``head_dim`` and a value sum over ``head_dim``: ``4 * head_dim``
    FLOPs; K and V rows are read once for the ``heads / kv_heads`` query
    heads that share them, queries in and context out."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    q_width, kv_row = widths(cfg)
    window = cfg["sliding_window"]
    full_tokens, window_tokens = cached_tokens_read(
        cfg, live_context_tokens, slots)

    def decode(tokens):
        return (4 * heads * d * tokens,
                CACHE_BYTES * (2 * kv_row * tokens + 2 * slots * q_width))

    # keys a position sees, summed over the bucket's positions
    causal_pairs = seq * (seq + 1) // 2
    band = min(window, seq)
    window_pairs = band * (band + 1) // 2 + (seq - band) * window
    prefill_bytes = CACHE_BYTES * seq * (2 * q_width + 2 * kv_row)
    out = {}
    out["gqa_decode_flops"], out["gqa_decode_bytes"] = decode(full_tokens)
    out["window_decode_flops"], out["window_decode_bytes"] = decode(
        window_tokens)
    out["gqa_prefill_flops"] = 4 * heads * d * causal_pairs
    out["window_prefill_flops"] = 4 * heads * d * window_pairs
    out["gqa_prefill_bytes"] = out["window_prefill_bytes"] = prefill_bytes
    return out
