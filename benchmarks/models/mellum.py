"""Mellum 2 (JetBrains Mellum2-12B-A2.5B, ``model_type`` ``mellum``) for
training: every layer a mixture of experts, sliding-window and full
attention over grouped KV heads."""

from . import _init

REFERENCE = "benchmarks.reference.mellum"
WINDOW = "sliding_attention"


def param_shapes(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q_width = cfg["num_attention_heads"] * d
    kv_width = cfg["num_key_value_heads"] * d
    width, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    layer = {
        "input_norm": {"scale": (h,)},
        "qkv": {"kernel": (h, q_width + 2 * kv_width)},
        "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
        "attn_out": {"kernel": (q_width, h)},
        "post_norm": {"scale": (h,)},
        "moe": {"router": {"kernel": (h, cfg["published_num_experts"])},
                "experts": {"gate_up": (held, h, 2 * width),
                            "down": (held, width, h)}}}
    return {"embed": (cfg["vocab_size"], h),
            "layers": {f"layer_{n}": layer
                       for n in range(cfg["num_hidden_layers"])},
            "final_norm": {"scale": (h,)},
            "lm_head": {"kernel": (h, cfg["vocab_size"])}}


def init_params(cfg, seed, out_shardings=None):
    return _init.init_from_shapes(param_shapes(cfg), seed,
                                  cfg["initializer_range"], out_shardings)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.mellum import (MellumConfig,
                                             MellumForCausalLMTPU)

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "layer_types", "sliding_window", "rope_parameters",
            "rms_norm_eps", "max_position_embeddings", "initializer_range",
            "router_aux_loss_coef", "first_expert")
    extra = {k: cfg[k] for k in ("remat", "loss_chunk", "attn_block",
                                 "expert_tiling") if k in cfg}
    return MellumForCausalLMTPU(MellumConfig(
        **{k: cfg[k] for k in keys}, **extra,
        num_experts=cfg["published_num_experts"],
        experts_held=cfg["num_experts"]))


def eval_inputs(batch, rows):
    """The first ``rows`` rows: ``engine.eval_batch`` on ids without labels
    returns the logits at every position."""
    return {"input_ids": batch["input_ids"][:rows]}


def dropout_rates(cfg):
    return {"embedding": 0.0, "hidden": 0.0, "attention": 0.0}


def attention_shape(cfg, traffic, global_batch):
    """The ONE full-attention layer's call (layers, batch, heads, seq,
    head_dim, causal): ``attn_kernel_*_per_layer`` in the run's counts are
    that kernel's.  (The byte count there takes K and V for as many heads
    as Q: an overcount the kernel's compute bound leaves without effect.)"""
    full = sum(kind != WINDOW for kind in cfg["layer_types"])
    return (full, global_batch, cfg["num_attention_heads"],
            traffic["seq_len"], cfg["head_dim"], True)


def keys_seen(seq, window=None):
    """(query, key) pairs a causal layer scores over one sequence: every
    key up to the query's own, its last ``window`` under a window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def held_pairs_per_token(cfg):
    """(token, choice) pairs that fall to the experts held here, a token,
    under an even routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published_num_experts"])


def _layer_matmul_flops_per_token(cfg):
    """Forward: the fused q/k/v and output projections, the router, and
    both products of the held experts a token reaches on average."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q_width = cfg["num_attention_heads"] * d
    kv_width = cfg["num_key_value_heads"] * d
    expert = 3 * h * cfg["moe_intermediate_size"]
    return 2 * (h * (q_width + 2 * kv_width) + q_width * h
                + h * cfg["published_num_experts"]
                + held_pairs_per_token(cfg) * expert)


def train_flops_per_step(cfg, traffic, global_batch):
    """Forward + backward (3 x the forward's products; recomputation not
    counted): the layers' projections, router and held experts, attention
    over the keys each layer's mask allows (4 d a pair: q.k and p.v), the
    head over the held vocabulary on every position."""
    seq = traffic["seq_len"]
    tokens = global_batch * seq
    pairs = sum(keys_seen(seq, cfg["sliding_window"] if kind == WINDOW
                          else None) for kind in cfg["layer_types"])
    fwd = tokens * cfg["num_hidden_layers"] \
        * _layer_matmul_flops_per_token(cfg)
    fwd += global_batch * pairs * 4 * cfg["head_dim"] \
        * cfg["num_attention_heads"]
    fwd += tokens * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return 3 * fwd


def _attention_kernel_counts(cfg, batch, seq, window):
    """(FLOPs, lower bound of bytes) of one layer's flash kernels in one
    step: forward q.k and p.v, backward q.k again and dv, dp, dq, dk — 7
    products of 2 d a scored pair; Q, O, dO, dQ of the query heads and K,
    V, dK, dV of the KV heads, each tensor touched once a pass that needs
    it (forward: Q K V in, O out; backward: Q K V O dO in, dQ dK dV out)."""
    d, heads = cfg["head_dim"], cfg["num_attention_heads"]
    flops = 7 * 2 * d * heads * batch * keys_seen(seq, window)
    row = batch * seq * d * 2
    return flops, row * (6 * heads + 6 * cfg["num_key_value_heads"])


def _grouped_product_counts(cfg, tokens):
    """(FLOPs, lower bound of bytes) of one layer's grouped products in one
    step over the pairs held here under an even routing: forward both
    products, backward d lhs and d rhs of each (3 x the forward's FLOPs);
    bytes: each product's lhs, weights and output once forward, and
    backward the output's gradient, the weights and the lhs in, d lhs and
    d weights out."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = tokens * held_pairs_per_token(cfg)
    flops = 3 * 2 * pairs * 3 * h * width
    weights = cfg["num_experts"] * 3 * h * width * 2
    rows = pairs * 2 * (h + 2 * width + width + h)   # lhs and out, both
    return flops, 3 * weights + 3 * rows


def counts(cfg, traffic, global_batch):
    """Operations and bytes of the kernels this configuration adds, a
    layer a step (forward + backward; the recomputed forward is time, not
    work): the full layer's grouped flash kernels, a window layer's, and a
    layer's grouped expert products."""
    seq = traffic["seq_len"]
    out = {}
    for name, window in (("gqa_attn", None),
                         ("window_attn", cfg["sliding_window"])):
        flops, moved = _attention_kernel_counts(cfg, global_batch, seq,
                                                window)
        out[f"{name}_kernel_flops_per_layer"] = flops
        out[f"{name}_kernel_bytes_per_layer"] = moved
    flops, moved = _grouped_product_counts(cfg, global_batch * seq)
    out["moe_grouped_flops_per_layer"] = flops
    out["moe_grouped_bytes_per_layer"] = moved
    return out
