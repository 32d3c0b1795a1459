"""A tiny Xing-4.0 cell for the CPU tests: the published model's shape in
small (latent attention with a decoupled rotary part, four residual streams
mixed by Sinkhorn-normalised maps, two dense layers, two expert layers of 8
sigmoid-scored experts, all held, 3 a token)."""

from benchmarks import common

MODEL = {
    "vocab_size": 256, "hidden_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 2, "n_routed_experts": 8,
    "published_n_routed_experts": 8, "first_expert": 0,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.0, "norm_topk_prob": True,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64},
    "max_position_embeddings": 2560, "initializer_range": 0.2,
    "hc_alpha_mean": 0.1, "hc_alpha_std": 0.02, "hc_bias_std": 1.0,
    "router_bias_std": 0.3, "weights_dtype": "float32",
    "decode_batch_for_counts": 4}

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 8, "kv_blocks": 33, "max_batch_slots": 4,
    "max_seq_len": 64, "prefill_buckets": [16, 32], "token_budget": 256,
    "max_new_tokens": 16, "weights_dtype": "float32"}}


def serve_spec(limits, model=None):
    traffic = common.load_traffic("docqa_backlog")
    traffic["pairs"] = [[max(4, p // 512), max(3, a // 64)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=8, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.docqa_backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "xing",
                   "model_config": dict(model or MODEL), "engine": ENGINE},
        "traffic": traffic}
