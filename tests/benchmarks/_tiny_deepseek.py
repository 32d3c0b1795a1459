"""A tiny DeepSeek-V2 cell for the CPU tests: the published model's shape
in small (latent attention with a decoupled rotary part, one dense layer,
two expert layers of 16 experts in 4 groups of which group 0 is held)."""

from benchmarks import common

MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "n_routed_experts": 4,
    "published_n_routed_experts": 16, "first_expert": 0,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 4.0, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64},
    "max_position_embeddings": 2560, "initializer_range": 0.2,
    "weights_dtype": "float32", "decode_batch_for_counts": 4}

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 8, "kv_blocks": 33, "max_batch_slots": 4,
    "max_seq_len": 64, "prefill_buckets": [16, 32], "token_budget": 256,
    "max_new_tokens": 16, "weights_dtype": "float32"}}
ENGINE_BF16 = {"steps_per_print": 10 ** 9, "inference": dict(ENGINE["inference"], weights_dtype="bfloat16")}


def serve_spec(limits, model=None):
    traffic = common.load_traffic("repo_backlog")
    traffic["pairs"] = [[max(4, p // 256), max(3, a // 256)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=8, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.repo_backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "deepseek_v2",
                   "model_config": dict(model or MODEL), "engine": ENGINE},
        "traffic": traffic}
