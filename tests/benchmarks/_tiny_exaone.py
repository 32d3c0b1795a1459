"""A tiny K-EXAONE cell for the CPU tests: the published model's shape in
small — the pattern window, window, window, full, window over grouped KV
heads of 128 (the width the flash kernel's grouped indexing needs), a window
of 12 keys over pages of 8 (no multiple of the page: a ring of 3), one dense
layer and four sparse ones of 16 experts of which 4 are held."""

from benchmarks import common

PATTERN = ["sliding_attention"] * 3 + ["full_attention"]

MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "published_num_experts": 16, "first_expert": 0,
    "num_experts_per_tok": 3, "num_shared_experts": 1,
    "layer_types": PATTERN * 3, "sliding_window": 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 11,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 2560, "initializer_range": 0.2,
    "weights_dtype": "float32", "decode_batch_for_counts": 4}

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 8, "kv_blocks": 49, "max_batch_slots": 4,
    "max_seq_len": 96, "prefill_buckets": [32, 48], "token_budget": 384,
    "max_new_tokens": 40, "weights_dtype": "float32"}}


def serve_spec(limits, model=None):
    traffic = common.load_traffic("reason_backlog")
    traffic["pairs"] = [[max(4, p // 128), max(3, a // 256)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=8, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.reason_backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "exaone_moe",
                   "model_config": dict(model or MODEL), "engine": ENGINE},
        "traffic": traffic}
