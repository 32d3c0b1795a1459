"""A tiny Ouro cell for the CPU tests: the published model's shape in small
— hidden 256 as 2 heads of 128 (the width the flash kernel's projection
layout takes a head a block at), MLP 512, 3 layers run 3 times over the same
weights (9 cache planes), vocabulary 512, pages of 8."""

from benchmarks import common

MODEL = {
    "vocab_size": 512, "hidden_size": 256, "num_hidden_layers": 3,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 128,
    "intermediate_size": 512, "total_ut_steps": 3,
    "early_exit_threshold": 1, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "max_position_embeddings": 640, "initializer_range": 0.2,
    "weights_dtype": "float32"}

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 8, "kv_blocks": 49, "max_batch_slots": 4,
    "max_seq_len": 96, "prefill_buckets": [32, 48], "token_budget": 384,
    "max_new_tokens": 40, "weights_dtype": "float32"}}


def serve_spec(limits, model=None):
    traffic = common.load_traffic("think_backlog")
    traffic["pairs"] = [[max(4, p // 6), max(3, a // 10)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=8, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.think_backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "ouro",
                   "model_config": dict(model or MODEL), "engine": ENGINE},
        "traffic": traffic}
