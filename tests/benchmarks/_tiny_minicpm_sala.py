"""A tiny MiniCPM-SALA cell for the CPU tests: the published model's shape in
small — one ``minicpm4`` layer and three ``lightning-attn`` ones, hidden 128
as 4 query heads over 2 KV heads of 32 (4 lightning heads of 32), MLP 256,
vocabulary 512, pages of 16 — and a ``sparse_config`` cut with it: kernels of
8 keys every 4, 6 blocks of 16 a query (block 0, the 20-token window's two or
three, the rest by score), dense up to 96 tokens."""

from benchmarks import common

SPARSE_CONFIG = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16,
                 "topk": 6, "init_blocks": 1, "window_size": 20,
                 "dense_len": 96}

MODEL = {
    "vocab_size": 512, "hidden_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 256, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 32,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "lightning-attn"],
    "sparse_config": SPARSE_CONFIG, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 32, "mup_denominator": 32,
    "max_position_embeddings": 1024, "initializer_range": 0.2,
    "weights_dtype": "float32", "decode_batch_for_counts": 3}

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 16, "kv_blocks": 3 * 16 + 1, "max_batch_slots": 3,
    "max_seq_len": 256, "prefill_buckets": [64, 128, 192],
    "token_budget": 768, "max_new_tokens": 100, "weights_dtype": "float32"}}


def serve_spec(limits, model=None):
    traffic = common.load_traffic("longdoc_backlog")
    traffic["pairs"] = [[max(4, p // 90), max(3, a // 60)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=5, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.longdoc_backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "minicpm_sala",
                   "model_config": dict(model or MODEL), "engine": ENGINE},
        "traffic": traffic}
