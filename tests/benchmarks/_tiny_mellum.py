"""A tiny Mellum cell for the CPU tests: the published model's shape in
small — one period sliding, sliding, sliding, full over grouped KV heads of
128 (the width the flash kernels' grouped indexing needs), a window of 40
keys over blocks of 32 (no multiple of the block: a band of 3), 8 experts
of which a quarter is held, 2 a token, YaRN over an original context of 64
so that the ramp lies inside the head."""

from benchmarks import common

S, F = "sliding_attention", "full_attention"

MODEL = {
    "vocab_size": 256, "source_vocab_size": 1024, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 128, "moe_intermediate_size": 32,
    "num_experts": 2, "published_num_experts": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "layer_types": [S, S, S, F], "mlp_layer_types": ["sparse"] * 4,
    "sliding_window": 40,
    "rope_parameters": {
        F: {"rope_type": "yarn", "rope_theta": 10000, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 10000}},
    "rms_norm_eps": 1e-6, "max_position_embeddings": 1024,
    "initializer_range": 0.2, "router_aux_loss_coef": 0.01,
    "remat": True, "loss_chunk": 64, "attn_block": [32, 64],
    "expert_tiling": [16, 128, 128]}
ADAM = {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8, "weight_decay": 0.0}


def train_spec(limits, model=None, bf16=False):
    return {
        "name": "tiny.code8k", "chips": 1, "per_layer": [], "end_to_end": [],
        "config": {"name": "tiny", "kind": "train", "model": "mellum",
                   "model_config": dict(model or MODEL), "mesh": {"data": 1},
                   "optimizer": ADAM,
                   "engine": {"steps_per_print": 10 ** 9,
                              "optimizer": {"type": "Adam", "params": ADAM},
                              "bf16": {"enabled": bf16}}},
        "traffic": dict(common.load_traffic("code8k"), seq_len=128,
                        batch_per_chip=2, pool=6, trace_steps=2,
                        limits={"tiny": limits})}
