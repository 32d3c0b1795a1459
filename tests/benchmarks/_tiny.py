"""Tiny cells for the CPU tests: the published models' shapes in small."""

from benchmarks import common

BERT = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 256,
        "max_position_embeddings": 64, "type_vocab_size": 2,
        "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
        "initializer_range": 0.02, "layer_norm_eps": 1e-12}
GPT2 = {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
        "num_heads": 4, "max_position_embeddings": 64, "embd_dropout": 0.0,
        "attn_dropout": 0.0, "resid_dropout": 0.0,
        "initializer_range": 0.02, "layer_norm_eps": 1e-5}
ADAM = {"lr": 1e-3, "betas": [0.9, 0.999], "eps": 1e-8, "weight_decay": 0.0}


def train_spec(limits, dropout=0.1, bf16=True):
    model = dict(BERT, hidden_dropout_prob=dropout,
                 attention_probs_dropout_prob=dropout)
    # the leaves the check names are the real configuration's
    named = common.load_cell("bert_large.seq128")["config"]["check"]
    return {
        "name": "tiny.mlm", "chips": 1, "per_layer": [], "end_to_end": [],
        "config": {"name": "tiny", "kind": "train", "model": "bert",
                   "model_config": model, "mesh": {"data": 1},
                   "optimizer": ADAM, "check": named,
                   "engine": {"steps_per_print": 10 ** 9,
                              "optimizer": {"type": "Adam", "params": ADAM},
                              "bf16": {"enabled": bf16}}},
        "traffic": {"generator": "mlm_batches", "seq_len": 64,
                    "batch_per_chip": 16, "predictions_per_seq": 10,
                    "pool": 6, "check_block_rows": 8, "trace_steps": 2,
                    "eval_rows_per_chip": 2,
                    "limits": {"tiny": limits}}}


def serve_spec(limits):
    traffic = common.load_traffic("backlog")
    traffic["pairs"] = [[max(4, p // 16), max(3, a // 16)]
                        for p, a in traffic["pairs"]]
    traffic.update(callers=8, limits={"tiny": limits}, trace_seconds=0.3)
    return {
        "name": "tiny.backlog", "chips": 1, "per_layer": [],
        "end_to_end": [],
        "config": {"name": "tiny", "kind": "serve", "model": "gpt2",
                   "model_config": GPT2,
                   "engine": {"steps_per_print": 10 ** 9, "inference": {
                       "kv_block_size": 8, "kv_blocks": 33,
                       "max_batch_slots": 4, "max_seq_len": 64,
                       "prefill_buckets": [8, 16, 32], "token_budget": 256,
                       "max_new_tokens": 16, "weights_dtype": "float32"}}},
        "traffic": traffic}
