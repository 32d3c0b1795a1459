"""Synthetic BERT pretraining batches in the bing_bert layout: random
token ids, a full attention mask, exactly ``predictions_per_seq`` labelled
positions a row (so every seed has the same amount of work), NSP labels."""

import numpy as np

from ._requests import token_id_range


def make(traffic, model_cfg, seed, global_batch):
    rng = np.random.default_rng(int(seed))
    seq, n_pred = traffic["seq_len"], traffic["predictions_per_seq"]
    pool = []
    for _ in range(traffic["pool"]):
        ids = rng.integers(0, token_id_range(model_cfg),
                           size=(global_batch, seq), dtype=np.int32)
        pos = np.argsort(rng.random((global_batch, seq)), axis=1)[:, :n_pred]
        labels = np.full((global_batch, seq), -100, np.int32)
        np.put_along_axis(labels, pos,
                          np.take_along_axis(ids, pos, axis=1), axis=1)
        pool.append({
            "input_ids": ids,
            "attention_mask": np.ones((global_batch, seq), np.int32),
            "token_type_ids": np.zeros((global_batch, seq), np.int32),
            "masked_lm_labels": labels,
            "next_sentence_labels": rng.integers(
                0, 2, size=(global_batch,), dtype=np.int32)})
    return pool


def tokens_per_step(traffic, global_batch):
    return global_batch * traffic["seq_len"]
