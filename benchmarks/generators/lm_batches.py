"""Synthetic language-model batches: random token ids, full sequences."""

import numpy as np

from ._requests import token_id_range


def make(traffic, model_cfg, seed, global_batch):
    rng = np.random.default_rng(int(seed))
    return [{"input_ids": rng.integers(
        0, token_id_range(model_cfg), size=(global_batch, traffic["seq_len"]),
        dtype=np.int32)} for _ in range(traffic["pool"])]


def tokens_per_step(traffic, global_batch):
    return global_batch * traffic["seq_len"]
