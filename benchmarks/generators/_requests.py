"""The request list both serving generators share: a fixed list of
(prompt length, answer length) pairs from the traffic file, cycled as
needed, in the file's order for every seed (permuted by the seed, a window
held a lighter or a heavier part of the list: PERF.md, section 4);
``--seed`` draws the token ids, below the published vocabulary."""

import numpy as np


def token_id_range(model_cfg):
    """Token ids are drawn below the published vocabulary: the rows a
    padded table adds are never indexed."""
    return model_cfg.get("published_vocab_size", model_cfg["vocab_size"])


class RequestList:
    def __init__(self, traffic, model_cfg, seed):
        self.pairs = [tuple(p) for p in traffic["pairs"]]
        self.seed = int(seed)
        self.vocab = token_id_range(model_cfg)

    def lengths(self, index):
        return self.pairs[index % len(self.pairs)]

    def request(self, index, answer=None):
        """Request number ``index`` of the run: (prompt ids, answer length).
        The same for the same seed, whenever it is asked for."""
        prompt_len, answer_len = self.lengths(index)
        rng = np.random.default_rng([self.seed, 1, index])
        prompt = rng.integers(0, self.vocab, size=prompt_len, dtype=np.int32)
        return prompt, int(answer if answer is not None else answer_len)
