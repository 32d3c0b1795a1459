"""The flash forward kernel with grouped KV heads and a causal window, on
the CPU through Pallas' interpreter, against plain attention."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.flash_attention import (
    _band, flash_attention_forward)

HEADS, KV_HEADS, D = 4, 2, 128


def plain(q, k, v, window):
    """[s, h, d] x [s, h_kv, d] -> [s, h, d], query head i over KV head
    i // group, position t over keys max(0, t - window + 1) .. t."""
    s = q.shape[0]
    group = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, group, axis=1), np.repeat(v, group, axis=1)
    scores = np.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    t, u = np.arange(s)[:, None], np.arange(s)[None, :]
    allowed = u <= t
    if window is not None:
        allowed &= t - u < window
    scores = np.where(allowed, scores, -np.inf)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(axis=-1, keepdims=True), v)


def operands(s, seed):
    rng = np.random.RandomState(seed)
    return (rng.standard_normal((s, HEADS, D)).astype(np.float32),
            rng.standard_normal((s, KV_HEADS, D)).astype(np.float32),
            rng.standard_normal((s, KV_HEADS, D)).astype(np.float32))


@pytest.mark.parametrize("s,block,window", [
    (512, 64, 128),      # the cell's window over blocks of 64: a band of 3
    (512, 64, 100),      # a window that is no multiple of the block
    (512, 128, 128),     # band of 2
    (256, 64, 1),        # every position sees itself alone
    (256, 64, 65),
    (128, 128, 50),      # one block: the straight-line path
    (256, 64, 4096),     # wider than the sequence: plain causal
    (256, 64, None),     # no window: grouped heads alone
], ids=lambda x: str(x))
def test_grouped_heads_and_window_match_plain_attention(s, block, window):
    q, k, v = operands(s, s + block)
    got = flash_attention_forward(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        causal=True, block_q=block, block_k=block, window=window,
        interpret=True)[0]
    want = plain(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    if window is not None and 1 < window < s:
        # one key more or fewer is another answer
        for off in (-1, 1):
            assert np.abs(np.asarray(got)
                          - plain(q, k, v, window + off)).max() > 1e-4


def test_band_counts_the_blocks_a_window_reaches():
    # 128 keys over blocks of 64: a query at a block's first row reaches
    # 127 keys back, into the second block before its own
    assert _band(128, 64, 96) == 3
    assert _band(128, 128, 48) == 2
    assert _band(128, 512, 12) == 2
    assert _band(129, 128, 48) == 2
    assert _band(130, 128, 48) == 3
    assert _band(1, 64, 8) == 1
    assert _band(4096, 64, 4) == 4     # never more than the sequence has


def test_grouped_heads_need_whole_lane_tiles():
    q = jnp.zeros((1, 64, 4, 64))
    kv = jnp.zeros((1, 64, 2, 64))
    with pytest.raises(AssertionError, match="multiples of 128"):
        flash_attention_forward(q, kv, kv, causal=True, block_q=64,
                                block_k=64, interpret=True)
