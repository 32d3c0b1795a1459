"""The flash forward kernel with grouped KV heads and a causal window, on
the CPU through Pallas' interpreter, against plain attention."""

import math
import re

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


# -- the differentiable call: grouped heads and the window, backward ---------

def dense(q, k, v, window):
    """``plain`` in jax on [b, s, h, d] operands, for ``jax.grad``."""
    import jax

    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / math.sqrt(q.shape[-1])
    t, u = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = u <= t
    if window is not None:
        allowed &= t - u < window
    p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


@pytest.mark.parametrize("s,block,window,kv_heads", [
    (256, 64, None, 2),     # grouped heads alone: the full layer
    (256, 64, 100, 2),      # both: a band of 3, no multiple of the block
    (256, 64, 64, 2),       # a band of 2
    (256, 64, 100, 4),      # the window alone (a KV head a query head)
    (256, 64, 4096, 2),     # wider than the sequence: plain causal
    (128, 128, 50, 1),      # one block a row: the streamed pair all the same
    (192, 64, 1, 2),        # every position sees itself alone
], ids=lambda x: str(x))
def test_grouped_and_windowed_backward_matches_dense_attention(
        s, block, window, kv_heads):
    import jax

    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    rng = np.random.RandomState(s + block + kv_heads)
    q = jnp.asarray(rng.standard_normal((2, s, HEADS, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, kv_heads, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, kv_heads, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, s, HEADS, D)), jnp.float32)

    def kernel_loss(q, k, v):
        out = flash_attention(q, k, v, None, None, True, block, block, True,
                              0.0, window)
        return jnp.sum(out * w)

    def dense_loss(q, k, v):
        return jnp.sum(dense(q, k, v, window) * w)

    got = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape, name
        scale = max(float(jnp.abs(r).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=3e-5 * scale, rtol=1e-4,
                                   err_msg=f"d{name}")
    if window is not None and 1 < window < s:
        # one key more is another gradient: the band is the window's
        other = jax.grad(lambda q, k, v: jnp.sum(
            dense(q, k, v, window + 1) * w), argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.abs(got[1] - other[1]).max()) > 1e-3


def test_backward_grid_streams_the_band_and_the_group():
    """The streamed dimension of both backward kernels is the band under a
    window (blocks outside it are never fetched), and the dk/dv kernel runs
    a step a KV head over its group's query heads."""
    import jax

    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    def grids(window):
        q = jnp.zeros((1, 512, HEADS, D))
        kv = jnp.zeros((1, 512, KV_HEADS, D))
        text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, None, None, True, 64, 64, True, 0.0, window).sum(),
            argnums=(0, 1, 2)))(q, kv, kv))
        return [tuple(int(n) for n in g.split(","))
                for g in re.findall(r"grid=\(([\d, ]+)\)", text)]

    group = HEADS // KV_HEADS
    # forward, dq: (batch, query heads, q blocks, streamed k blocks);
    # dk/dv: (batch, KV heads, k blocks, group x streamed q blocks)
    assert grids(None) == [(1, HEADS, 8, 8), (1, HEADS, 8, 8),
                           (1, KV_HEADS, 8, group * 8)]
    assert grids(128) == [(1, HEADS, 8, 3), (1, HEADS, 8, 3),
                          (1, KV_HEADS, 8, group * 3)]


def test_plain_heads_keep_their_backward_kernels():
    """One KV head a query head and no window: the kernels BERT and GPT-2
    reach are the ones they reached (the fused single tile, unnamed)."""
    import jax

    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    x = jnp.zeros((1, 128, 2, 64))
    text = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, x, x, None, None, False, 128, 128, True, 0.0).sum()))(x))
    # the forward's grid, then the fused single tile's: the steps alone
    assert re.findall(r"grid=\(([\d, ]+)\)", text) == ["1, 1, 1, 1", "1, 1"]
    assert "train_attention" not in text
