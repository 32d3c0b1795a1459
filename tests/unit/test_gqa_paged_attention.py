"""The grouped-KV / windowed paged decode kernel on the CPU through Pallas'
interpreter, against plain attention over each slot's own positions: full
layers over a whole-context table, window layers over a RING of pages that
is written position by position as the engine would (a page holds whatever
was written there last)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.paged_attention import (
    check_gqa_tpu_geometry, paged_decode_attention, ring_pages)

HEADS, KV_HEADS, HEAD_DIM, LAYERS = 8, 2, 16, 2


def plain(q, keys, values, first):
    """softmax(q k / sqrt(d)) v of one slot: ``q [heads, d]`` over
    ``keys`` / ``values`` ``[positions, kv_heads, d]`` from ``first`` on,
    query head i reading KV head i // group."""
    group = HEADS // KV_HEADS
    k = np.repeat(keys[first:], group, axis=1)
    v = np.repeat(values[first:], group, axis=1)
    s = np.einsum("hd,shd->hs", q, k) / math.sqrt(HEAD_DIM)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    return np.einsum("hs,shd->hd", p / p.sum(axis=1, keepdims=True), v)


def paged_state(ctx, block_size, per_seq, ring, seed):
    """Caches ``[layers, blocks, bs, kv_heads * d]`` holding every slot's
    positions ``0 .. ctx[b]`` written in order through its table (page
    ``(pos // bs) % per_seq`` when ``ring``), shuffled tables, and the
    slots' own key/value sequences."""
    rng = np.random.RandomState(seed)
    slots = len(ctx)
    n_blocks = 1 + slots * per_seq + 2
    tables = rng.permutation(np.arange(1, n_blocks))[
        :slots * per_seq].reshape(slots, per_seq).astype(np.int32)
    row = KV_HEADS * HEAD_DIM
    caches = rng.standard_normal((2, LAYERS, n_blocks, block_size, row))
    seqs = []
    for b in range(slots):
        n = ctx[b] + 1
        kv = rng.standard_normal((2, n, KV_HEADS, HEAD_DIM))
        seqs.append(kv)
        for pos in range(n):
            page = pos // block_size
            page = page % per_seq if ring else page
            caches[:, 1, tables[b, page], pos % block_size] = \
                kv[:, pos].reshape(2, row)
    return caches.astype(np.float32), tables, seqs


@pytest.mark.parametrize("pages", [1, 2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_grouped_heads_over_the_whole_context(dtype, pages):
    bs, per_seq = 8, 6
    ctx = np.array([0, 1, 7, 8, 9, 47, 30], np.int32)
    caches, tables, seqs = paged_state(ctx, bs, per_seq, False, 1)
    q = np.random.RandomState(2).standard_normal(
        (len(ctx), HEADS * HEAD_DIM)).astype(np.float32)
    got = paged_decode_attention(
        jnp.asarray(q, dtype), jnp.asarray(caches[0], dtype),
        jnp.asarray(caches[1], dtype), tables, ctx, layer=1,
        num_heads=HEADS, pages_per_step=pages, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for b, (keys, values) in enumerate(seqs):
        want = plain(q[b].reshape(HEADS, HEAD_DIM), keys, values, 0)
        np.testing.assert_allclose(
            np.asarray(got[b], np.float32).reshape(HEADS, HEAD_DIM), want,
            atol=tol, rtol=tol)


@pytest.mark.parametrize("window,block_size", [(128, 64), (100, 64), (8, 8),
                                               (13, 8), (5, 8)])
def test_window_over_a_ring_that_wrapped(window, block_size):
    """Contexts shorter than the window, at its edge, past it and past
    several wraps of the ring: exactly the last ``window`` positions are
    attended — one more or one fewer key shows."""
    per_seq = ring_pages(window, block_size)
    bs = block_size
    ctx = np.array([0, window - 2, window - 1, window, bs * per_seq - 1,
                    bs * per_seq, bs * per_seq + 1, 5 * bs * per_seq + 3,
                    3 * bs - 1, 3 * bs], np.int32)
    caches, tables, seqs = paged_state(ctx, bs, per_seq, True, 3)
    q = np.random.RandomState(4).standard_normal(
        (len(ctx), HEADS * HEAD_DIM)).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(caches[0]), jnp.asarray(caches[1]),
        tables, ctx, layer=1, num_heads=HEADS, window=window,
        pages_per_step=per_seq, interpret=True))
    for b, (keys, values) in enumerate(seqs):
        first = max(ctx[b] - window + 1, 0)
        want = plain(q[b].reshape(HEADS, HEAD_DIM), keys, values, first)
        np.testing.assert_allclose(got[b].reshape(HEADS, HEAD_DIM), want,
                                   atol=2e-5, rtol=2e-5)
        if first > 0:
            # 129 keys (one too many) is another answer
            wrong = plain(q[b].reshape(HEADS, HEAD_DIM), keys, values,
                          first - 1)
            assert np.abs(got[b].reshape(HEADS, HEAD_DIM) - wrong).max() \
                > 1e-4


def test_window_over_a_whole_context_table_reads_the_last_pages_only():
    """A window layer may also keep the whole context: pages below the
    window are not fetched (poisoned here), whatever the table's width."""
    bs, per_seq, window = 8, 8, 12
    ctx = np.array([63, 40, 5], np.int32)
    caches, tables, seqs = paged_state(ctx, bs, per_seq, False, 5)
    for b in range(len(ctx)):
        for page in range(max(ctx[b] - window + 1, 0) // bs):
            caches[:, 1, tables[b, page]] = np.nan
    q = np.random.RandomState(6).standard_normal(
        (len(ctx), HEADS * HEAD_DIM)).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(caches[0]), jnp.asarray(caches[1]),
        tables, ctx, layer=1, num_heads=HEADS, window=window,
        pages_per_step=2, interpret=True))
    for b, (keys, values) in enumerate(seqs):
        want = plain(q[b].reshape(HEADS, HEAD_DIM), keys, values,
                     max(ctx[b] - window + 1, 0))
        np.testing.assert_allclose(got[b].reshape(HEADS, HEAD_DIM), want,
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,block_size,pages", [
    (128, 64, 3), (129, 64, 3), (130, 64, 4), (64, 64, 2), (100, 64, 3),
    (1, 64, 2), (2, 8, 2), (8, 8, 2), (9, 8, 2), (10, 8, 3)])
def test_ring_pages_hold_the_window_and_the_page_being_written(
        window, block_size, pages):
    assert ring_pages(window, block_size) == pages
    # by exhaustion: the window ending at any position lies in distinct
    # ring pages
    for t in range(4 * pages * block_size):
        logical = {p // block_size for p in range(max(t - window + 1, 0),
                                                  t + 1)}
        assert len({p % pages for p in logical}) == len(logical)


def test_geometry_check_names_what_a_tpu_cannot_tile():
    check_gqa_tpu_geometry(8, 128, 64)
    with pytest.raises(ValueError, match="head_dim=64"):
        check_gqa_tpu_geometry(8, 64, 64)
    with pytest.raises(ValueError, match="kv_block_size=12"):
        check_gqa_tpu_geometry(8, 128, 12)
