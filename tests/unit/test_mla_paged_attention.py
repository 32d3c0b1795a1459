"""The latent paged decode kernel against plain ``jnp`` through Pallas'
interpreter: dead slots, a partly filled last page, one slot far longer
than the rest, one and two pages a step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.mla_paged_attention import (
    check_tpu_geometry, mla_paged_decode_attention, padded_row_width)

LAYERS, BLOCK, ROW, VALUE = 2, 8, 256, 128


def _case(ctx_lens, heads, blocks_per_seq, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    slots = len(ctx_lens)
    n_blocks = 1 + slots * blocks_per_seq
    cache = rng.standard_normal((LAYERS, n_blocks, BLOCK, ROW)).astype(
        np.float32)
    q = rng.standard_normal((slots, heads, ROW)).astype(np.float32)
    # every slot owns distinct blocks, in a shuffled order; dead slots
    # point at the null block
    ids = rng.permutation(np.arange(1, n_blocks)).reshape(
        slots, blocks_per_seq)
    tables = np.where(np.asarray(ctx_lens)[:, None] >= 0, ids, 0)
    dead = [i for i, c in enumerate(ctx_lens) if c < 0]
    tables[dead] = 0
    ctx = np.maximum(np.asarray(ctx_lens), 0).astype(np.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(cache, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(ctx))


def _plain(q, cache, tables, ctx, layer, scale):
    q, cache = np.asarray(q, np.float64), np.asarray(cache, np.float64)
    out = np.zeros(q.shape[:2] + (VALUE,))
    for b in range(q.shape[0]):
        rows = cache[layer, np.asarray(tables[b])].reshape(-1, ROW)
        rows = rows[:int(ctx[b]) + 1]
        s = q[b] @ rows.T * scale
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[b] = (p / p.sum(axis=-1, keepdims=True)) @ rows[:, :VALUE]
    return out


@pytest.mark.parametrize("pages", [1, 2])
@pytest.mark.parametrize("ctx_lens,heads", [
    ([0, 5, 7, 8, 20], 8),          # first token, page edges, partly filled
    ([3, -1, 61, -1, 9, 2], 4),     # dead slots; one slot far longer
    ([63, 62, 0], 16),              # a full table
])
def test_kernel_matches_plain_attention(ctx_lens, heads, pages):
    q, cache, tables, ctx = _case(ctx_lens, heads, blocks_per_seq=8)
    scale = 0.11
    for layer in range(LAYERS):
        got = mla_paged_decode_attention(
            q, cache, tables, ctx, layer=layer, value_width=VALUE,
            scale=scale, pages_per_step=pages, interpret=True)
        want = _plain(q, cache, tables, ctx, layer, scale)
        live = [i for i, c in enumerate(ctx_lens) if c >= 0]
        np.testing.assert_allclose(np.asarray(got)[live], want[live],
                                   rtol=2e-4, atol=2e-4)


def test_bf16_pages_accumulate_in_fp32():
    q, cache, tables, ctx = _case([17, 40], 8, blocks_per_seq=8,
                                  dtype=jnp.bfloat16)
    got = mla_paged_decode_attention(
        q, cache, tables, ctx, layer=1, value_width=VALUE, scale=0.05,
        interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _plain(q.astype(jnp.float32), cache.astype(jnp.float32), tables,
                  ctx, 1, 0.05)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("row,value,block,ok", [
    (640, 512, 64, True), (576, 512, 64, False), (640, 500, 64, False),
    (640, 512, 12, False)])
def test_geometry_check(row, value, block, ok):
    if ok:
        check_tpu_geometry(row, value, block)
    else:
        with pytest.raises(ValueError, match="cannot tile"):
            check_tpu_geometry(row, value, block)
    assert padded_row_width(576) == 640 and padded_row_width(640) == 640
