"""Sparse attention tests: layouts against pinned data + block-sparse
numerics vs dense attention (model: reference
``tests/unit/test_sparse_attention.py`` approach of checking against a
dense equivalent)."""

import math
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import (
    BertSparseSelfAttention, BigBirdSparsityConfig, BSLongformerSparsityConfig,
    DenseSparsityConfig, FixedSparsityConfig, SparseAttentionUtils,
    SparseSelfAttention, SparsityConfig, VariableSparsityConfig,
    block_sparse_attention, layout_gather_indices)

LAYOUTS_PATH = os.path.join(os.path.dirname(__file__), "baselines",
                            "sparse_layouts.npz")


@pytest.fixture(scope="module")
def pinned_layouts():
    with np.load(LAYOUTS_PATH) as f:
        return dict(f)


CASES = [
    ("dense", "DenseSparsityConfig", dict(num_heads=4, block=16)),
    ("fixed_bi", "FixedSparsityConfig",
     dict(num_heads=4, block=16, num_local_blocks=4, num_global_blocks=1)),
    ("fixed_uni", "FixedSparsityConfig",
     dict(num_heads=4, block=16, num_local_blocks=4, num_global_blocks=2,
          attention="unidirectional")),
    ("fixed_horiz", "FixedSparsityConfig",
     dict(num_heads=4, block=16, num_local_blocks=4, num_global_blocks=1,
          horizontal_global_attention=True)),
    ("fixed_perhead", "FixedSparsityConfig",
     dict(num_heads=4, block=16, num_local_blocks=4, num_global_blocks=1,
          different_layout_per_head=True, num_different_global_patterns=4)),
    ("variable", "VariableSparsityConfig",
     dict(num_heads=4, block=16, num_random_blocks=0,
          local_window_blocks=[2, 4], global_block_indices=[0, 5])),
    ("variable_span", "VariableSparsityConfig",
     dict(num_heads=4, block=16, num_random_blocks=0,
          global_block_indices=[0], global_block_end_indices=[2],
          horizontal_global_attention=True)),
    ("variable_uni", "VariableSparsityConfig",
     dict(num_heads=4, block=16, num_random_blocks=0,
          attention="unidirectional")),
    ("bigbird", "BigBirdSparsityConfig",
     dict(num_heads=4, block=16, num_random_blocks=1,
          num_sliding_window_blocks=3, num_global_blocks=1)),
    ("longformer", "BSLongformerSparsityConfig",
     dict(num_heads=4, block=16, num_sliding_window_blocks=3,
          global_block_indices=[0, 7])),
]


@pytest.mark.parametrize("name,cls,kwargs", CASES, ids=[c[0] for c in CASES])
@pytest.mark.parametrize("seq_len", [128, 256])
def test_layout_matches_reference(name, cls, kwargs, seq_len, pinned_layouts):
    """Byte-identical layouts vs ``baselines/sparse_layouts.npz``
    (randomness pinned by seeding python's `random`).  A REGRESSION PIN of
    what this implementation produced when the file was written, not a
    comparison with the reference: the reference tree this test used to
    execute is not on the machines that run the tests."""
    random.seed(1234)
    ours = getattr(
        __import__("deepspeed_tpu.ops.sparse_attention", fromlist=[cls]),
        cls)(**kwargs).make_layout(seq_len)
    pinned = pinned_layouts[f"{seq_len}-{name}"]
    assert ours.shape == pinned.shape
    assert (ours == pinned).all(), (
        f"{name}: layouts differ in {(ours != pinned).sum()} cells")


def test_layout_validation_errors():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, num_local_blocks=4,
                            num_global_blocks=3)
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, num_different_global_patterns=2)
    with pytest.raises(ValueError):
        SparsityConfig(num_heads=2, block=16).setup_layout(100)
    with pytest.raises(NotImplementedError):
        FixedSparsityConfig(num_heads=2, attention="diagonal")


def _dense_reference(q, k, v, layout, block, causal=False,
                     key_padding_mask=None):
    """Dense attention with the layout expanded to an element mask."""
    b, s, h, d = q.shape
    lay = np.asarray(layout)
    if lay.shape[0] == 1 and h > 1:
        lay = np.broadcast_to(lay, (h,) + lay.shape[1:])
    el = np.kron(lay, np.ones((block, block)))  # [h, s, s]
    mask = el.astype(bool)
    if causal:
        mask &= np.tril(np.ones((s, s), bool))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.asarray(mask)[None], scores, -1e9)
    if key_padding_mask is not None:
        scores = scores + jnp.asarray(key_padding_mask)[:, None, None, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


@pytest.mark.parametrize("cfg", [
    FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2),
    FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                        attention="unidirectional"),
    BigBirdSparsityConfig(num_heads=2, block=16, num_random_blocks=1,
                          num_sliding_window_blocks=3, num_global_blocks=1),
    BSLongformerSparsityConfig(num_heads=2, block=16,
                               num_sliding_window_blocks=3),
], ids=["fixed", "fixed_uni", "bigbird", "longformer"])
def test_block_sparse_matches_dense(cfg):
    random.seed(0)
    s, b, h, d = 128, 2, 2, 32
    layout = cfg.make_layout(s)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    causal = getattr(cfg, "attention", "bidirectional") == "unidirectional"

    out = block_sparse_attention(q, k, v, layout, causal=causal)
    ref = _dense_reference(q, k, v, layout, cfg.block, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_block_sparse_gradients_match_dense():
    random.seed(0)
    s, b, h, d = 64, 1, 2, 16
    cfg = FixedSparsityConfig(num_heads=h, block=16, num_local_blocks=2)
    layout = cfg.make_layout(s)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)

    g1 = jax.grad(lambda q, k, v: jnp.sum(
        block_sparse_attention(q, k, v, layout) ** 2), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        _dense_reference(q, k, v, layout, cfg.block) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-5)


def test_block_sparse_key_padding_mask():
    random.seed(0)
    s, b, h, d = 64, 2, 2, 16
    cfg = BSLongformerSparsityConfig(num_heads=h, block=16)
    layout = cfg.make_layout(s)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    kpm = np.zeros((b, s), np.float32)
    kpm[:, 48:] = -1e9  # mask the tail

    out = block_sparse_attention(q, k, v, layout, key_padding_mask=kpm)
    ref = _dense_reference(q, k, v, layout, cfg.block, key_padding_mask=kpm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sparse_self_attention_module():
    random.seed(0)
    s, b, h, d = 64, 2, 4, 16
    attn = SparseSelfAttention(
        FixedSparsityConfig(num_heads=h, block=16, num_local_blocks=2),
        max_seq_length=128)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    kpm = np.ones((b, s), np.float32)  # 'mul' mode... default is add
    out = attn(q, k, v, key_padding_mask=kpm * 0.0)
    assert out.shape == (b, h, s, d)
    # layout caching: same object returned
    assert attn.get_layout(s) is attn.get_layout(s)
    # seq beyond master layout rejected
    with pytest.raises(ValueError):
        attn.get_layout(256)


def test_bert_sparse_self_attention():
    random.seed(0)

    class Cfg:
        hidden_size = 64
        num_attention_heads = 4
        initializer_range = 0.02

    layer = BertSparseSelfAttention(
        Cfg(), FixedSparsityConfig(num_heads=4, block=16, num_local_blocks=2))
    params = layer.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 64, 64)), jnp.float32)
    mask = np.ones((2, 64), np.float32)
    out = layer.apply(params, x, mask)
    assert out.shape == (2, 64, 64)
    assert np.isfinite(np.asarray(out)).all()


def test_pad_unpad_roundtrip():
    ids = np.arange(2 * 30, dtype=np.int32).reshape(2, 30)
    am = np.ones((2, 30), np.int32)
    pad_len, pids, pam, ptt, ppos, pemb = SparseAttentionUtils.pad_to_block_size(
        block_size=16, input_ids=ids, attention_mask=am, pad_token_id=9)
    assert pad_len == 2
    assert pids.shape == (2, 32) and int(pids[0, -1]) == 9
    assert pam.shape == (2, 32) and int(pam[0, -1]) == 0
    seq_out = np.zeros((2, 32, 8))
    unp = SparseAttentionUtils.unpad_sequence_output(pad_len, seq_out)
    assert unp.shape == (2, 30, 8)


def test_extend_position_embedding():
    table = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = SparseAttentionUtils.extend_position_embedding(table, 10)
    assert out.shape == (10, 3)
    np.testing.assert_allclose(np.asarray(out[4:8]), table)


def test_layout_gather_indices():
    layout = np.zeros((1, 4, 4), np.int64)
    layout[0, 0, 0] = 1
    layout[0, 2, [1, 3]] = 1
    idx, valid = layout_gather_indices(layout)
    assert idx.shape == (1, 4, 2)
    assert valid[0, 0].tolist() == [True, False]
    assert idx[0, 2].tolist() == [1, 3]
    assert valid[0, 1].tolist() == [False, False]


def test_fully_masked_rows_yield_zero():
    """Queries whose every key is padded out produce exactly zero output
    (and no NaN), matching the flash kernel's fully-masked-row contract."""
    rng = np.random.default_rng(0)
    b, s, h, d, blk = 2, 64, 2, 16, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
               for _ in range(3))
    layout = np.ones((1, s // blk, s // blk), np.int64)
    # batch 1: every key masked -> all rows fully masked
    kpm = np.zeros((b, s), np.float32)
    kpm[1, :] = -1e9
    out = block_sparse_attention(q, k, v, layout,
                                 key_padding_mask=jnp.asarray(kpm))
    out = np.asarray(out)
    assert np.isfinite(out).all(), "NaN/inf leaked from fully-masked rows"
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
    assert np.abs(out[0]).max() > 0


# ---------------------------------------------------------------------------
# Pallas LUT-driven block-sparse flash kernel (interpret mode) vs the
# gather-based reference implementation
# ---------------------------------------------------------------------------

def _rand_qkv(b, s, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32) for k in ks)


def _random_layout(h, nb, density=0.4, seed=0, diagonal=True):
    rng = np.random.default_rng(seed)
    layout = (rng.random((h, nb, nb)) < density).astype(np.int64)
    if diagonal:
        for hi in range(h):
            np.fill_diagonal(layout[hi], 1)
    return layout


def test_build_block_luts():
    from deepspeed_tpu.ops.sparse_attention import build_block_luts

    layout = np.zeros((1, 3, 3), np.int64)
    layout[0, 0, [0, 2]] = 1
    layout[0, 1, 1] = 1
    layout[0, 2, :] = 1
    lut, cnt, tlut, tcnt = build_block_luts(layout)
    assert cnt.tolist() == [[2, 1, 3]]
    assert lut[0, 0, :2].tolist() == [0, 2]
    # transpose: key block 0 is attended by q blocks 0 and 2
    assert tcnt.tolist() == [[2, 2, 2]]
    assert tlut[0, 0, :2].tolist() == [0, 2]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("per_head", [False, True])
def test_flash_block_sparse_matches_gather(causal, per_head):
    """LUT-driven Pallas kernel (interpret) == gather-based reference, fwd
    and grads, for a random irregular layout."""
    from deepspeed_tpu.ops.sparse_attention import (
        block_sparse_attention, flash_block_sparse_attention)

    b, s, h, d, nb = 2, 128, 2, 64, 4
    q, k, v = _rand_qkv(b, s, h, d, seed=11)
    layout = _random_layout(h if per_head else 1, nb, seed=5)

    out_ref = block_sparse_attention(q, k, v, layout, causal=causal)
    out = flash_block_sparse_attention(q, k, v, layout, causal=causal,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_block_sparse_attention(
            q, k, v, layout, causal=causal, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout,
                                              causal=causal) ** 2)

    g = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gk, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_block_sparse_bigbird_layout():
    """The BigBird config's layout runs through the kernel and matches the
    gather path (the reference's marquee sparse pattern)."""
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, block_sparse_attention,
        flash_block_sparse_attention)

    b, s, h, d = 1, 256, 4, 64
    cfg = BigBirdSparsityConfig(num_heads=h, block=32,
                                num_random_blocks=1, num_sliding_window_blocks=3,
                                num_global_blocks=1)
    layout = cfg.make_layout(s)
    q, k, v = _rand_qkv(b, s, h, d, seed=3)
    out_ref = block_sparse_attention(q, k, v, layout)
    out = flash_block_sparse_attention(q, k, v, layout, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


def test_build_super_luts():
    """2-D aggregation LUTs: super-tile activity, counts, and G·G-bit
    sub-block masks (bit = row_g·G + col_g)."""
    from deepspeed_tpu.ops.sparse_attention.flash_block_sparse import (
        build_super_luts)

    layout = np.zeros((1, 4, 4), np.int64)
    layout[0, 0, [0, 2]] = 1
    layout[0, 1, [1]] = 1
    layout[0, 2, [2, 3]] = 1
    layout[0, 3, [3]] = 1
    slut, scnt, smask, stlut, stcnt, stmask = build_super_luts(layout, G=2)
    # super tile (0,0) = rows {0,1} x cols {0,1}: (0,0) bit0, (1,1) bit3
    # super tile (0,1) = rows {0,1} x cols {2,3}: (0,2) bit0
    assert scnt[0, 0] == 2 and slut[0, 0, :2].tolist() == [0, 1]
    assert smask[0, 0, :2].tolist() == [0b1001, 0b0001]
    # super row 1 touches only super col 1: (2,2) b0, (2,3) b1, (3,3) b3
    assert scnt[0, 1] == 1 and slut[0, 1, 0] == 1
    assert smask[0, 1, 0] == 0b1011
    # transpose: super col 0 attended only by super row 0
    assert stcnt[0, 0] == 1 and stlut[0, 0, 0] == 0
    assert stmask[0, 0, 0] == 0b1001
    assert stcnt[0, 1] == 2 and stlut[0, 1, :2].tolist() == [0, 1]
    assert stmask[0, 1, :2].tolist() == [0b0001, 0b1011]


@pytest.mark.parametrize("q_agg", ["never", "auto", 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_block_sparse_q_agg_parity(q_agg, causal):
    """Aggregated (multi-row-per-tile) kernel == unaggregated == gather
    reference, fwd and grads — the masking must be exactly equivalent to
    running each layout row in its own tile."""
    from deepspeed_tpu.ops.sparse_attention import (
        block_sparse_attention, flash_block_sparse_attention)

    b, s, h, d, nb = 1, 256, 2, 64, 8
    q, k, v = _rand_qkv(b, s, h, d, seed=21)
    layout = _random_layout(h, nb, density=0.3, seed=13)

    out_ref = block_sparse_attention(q, k, v, layout, causal=causal)
    out = flash_block_sparse_attention(q, k, v, layout, causal=causal,
                                       interpret=True, q_agg=q_agg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_block_sparse_attention(
            q, k, v, layout, causal=causal, interpret=True,
            q_agg=q_agg) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout,
                                              causal=causal) ** 2)

    g = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gk, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch (q_agg={q_agg})")


def test_flash_block_sparse_empty_row_zero_output():
    """A query block with NO active key blocks must produce zero output
    (same contract as the gather implementation's fully-masked guard)."""
    from deepspeed_tpu.ops.sparse_attention import flash_block_sparse_attention

    b, s, h, d, nb = 1, 64, 1, 64, 4
    q, k, v = _rand_qkv(b, s, h, d, seed=9)
    layout = np.ones((1, nb, nb), np.int64)
    layout[0, 2, :] = 0  # q block 2 attends to nothing
    out = flash_block_sparse_attention(q, k, v, layout, interpret=True)
    blk = s // nb
    np.testing.assert_allclose(np.asarray(out[:, 2 * blk:3 * blk]), 0.0,
                               atol=1e-6)
    assert np.abs(np.asarray(out[:, :2 * blk])).max() > 0
