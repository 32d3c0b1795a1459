"""Compiled (non-interpret) kernel numerics on a real TPU chip.

The CI suite runs the same numerics in interpret mode on CPU; a Mosaic
lowering/layout regression would surface there only as a bench failure.
This module is the cheap on-chip gate: ``DS_TEST_TPU=1 python -m pytest
-m tpu`` runs every kernel compiled on the real chip in a couple of
minutes (PERF.md methodology).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.attention import reference_attention
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.paged_attention import (
    paged_decode_attention)

from .test_paged_attention import oracle as paged_oracle
from .test_paged_attention import slot_state

pytestmark = pytest.mark.tpu


@pytest.fixture(autouse=True)
def _full_matmul_precision():
    """fp32 operands otherwise run the MXU at reduced (bf16-passes)
    precision on TPU, drowning kernel-vs-reference comparisons in matmul
    noise that has nothing to do with the kernels."""
    with jax.default_matmul_precision("float32"):
        yield


def rand_qkv(b, s, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_compiled_flash_forward(causal):
    q, k, v = rand_qkv(2, 512, 4, 64)
    out = flash_attention(q, k, v, causal=causal)
    out_ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4, rtol=2e-4)


def test_compiled_flash_backward():
    q, k, v = rand_qkv(1, 512, 2, 64, seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_compiled_flash_key_padding_mask():
    b, s = 2, 512
    q, k, v = rand_qkv(b, s, 2, 64, seed=5)
    kvm = np.zeros((b, s), np.float32)
    kvm[0, :400] = 1.0
    kvm[1, :137] = 1.0
    kvm = jnp.asarray(kvm)
    additive = (1.0 - kvm[:, None, None, :]) * -1e9
    out = flash_attention(q, k, v, kv_mask=kvm)
    out_ref = reference_attention(q, k, v, mask=additive)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4, rtol=2e-4)


def test_compiled_flash_streamed_kv():
    """Multi-k-block (streamed VMEM scratch) path: kv 2048 with 512 blocks."""
    q, k, v = rand_qkv(1, 2048, 2, 64, seed=7)
    out = flash_attention(q, k, v, block_q=512, block_k=512)
    out_ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4, rtol=2e-4)


def test_compiled_flash_dropout_deterministic_and_unbiased():
    """In-kernel hardware-PRNG dropout compiles, regenerates bit-identical
    masks across calls, varies with the seed, and keeps the output mean
    near the no-dropout mean (inverse-keep scaling)."""
    q, k, v = rand_qkv(2, 512, 4, 64, seed=9)
    seed = jnp.asarray([42, 7], jnp.int32)
    a = flash_attention(q, k, v, dropout_seed=seed, dropout_rate=0.25)
    b = flash_attention(q, k, v, dropout_seed=seed, dropout_rate=0.25)
    assert jnp.array_equal(a, b)
    c = flash_attention(q, k, v, dropout_seed=jnp.asarray([43, 7], jnp.int32),
                        dropout_rate=0.25)
    assert not jnp.array_equal(a, c)
    base = flash_attention(q, k, v)
    # dropout is unbiased in expectation; at this tile count the mean of
    # |out| stays within a few percent
    ratio = float(jnp.mean(jnp.abs(a)) / jnp.mean(jnp.abs(base)))
    assert 0.85 < ratio < 1.25, ratio


def test_compiled_block_sparse_kernel():
    """LUT-driven block-sparse flash kernel compiled on-chip vs the
    gather-based reference implementation."""
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, block_sparse_attention,
        flash_block_sparse_attention)

    b, s, h, d = 1, 1024, 4, 64
    cfg = BigBirdSparsityConfig(num_heads=h, block=128,
                                num_random_blocks=1,
                                num_sliding_window_blocks=3,
                                num_global_blocks=1)
    layout = cfg.make_layout(s)
    q, k, v = rand_qkv(b, s, h, d, seed=11)
    out = flash_block_sparse_attention(q, k, v, layout)
    out_ref = block_sparse_attention(q, k, v, layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4, rtol=2e-4)

    def loss_k(q, k, v):
        return jnp.sum(flash_block_sparse_attention(q, k, v, layout) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout) ** 2)

    g = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for gk, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("slots,block_size", [(16, 64), (2, 16)])
def test_compiled_paged_decode_attention(slots, block_size, dtype, tol):
    """The decode kernel at GPT-2-large width (20 heads of 64), compiled:
    mixed context lengths, shuffled tables, two dead slots on the null
    block, against the full-table gather."""
    heads, hidden, layers, per_seq = 20, 1280, 2, 16
    dead = (1, 3) if slots > 3 else ()
    n_blocks, tables, ctx = slot_state(slots, block_size, dead, seed=2,
                                       max_blocks=per_seq)
    keys = jax.random.split(jax.random.PRNGKey(slots), 3)
    shape = (layers, n_blocks, block_size, hidden)
    k_cache = jax.random.normal(keys[0], shape, dtype)
    v_cache = jax.random.normal(keys[1], shape, dtype)
    q = jax.random.normal(keys[2], (slots, hidden), dtype)
    got = paged_decode_attention(q, k_cache, v_cache, tables, ctx, layer=1,
                                 num_heads=heads)
    want = paged_oracle(q, k_cache, v_cache, 1, tables, ctx, heads=heads)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
