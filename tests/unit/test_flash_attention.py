"""Flash-attention numerics vs the jnp reference (the reference repo's
strategy for kernel tests: compare fused kernel against a layer-by-layer
baseline with tolerances, ``tests/unit/test_cuda_forward.py:23``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import flash_attention as fa
from deepspeed_tpu.ops.transformer.attention import reference_attention
from deepspeed_tpu.ops.transformer.flash_attention import (
    flash_attention, flash_attention_forward, flash_self_attention)


@pytest.fixture(autouse=True)
def fresh_kernel_traces():
    """The kernel calls are traced once a geometry (``fa._fwd_kernels``,
    ``fa._bwd_kernels``): a test that replaces what a trace reads
    (``_keep_mask``, ``_ROW_BLOCK_BYTES``) neither meets nor leaves
    another's."""
    def clear():
        fa._fwd_kernels.clear_cache()
        fa._bwd_kernels.clear_cache()

    clear()
    yield
    clear()


def rand_qkv(b, s, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def padding_masks(b, s, lengths):
    """(kv_mask [b,s] 1/0, additive [b,1,1,s]) for per-row visible lengths."""
    kvm = np.zeros((b, s), np.float32)
    for i, n in enumerate(lengths):
        kvm[i, :n] = 1.0
    kvm = jnp.asarray(kvm)
    additive = (1.0 - kvm[:, None, None, :]) * -1e9
    return kvm, additive


def _bert(batch, seq, heads=16, d=64):
    return (batch, seq, heads, d)


@pytest.mark.parametrize("platform,q,k,data,kernel", [
    ("tpu", _bert(112, 128), None, 1, True),   # bert_large.seq128's step
    ("tpu", _bert(2, 128), None, 1, True),     # ...and its eval_batch rows
    ("tpu", _bert(1, 128), None, 1, False),    # a serving prefill: one row
    ("tpu", _bert(1, 256), None, 1, False),
    ("tpu", _bert(4, 256), None, 1, True),     # four rows of 256 a step
    ("tpu", _bert(3, 256), None, 1, True),     # three
    ("tpu", _bert(5, 256), None, 1, False),    # no 2, 3 or 4 rows divide 5
    ("tpu", _bert(4, 384), None, 1, False),    # blocks of 128: streamed
    ("tpu", _bert(32, 512), None, 1, True),    # bert_large.seq512, as before
    ("cpu", _bert(32, 512), None, 1, False),   # off the TPU: never the kernel
    ("cpu", _bert(112, 128), None, 1, False),
    ("tpu", _bert(8, 128, heads=25), None, 1, False),  # flattened layout
    ("tpu", _bert(8, 128, d=192), None, 1, False),     # key width 192
    ("tpu", _bert(32, 80), _bert(32, 512), 1, False),  # query-gathered layer
    ("tpu", _bert(32, 128), _bert(32, 512), 1, False),
    ("tpu", _bert(8, 128), None, 4, True),     # two rows a device
    ("tpu", _bert(4, 128), None, 4, False),    # one row a device
    ("tpu", _bert(6, 128), None, 4, True),     # 4 does not divide 6: whole
])
def test_dispatch_follows_platform_and_shape(monkeypatch, platform, q, k,
                                             data, kernel):
    """The one decision ``dot_product_attention`` takes from what it
    observes, at the benchmark cells' shapes (BERT-large: 16 heads of 64)
    and around them: from 512 the kernels; below, where the operands are
    in the projection's layout and a grid step can hold two or more of the
    device's batch rows."""
    from deepspeed_tpu.ops.transformer.attention import _use_pallas
    from deepspeed_tpu.parallel import make_mesh, mesh

    monkeypatch.setattr(mesh, "current_platform", lambda: platform)
    current = make_mesh({"data": data}) if data > 1 else None
    monkeypatch.setattr(mesh, "get_current_mesh", lambda: current)
    q = jax.ShapeDtypeStruct(q, jnp.bfloat16)
    k = q if k is None else jax.ShapeDtypeStruct(k, jnp.bfloat16)
    assert _use_pallas(q, k) is kernel


def test_self_attention_hands_the_kernel_the_fused_projection(monkeypatch):
    """``TransformerLayer``'s call: where the flash kernel runs it gets the
    [b, s, 3, h, d] projection whole, elsewhere its three slices go to
    ``dot_product_attention``; the result is the same either way."""
    from deepspeed_tpu.ops.transformer import attention
    from deepspeed_tpu.parallel import mesh

    qkv = jax.random.normal(jax.random.PRNGKey(23), (2, 512, 3, 2, 64))
    kvm, additive = padding_masks(2, 512, [512, 300])
    want = reference_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                               mask=additive, causal=True)
    off_tpu = attention.self_attention(qkv, key_padding_mask=kvm, causal=True)
    np.testing.assert_allclose(np.asarray(off_tpu), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    seen = []

    def kernel(qkv, **kw):
        seen.append(qkv.shape)
        return flash_self_attention(qkv, interpret=True, **kw)

    monkeypatch.setattr(mesh, "current_platform", lambda: "tpu")
    monkeypatch.setattr(mesh, "get_current_mesh", lambda: None)
    monkeypatch.setattr(fa, "flash_self_attention", kernel)
    on_tpu = attention.self_attention(qkv, key_padding_mask=kvm, causal=True)
    assert seen == [qkv.shape]
    np.testing.assert_allclose(np.asarray(on_tpu), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# Operand layouts (``flash_attention._Operands``): two 64-wide heads a
# 128-lane block, one 128-wide head a block — and, by ``BLOCKS``, streamed
# over several k blocks or one tile (forward + the fused backward).
SHAPES = [(4, 64), (2, 128)]
BLOCKS = [128, None]
shapes = pytest.mark.parametrize("h,d", SHAPES, ids=["h4-d64", "h2-d128"])
blocks = pytest.mark.parametrize("block", BLOCKS, ids=["streamed", "tile"])
entries = pytest.mark.parametrize("entry", ["qkv", "fused"])


def attend(entry, q, k, v, **kw):
    """``flash_attention`` on q, k, v, or ``flash_self_attention`` on the
    fused projection they are the thirds of."""
    if entry == "qkv":
        return flash_attention(q, k, v, interpret=True, **kw)
    return flash_self_attention(jnp.stack([q, k, v], axis=2), interpret=True,
                                **kw)


def assert_grads_match(loss_flash, loss_ref, q, k, v):
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")
    return g_flash


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [256, 384])
@shapes
@blocks
def test_flash_forward_matches_reference(causal, s, h, d, block):
    q, k, v = rand_qkv(2, s, h, d)
    out_ref = reference_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@shapes
@blocks
@entries
def test_flash_backward_matches_reference(causal, h, d, block, entry):
    q, k, v = rand_qkv(1, 256, h, d, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(attend(entry, q, k, v, causal=causal, block_q=block,
                              block_k=block) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    assert_grads_match(loss_flash, loss_ref, q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@shapes
@blocks
def test_flash_key_padding_mask_forward(causal, h, d, block):
    b, s = 2, 256
    q, k, v = rand_qkv(b, s, h, d, seed=5)
    kvm, additive = padding_masks(b, s, [200, 131])
    out_ref = reference_attention(q, k, v, mask=additive, causal=causal)
    out = flash_attention(q, k, v, kv_mask=kvm, causal=causal, block_q=block,
                          block_k=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@shapes
@blocks
@entries
def test_flash_key_padding_mask_backward(causal, h, d, block, entry):
    b, s = 2, 256
    q, k, v = rand_qkv(b, s, h, d, seed=7)
    kvm, additive = padding_masks(b, s, [256, 77])

    def loss_flash(q, k, v):
        return jnp.sum(attend(entry, q, k, v, kv_mask=kvm, causal=causal,
                              block_q=block, block_k=block) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, mask=additive,
                                           causal=causal) ** 2)

    g_flash = assert_grads_match(loss_flash, loss_ref, q, k, v)
    # masked keys must receive exactly zero dK/dV
    for g, name in zip(g_flash[1:], "kv"):
        masked_part = np.asarray(g)[1, 77:]
        np.testing.assert_array_equal(masked_part, 0.0,
                                      err_msg=f"d{name} leak into padding")


@shapes
@blocks
@entries
def test_flash_fully_masked_row_is_zero(h, d, block, entry):
    """A sequence whose every key is padded out must yield zero output and
    zero gradients (not NaN/garbage from an all-NEG_INF softmax)."""
    b, s = 2, 256
    q, k, v = rand_qkv(b, s, h, d, seed=9)
    kvm, _ = padding_masks(b, s, [128, 0])
    out = attend(entry, q, k, v, kv_mask=kvm, block_q=block, block_k=block)
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[1], 0.0)

    def loss(q, k, v):
        return jnp.sum(attend(entry, q, k, v, kv_mask=kvm, block_q=block,
                              block_k=block) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g, name in zip(grads, "qkv"):
        g = np.asarray(g)
        assert np.all(np.isfinite(g)), f"d{name} not finite"
        np.testing.assert_array_equal(g[1], 0.0,
                                      err_msg=f"d{name} on masked batch row")


def test_fused_backward_without_the_resident_row_block(monkeypatch):
    """Past ``_ROW_BLOCK_BYTES`` a batch row's [s, 3·h·d] gradient block is
    not kept in VMEM: the fused backward writes dq, dk, dv apart and they
    are concatenated, to the same gradient."""
    qkv = jax.random.normal(jax.random.PRNGKey(29), (2, 256, 3, 4, 64))

    def loss(qkv):
        return jnp.sum(flash_self_attention(qkv, causal=True,
                                            interpret=True) ** 2)

    resident = jax.grad(loss)(qkv)
    monkeypatch.setattr(fa, "_ROW_BLOCK_BYTES", 0)
    np.testing.assert_array_equal(np.asarray(jax.grad(loss)(qkv)),
                                  np.asarray(resident))


@pytest.fixture
def layouts(monkeypatch):
    """The operand layout of every kernel geometry logged in the test."""
    seen = []
    monkeypatch.setattr(fa, "_log_geometry", lambda *a: seen.append(a[-1]))
    return seen


@pytest.mark.parametrize("h,d,layout", [
    (4, 64, "heads/block=2, projection layout"),
    (2, 128, "heads/block=1, projection layout"),
    (25, 64, "flattened"),   # gpt2_xl: an odd number of 64-wide heads
], ids=["h4-d64", "h2-d128", "h25-d64"])
def test_operand_layout_follows_heads_and_width(layouts, h, d, layout):
    """Which layout a shape takes, and that the one it falls back to still
    computes attention, forward and gradient, through either entry."""
    q, k, v = rand_qkv(1, 128, h, d, seed=13)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    for entry in ("qkv", "fused"):
        assert_grads_match(
            lambda q, k, v: jnp.sum(attend(entry, q, k, v, causal=True) ** 2),
            loss_ref, q, k, v)
    fused = layout if layout == "flattened" else layout + ", fused qkv"
    assert set(layouts) == {layout, fused}


def test_forward_at_key_width_192_value_width_128_stays_flattened(layouts):
    """The latent-attention prefill's widths: no whole number of 192-wide
    heads fills lane tiles, so this entry keeps [b·h, s, d] operands."""
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q, k = (jax.random.normal(key, (1, 256, 2, 192)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, 128))
    out = flash_attention_forward(q, k, v, causal=True, block_q=128,
                                  block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)
    assert layouts == ["flattened"]


@shapes
@blocks
@entries
def test_dropout_masks_are_seeded_by_batch_times_heads_plus_head(
        monkeypatch, h, d, block, entry):
    """The keep mask of head ``n`` of batch row ``r`` is drawn from the seed
    words ``(seed[0] ^ (r·h + n), seed[1] ^ tile)`` whatever the operand
    layout, forward and backward: the masks are the ones the kernels drew
    while every grid step held one head.  The hardware PRNG exists on a
    TPU only, so ``_keep_mask`` is replaced by a hash of the same
    arguments, and the kernels are compared with attention under the
    explicit mask that hash gives for head ``r·h + n``
    (``test_flash_dropout_matches_explicit_mask_reference`` checks the real
    draw on a chip)."""
    b, s, rate = 2, 256, 0.25
    blk = block or s
    _, inv_keep = fa._dropout_thresh(rate)

    def keep_of(head, j, kb, rows, cols):
        # a pure function of (head, tile): 3 of 4 kept
        return (head * 7 + j * 5 + kb * 3 + rows * 13 + cols * 11) % 4 != 0

    def fake_keep_mask(seed_ref, i, j, kb, shape, thresh):
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return keep_of(seed_ref[0] ^ i, j, kb, rows, cols)

    monkeypatch.setattr(fa, "_keep_mask", fake_keep_mask)
    seed = np.asarray([40, 0], np.int32)
    heads = (seed[0] ^ np.arange(b * h)).reshape(b, h, 1, 1)
    pos = np.arange(s)
    keep = keep_of(heads, pos[:, None] // blk, pos[None, :] // blk,
                   pos[:, None] % blk, pos[None, :] % blk)  # [b, h, s, s]
    q, k, v = rand_qkv(b, s, h, d, seed=19)

    def loss_ref(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        probs = jnp.where(keep, jax.nn.softmax(scores, axis=-1) * inv_keep, 0.0)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", probs, v) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(attend(entry, q, k, v, dropout_seed=jnp.asarray(seed),
                              dropout_rate=rate, block_q=block,
                              block_k=block) ** 2)

    np.testing.assert_allclose(float(loss_flash(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=1e-5)
    assert_grads_match(loss_flash, loss_ref, q, k, v)


def hashed_keep_mask(seed_ref, i, j, kb, shape, thresh):
    """Stand-in for ``_keep_mask`` off the TPU, as the test above has it: a
    pure function of the arguments the hardware PRNG is seeded by."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return ((seed_ref[0] ^ i) * 7 + j * 5 + kb * 3 + rows * 13
            + cols * 11) % 4 != 0


@pytest.mark.parametrize("rows,s,causal", [
    (1, 128, False), (2, 128, False), (4, 128, False), (8, 128, False),
    (2, 256, False), (4, 256, False),
    # a causal decoder trained under 512 (no cell: ROADMAP W4)
    (4, 128, True), (2, 256, True),
], ids=["rows1", "rows2", "rows4", "rows8", "rows2-s256", "rows4-s256",
        "rows4-causal", "rows2-s256-causal"])
@pytest.mark.parametrize("masked", [True, False], ids=["mask", "nomask"])
@pytest.mark.parametrize("dropout", [0.25, 0.0], ids=["dropout", "nodrop"])
@entries
def test_rows_a_step_equal_one_row_a_step(monkeypatch, rows, s, causal,
                                          masked, dropout, entry):
    """Where a batch row is one tile a grid step holds ``rows`` of them
    (``_Operands.tile``): output, logsumexp and every gradient are bit for
    bit those of one row a step — same tiles, same dropout seeds — and,
    without dropout, the reference's within the file's tolerance."""
    b, h, d = max(4, rows), 4, 64
    logged = []     # rows a step of every geometry logged
    monkeypatch.setattr(fa, "_log_geometry", lambda *a: logged.append(a[-3]))
    monkeypatch.setattr(fa, "_keep_mask", hashed_keep_mask)
    q, k, v = rand_qkv(b, s, h, d, seed=31)
    w = jax.random.normal(jax.random.PRNGKey(37), (b, s, h, d))
    kvm, additive = padding_masks(b, s, ([128, 77, 128, 100] * 2)[:b])
    if not masked:
        kvm = additive = None
    seed = jnp.asarray([40, 3], jnp.int32) if dropout else None

    def run(step_rows):
        monkeypatch.setattr(fa, "_STEP_ROWS", step_rows)
        if entry == "fused":
            qkv = jnp.stack([q, k, v], axis=2)
            _, res = fa._flash_fused_fwd(qkv, kvm, seed, causal, None, None,
                                         True, dropout)
        else:
            _, res = fa._flash_fwd(q, k, v, kvm, seed, causal, None, None,
                                   True, dropout)
        out, grads = jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * attend(
                entry, q, k, v, kv_mask=kvm, dropout_seed=seed,
                causal=causal, dropout_rate=dropout)),
            argnums=(0, 1, 2))(q, k, v)
        return (res[-2].reshape(b, s, h, d), res[-1].reshape(b * h, 1, s),
                out, *grads)

    one = run(s)
    assert set(logged) == {1}
    del logged[:]
    many = run(rows * s)
    assert set(logged) == {rows}
    for a, r in zip(many, one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    if not dropout:
        want = jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * reference_attention(
                q, k, v, mask=additive, causal=causal)),
            argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(many[2:], (want[0], *want[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=5e-4, rtol=5e-4)


def test_flash_dropout_zero_rate_identity():
    """rate=0 with a seed present must be the exact no-dropout program."""
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
               for _ in range(3))
    base = flash_attention(q, k, v, interpret=True)
    seeded = flash_attention(q, k, v, dropout_seed=jnp.asarray(5, jnp.int32),
                             dropout_rate=0.0, interpret=True)
    assert jnp.array_equal(base, seeded)


@pytest.mark.tpu
def test_flash_dropout_matches_explicit_mask_reference():
    """On-chip: assemble the kernel's regenerable keep masks with a probe
    kernel (same 2-word XOR-fold seeding as ``_keep_mask``), then check
    fwd/dq/dk/dv against a pure-jax attention using that exact mask
    (rel err < 1e-2)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.ops.transformer.flash_attention import (_auto_blocks,
                                                               _dropout_thresh)

    B, S, H, D, RATE = 2, 512, 4, 64, 0.3
    BQ, BK = _auto_blocks(S, S)
    thresh, inv = _dropout_thresh(RATE)
    rng = np.random.default_rng(0)
    q, k, v, w = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
                  for _ in range(4))
    seed = jnp.asarray(123, jnp.int32)

    def tile_kernel(seed_ref, o_ref):
        i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        tile = jnp.int32(j) * jnp.int32(1 << 15) + jnp.int32(kb)
        pltpu.prng_seed(seed_ref[0] ^ jnp.int32(i), seed_ref[1] ^ tile)
        bits = jax.lax.bitcast_convert_type(
            pltpu.prng_random_bits((BQ, BK)), jnp.uint32)
        o_ref[0] = (bits >= jnp.uint32(thresh)).astype(jnp.float32)

    bh = B * H
    M = pl.pallas_call(
        tile_kernel, grid=(bh, S // BQ, S // BK),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, BQ, BK), lambda i, j, kb: (i, j, kb)),
        out_shape=jax.ShapeDtypeStruct((bh, S, S), jnp.float32),
    )(jnp.asarray([123, 0], jnp.int32)).reshape(B, H, S, S)

    def ref_with_mask(q_, k_, v_):
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) / np.sqrt(D)
        A = M * jax.nn.softmax(s_, axis=-1) * inv
        return jnp.einsum("bhqk,bkhd->bqhd", A, v_)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    out_f = flash_attention(q, k, v, dropout_seed=seed, dropout_rate=RATE)
    assert rel(out_f, ref_with_mask(q, k, v)) < 1e-2
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, dropout_seed=seed, dropout_rate=RATE) * w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref_with_mask(*a) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert rel(a, b) < 1e-2
    # determinism + seed sensitivity
    again = flash_attention(q, k, v, dropout_seed=seed, dropout_rate=RATE)
    assert jnp.array_equal(out_f, again)
    other = flash_attention(q, k, v, dropout_seed=jnp.asarray(7, jnp.int32),
                            dropout_rate=RATE)
    assert not jnp.array_equal(out_f, other)




@pytest.mark.parametrize("dims", [{"data": 4}, {"data": 2, "model": 2}],
                         ids=["data4", "data2-model2"])
@entries
def test_kernel_call_is_sharded_over_the_mesh(dims, entry):
    """XLA cannot partition a Mosaic kernel call, so the dispatch wraps it
    in a shard_map over every mesh axis not manual yet: each device runs
    the kernel on its own batch (and head) shard, forward and backward —
    at the top level of a GSPMD step and nested inside a shard_map that is
    manual over ``data`` alone, as the engine's bucketed exchange is."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.transformer.attention import shard_kernel_over_mesh
    from deepspeed_tpu.parallel import make_mesh
    from deepspeed_tpu.parallel.mesh import (get_current_mesh,
                                             set_current_mesh)

    b, s, h = 8, 128, 4
    q, k, v = rand_qkv(b, s, h, 64, seed=21)
    kvm, additive = padding_masks(b, s, [128, 100] * (b // 2))
    seen = []

    def kernel(q, k, v, mask, seed):
        # through the fused entry ``q`` is the whole [b, s, 3, h, d] shard
        seen.append((q.shape[:2] + q.shape[-2:], mask.shape))
        if entry == "fused":
            assert k is None and v is None and q.shape[2] == 3
            return flash_self_attention(q, kv_mask=mask, causal=True,
                                        interpret=True)
        return flash_attention(q, k, v, kv_mask=mask, causal=True,
                               interpret=True)

    def loss(q, k, v, mask):
        if entry == "fused":
            out = shard_kernel_over_mesh(kernel, jnp.stack([q, k, v], axis=2),
                                         kv_mask=mask)
        else:
            out = shard_kernel_over_mesh(kernel, q, k, v, kv_mask=mask)
        return jnp.sum(out ** 2)

    want, g_want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, mask=additive, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    mesh, before = make_mesh(dims), get_current_mesh()
    set_current_mesh(mesh)
    try:
        top = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
            q, k, v, kvm)

        def nested(q, k, v, mask):
            def body(q, k, v, mask):
                val, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q, k, v, mask)
                return jax.lax.psum(val, "data"), g

            rows = P("data")
            return shard_map(body, mesh=mesh, in_specs=(rows,) * 4,
                             out_specs=(P(), (rows,) * 3),
                             axis_names={"data"}, check_vma=False)(
                                 q, k, v, mask)

        inner = jax.jit(nested)(q, k, v, kvm)
    finally:
        set_current_mesh(before)
    shard = (b // dims["data"], s, h // dims.get("model", 1), 64)
    assert set(seen) == {(shard, (shard[0], s))}
    for got, g_got in (top, inner):
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        for a, r, name in zip(g_got, g_want, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name} mismatch")
