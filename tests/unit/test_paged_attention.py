"""The paged decode-attention kernel and the one-scatter append, on the
CPU through Pallas' interpreter, against a plain gather +
``reference_attention`` oracle written here: the full-table gather the
decode program used to run."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import NULL_BLOCK, DeepSpeedInferenceConfig
from deepspeed_tpu.inference.kv_cache import init_kv_cache
from deepspeed_tpu.inference.model import build_decode
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadTPU
from deepspeed_tpu.ops.transformer.attention import reference_attention
from deepspeed_tpu.ops.transformer.paged_attention import (
    check_tpu_geometry, paged_decode_attention)

HEADS, HEAD_DIM, LAYERS = 4, 16, 2
HIDDEN = HEADS * HEAD_DIM
MAX_BLOCKS = 4          # blocks per sequence: max_seq = 4 * block_size


def oracle(q, k_cache, v_cache, layer, tables, ctx_lens, heads=HEADS):
    """Gather every slot's whole table, mask past ``ctx_len``, attend."""
    slots, hidden = q.shape
    k = jnp.take(k_cache[layer], tables, axis=0).reshape(
        slots, -1, heads, hidden // heads)
    v = jnp.take(v_cache[layer], tables, axis=0).reshape(
        slots, -1, heads, hidden // heads)
    visible = jnp.arange(k.shape[1])[None, :] <= ctx_lens[:, None]
    mask = jnp.where(visible, 0.0, -1e9)[:, None, None, :]
    ctx = reference_attention(
        q.reshape(slots, 1, heads, hidden // heads), k, v, mask=mask)
    return ctx.reshape(slots, hidden)


def slot_state(slots, block_size, dead, seed, max_blocks=MAX_BLOCKS):
    """Context lengths cycling through {0, 1, bs-1, bs, bs+1, max_seq-1}
    over shuffled, non-contiguous block tables; the slots in ``dead`` sit
    on the null block at context 0 as the engine parks them."""
    rng = np.random.RandomState(seed)
    max_seq = max_blocks * block_size
    lens = [0, 1, block_size - 1, block_size, block_size + 1, max_seq - 1]
    ctx = np.array([lens[(b + seed) % len(lens)] for b in range(slots)],
                   np.int32)
    n_blocks = 1 + slots * max_blocks + 3
    tables = rng.permutation(np.arange(1, n_blocks))[
        :slots * max_blocks].reshape(slots, max_blocks).astype(np.int32)
    for b in dead:
        tables[b] = NULL_BLOCK
        ctx[b] = 0
    return n_blocks, tables, ctx


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dead", [(), (1,), (0, 2)],
                         ids=["all-live", "one-dead", "two-dead"])
@pytest.mark.parametrize("slots", [1, 16])
@pytest.mark.parametrize("block_size", [16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_kernel_matches_gather_oracle(dtype, block_size, slots, dead):
    dead = tuple(b for b in dead if b < slots)
    n_blocks, tables, ctx = slot_state(slots, block_size, dead, seed=slots)
    keys = jax.random.split(jax.random.PRNGKey(block_size + slots), 3)
    shape = (LAYERS, n_blocks, block_size, HIDDEN)
    k_cache = jax.random.normal(keys[0], shape, dtype)
    v_cache = jax.random.normal(keys[1], shape, dtype)
    q = jax.random.normal(keys[2], (slots, HIDDEN), dtype)
    got = paged_decode_attention(q, k_cache, v_cache, tables, ctx, layer=1,
                                 num_heads=HEADS, interpret=True)
    want = oracle(q, k_cache, v_cache, 1, tables, ctx)
    assert got.shape == (slots, HIDDEN) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_kernel_reads_live_blocks_only():
    """Poison (NaN) in every page past a slot's live blocks: none of
    them may be fetched, so the context stays finite and right."""
    bs, slots = 16, 3
    n_blocks, tables, ctx = slot_state(slots, bs, (), seed=1)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (LAYERS, n_blocks, bs, HIDDEN)
    k_cache = np.array(jax.random.normal(keys[0], shape, jnp.float32))
    v_cache = np.array(jax.random.normal(keys[1], shape, jnp.float32))
    q = jax.random.normal(keys[2], (slots, HIDDEN), jnp.float32)
    want = oracle(q, jnp.asarray(k_cache), jnp.asarray(v_cache), 0,
                  tables, ctx)
    for b in range(slots):
        live = ctx[b] // bs + 1
        for j in range(live, MAX_BLOCKS):
            k_cache[0, tables[b, j]] = np.nan
            v_cache[0, tables[b, j]] = np.nan
    got = paged_decode_attention(q, jnp.asarray(k_cache),
                                 jnp.asarray(v_cache), tables, ctx,
                                 layer=0, num_heads=HEADS, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- the decode step
def decode_geometry(slots, block_size):
    mc = GPT2Config(vocab_size=128, hidden_size=HIDDEN, num_layers=LAYERS,
                    num_heads=HEADS,
                    max_position_embeddings=MAX_BLOCKS * block_size,
                    embd_dropout=0.0, attn_dropout=0.0, resid_dropout=0.0)
    n_blocks = 1 + slots * MAX_BLOCKS + 3
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": block_size, "kv_blocks": n_blocks,
        "max_batch_slots": slots, "max_seq_len": MAX_BLOCKS * block_size,
        "prefill_buckets": [block_size], "token_budget": 4096}})
    return mc, icfg


@pytest.mark.parametrize("dead", [(), (1,), (0, 2)],
                         ids=["all-live", "one-dead", "two-dead"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("block_size,slots", [(16, 16), (64, 4), (16, 1)])
def test_decode_append_writes_only_its_rows(block_size, slots, dtype, dead):
    """One decode step: each live slot's new K/V row lands at
    ``(table[ctx_len // bs], ctx_len % bs)`` of every layer, and every
    other row of the cache — the untouched blocks whole — is
    bit-identical before and after.  Two dead slots scatter to the same
    row of the null block: any winner, it is scratch."""
    dead = tuple(b for b in dead if b < slots)
    mc, icfg = decode_geometry(slots, block_size)
    _, tables, ctx = slot_state(slots, block_size, dead, seed=3)
    params = GPT2LMHeadTPU(mc).init(jax.random.PRNGKey(1))
    if dtype == jnp.bfloat16:
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    k0, v0 = init_kv_cache(LAYERS, icfg.kv_blocks, block_size, HEADS,
                           HEAD_DIM, dtype)
    k0 = jax.random.normal(keys[0], k0.shape, dtype)
    v0 = jax.random.normal(keys[1], v0.shape, dtype)
    tokens = np.arange(slots, dtype=np.int32) + 5
    decode = jax.jit(build_decode(mc, icfg))
    nxt, k1, v1 = decode(params, k0, v0, tables, ctx, tokens)
    assert nxt.shape == (slots,)
    written = np.zeros(k0.shape[:3], bool)
    block_ids = tables[np.arange(slots), ctx // block_size]
    written[:, block_ids, ctx % block_size] = True
    for before, after in ((k0, k1), (v0, v1)):
        before, after = (np.asarray(a, np.float32) for a in (before, after))
        np.testing.assert_array_equal(after[~written], before[~written])
        live = [b for b in range(slots) if b not in dead]
        rows = after[:, block_ids[live], (ctx % block_size)[live]]
        old = before[:, block_ids[live], (ctx % block_size)[live]]
        assert np.isfinite(rows).all()
        # a projection of this step's hidden state, not the noise that
        # was there
        assert (np.abs(rows - old).max(axis=-1) > 0).all()
    # the appended rows are the ones the step attended to: a second
    # kernel call over the returned cache reads them back at ctx_len
    q = jax.random.normal(keys[0], (slots, HIDDEN), dtype)
    got = paged_decode_attention(q, k1, v1, tables, ctx, layer=0,
                                 num_heads=HEADS, interpret=True)
    want = oracle(q, k1, v1, 0, tables, ctx)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_decode_program_holds_no_full_table_gather(monkeypatch):
    """The ``serve_decode`` program as it is lowered FOR THE TPU (the
    kernel one custom call, not the interpreter's emulation of it) at a
    small geometry: one kernel call a layer, at most two scatter/update
    ops a layer (the appends), and no array with a per-slot
    ``max_seq_len`` dimension — the gathered context, the mask and the
    score tensor of the old program — so the gather cannot come back
    unnoticed on a CPU-only PR."""
    from deepspeed_tpu.inference import model as serve_model

    slots, block_size = 3, 8
    mc, icfg = decode_geometry(slots, block_size)
    max_seq = icfg.max_seq_len      # 32: no other size of this model
    assert max_seq not in (HIDDEN, 3 * HIDDEN, 4 * HIDDEN, mc.vocab_size,
                           icfg.kv_blocks, block_size, slots)
    monkeypatch.setattr(serve_model, "current_platform", lambda: "tpu")
    params = jax.eval_shape(
        lambda: GPT2LMHeadTPU(mc).init(jax.random.PRNGKey(0)))
    cache = jax.ShapeDtypeStruct(
        (LAYERS, icfg.kv_blocks, block_size, HIDDEN), jnp.float32)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = jax.jit(build_decode(mc, icfg), donate_argnums=(1, 2)).trace(
        params, cache, cache, ints(slots, MAX_BLOCKS), ints(slots),
        ints(slots)).lower(lowering_platforms=("tpu",)).as_text()
    # one kernel (the layer is a scalar operand), called once a layer
    assert text.count("@tpu_custom_call") == 1
    assert text.count("call @paged_decode_attention") == LAYERS
    updates = len(re.findall(
        r'= "?stablehlo\.(?:scatter|dynamic_update_slice)"?[ (]', text))
    assert 0 < updates <= 2 * LAYERS, updates
    shapes = {tuple(int(n) for n in dims.split("x"))
              for dims in re.findall(r"tensor<(\d+(?:x\d+)*)x[a-z]", text)}
    per_slot = sorted(shape for shape in shapes
                      if shape[0] == slots and max_seq in shape[1:])
    assert not per_slot, f"per-slot max_seq_len arrays: {per_slot}"
    # the old program's gathered context, had it stayed, would be here
    assert (slots, MAX_BLOCKS) in shapes and (slots, HIDDEN) in shapes


@pytest.mark.parametrize("hidden,block_size,ok", [
    (1280, 64, True),        # GPT-2-large, the benchmark's
    (1280, 8, True),
    (768, 16, True),
    (1280, 4, False),        # half a sublane tile
    (64, 64, False),         # half a lane row
    (1600, 64, False),       # GPT-2-xl: 12.5 lane rows
])
def test_tpu_geometry_check(hidden, block_size, ok):
    """What ``tests/unit/test_tpu_compile.py`` shows Mosaic takes and
    refuses; the engine raises it at construction on a TPU."""
    if ok:
        check_tpu_geometry(hidden, block_size)
    else:
        with pytest.raises(ValueError, match="cannot tile"):
            check_tpu_geometry(hidden, block_size)


def test_engine_refuses_untileable_geometry_on_tpu(monkeypatch):
    """On a TPU the engine raises at construction for a cache the kernel
    cannot tile — no silent second path; elsewhere the interpreter runs
    the same geometry (every other serving test)."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference import engine as serve_engine

    mc, icfg = decode_geometry(2, 8)
    model = GPT2LMHeadTPU(mc)
    params = model.init(jax.random.PRNGKey(0))
    config = {"inference": {
        "kv_block_size": 8, "kv_blocks": icfg.kv_blocks,
        "max_batch_slots": 2, "max_seq_len": icfg.max_seq_len,
        "prefill_buckets": [8], "token_budget": 64}}
    monkeypatch.setattr(serve_engine, "current_platform", lambda: "tpu")
    with pytest.raises(ValueError, match="heads\\*head_dim=64"):
        InferenceEngine(model, params, config=config)
