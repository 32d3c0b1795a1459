"""MiniCPM-SALA's serving pieces (``models/minicpm_sala.py``,
``ops/transformer/sparse_attention.py``, ``lightning_attention.py``) against
the plain reference, piece by piece at a tiny size on the CPU (the kernels run
through Pallas' interpreter): the cache groups and what the engine allocates
for them (a float32 state beside the serving dtype's pages), the chunked
lightning prefill and the one-token update against the literal recurrence, the
decode choice of blocks and the prefill mask against the reference's choice,
the compressed keys a decode step closes, a requeued request, the counters.
The logits through the engine against the reference's full forward, and the
planted faults, are ``tests/benchmarks/test_bench_minicpm_sala.py``."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala as ref
from deepspeed_tpu.inference import DeepSpeedInferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import NULL_BLOCK
from deepspeed_tpu.models.minicpm_sala import (LIGHTNING, SPARSE,
                                               MiniCPMSALAConfig,
                                               MiniCPMSALAForServing)
from deepspeed_tpu.ops.transformer import lightning_attention as lightning
from deepspeed_tpu.ops.transformer import sparse_attention as sparse

SPARSE_CONFIG = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
                     init_blocks=1, window_size=20, dense_len=96)
MODEL = dict(
    vocab_size=512, hidden_size=128, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    intermediate_size=256, lightning_nh=4, lightning_nkv=4,
    lightning_head_dim=32, mixer_types=[SPARSE] + [LIGHTNING] * 3,
    sparse_config=SPARSE_CONFIG, rope_theta=10000, dim_model_base=32,
    max_position_embeddings=1024, initializer_range=0.2)
# what the reference reads: the same numbers under the config.json's keys
REF = dict(MODEL, rms_norm_eps=1e-6, scale_emb=12, scale_depth=1.4,
           mup_denominator=32)

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 16, "kv_blocks": 3 * 16 + 1, "max_batch_slots": 3,
    "max_seq_len": 256, "prefill_buckets": [64, 192], "token_budget": 768,
    "max_new_tokens": 100, "weights_dtype": "float32"}}

GEOMETRY = sparse.SparseGeometry(**SPARSE_CONFIG)


def tiny_model(**changes):
    return MiniCPMSALAForServing(MiniCPMSALAConfig(**{**MODEL, **changes}))


def seeded(model, seed=1):
    leaves, tree = jax.tree_util.tree_flatten(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        jnp.ones(shape) if len(shape) == 1
        else 0.2 * jax.random.normal(key, shape)
        for key, shape in zip(keys, leaves)])


@pytest.fixture(scope="module")
def served():
    model = tiny_model()
    return model, seeded(model)


# -- the cache: pages, compressed keys, and a state that is not pages ----------

def test_the_cache_groups_are_pages_compressed_keys_and_a_float32_state():
    icfg = DeepSpeedInferenceConfig(ENGINE)
    serving = tiny_model().serving()
    kv, ckeys, state = serving.cache_groups(icfg)
    assert (kv.name, kv.layers, kv.pages, kv.dtype) == ("kv", 1, None, None)
    assert kv.buffers == {"k_cache": 64, "v_cache": 64}
    # a context of 256 closes (256 - 8) / 4 + 1 = 63 kernels: one page of 16
    # rows holds 16 of them
    assert (ckeys.name, ckeys.layers, ckeys.pages) == ("ckeys", 1, 4)
    assert ckeys.buffers == {"ck_cache": 64} and ckeys.dtype is None
    # 4 heads x 32 x 32 values are one block of 16 rows x 256
    assert (state.name, state.layers, state.pages, state.dtype) == (
        "state", 3, 1, "float32")
    assert state.buffers == {"state": 4 * 32 * 32 // 16}
    assert list(serving.cache_buffers(icfg)) == [
        "k_cache", "v_cache", "ck_cache", "state"]
    assert serving.build_decode(icfg).__name__ == "decode"
    assert serving.build_prefill(icfg, 64).__name__ == "prefill"
    # a model of one kind of layer names no group for the other
    only = tiny_model(mixer_types=[LIGHTNING] * 4).serving()
    assert [g.name for g in only.cache_groups(icfg)] == ["state"]


def test_the_published_state_is_exactly_one_block_of_64_rows_by_8192():
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 64, "kv_blocks": 64 * 304 + 1,
        "max_batch_slots": 64, "max_seq_len": 19456,
        "prefill_buckets": [16384], "token_budget": 64 * 19456,
        "max_new_tokens": 3072, "weights_dtype": "bfloat16"}})
    serving = MiniCPMSALAForServing(MiniCPMSALAConfig(
        num_hidden_layers=4)).serving()
    kv, ckeys, state = serving.cache_groups(icfg)
    assert (kv.layers, kv.num_blocks(icfg), kv.table_width(icfg)) == (
        1, 19457, 304)
    assert kv.buffers == {"k_cache": 256, "v_cache": 256}
    # (19456 - 32) / 16 + 1 = 1215 kernels in 19 pages of 64
    assert (ckeys.pages, ckeys.num_blocks(icfg)) == (19, 64 * 19 + 1)
    assert (state.layers, state.num_blocks(icfg), state.table_width(icfg),
            state.buffers) == (3, 65, 1, {"state": 8192})
    assert 64 * 8192 * 4 == 32 * 128 * 128 * 4 == 2_097_152
    # a decode step's choice holds top_k block numbers, or the 128 blocks of
    # a context that is still dense
    assert serving.geometry.decode_width(304) == 128


def test_the_engine_gives_the_state_a_dtype_of_its_own(served):
    model, params = served
    config = {**ENGINE, "inference": {**ENGINE["inference"],
                                      "weights_dtype": "bfloat16"}}
    engine = InferenceEngine(model, params, config=config)
    k, v, ck, state = engine._caches
    assert k.dtype == v.dtype == ck.dtype == jnp.bfloat16
    assert state.dtype == jnp.float32
    assert k.shape == (1, 49, 16, 64) and ck.shape == (1, 3 * 4 + 1, 16, 64)
    assert state.shape == (3, 3 + 1, 16, 256)
    assert engine.cache_block_bytes == {
        "k_cache": 16 * 64 * 2, "v_cache": 16 * 64 * 2,
        "ck_cache": 16 * 64 * 2, "state": 3 * 16 * 256 * 4}
    assert [a.pages_per_request for a in engine.allocators] == [None, 4, 1]
    engine.close()


# -- the lightning layer ---------------------------------------------------------

def _qkv(seq, heads=4, d=32, seed=0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), (seq, heads, d))
            for i in range(3)]


@pytest.mark.parametrize("true_len", [1, 31, 32, 33, 70, 96])
def test_the_chunked_prefill_is_the_recurrence(true_len):
    """Outputs at every prompt position and the state after the last one,
    for a prompt that ends at a chunk's first row, its last, inside one and
    at the bucket's end: the padding neither adds nor decays."""
    q, k, v = _qkv(96)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = ref.lightning_scan(
            q[:true_len], k[:true_len], v[:true_len], REF)
        o, s = lightning.lightning_prefill_scan(
            q.reshape(96, -1), k.reshape(96, -1), v.reshape(96, -1),
            true_len, heads=4, chunk=32, interpret=True)
    np.testing.assert_allclose(o[:true_len].reshape(true_len, 4, 32),
                               want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_the_decays_are_the_references():
    np.testing.assert_allclose(np.exp(-lightning.decay_slopes(32)),
                               ref.decays({"lightning_nh": 32}), rtol=1e-6)
    lam = ref.decays({"lightning_nh": 32})
    assert lam[0] == pytest.approx(math.exp(-2 ** -0.25))
    assert lam[31] == pytest.approx(math.exp(-2 ** -8))


def test_the_decode_update_rewrites_the_slots_blocks_in_place():
    state = lightning.lightning_prefill_scan(
        *(x.reshape(64, -1) for x in _qkv(64)), 64, heads=4, chunk=32,
        interpret=True)[1]
    block = lightning.state_to_block(state, 16)
    np.testing.assert_array_equal(lightning.block_to_state(block, 4, 32),
                                  state)
    cache = jnp.zeros((2, 5, 16, 256)).at[1, 3].set(block).at[
        1, 2].set(2 * block)
    ids = jnp.array([3, NULL_BLOCK, 2], jnp.int32)
    q, k, v = _qkv(3, seed=7)
    o, new = lightning.lightning_decode_update(q, k, v, cache, ids, layer=1,
                                               interpret=True)
    lam = ref.decays(REF)[:, None, None]
    for slot, scale in ((0, 1.0), (2, 2.0)):
        want = lam * (scale * state) + k[slot][:, :, None] \
            * v[slot][:, None, :]
        np.testing.assert_allclose(
            lightning.block_to_state(new[1, ids[slot]], 4, 32), want,
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            o[slot], jnp.einsum("hi,hij->hj", q[slot], want) / math.sqrt(32),
            rtol=1e-5, atol=1e-5)
    # the other layer and the blocks no slot names are as they were
    assert not np.asarray(new[0]).any() and not np.asarray(new[1, 1]).any()
    assert not np.asarray(new[1, 4]).any()


# -- the sparse layer's choice ------------------------------------------------------

def _choice_inputs(seq=240, seed=3):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (seq, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (seq, 2, 32))
    return q, k


def test_block_scores_take_the_kernels_that_overlap_a_block():
    """Block b of 16 tokens is overlapped by the kernels 4b-1 .. 4b+3 of 8
    keys every 4 (the published 64 / 32 / 16 have the same five)."""
    r = jnp.arange(1.0, 41.0)[None]          # 40 kernels, scores 1..40
    scores = np.asarray(GEOMETRY.block_scores(r, 10))[0]
    assert scores[0] == 4 and scores[1] == 8          # kernels 0-3; 3-7
    np.testing.assert_array_equal(scores, 4.0 * np.arange(1, 11))
    low_first = GEOMETRY.block_scores(-r + 41.0, 10)
    # descending scores: block b's best is the kernel before it, 4b - 1
    assert np.asarray(low_first)[0, 3] == 41 - 12
    assert sparse.SparseGeometry(32, 16, 64, 64, 1, 2048, 8192).reach == 1


@pytest.mark.parametrize("positions", [
    # kernel edges (t = 3, 7 mod 16), block edges (15 mod 16), the first
    # kernel (7), under and over top_k visible blocks, the context's end
    [0, 6, 7, 8, 15, 16, 95, 96, 97, 99, 103, 111, 112, 150, 191, 239]])
def test_the_decode_choice_is_the_references(positions):
    """Compressed keys in pages by a table, scored by the kernel, chosen by
    ``choose_decode_blocks``: the reference's chosen set at every position,
    whatever the pages' order in memory."""
    q, k = _choice_inputs()
    seq, bs, pages = 240, 16, 4
    ck = ref.compressed_keys(k, REF)                     # [59, 2, 32]
    rng = np.random.default_rng(0)
    table = rng.permutation(np.arange(1, 9))[:pages]
    cache = np.zeros((2, 9, bs, 64), np.float32)
    rows = np.zeros((pages * bs, 64), np.float32)
    rows[:ck.shape[0]] = np.asarray(ck).reshape(-1, 64)
    cache[1, table] = rows.reshape(pages, bs, 64)
    t = jnp.asarray(positions, jnp.int32)
    r = sparse.sparse_block_select(
        q[t], jnp.asarray(cache), jnp.tile(table, (len(positions), 1)), t,
        layer=1, kv_heads=2, geometry=GEOMETRY, interpret=True)
    chosen, counts = sparse.choose_decode_blocks(r, t, GEOMETRY, seq // bs)
    want, margin = ref.choose_blocks(q[t], ck, t, t + 1 > 96, REF, seq)
    for n, pos in enumerate(positions):
        if float(margin[n]) < 1e-4:
            continue                      # a near-tie: either choice stands
        for g in range(2):
            got = set(np.asarray(chosen[n, g, :int(counts[n, g])]).tolist())
            assert got == set(np.nonzero(np.asarray(want[g, n]))[0]), (pos, g)
        visible = pos // bs + 1
        assert int(counts[n, 0]) == (visible if pos + 1 <= 96
                                     else min(visible, 6))


def test_the_choice_forces_block_0_and_the_window_and_counts_them():
    q, k = _choice_inputs()
    ck = ref.compressed_keys(k, REF)
    t = jnp.asarray([239])
    want, _ = ref.choose_blocks(q[t], ck, t, jnp.asarray([True]), REF, 240)
    chosen = np.asarray(want)[:, 0]                       # [2, 15]
    assert chosen.sum(axis=1).tolist() == [6, 6]
    # block 0, and the blocks that meet positions 220..239: 13 and 14
    assert chosen[:, [0, 13, 14]].all()


def test_the_prefill_mask_is_the_references_choice():
    q, k = _choice_inputs(192)
    ck = sparse.compress_keys(k.reshape(192, -1), GEOMETRY)
    np.testing.assert_allclose(
        ck, np.asarray(ref.compressed_keys(k, REF)).reshape(-1, 64),
        rtol=1e-5, atol=1e-6)
    mask = sparse.prefill_block_mask(q, ck.reshape(-1, 2, 32), 150, GEOMETRY,
                                     kv_heads=2, row_block=64)
    t = jnp.arange(192)
    want, margin = ref.choose_blocks(q, ref.compressed_keys(k, REF), t,
                                     jnp.ones((192,), bool), REF, 192)
    decided = np.asarray(margin) >= 1e-4
    assert decided.sum() > 100
    np.testing.assert_array_equal(np.asarray(mask)[:, decided],
                                  np.asarray(want)[:, decided])
    # a prompt of at most dense_len chooses everything: causality masks
    assert np.asarray(sparse.prefill_block_mask(
        q, ck.reshape(-1, 2, 32), 96, GEOMETRY, kv_heads=2)).all()


def _top_k_set(scores, top):
    """The oracle: ``lax.top_k``'s ids whose values stand above -inf, as a
    boolean row."""
    values, ids = jax.lax.top_k(scores, min(top, scores.shape[-1]))
    rows = np.zeros(scores.shape, bool)
    np.put_along_axis(rows, np.asarray(ids), np.asarray(values) > -np.inf,
                      axis=-1)
    return rows


def _block_scores(kind, blocks, rows=24, seed=5):
    """``[2, rows, blocks]`` scores >= 0 of one kind of difficulty."""
    rng = np.random.default_rng(seed)
    s = rng.random((2, rows, blocks)).astype(np.float32)
    if kind == "tied":            # four values: every place is tied over
        s = np.round(s * 3) / 3
    elif kind == "coarse":        # the k-th place tied a few times
        s = np.round(s * 40) / 40
    elif kind == "zeros":         # columns of exact zeros, most of a row
        s[..., rng.random(blocks) < 0.7] = 0.0
    return s


@pytest.mark.parametrize("blocks", [15, 40, 160, 256, 304])
@pytest.mark.parametrize("kind", ["continuous", "tied", "coarse", "zeros"])
def test_the_chosen_set_is_top_ks(blocks, kind):
    """``top_blocks`` names ``lax.top_k``'s set, ties to the lower block: on
    raw scores, and under ``adjusted`` — rows that see 0, fewer than, exactly
    and more than ``top`` blocks, their forced blocks at +inf (with a window
    of 30 blocks more of them than ``top``).  Blocks 40 are fewer than the
    published ``top`` 64."""
    scores = _block_scores(kind, blocks)
    for top in (6, 64):
        np.testing.assert_array_equal(
            sparse.top_blocks(jnp.asarray(scores), min(top, blocks)),
            _top_k_set(scores, top))
    for window in (20, 480):
        g = sparse.SparseGeometry(**dict(SPARSE_CONFIG, window_size=window))
        # positions in blocks 0, 1, 4, 5 (exactly top), 6, ... and the last
        positions = jnp.asarray(sorted(
            {0, 15, 16, 79, 80, 95, 96, 16 * blocks - 1}
            | set(range(7, 16 * blocks, 16 * blocks // 15))))[:24]
        adjusted = g.adjusted(jnp.asarray(scores[:, :len(positions)]),
                              positions)
        want = _top_k_set(adjusted, g.topk)
        assert (np.isinf(np.asarray(adjusted)) & (np.asarray(adjusted) > 0)
                ).sum(-1).max() > (g.topk if window == 480 else 1)
        assert not want[:, 0, 1:].any() and want[:, 0, 0].all()
        np.testing.assert_array_equal(
            sparse.top_blocks(adjusted, min(g.topk, blocks)), want)


@pytest.mark.parametrize("blocks", [15, 40, 304])
@pytest.mark.parametrize("kind", ["continuous", "tied", "zeros"])
@pytest.mark.parametrize("window", [20, 480])
def test_the_decode_list_is_top_ks_set_in_block_order(blocks, kind, window):
    """``choose_decode_blocks``' first ``counts`` entries are ``lax.top_k``'s
    set over the same adjusted block scores, ascending and each once; the
    entries past a count lie inside the slot's table row."""
    config = dict(SPARSE_CONFIG, window_size=window, dense_len=32)
    g = sparse.SparseGeometry(**config)
    # [2, kernels], four kernels a block
    r = jnp.asarray(_block_scores(kind, 4 * blocks, rows=1)[:, 0])
    positions = jnp.asarray(sorted(
        {40, 47, 48, 79, 80, 95, 96, 111, 112, 16 * blocks - 1}
        & set(range(16 * blocks))) + list(range(133, 16 * blocks, 509)))
    r = jnp.where(jnp.arange(4 * blocks) < g.closed(positions)[:, None, None],
                  r[None], 0.0)
    chosen, counts = sparse.choose_decode_blocks(r, positions, g, blocks)
    chosen, counts = np.asarray(chosen), np.asarray(counts)
    want = _top_k_set(g.adjusted(g.block_scores(r, blocks),
                                 positions[:, None]), g.topk)
    assert chosen.shape[2] == g.decode_width(blocks)
    assert ((0 <= chosen) & (chosen < blocks)).all()
    for n, g_head in np.ndindex(*counts.shape):
        listed = chosen[n, g_head, :counts[n, g_head]].tolist()
        assert listed == np.nonzero(want[n, g_head])[0].tolist(), (n, g_head)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_choice_holds_no_sort_at_the_published_geometry(program):
    """``lax.top_k`` is a sort of every row on the TPU (a tenth of the
    long-document cell's prefill before PR 41): neither program's choice
    lowers to one."""
    g = sparse.SparseGeometry(32, 16, 64, 64, 1, 2048, 8192)
    if program == "prefill":
        text = jax.jit(functools.partial(
            sparse.prefill_block_mask, geometry=g, kv_heads=2)).lower(
                jax.ShapeDtypeStruct((16384, 32, 128), jnp.bfloat16),
                jax.ShapeDtypeStruct((1023, 2, 128), jnp.bfloat16),
                jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    else:
        text = jax.jit(functools.partial(
            sparse.choose_decode_blocks, geometry=g,
            blocks_per_seq=304)).lower(
                jax.ShapeDtypeStruct((64, 2, 1216), jnp.float32),
                jax.ShapeDtypeStruct((64,), jnp.int32)).as_text()
    assert "stablehlo.while" in text and "stablehlo.compare" in text
    assert "sort" not in text and "top_k" not in text


def test_sparse_prefill_attention_is_masked_attention():
    q, k = _choice_inputs(192)
    v = jax.random.normal(jax.random.PRNGKey(9), (192, 2, 32))
    mask = np.asarray(ref.choose_blocks(
        q, ref.compressed_keys(k, REF), jnp.arange(192),
        jnp.ones((192,), bool), REF, 192)[0])
    out = sparse.sparse_prefill_attention(
        q.reshape(192, -1), k.reshape(192, -1), v.reshape(192, -1),
        jnp.asarray(mask), kv_heads=2, block_size=16, block_q=64, block_k=32,
        interpret=True)
    keys = np.arange(192)
    allowed = mask[:, :, keys // 16] & (keys[None, None] <= keys[None, :,
                                                                 None])
    scores = np.einsum("tghd,sgd->gths", np.asarray(q).reshape(192, 2, 2, 32),
                       np.asarray(k)) / math.sqrt(32)
    scores = np.where(allowed[:, :, None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("gths,sgd->tghd", probs, np.asarray(v))
    np.testing.assert_allclose(out, want.reshape(192, -1), rtol=2e-4,
                               atol=2e-4)


# -- through the engine --------------------------------------------------------------

def _serve(model, params, requests, interrupt=None):
    """Serve ``requests`` and return each one's tokens; ``interrupt(engine,
    step)`` after every step."""
    engine = InferenceEngine(model, params, config=ENGINE)
    rids = [engine.submit(p, max_new_tokens=n) for p, n in requests]
    step, shares = 0, []
    while not engine.scheduler.idle() or engine._unread:
        engine.step()
        step += 1
        if engine.model_counters:
            shares.append(float(engine.model_counters["sparse_read_share"]))
        if interrupt is not None:
            interrupt(engine, step)
    tokens = [list(engine.request(rid).generated) for rid in rids]
    for allocator in engine.allocators:
        assert allocator.free_blocks == allocator.capacity
    counters = dict(engine.model_counters, least_read_share=min(shares))
    engine.close()
    return tokens, counters


def test_a_requeued_request_serves_the_same_tokens(served):
    """A request torn out of its slot mid-answer and queued again is
    prefilled afresh into another grant: its state block is overwritten,
    not inherited, and the tokens are the uninterrupted run's.  Two other
    requests decode beside it, at other lengths."""
    model, params = served
    rng = np.random.default_rng(4)
    requests = [(rng.integers(0, 512, size=n), new)
                for n, new in ((150, 24), (40, 30), (101, 20))]
    plain, counters = _serve(model, params, requests)

    def interrupt(engine, step):
        if step == 9:
            request = engine.request("req-0")
            assert request.state == "active" and request.generated
            engine.scheduler.abort(request)
            request.reset_for_requeue()
            engine.resubmit(request)

    again, _ = _serve(model, params, requests, interrupt)
    assert again == plain and [len(t) for t in plain] == [24, 30, 20]
    # the decode program's gauges: at most 6 pages read a (slot, KV head),
    # under the pages held once a context has outgrown them
    assert set(counters) == {"sparse_pages_read_mean",
                             "sparse_pages_live_mean", "sparse_read_share",
                             "least_read_share"}
    assert float(counters["sparse_pages_read_mean"]) <= 6.0
    assert 0.0 < counters["least_read_share"] < 0.8
    assert float(counters["sparse_read_share"]) <= 1.0


def test_the_decode_closes_a_compressed_key_every_stride(served):
    """After prefill and decode the request's pages of compressed keys hold
    the means of its cached keys' windows, the ones closed by decode steps
    (from the cache's last 8 rows) among them."""
    model, params = served
    engine = InferenceEngine(model, params, config=ENGINE)
    rng = np.random.default_rng(8)
    engine.submit(rng.integers(0, 512, size=37), max_new_tokens=30)
    for _ in range(20):
        engine.step()
    request = engine.scheduler.slots[0]
    context = len(request.prompt) + request.dispatched - 1   # rows cached
    k_cache, _, ck_cache, _ = engine._caches
    keys = np.asarray(k_cache[0, np.asarray(request.grants[0])]).reshape(
        -1, 64)[:context]
    closed = (context - 8) // 4 + 1
    want = np.stack([keys[4 * j:4 * j + 8].mean(axis=0)
                     for j in range(closed)])
    got = np.asarray(ck_cache[0, np.asarray(request.grants[1])]).reshape(
        -1, 64)[:closed]
    assert closed > (37 - 8) // 4 + 1             # decode closed some
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    engine.run()
    engine.close()


def test_a_dead_slots_table_points_every_group_at_the_null_block(served):
    model, params = served
    engine = InferenceEngine(model, params, config=ENGINE)
    engine.submit(np.arange(1, 30), max_new_tokens=4)
    engine.step()
    engine.step()
    kv, ck, state = engine._tables
    assert (kv[1:] == NULL_BLOCK).all() and (ck[1:] == NULL_BLOCK).all()
    assert (state[1:] == NULL_BLOCK).all() and state[0, 0] != NULL_BLOCK
    assert state.shape == (3, 1) and ck.shape == (3, 4)
    engine.run()
    engine.close()


def test_the_logits_carry_the_mup_divisor(served):
    """``logits = W_head RMSNorm(x) / (hidden_size / dim_model_base)``: the
    reference's, and 4 x smaller than without the divisor at this size."""
    model, params = served
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 128))
    with jax.default_matmul_precision("highest"):
        got = model.serving().logits(params, x)
        want = ref.rms_norm(params["final_norm"], x, 1e-6) \
            @ params["lm_head"]["kernel"] / ref.head_scale(REF)
        bare = tiny_model(dim_model_base=128).serving().logits(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(bare, 4.0 * np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert model.config.residual_scale == pytest.approx(1.4 / math.sqrt(32))


STATE_LIMIT = 1e-4


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_the_state_after_prefill_and_decode_is_the_recurrences(
        served, state_dtype, monkeypatch):
    """A prompt padded to its bucket, then 61 decode steps: the request's
    state block in every lightning layer is the reference's state after the
    same tokens, to 1e-4 of its largest value — and kept in bfloat16 (the
    planted fault) it is off by 100 times that."""
    from deepspeed_tpu.models import minicpm_sala as program
    model, params = served
    if state_dtype != "float32":
        groups = program.MiniCPMSALAServing.cache_groups
        monkeypatch.setattr(
            program.MiniCPMSALAServing, "cache_groups",
            lambda self, icfg: [g._replace(dtype=state_dtype)
                                if g.name == "state" else g
                                for g in groups(self, icfg)])
    engine = InferenceEngine(model, params, config=ENGINE)
    prompt = np.random.default_rng(6).integers(0, 512, size=45)
    engine.submit(prompt, max_new_tokens=90)
    for _ in range(61):
        engine.step()
    request = engine.scheduler.slots[0]
    # the prefill's token and one a decode: the state holds the prompt and
    # every token a decode took as its input
    assert request.dispatched == 62
    tokens = np.concatenate([prompt, request.generated[:61]])
    block = int(request.grants[2][0])
    got = [lightning.block_to_state(
        engine._caches[3][plane, block].astype(jnp.float32), 4, 32)
        for plane in range(3)]
    engine.run()
    engine.close()
    want = ref.states(params, jnp.asarray(tokens, jnp.int32), REF)
    worst = max(float(jnp.abs(got[plane] - want[layer]).max()
                      / jnp.abs(want[layer]).max())
                for plane, layer in enumerate((1, 2, 3)))
    print("state gap", state_dtype, worst)
    if state_dtype == "float32":
        assert worst <= STATE_LIMIT
    else:
        assert worst >= 100 * STATE_LIMIT
