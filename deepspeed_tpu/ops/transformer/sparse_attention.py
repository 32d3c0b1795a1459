"""Block-selected sparse attention for serving (InfLLM-V2 as MiniCPM4
publishes it): a query reads ``top_k`` pages of 64 keys, chosen from scores
over COMPRESSED keys (means of 32 keys every 16), instead of its whole
context (Pallas/Mosaic kernels and the choice between them).

Per KV head ``g`` and query position ``t`` (``models/minicpm_sala.py`` has the
layer; ``benchmarks/reference/minicpm_sala.py`` the plain form):

    r_j   = sum_{h in g} softmax_j(q^h . c_j / sqrt(d))     over the kernels closed at t
    R_b   = max of r_j over the kernels that overlap block b
    B_t   = the first blocks, the blocks that meet the last ``window`` positions,
            then the highest R_b of the rest, ``top_k`` in all

**Decode**: :func:`sparse_block_select` scores every slot's compressed keys
(read from their own pages, ``[block_size, kv_heads * d]`` rows addressed by
a table of their own) and returns ``r``; :func:`choose_decode_blocks` turns
it into ``top_k`` block numbers a (slot, KV head) on the device
(:func:`top_blocks`, then a compaction by counts: no sort, no host round
trip); :func:`sparse_paged_decode_attention` reads those pages only — each
page's lanes of ONE KV head, by the K/V block table, ``pages_per_step``
pages a product, the page that holds ``t`` masked past it — for the
``heads / kv_heads`` query heads that share the choice.
A context of at most ``dense_len`` tokens chooses every visible block: the
same kernel sweeps it.

**Prefill**: :func:`prefill_block_mask` makes the per-row choice for a whole
bucket (plain XLA over row blocks; the mask ``[kv_heads, seq, blocks]`` is
:func:`top_blocks`' own rows), and :func:`sparse_prefill_attention` is
flash attention under it: dense tiles,
each masked by the rows' chosen blocks (expanded to keys by one small
product a tile) and by causality, the ``heads / kv_heads`` query heads of a
group sharing a tile's K, V and mask.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MASK_VALUE = -1e30
_VMEM_LIMIT = 100 * 1024 * 1024


class SparseGeometry:
    """The seven numbers of a ``sparse_config`` and what follows from them
    for pages of ``block_size`` tokens."""

    def __init__(self, kernel_size, kernel_stride, block_size, topk,
                 init_blocks, window_size, dense_len):
        self.kernel_size, self.kernel_stride = kernel_size, kernel_stride
        self.block_size, self.topk = block_size, topk
        self.init_blocks, self.window_size = init_blocks, window_size
        self.dense_len = dense_len
        assert block_size % kernel_stride == 0
        # kernels that start in a block, and those of the block before
        # that reach into it
        self.per_block = block_size // kernel_stride
        self.reach = -(-kernel_size // kernel_stride) - 1
        assert self.reach <= self.per_block

    def _key(self):
        return (self.kernel_size, self.kernel_stride, self.block_size,
                self.topk, self.init_blocks, self.window_size,
                self.dense_len)

    def __eq__(self, other):
        return isinstance(other, SparseGeometry) \
            and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def closed(self, positions):
        """Compressed keys whose last key is at or before ``positions``."""
        return jnp.maximum(positions + 1 - self.kernel_size
                           + self.kernel_stride, 0) // self.kernel_stride

    def kernels(self, max_seq_len):
        """Compressed keys a context of ``max_seq_len`` can close."""
        return max((max_seq_len - self.kernel_size) // self.kernel_stride
                   + 1, 0)

    def decode_width(self, blocks_per_seq):
        """Block numbers a decode step's choice may hold: ``top_k``, or
        every block of a context that is still dense."""
        return min(max(self.topk, -(-self.dense_len // self.block_size)),
                   blocks_per_seq)

    def block_scores(self, r, blocks):
        """``R [..., blocks]`` from ``r [..., kernels]``: the largest score
        among the kernels that overlap a block."""
        need = blocks * self.per_block
        have = r.shape[-1]
        if have < need:
            r = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, need - have)])
        by_block = r[..., :need].reshape(*r.shape[:-1], blocks,
                                         self.per_block)
        own = by_block.max(axis=-1)
        if not self.reach:
            return own
        lead = by_block[..., self.per_block - self.reach:].max(axis=-1)
        lead = jnp.pad(lead[..., :-1],
                       [(0, 0)] * (lead.ndim - 1) + [(1, 0)])
        return jnp.maximum(own, lead)

    def adjusted(self, scores, positions):
        """``scores [..., rows, blocks]`` with the forced blocks of each
        row's position at +inf and the blocks past it at -inf."""
        blocks = scores.shape[-1]
        block = jnp.arange(blocks)
        visible = block <= (positions // self.block_size)[..., None]
        first_window = jnp.maximum(positions - (self.window_size - 1),
                                   0) // self.block_size
        forced = visible & ((block < self.init_blocks)
                            | (block >= first_window[..., None]))
        return jnp.where(forced, jnp.inf,
                         jnp.where(visible, scores, -jnp.inf))


def top_blocks(scores, top):
    """The ``top`` best blocks of each row of ``scores [..., blocks]`` as a
    boolean row: exactly the set that jax's own top-k names (the tests hold
    it to that), less its entries at -inf.  Equal scores go to the lower
    block; a row with fewer than ``top`` blocks above -inf takes them all.
    ``scores`` are :meth:`SparseGeometry.adjusted`'s: float32, each +inf,
    -inf or >= +0.

    The model needs the SET, and the library's top-k is a full sort of
    every row on the TPU (a tenth of a 16k prefill), so the set is counted
    out instead.  The bit patterns of such floats order them as int32 (-inf
    below them all), so the ``top``-th largest is built from its highest
    bit down, one compare-and-count over the row a bit; what lies above it
    is in, and of its equals the first that still fit."""
    keys = jax.lax.bitcast_convert_type(scores, jnp.int32)

    def with_bit(i, kth):
        raised = kth | (jnp.int32(1) << (30 - i))
        enough = (keys >= raised[..., None]).sum(
            axis=-1, dtype=jnp.int32) >= top
        return jnp.where(enough, raised, kth)

    # stays 0 where fewer than ``top`` stand above -inf: all of them enter
    kth = jax.lax.fori_loop(
        0, 31, with_bit, jnp.zeros(keys.shape[:-1], jnp.int32))[..., None]
    above, tied = keys > kth, keys == kth
    room = top - above.sum(axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                            <= room))


def _precision(dtype):
    # as paged_attention.py: bf16 products pinned, fp32 pages ambient
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


# -- decode: scores over the compressed keys ---------------------------------

def _select_kernel(layer_ref, tables_ref, ctx_lens_ref, q_ref, ck_hbm, r_ref,
                   buf, sems, *, pages, block_size, slots, kv_heads, geometry,
                   scale):
    b = pl.program_id(0)
    layer = layer_ref[0]
    heads, head_dim = q_ref.shape[1:]
    group = heads // kv_heads
    span = pages * block_size
    precision = _precision(buf.dtype)

    def copies(slot):
        """(copy, whether the slot's closed kernels reach the page) of
        every page of slot ``slot``, into the buffer of its parity."""
        which = slot % 2
        needed = -(-geometry.closed(ctx_lens_ref[slot]) // block_size)
        return [(pltpu.make_async_copy(
            ck_hbm.at[layer, tables_ref[slot * pages + p]],
            buf.at[which, pl.ds(p * block_size, block_size)],
            sems.at[which, p]), p < needed) for p in range(pages)]

    def start(slot):
        for copy, needed in copies(slot):
            pl.when(needed)(copy.start)

    pl.when(b == 0)(lambda: start(0))
    pl.when(b + 1 < slots)(lambda: start(jnp.minimum(b + 1, slots - 1)))
    for copy, needed in copies(b):
        pl.when(needed)(copy.wait)

    n = geometry.closed(ctx_lens_ref[b])
    closed = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) < n
    ck = buf[b % 2]
    for g in range(kv_heads):
        s = jax.lax.dot_general(
            q_ref[0, g * group:(g + 1) * group],
            ck[:, g * head_dim:(g + 1) * head_dim],
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale
        # a page not fetched holds whatever was there: selected away
        s = jnp.where(closed, s, _MASK_VALUE)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        p = jnp.where(closed, p / p.sum(axis=-1, keepdims=True), 0.0)
        r_ref[0, g:g + 1, :] = p.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("kv_heads", "geometry",
                                             "interpret"))
def sparse_block_select(q, ck_cache, ck_tables, ctx_lens, *, layer, kv_heads,
                        geometry, interpret=False):
    """``r [slots, kv_heads, pages * block_size]`` (float32): for each slot
    and KV head, every compressed key's softmax score summed over the
    group's query heads (0 for a kernel not closed at the slot's position).

    ``q [slots, heads, d]``; ``ck_cache [layers, blocks, block_size,
    kv_heads * d]`` the pages of compressed keys (key ``j`` of a request is
    row ``j % block_size`` of its page ``j // block_size``); ``ck_tables
    [slots, pages]`` their ids; ``ctx_lens [slots]`` the positions of the
    tokens being decoded."""
    slots, heads, d = q.shape
    block_size = ck_cache.shape[2]
    pages = ck_tables.shape[1]
    span = pages * block_size
    kernel = functools.partial(
        _select_kernel, pages=pages, block_size=block_size, slots=slots,
        kv_heads=kv_heads, geometry=geometry, scale=1.0 / math.sqrt(d))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, d), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, kv_heads, span),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, span, ck_cache.shape[3]), ck_cache.dtype),
                pltpu.SemaphoreType.DMA((2, pages))]),
        out_shape=jax.ShapeDtypeStruct((slots, kv_heads, span), jnp.float32),
        interpret=interpret,
        name="sparse_block_select",
        **params,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      ck_tables.reshape(-1).astype(jnp.int32), ctx_lens.astype(jnp.int32),
      q.astype(ck_cache.dtype), ck_cache)


def choose_decode_blocks(r, ctx_lens, geometry, blocks_per_seq):
    """``(chosen [slots, kv_heads, width] block numbers, counts [slots,
    kv_heads])`` of a decode step from :func:`sparse_block_select`'s
    ``r``: the forced blocks and the best of the rest, ``top_k`` in all
    (:func:`top_blocks`' set, in ascending block order) — or every visible
    block while the context (the new token in it) is at most ``dense_len``.
    Entries past a count are block 0: fetched with the rest of their
    product and masked, never read."""
    g = geometry
    width = g.decode_width(blocks_per_seq)
    positions = ctx_lens[:, None]                       # [slots, 1]
    scores = g.adjusted(g.block_scores(r, blocks_per_seq), positions)
    top = min(g.topk, blocks_per_seq)
    # one row a (slot, KV head): tiles of [kv_heads, blocks] are mostly
    # padding, and every pass below would walk them
    rows = scores.reshape(-1, blocks_per_seq)
    # the set as a list: the j-th chosen block is the first with j + 1 of
    # them up to it, so as many blocks lie before it as hold at most j
    held = jnp.cumsum(top_blocks(rows, top), axis=-1, dtype=jnp.int32)
    place = jnp.arange(top, dtype=jnp.int32)
    ids = (held[:, None, :] <= place[:, None]).sum(axis=-1, dtype=jnp.int32)
    ids = jnp.where(place < held[:, -1:], ids, 0).reshape(
        *scores.shape[:-1], top)
    visible = positions // g.block_size + 1
    dense = positions + 1 <= g.dense_len
    every = jnp.arange(width, dtype=jnp.int32)
    chosen = jnp.where(dense[..., None], every,
                       jnp.pad(ids, ((0, 0), (0, 0), (0, width - top))))
    counts = jnp.where(dense, jnp.minimum(visible, width),
                       jnp.minimum(visible, top))
    return chosen, jnp.broadcast_to(counts, chosen.shape[:2]).astype(
        jnp.int32)


# -- decode: attention over the chosen pages ---------------------------------

def _decode_kernel(layer_ref, tables_ref, ctx_lens_ref, chosen_ref,
                   counts_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                   *, kv_heads, block_size, slots, blocks_per_seq, width,
                   pages, scale):
    layer = layer_ref[0]
    heads, head_dim = q_ref.shape[1:]
    group = heads // kv_heads
    span = pages * block_size
    pos_in_span = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    precision = _precision(k_buf.dtype)

    def block_at(b, g, index):
        """The ``index``-th chosen block of (slot ``b``, KV head ``g``);
        past the list's end, its last entry (masked, never counted)."""
        return chosen_ref[(b * kv_heads + g) * width
                          + jnp.minimum(index, width - 1)]

    def copies(b, g, fetch, which):
        """The K and V copies of the ``fetch``-th fetch of (``b``, ``g``):
        ``pages`` chosen pages, each one's lanes of KV head ``g``."""
        out = []
        lanes = pl.ds(g * head_dim, head_dim)
        for p in range(pages):
            page = tables_ref[b * blocks_per_seq
                              + block_at(b, g, fetch * pages + p)]
            rows = pl.ds(p * block_size, block_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page, :, lanes], k_buf.at[which, rows],
                sems.at[0, which, p]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page, :, lanes], v_buf.at[which, rows],
                sems.at[1, which, p]))
        return out

    def start(b, g, fetch, which):
        for copy in copies(b, g, fetch, which):
            copy.start()

    start(0, 0, 0, 0)

    def head_body(b, g, step0):
        """KV head ``g`` (static) of slot ``b``: its group's context and
        the fetches made."""
        ctx_len = ctx_lens_ref[b]
        count = counts_ref[b * kv_heads + g]
        n_fetches = (count + pages - 1) // pages
        q = q_ref[b, g * group:(g + 1) * group]

        def fetch_body(f, carry):
            m, l, acc = carry
            which = (step0 + f) % 2
            in_head = f + 1 < n_fetches

            # the fetch after this one: this head's next, else the next
            # head's (or slot's) first; the last of all has none
            @pl.when(in_head)
            def _next_fetch():
                start(b, g, f + 1, 1 - which)

            if g + 1 < kv_heads:
                @pl.when(jnp.logical_not(in_head))
                def _next_head():
                    start(b, g + 1, 0, 1 - which)
            else:
                @pl.when(jnp.logical_not(in_head) & (b + 1 < slots))
                def _next_slot():
                    start(jnp.minimum(b + 1, slots - 1), 0, 0, 1 - which)

            for copy in copies(b, g, f, which):
                copy.wait()
            k, v = k_buf[which], v_buf[which]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            # rows of each page that hold keys at or before the token: all
            # of a chosen page, the page of the token up to it, none of an
            # entry past the count
            live = jnp.zeros((1, span), jnp.int32)
            for p in range(pages):
                index = f * pages + p
                block = block_at(b, g, index)
                rows = jnp.where(block == ctx_len // block_size,
                                 ctx_len % block_size + 1, block_size)
                rows = jnp.where(index < count, rows, 0)
                live = jnp.where(pos_in_span // block_size == p, rows, live)
            visible = pos_in_span % block_size < live
            s = jnp.where(visible, s, _MASK_VALUE)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                         preferred_element_type=jnp.float32)
            return m_new, l, alpha * acc + pv

        m, l, acc = jax.lax.fori_loop(
            0, n_fetches, fetch_body,
            (jnp.full((group, 1), _MASK_VALUE, jnp.float32),
             jnp.zeros((group, 1), jnp.float32),
             jnp.zeros((group, head_dim), jnp.float32)))
        return acc / l, step0 + n_fetches

    def slot_body(b, step0):
        outs = []
        for g in range(kv_heads):
            out, step0 = head_body(b, g, step0)
            outs.append(out)
        o_ref[b] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)
        return step0

    jax.lax.fori_loop(0, slots, slot_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("num_heads", "pages_per_step",
                                             "interpret"))
def sparse_paged_decode_attention(q, k_cache, v_cache, block_tables,
                                  ctx_lens, chosen, counts, *, layer,
                                  num_heads, pages_per_step=8,
                                  interpret=False):
    """Context ``[slots, hidden]`` of one decode step at layer ``layer``
    over the CHOSEN pages: ``chosen [slots, kv_heads, width]`` block
    numbers (positions in the slot's table row) of which the first
    ``counts [slots, kv_heads]`` are read, the rest never.  ``q [slots,
    hidden]``; the caches ``[layers, blocks, block_size, kv_heads * d]``
    already hold the new token's row at position ``ctx_lens[b]``; the page
    that holds that position is masked past it."""
    slots, hidden = q.shape
    _, _, block_size, row = k_cache.shape
    head_dim = hidden // num_heads
    kv_heads = row // head_dim
    blocks_per_seq = block_tables.shape[1]
    width = chosen.shape[2]
    pages = min(pages_per_step, width)
    kernel = functools.partial(
        _decode_kernel, kv_heads=kv_heads, block_size=block_size, slots=slots,
        blocks_per_seq=blocks_per_seq, width=width, pages=pages,
        scale=1.0 / math.sqrt(head_dim))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    whole = (slots, num_heads, head_dim)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[pl.BlockSpec(whole, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(whole, lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * block_size, head_dim), k_cache.dtype),
                pltpu.VMEM((2, pages * block_size, head_dim), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2, pages))]),
        out_shape=jax.ShapeDtypeStruct(whole, q.dtype),
        interpret=interpret,
        name="sparse_paged_decode_attention",
        **params,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.reshape(-1).astype(jnp.int32), ctx_lens.astype(jnp.int32),
      chosen.reshape(-1).astype(jnp.int32),
      counts.reshape(-1).astype(jnp.int32),
      q.reshape(whole).astype(k_cache.dtype), k_cache, v_cache)
    return out.reshape(slots, hidden)


# -- prefill ------------------------------------------------------------------

def compress_keys(k, geometry):
    """``c_j [kernels, width]`` of a bucket's keys ``k [seq, width]``: the
    mean of ``kernel_size`` keys every ``kernel_stride`` (float32 sums)."""
    g = geometry
    seq, width = k.shape
    assert g.kernel_size % g.kernel_stride == 0 and seq % g.kernel_stride == 0
    strides = k.astype(jnp.float32).reshape(
        seq // g.kernel_stride, g.kernel_stride, width).sum(axis=1)
    parts = g.kernel_size // g.kernel_stride
    n = seq // g.kernel_stride - parts + 1
    total = sum(strides[i:i + n] for i in range(parts))
    return total / g.kernel_size


def prefill_block_mask(q, ck, true_len, geometry, *, kv_heads, row_block=512):
    """The chosen blocks of every query row of a bucket, ``[kv_heads, seq,
    blocks]`` (bool): ``q [seq, heads, d]``, ``ck [kernels, kv_heads, d]``
    (:func:`compress_keys`).  A prompt of at most ``dense_len`` tokens
    chooses every block (causality alone masks).  Scores in float32, row
    blocks one after another (32 heads x 16,384 rows x 1,023 kernels of
    float32 would be 2.1 GB at once)."""
    g = geometry
    seq, heads, d = q.shape
    group = heads // kv_heads
    blocks = seq // g.block_size
    top = min(g.topk, blocks)
    kernel_end = jnp.arange(ck.shape[0]) * g.kernel_stride + g.kernel_size - 1
    ck = ck.astype(q.dtype)

    def rows(args):
        positions, qb = args
        qg = qb.reshape(-1, kv_heads, group, d)
        s = jnp.einsum("tghd,jgd->gthj", qg, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        closed = kernel_end[None, :] <= positions[:, None]
        s = jnp.where(closed[None, :, None, :], s, _MASK_VALUE)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        p = jnp.where(closed[None, :, None, :],
                      p / p.sum(axis=-1, keepdims=True), 0.0)
        scores = g.adjusted(g.block_scores(p.sum(axis=2), blocks), positions)
        # forced blocks stand at +inf, blocks past the row at -inf (never
        # chosen); equal scores go to the lower block, as in decode
        return top_blocks(scores, top)

    block = math.gcd(seq, row_block)
    chosen = jax.lax.map(rows, (jnp.arange(seq).reshape(-1, block),
                                q.reshape(-1, block, heads, d)))
    chosen = chosen.transpose(1, 0, 2, 3).reshape(kv_heads, seq, blocks)
    return chosen | (true_len <= g.dense_len)


def _prefill_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_sc, l_sc, acc_sc,
                    *, group, head_dim, block_q, block_k, block_size,
                    k_steps, scale):
    i, j = pl.program_id(1), pl.program_id(2)
    precision = _precision(q_ref.dtype)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # tiles wholly above the diagonal hold no visible key
    @pl.when(j * block_k <= i * block_q + block_q - 1)
    def _step():
        # the rows' chosen blocks, expanded to this tile's keys: column c
        # of the tile is key j * block_k + c, in block (that) // block_size
        blocks = mask_ref.shape[-1]
        key_block = (j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (blocks, block_k), 1)) // block_size
        expand = (jax.lax.broadcasted_iota(jnp.int32, (blocks, block_k), 0)
                  == key_block).astype(mask_ref.dtype)
        chosen = jnp.dot(mask_ref[...], expand,
                         preferred_element_type=jnp.float32) > 0.5
        row = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        col = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        visible = chosen & (col <= row)
        k, v = k_ref[...], v_ref[...]
        for h in range(group):
            q = q_ref[:, h * head_dim:(h + 1) * head_dim]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible, s, _MASK_VALUE)
            m = m_sc[h]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            l_sc[h] = alpha * l_sc[h] + p.sum(axis=-1, keepdims=True)
            acc_sc[h] = alpha * acc_sc[h] + jnp.dot(
                p.astype(v.dtype), v, precision=precision,
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == k_steps - 1)
    def _finalize():
        for h in range(group):
            o_ref[:, h * head_dim:(h + 1) * head_dim] = (
                acc_sc[h] / l_sc[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "block_size",
                                             "block_q", "block_k",
                                             "interpret"))
def sparse_prefill_attention(q, k, v, mask, *, kv_heads, block_size,
                             block_q=256, block_k=512, interpret=False):
    """Causal attention of one bucket under a per-row choice of key
    blocks: ``q [seq, heads * d]``, ``k``, ``v`` ``[seq, kv_heads * d]``,
    ``mask [kv_heads, seq, blocks]`` (bool; block ``b`` is keys ``[b *
    block_size, (b + 1) * block_size)``; a row's own block must be chosen).
    Returns ``[seq, heads * d]`` in ``q``'s dtype."""
    seq, hidden = q.shape
    head_dim = k.shape[1] // kv_heads
    group = hidden // head_dim // kv_heads
    block_q, block_k = math.gcd(seq, block_q), math.gcd(seq, block_k)
    k_steps = seq // block_k
    # the expansion is a product over the blocks: whole lane tiles of them
    blocks = mask.shape[-1]
    padded = -(-blocks // _LANES) * _LANES
    mask = jnp.pad(mask.astype(q.dtype),
                   ((0, 0), (0, 0), (0, padded - blocks)))

    def last_step(i):
        return (i * block_q + block_q - 1) // block_k

    kernel = functools.partial(
        _prefill_kernel, group=group, head_dim=head_dim, block_q=block_q,
        block_k=block_k, block_size=block_size, k_steps=k_steps,
        scale=1.0 / math.sqrt(head_dim))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}
    # a tile above the diagonal names the last one under it: not fetched
    keys = pl.BlockSpec(
        (block_k, head_dim),
        lambda g, i, j: (jnp.minimum(j, last_step(i)), g))
    queries = pl.BlockSpec((block_q, group * head_dim),
                           lambda g, i, j: (i, g))
    return pl.pallas_call(
        kernel,
        grid=(kv_heads, seq // block_q, k_steps),
        in_specs=[queries, keys, keys,
                  pl.BlockSpec((None, block_q, padded),
                               lambda g, i, j: (g, i, 0))],
        out_specs=queries,
        scratch_shapes=[pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, head_dim), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((seq, hidden), q.dtype),
        interpret=interpret,
        name="sparse_prefill_attention",
        **params,
    )(q, k, v, mask)
