"""Paged decode attention: one query a slot over the LIVE blocks of a
paged KV cache (Pallas/Mosaic).

The serving decode step attends one new token per batch slot to that
slot's context, which lives in non-contiguous cache pages named by a
block table.  This kernel walks, for each slot, only the
``ceil((ctx_len + 1) / block_size)`` pages that hold live tokens: each
page is fetched from HBM by the id in the table (manual double-buffered
DMA, the next page — of this slot or the next — in flight while the
current one is computed), folded into a running max / sum / accumulator
(online softmax, fp32), and the tail of the last page is masked by
position.  HBM traffic follows the tokens cached, not ``max_seq_len``;
no ``[slots, max_seq, ...]`` array, additive mask or score tensor of
``max_seq`` width exists.  A dead slot (``ctx_len`` 0, table all on the
null block) costs one page.

Cache layout (``inference/kv_cache.py``):
``[layers, blocks, block_size, heads * head_dim]`` — a page is a dense
``[block_size, hidden]`` tile whatever the head width (a ``head_dim``
of 64 as the minor dimension would pad every page to twice its bytes
in HBM and in every DMA), and a token's K or V is one row, exactly what
the fused-QKV projection emits.  The whole cache is handed to the kernel
in place (``memory_space=ANY``) with the layer index as a scalar, so no
layer slice is ever materialised and every layer runs one kernel.

Per-head scores without slicing 64-lane heads out of a 128-lane tile:
the slot's query row is expanded to a block-diagonal
``[heads, hidden]`` matrix (row h holds q_h in lanes h*d..h*d+d-1,
zero elsewhere), so ONE matmul a page gives every head's scores,
``Q~ . K_page^T -> [heads, block_size]``, and one more the values,
``P . V_page -> [heads, hidden]``, of which row h's lanes of head h are
read out at the end.  The zeros add nothing, so the mathematics is the
per-head dot product: cache-dtype K, V and probabilities into the
matmuls, fp32 accumulation, fp32 scores and softmax, scale 1/sqrt(d).
That is ``heads`` times the useful FLOPs and still well under a page's
DMA time: decode is bound by bytes.

Grouped KV heads and windows (``_gqa_kernel``; chosen from the shapes, the
cache's row narrower than the query's, or from ``window``): the cache row is
``kv_heads * head_dim`` and the ``heads / kv_heads`` query heads of a group
share a K/V lane range, so a page is fetched ONCE for all of them — the
group's queries ``[group, head_dim]`` against the page's lanes of that KV
head, one small product a KV head, nothing repeated in HBM or VMEM.
``pages_per_step`` pages are fetched into one buffer and multiplied
together (as ``mla_paged_attention.py`` does).  With ``window`` the page
loop is bounded BELOW as well as above: only the pages that hold positions
``ctx_len - window + 1 .. ctx_len`` are fetched, and a page's table entry
is its number modulo the table's width, so a window layer's table may be a
RING of ``ring_pages(window, block_size)`` pages whatever the context's
length (``inference/kv_cache.py``).  In a device trace the two uses are
named ``gqa_paged_decode_attention`` and ``window_paged_decode_attention``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES, _SUBLANES = 128, 8
_MASK_VALUE = -1e30


def check_tpu_geometry(hidden, block_size):
    """Raise for a cache geometry the kernel cannot tile on a TPU (what
    Mosaic refuses when compiled for a v5e): a page is DMA'd as one
    ``[block_size, hidden]`` slice of the cache, so ``hidden`` must fill
    128-lane rows and ``block_size`` whole 8-row sublane tiles."""
    if hidden % _LANES or block_size % _SUBLANES:
        raise ValueError(
            f"paged decode attention cannot tile this KV cache on TPU: "
            f"heads*head_dim={hidden} must be a multiple of {_LANES} and "
            f"kv_block_size={block_size} a multiple of {_SUBLANES}")


def _kernel(layer_ref, tables_ref, ctx_lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, *, heads, head_dim, block_size, slots,
            blocks_per_seq, scale):
    layer = layer_ref[0]
    hidden = heads * head_dim
    # one query row a head, padded to whole fp32 sublane tiles
    rows = -(-heads // _SUBLANES) * _SUBLANES

    row = jax.lax.broadcasted_iota(jnp.int32, (rows, hidden), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hidden), 1)
    # row h owns the lanes of head h (rows past ``heads`` own none)
    diag = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)
    pos_in_block = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 1)
    # bf16 products are exact in the fp32 accumulator, and Mosaic refuses
    # bf16 operands under an ambient fp32 matmul precision; fp32 pages
    # follow the ambient precision as the program's other matmuls do
    precision = (jax.lax.Precision.DEFAULT
                 if k_buf.dtype == jnp.bfloat16 else None)

    def page_copies(page, buf):
        return (pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[buf],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[buf],
                                      sems.at[1, buf]))

    def start(page, buf):
        for copy in page_copies(page, buf):
            copy.start()

    start(tables_ref[0], 0)

    def slot_body(b, step0):
        ctx_len = ctx_lens_ref[b]
        # the new token sits at position ctx_len: ctx_len + 1 are live
        n_live = ctx_len // block_size + 1
        q_tilde = jnp.where(diag, q_ref[b].astype(jnp.float32),
                            0.0).astype(k_buf.dtype)

        def block_body(j, carry):
            m, l, acc = carry
            buf = (step0 + j) % 2
            # the page after this one: this slot's next live block, else
            # the next slot's first; the last page of all has none
            in_slot = j + 1 < n_live
            nxt_b = jnp.minimum(b + 1, slots - 1)
            nxt_page = jnp.where(
                in_slot,
                tables_ref[b * blocks_per_seq
                           + jnp.minimum(j + 1, blocks_per_seq - 1)],
                tables_ref[nxt_b * blocks_per_seq])

            @pl.when(in_slot | (b + 1 < slots))
            def _prefetch():
                start(nxt_page, 1 - buf)

            k_copy, v_copy = page_copies(0, buf)
            k_copy.wait()
            s = jax.lax.dot_general(
                q_tilde, k_buf[buf], (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(j * block_size + pos_in_block <= ctx_len, s,
                          _MASK_VALUE)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            v_copy.wait()
            pv = jnp.dot(p.astype(v_buf.dtype), v_buf[buf],
                         precision=precision,
                         preferred_element_type=jnp.float32)
            return m_new, l, alpha * acc + pv

        m, l, acc = jax.lax.fori_loop(
            0, n_live, block_body,
            (jnp.full((rows, 1), _MASK_VALUE, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, hidden), jnp.float32)))
        out = jnp.where(diag, acc / l, 0.0).sum(axis=0, keepdims=True)
        o_ref[b] = out.astype(o_ref.dtype)
        return step0 + n_live

    jax.lax.fori_loop(0, slots, slot_body, jnp.int32(0))


def ring_pages(window, block_size):
    """Pages a ring must hold so that the ``window`` positions ending at
    any position lie in distinct pages of it, the one being written among
    them: 128 keys in pages of 64 take 3."""
    return (max(window, 2) - 2) // block_size + 2


def check_gqa_tpu_geometry(kv_heads, head_dim, block_size):
    """Raise for a grouped-KV cache the kernel cannot tile on a TPU: a KV
    head's keys are a lane slice of the page, so ``head_dim`` must fill
    128-lane tiles, and ``block_size`` whole sublane tiles."""
    if head_dim % _LANES or block_size % _SUBLANES:
        raise ValueError(
            f"grouped paged decode attention cannot tile this KV cache on "
            f"TPU: head_dim={head_dim} must be a multiple of {_LANES} "
            f"({kv_heads} KV heads a row) and kv_block_size={block_size} a "
            f"multiple of {_SUBLANES}")


def _gqa_kernel(layer_ref, tables_ref, ctx_lens_ref, q_ref, k_hbm, v_hbm,
                o_ref, k_buf, v_buf, sems, *, kv_heads, block_size, slots,
                blocks_per_seq, pages, window, scale):
    layer = layer_ref[0]
    heads, head_dim = q_ref.shape[1:]
    group = heads // kv_heads
    span = pages * block_size
    pos_in_span = jax.lax.broadcasted_iota(jnp.int32, (heads, span), 1)
    # as the kernel above: bf16 products pinned, fp32 pages ambient
    precision = (jax.lax.Precision.DEFAULT
                 if k_buf.dtype == jnp.bfloat16 else None)

    def first_page(b):
        """The first page slot ``b`` reads: 0, or with a window the page
        of its oldest visible position."""
        if window is None:
            return 0
        return jnp.maximum(ctx_lens_ref[b] - (window - 1), 0) // block_size

    def copies(b, fetch, which):
        """The K and V page copies of slot ``b``'s ``fetch``-th fetch.  A
        page's table entry is its number modulo the table's width: a ring
        wraps, and a page past the context (masked by position) reads
        some entry of the slot's own row."""
        out = []
        for p in range(pages):
            j = (first_page(b) + fetch * pages + p) % blocks_per_seq
            page = tables_ref[b * blocks_per_seq + j]
            rows = pl.ds(p * block_size, block_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[which, rows],
                sems.at[0, which, p]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[which, rows],
                sems.at[1, which, p]))
        return out

    def start(b, fetch, which):
        for copy in copies(b, fetch, which):
            copy.start()

    start(0, 0, 0)

    def slot_body(b, step0):
        ctx_len = ctx_lens_ref[b]
        first = first_page(b)
        # the new token sits at position ctx_len, in page ctx_len // bs
        n_fetches = (ctx_len // block_size - first + pages) // pages
        q = q_ref[b]

        def fetch_body(g, carry):
            m, l, acc = carry
            which = (step0 + g) % 2
            in_slot = g + 1 < n_fetches

            # the fetch after this one: this slot's next, else the next
            # slot's first; the last of all has none
            @pl.when(in_slot)
            def _next_fetch():
                start(b, g + 1, 1 - which)

            @pl.when(jnp.logical_not(in_slot) & (b + 1 < slots))
            def _next_slot():
                start(jnp.minimum(b + 1, slots - 1), 0, 1 - which)

            for copy in copies(b, g, which):
                copy.wait()
            k, v = k_buf[which], v_buf[which]
            # one product a KV head: its group of queries against its own
            # lanes of the pages
            s = jnp.concatenate([
                jax.lax.dot_general(
                    q[j * group:(j + 1) * group],
                    k[:, j * head_dim:(j + 1) * head_dim],
                    (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)
                for j in range(kv_heads)], axis=0) * scale
            pos = (first + g * pages) * block_size + pos_in_span
            visible = pos <= ctx_len
            if window is not None:
                visible &= pos > ctx_len - window
            s = jnp.where(visible, s, _MASK_VALUE)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            p = p.astype(v.dtype)
            pv = jnp.concatenate([
                jnp.dot(p[j * group:(j + 1) * group],
                        v[:, j * head_dim:(j + 1) * head_dim],
                        precision=precision,
                        preferred_element_type=jnp.float32)
                for j in range(kv_heads)], axis=0)
            return m_new, l, alpha * acc + pv

        m, l, acc = jax.lax.fori_loop(
            0, n_fetches, fetch_body,
            (jnp.full((heads, 1), _MASK_VALUE, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, head_dim), jnp.float32)))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return step0 + n_fetches

    jax.lax.fori_loop(0, slots, slot_body, jnp.int32(0))


def _gqa_call(q, k_cache, v_cache, block_tables, ctx_lens, layer, num_heads,
              window, pages_per_step, interpret):
    slots, hidden = q.shape
    _, _, block_size, row = k_cache.shape
    head_dim = hidden // num_heads
    kv_heads = row // head_dim
    assert row % head_dim == 0 and num_heads % kv_heads == 0
    blocks_per_seq = block_tables.shape[1]
    pages = min(pages_per_step, blocks_per_seq)
    kernel = functools.partial(
        _gqa_kernel, kv_heads=kv_heads, block_size=block_size, slots=slots,
        blocks_per_seq=blocks_per_seq, pages=pages, window=window,
        scale=1.0 / math.sqrt(head_dim))
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024)}
    whole = (slots, num_heads, head_dim)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(whole, lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(whole, lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * block_size, row), k_cache.dtype),
                pltpu.VMEM((2, pages * block_size, row), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2, pages)),
            ]),
        out_shape=jax.ShapeDtypeStruct(whole, q.dtype),
        interpret=interpret,
        name=("gqa_paged_decode_attention" if window is None
              else "window_paged_decode_attention"),
        **params,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.reshape(-1).astype(jnp.int32),
      ctx_lens.astype(jnp.int32),
      q.reshape(whole).astype(k_cache.dtype), k_cache, v_cache)
    return out.reshape(slots, hidden)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "window", "pages_per_step", "interpret"))
def paged_decode_attention(q, k_cache, v_cache, block_tables, ctx_lens, *,
                           layer, num_heads, window=None, pages_per_step=8,
                           interpret=False):
    """Context ``[slots, hidden]`` of one decode step at layer ``layer``
    (a scalar operand: every layer of a model runs the same traced
    kernel).

    ``q`` is this step's queries ``[slots, hidden]`` (heads contiguous);
    ``k_cache`` / ``v_cache`` the whole paged caches
    ``[layers, blocks, block_size, hidden]``, already holding the new
    token's row at position ``ctx_lens[b]`` of slot b; ``block_tables``
    ``[slots, blocks_per_seq]`` int32 page ids; ``ctx_lens`` ``[slots]``
    int32 context lengths BEFORE the new token.  Positions
    ``0..ctx_lens[b]`` are attended, nothing else is read.

    The kernel follows from the shapes: a cache row as wide as the query
    and no ``window`` is the block-diagonal kernel above; a narrower row
    (``kv_heads * head_dim``: grouped KV heads) or a ``window`` (only the
    last ``window`` positions are attended and fetched, the table a ring
    or the whole context's) is ``_gqa_kernel``, ``pages_per_step`` pages
    a product.
    """
    slots, hidden = q.shape
    _, _, block_size, cache_hidden = k_cache.shape
    assert hidden % num_heads == 0
    if cache_hidden != hidden or window is not None:
        return _gqa_call(q, k_cache, v_cache, block_tables, ctx_lens, layer,
                         num_heads, window, pages_per_step, interpret)
    head_dim = hidden // num_heads
    blocks_per_seq = block_tables.shape[1]

    kernel = functools.partial(
        _kernel, heads=num_heads, head_dim=head_dim,
        block_size=block_size, slots=slots, blocks_per_seq=blocks_per_seq,
        scale=1.0 / math.sqrt(head_dim))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((slots, 1, hidden), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((slots, 1, hidden),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_size, hidden), k_cache.dtype),
                pltpu.VMEM((2, block_size, hidden), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, 1, hidden), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.reshape(-1).astype(jnp.int32),
      ctx_lens.astype(jnp.int32), q[:, None, :], k_cache, v_cache)
    return out[:, 0]
