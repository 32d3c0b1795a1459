"""Absorbed latent-attention decode over a paged latent cache
(Pallas/Mosaic).

Multi-head latent attention caches, per token and layer, ONE row
``[c_kv ; k_r]`` — the normalised compressed key/value (``value_width``
lanes) followed by the rotated shared rotary key — instead of per-head
keys and values.  With the key up-projection absorbed into the query
(``q~_h = q_nope_h W_UK_h^T``), every head scores straight against that
row, ``(q~_h . c_kv(s) + q_rope_h . k_r(s)) * scale``, and sums the
*latent* values, ``u_h = sum_s a_h(s) c_kv(s)``; the caller applies
``W_UV`` afterwards.  So the cache's one "KV head" is shared by all the
query heads: a page is fetched once and used by every head, scores over
the whole row, values over its first ``value_width`` lanes.

The page walk is ``paged_attention.py``'s: all slots in one call, per
slot a loop over its live pages only, fetched by the ids in the block
table with manual double-buffered DMA (the next fetch — of this slot or
the next — in flight during the current product), online softmax in
fp32, the tail masked by position, a dead slot costs one fetch.
``pages_per_step`` pages are fetched into one buffer and multiplied
together, so that the score product's token dimension fills the MXU
(two 64-token pages make 128 columns).

Cache layout (``inference/kv_cache.py``): ``[layers, blocks, block_size,
row]`` with ``row`` padded to whole 128-lane tiles (DeepSeek-V2's 512 +
64 = 576 is stored 640 wide, the pad lanes zero in the cache and in the
query, so they add nothing to a score).  Per cached token the kernel
does ``2 * heads * (row + value_width)`` FLOPs against ``row`` elements
read: at 128 heads that is the v5e's ridge, bound by both at once.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES, _SUBLANES = 128, 8
_MASK_VALUE = -1e30


def padded_row_width(row):
    """The stored width of a ``row``-wide latent row: whole lane tiles."""
    return -(-row // _LANES) * _LANES


def check_tpu_geometry(row, value_width, block_size):
    """Raise for a latent cache the kernel cannot tile on a TPU: a page
    is DMA'd as one ``[block_size, row]`` slice and its values are the
    first ``value_width`` lanes, so both fill 128-lane tiles and
    ``block_size`` whole sublane tiles."""
    if row % _LANES or value_width % _LANES or block_size % _SUBLANES:
        raise ValueError(
            f"latent paged decode attention cannot tile this cache on "
            f"TPU: row={row} and value_width={value_width} must be "
            f"multiples of {_LANES} (pad the row: padded_row_width) and "
            f"kv_block_size={block_size} a multiple of {_SUBLANES}")


def _kernel(layer_ref, tables_ref, ctx_lens_ref, q_ref, cache_hbm, o_ref,
            buf, sems, *, value_width, block_size, slots, blocks_per_seq,
            pages, scale):
    layer = layer_ref[0]
    rows = q_ref.shape[1]
    span = pages * block_size
    pos_in_span = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    # bf16 products are exact in the fp32 accumulator, and Mosaic refuses
    # bf16 operands under an ambient fp32 matmul precision; fp32 pages
    # follow the ambient precision as the program's other matmuls do
    precision = (jax.lax.Precision.DEFAULT
                 if buf.dtype == jnp.bfloat16 else None)

    def copies(b, group, which):
        """The ``pages`` page copies of slot ``b``'s ``group``-th fetch;
        a table index past the slot's row reads its last entry (masked by
        position, like any page beyond the context)."""
        out = []
        for p in range(pages):
            j = jnp.minimum(group * pages + p, blocks_per_seq - 1)
            out.append(pltpu.make_async_copy(
                cache_hbm.at[layer, tables_ref[b * blocks_per_seq + j]],
                buf.at[which, pl.ds(p * block_size, block_size)],
                sems.at[which, p]))
        return out

    def start(b, group, which):
        for copy in copies(b, group, which):
            copy.start()

    start(0, 0, 0)

    def slot_body(b, step0):
        ctx_len = ctx_lens_ref[b]
        # the new token sits at position ctx_len: ctx_len + 1 are live
        n_groups = (ctx_len // block_size + pages) // pages
        q = q_ref[b]

        def group_body(g, carry):
            m, l, acc = carry
            which = (step0 + g) % 2
            in_slot = g + 1 < n_groups

            # the fetch after this one: this slot's next group, else the
            # next slot's first; the last of all has none
            @pl.when(in_slot)
            def _next_group():
                start(b, g + 1, 1 - which)

            @pl.when(jnp.logical_not(in_slot) & (b + 1 < slots))
            def _next_slot():
                start(jnp.minimum(b + 1, slots - 1), 0, 1 - which)

            for copy in copies(b, g, which):
                copy.wait()
            page = buf[which]
            s = jax.lax.dot_general(
                q, page, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(g * span + pos_in_span <= ctx_len, s, _MASK_VALUE)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            pv = jnp.dot(p.astype(buf.dtype), page[:, :value_width],
                         precision=precision,
                         preferred_element_type=jnp.float32)
            return m_new, l, alpha * acc + pv

        m, l, acc = jax.lax.fori_loop(
            0, n_groups, group_body,
            (jnp.full((rows, 1), _MASK_VALUE, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, value_width), jnp.float32)))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return step0 + n_groups

    jax.lax.fori_loop(0, slots, slot_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=(
    "value_width", "scale", "pages_per_step", "interpret"))
def mla_paged_decode_attention(q, cache, block_tables, ctx_lens, *, layer,
                               value_width, scale, pages_per_step=2,
                               interpret=False):
    """Latent values ``u [slots, heads, value_width]`` of one decode step
    at layer ``layer`` (a scalar operand: every layer runs the same traced
    kernel).

    ``q`` is this step's absorbed queries ``[slots, heads, row]`` (per
    head ``[q~ ; q_rope ; 0 pad]``, the row's own lane order); ``cache``
    the whole paged latent cache ``[layers, blocks, block_size, row]``,
    already holding the new token's row at position ``ctx_lens[b]`` of
    slot b; ``block_tables`` ``[slots, blocks_per_seq]`` int32 page ids;
    ``ctx_lens`` ``[slots]`` int32 context lengths BEFORE the new token.
    Scores are ``q . row * scale`` over positions ``0..ctx_lens[b]``;
    nothing else is read.
    """
    slots, heads, row = q.shape
    _, _, block_size, cache_row = cache.shape
    assert cache_row == row and value_width <= row
    blocks_per_seq = block_tables.shape[1]
    # one query row a head, padded to whole sublane tiles
    rows = -(-heads // _SUBLANES) * _SUBLANES
    if rows != heads:
        q = jnp.pad(q, ((0, 0), (0, rows - heads), (0, 0)))

    kernel = functools.partial(
        _kernel, value_width=value_width, block_size=block_size,
        slots=slots, blocks_per_seq=blocks_per_seq, pages=pages_per_step,
        scale=scale)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024)}
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((slots, rows, row), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((slots, rows, value_width),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_step * block_size, row),
                           cache.dtype),
                pltpu.SemaphoreType.DMA((2, pages_per_step)),
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, rows, value_width), q.dtype),
        interpret=interpret,
        name="mla_paged_decode_attention",
        **params,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.reshape(-1).astype(jnp.int32),
      ctx_lens.astype(jnp.int32), q.astype(cache.dtype), cache)
    return out[:, :heads]
