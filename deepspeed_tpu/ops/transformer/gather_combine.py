"""The expert layer's way out (Pallas/Mosaic): each token's weighted sum of
the rows its held (token, choice) pairs own in an array sorted by expert,

    ``dest[t] = sum_j c[t, j] * src[row[t, j]]`` over the ``j`` with
    ``group[t, j] >= 0``, in float32, the choices in their own order

with only those rows moved.  XLA's form of it is ``top_k`` row gathers of
``[tokens, hidden]`` masked after they arrive: a row costs it 54 to 128 ns
by its width, whoever owns it (``models/expert_shard.py``'s readings), and
one chip of ``n`` owns ``1/n`` of them.

A DMA cannot address one row of a tiled array (Mosaic: a slice of the
second-minor dimension is whole tiles), and a relayout of ``src`` to rows
of their own costs more than the gathers it would save.  The kernel uses
what the sort leaves instead: the pairs are sorted by expert and, inside
an expert, by token (a stable sort), so the rows a tile of consecutive
tokens reads from ONE expert's group are consecutive.  A grid step owns a
tile of tokens; for each held expert it copies the aligned chunks of 16
rows (8 of float32) that span the tile's rows in that group, HBM to VMEM,
two buffers by the step's parity so that the next tile's chunks arrive
under this tile's sums; then each token adds its pairs' rows out of the
buffer in float32 and writes its own row of the output block.  Every
destination row is written once, by one program instance: no scatter, no
atomics, and the result does not depend on the arrival order.

The tables (first chunk and number of chunks a (tile, expert), where each
pair's row stands in its tile's buffer) are built from ``row`` and
``group`` by a few small XLA fusions in the wrapper; the chunk tables are
scalar-prefetched whole and the pairs' are blocked by tile into SMEM.
Off the TPU the same kernel runs through Pallas' interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a 32-bit VMEM tile: the second-minor unit a DMA can address
_SUBLANES = 8


def _kernel(first_ref, count_ref, at_ref, c_ref, src, out_ref, buf, sems, *,
            tile, stride, held, pack):
    i = pl.program_id(0)
    # two rows of a 16-bit ``src`` share a 32-bit word, so 8 words' rows
    # are the 16 rows of its tile
    words = src.bitcast(jnp.uint32) if pack == 2 else src

    def copy(chunk, slot, which):
        return pltpu.make_async_copy(
            words.at[pl.ds(pl.multiple_of(chunk * _SUBLANES, _SUBLANES),
                           _SUBLANES)],
            buf.at[which, pl.ds(pl.multiple_of(slot * _SUBLANES, _SUBLANES),
                                _SUBLANES)],
            sems.at[which])

    def start(step, which):
        """Every chunk of tile ``step``, the experts' spans one after
        another in the buffer."""
        def expert(e, slot):
            first = first_ref[step * held + e]
            count = count_ref[step * held + e]

            def one(k, carry):
                copy(first + k, slot + k, which).start()
                return carry
            jax.lax.fori_loop(0, count, one, 0)
            return slot + count
        jax.lax.fori_loop(0, held, expert, jnp.int32(0))

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        start(i + 1, (i + 1) % 2)

    which = i % 2
    chunks = jax.lax.fori_loop(
        0, held, lambda e, n: n + count_ref[i * held + e], jnp.int32(0))

    def wait(k, carry):
        copy(0, 0, which).wait()
        return carry
    jax.lax.fori_loop(0, chunks, wait, 0)

    hidden = out_ref.shape[1]

    def token(t, carry):
        def pair(k, acc):
            at = at_ref[t * stride + k]
            c = c_ref[t * stride + k]
            word = buf[which, pl.ds(at // pack, 1), :]
            if pack == 2:
                # the even row is the word's low half: a bfloat16 is the
                # high half of its float32
                shift = (16 * (1 - at % 2)).astype(jnp.uint32)
                word = pltpu.bitcast(
                    (word << shift) & jnp.uint32(0xFFFF0000), jnp.float32)
            return acc + c * word
        # a token's entries: how many of its pairs are here, then each's
        out_ref[pl.ds(t, 1), :] = jax.lax.fori_loop(
            1, 1 + at_ref[t * stride], pair,
            jnp.zeros((1, hidden), jnp.float32))
        return carry
    jax.lax.fori_loop(0, tile, token, 0)


@functools.partial(jax.jit, static_argnames=("held", "tile", "interpret"))
def moe_gather_combine(src, row, group, c=None, *, held, tile=256,
                       interpret=False):
    """``dest [tokens, hidden]`` in float32 for ``src [rows, hidden]``
    (bfloat16 or float32) sorted by group, ``row [tokens, top_k]`` the row
    each pair owns, ``group [tokens, top_k]`` the pair's group among the
    ``held`` (negative: the pair is not here, and its ``row`` and ``c`` are
    not read) and ``c [tokens, top_k]`` its weight (None: 1).

    The rows that the pairs of ``tile`` consecutive tokens own in one
    group have to lie within ``tile * top_k`` rows of each other — they are
    consecutive where ``row`` comes from a stable sort by group — so that
    every tile's chunks fit its buffer; a pair whose row would not is given
    a NaN weight rather than another pair's row.  The jitted function's
    name is the kernel's name in a device trace.
    """
    tokens, top_k = row.shape
    rows, hidden = src.shape
    if src.dtype not in (jnp.bfloat16, jnp.float32):
        raise TypeError(f"src is {src.dtype}: bfloat16 or float32")
    pack = 4 // src.dtype.itemsize
    span = _SUBLANES * pack          # rows a chunk
    tile = min(tile, -(-tokens // _SUBLANES) * _SUBLANES)
    n_tiles = -(-tokens // tile)
    if rows % span:
        src = jnp.pad(src, ((0, -rows % span), (0, 0)))
    if c is None:
        c = jnp.ones(row.shape, jnp.float32)
    pad = n_tiles * tile - tokens
    if pad:
        row, c = (jnp.pad(a, ((0, pad), (0, 0))) for a in (row, c))
        group = jnp.pad(group, ((0, pad), (0, 0)), constant_values=-1)
    inside = group >= 0

    # a (tile, group)'s chunks: from the chunk of its lowest row to that of
    # its highest
    of_group = (group.reshape(n_tiles, tile * top_k, 1)
                == jnp.arange(held, dtype=group.dtype))
    tile_rows = row.reshape(n_tiles, tile * top_k, 1)
    highest = jnp.where(of_group, tile_rows, -1).max(axis=1)
    first = jnp.where(of_group, tile_rows, rows).min(axis=1) // span
    count = jnp.where(highest >= 0, highest // span - first + 1, 0)
    first = jnp.where(highest >= 0, first, 0)
    base = jnp.cumsum(count, axis=1) - count
    max_chunks = -(-tile * top_k // span) + 2 * held
    count = jnp.minimum(count, jnp.maximum(max_chunks - base, 0))
    # where a pair's row stands in its tile's buffer
    shift = ((base - first) * span)[:, None, :]
    at = row + jnp.where(of_group, shift, 0).sum(axis=2).reshape(row.shape)
    c = jnp.where(at < max_chunks * span, c.astype(jnp.float32), jnp.nan)
    # a token's entries: the number of its pairs that are here, then those
    # pairs in their own order; a power of two of them, so that a tile's
    # are whole 1024-word tiles of SMEM
    stride = max(8, 1 << top_k.bit_length())
    slot = jnp.cumsum(inside, axis=1)
    front = inside[:, :, None] & (slot[:, :, None] == jnp.arange(stride))
    at, c = (jnp.where(front, a[:, :, None], 0).sum(axis=1) for a in (at, c))
    at = at.at[:, 0].set(slot[:, -1])

    kernel = functools.partial(_kernel, tile=tile, stride=stride, held=held,
                               pack=pack)
    # the steps run in turn: a step starts the next step's copies
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024)}
    per_tile = pl.BlockSpec((tile * stride,), lambda i, *_: (i,),
                            memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                per_tile, per_tile,
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, hidden), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, max_chunks * _SUBLANES, hidden),
                           jnp.uint32 if pack == 2 else src.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, hidden),
                                       jnp.float32),
        interpret=interpret, name="moe_gather_combine", **params,
    )(first.reshape(-1).astype(jnp.int32),
      count.reshape(-1).astype(jnp.int32),
      at.reshape(-1).astype(jnp.int32), c.reshape(-1), src)
    return out[:tokens] if pad else out
