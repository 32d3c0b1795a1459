"""Lightning (linear) attention for serving: the chunked prefill that leaves
a request's state, and the one-token state update of decode (Pallas/Mosaic).

Per head ``h`` of width ``d`` with decay ``lambda_h = exp(-slope_h)``:

    S_t = lambda_h S_{t-1} + k_t^T v_t   (a [d, d] matrix, float32)
    o_t = q_t S_t / sqrt(d)

**Prefill** (``lightning_prefill_scan``) walks a bucket in chunks of
``chunk`` positions, one head a grid row, the state in VMEM between chunks:
inside a chunk the masked, decayed product ``((Q K^T) * D) V`` with ``D[i,
j] = lambda^(i-j)`` for ``i >= j`` (built once a head, never factored into
``lambda^i`` and ``lambda^-j``, which overflow), between chunks ``lambda^(i+1)
q_i S``.  The state it returns is the one after position ``true_len - 1``:
a chunk's update counts its first ``n = clip(true_len - start, 0, chunk)``
rows, ``S <- lambda^n S + sum_{i<n} lambda^(n-1-i) k_i^T v_i``, so the
bucket's padding neither adds nor decays.  Products take the operands in
their own dtype with a float32 accumulator; the state stays float32 and
meets bfloat16 queries as a high and a low bfloat16 part.

**Decode** (``lightning_decode_update``) reads each slot's state block out
of the paged state buffer by its block id, updates it and writes it back IN
PLACE (the buffer is aliased onto the output), entirely in float32 on the
vector unit: a step moves the state once each way and nothing else.

The state's block (``inference/kv_cache.py``: ``[block_size, heads * d * d /
block_size]`` float32) holds head ``h``'s rows ``a * block_size + r`` at
``[r, (h * A + a) * d : (h * A + a + 1) * d]`` with ``A = d / block_size``
(:func:`state_to_block`), so a head's half is a ``[block_size, d]`` tile of
whole lanes.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES, _SUBLANES = 128, 8
# heads a decode grid step updates: 8 heads of 128 x 128 are a 512 KB tile
_DECODE_HEADS = 8


def decay_slopes(heads):
    """``slope_h = 2^(-8 (h + 1) / heads)``, ``lambda_h = exp(-slope_h)``:
    Lightning Attention's ALiBi slopes, the same in every layer."""
    return (2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)).astype(np.float32)


def check_tpu_geometry(head_dim, block_size):
    """Raise for a state the kernels cannot tile on a TPU: a head's rows
    fill 128-lane tiles, and a block's rows divide a head's."""
    if head_dim % _LANES or block_size % _SUBLANES or head_dim % block_size:
        raise ValueError(
            f"lightning attention cannot tile this state on TPU: "
            f"head_dim={head_dim} must be a multiple of {_LANES} and of "
            f"kv_block_size={block_size}, itself a multiple of {_SUBLANES}")


def state_row_width(heads, head_dim, block_size):
    """Row width of the state buffer: one block holds a request's state."""
    assert head_dim % block_size == 0, (head_dim, block_size)
    return heads * head_dim * head_dim // block_size


def state_to_block(state, block_size):
    """``[heads, d, d]`` -> the block ``[block_size, heads * d * d /
    block_size]`` (module docstring)."""
    heads, d, _ = state.shape
    return state.reshape(heads, d // block_size, block_size, d).transpose(
        2, 0, 1, 3).reshape(block_size, -1)


def block_to_state(block, heads, head_dim):
    """The inverse of :func:`state_to_block`."""
    block_size = block.shape[0]
    return block.reshape(block_size, heads, head_dim // block_size,
                         head_dim).transpose(1, 2, 0, 3).reshape(
                             heads, head_dim, head_dim)


def _precision(dtype):
    # bf16 products are exact in the fp32 accumulator, and Mosaic refuses
    # bf16 operands under an ambient fp32 matmul precision; fp32 operands
    # follow the ambient precision as the program's other matmuls do
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _prefill_kernel(len_ref, slopes_ref, q_ref, k_ref, v_ref, o_ref,
                    state_ref, state, dmat, *, chunk, head_dim, chunks):
    h, c = pl.program_id(0), pl.program_id(1)
    slope = slopes_ref[h]
    precision = _precision(q_ref.dtype)

    @pl.when(c == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        dmat[...] = jnp.where(
            i >= j, jnp.exp(-slope * (i - j).astype(jnp.float32)), 0.0)

    def dot(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32)

    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    inside = (dot(q, k, ((1,), (1,))) * dmat[...]).astype(v.dtype)
    out = dot(inside, v, ((1,), (0,)))
    before = state[...]
    if q.dtype == jnp.bfloat16:
        high = before.astype(jnp.bfloat16)
        low = (before - high.astype(jnp.float32)).astype(jnp.bfloat16)
        carried = dot(q, high, ((1,), (0,))) + dot(q, low, ((1,), (0,)))
    else:
        carried = dot(q, before, ((1,), (0,)))
    out = out + jnp.exp(-slope * (pos + 1).astype(jnp.float32)) * carried
    o_ref[...] = (out * (1.0 / math.sqrt(head_dim))).astype(o_ref.dtype)

    # the chunk's first n rows are tokens; the rest neither add nor decay
    n = jnp.clip(len_ref[0] - c * chunk, 0, chunk)
    weight = jnp.where(
        pos < n, jnp.exp(-slope * (n - 1 - pos).astype(jnp.float32)), 0.0)
    weighted = (k.astype(jnp.float32) * weight).astype(k.dtype)
    kept = jnp.exp(jnp.zeros((1, head_dim), jnp.float32)
                   - slope * n.astype(jnp.float32))
    state[...] = kept * before + dot(weighted, v, ((0,), (0,)))

    @pl.when(c == chunks - 1)
    def _last_chunk():
        state_ref[...] = state[...]


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def lightning_prefill_scan(q, k, v, true_len, *, heads, chunk=256,
                           interpret=False):
    """``(o [seq, heads * d] float32, S [heads, d, d] float32)`` of one
    request's bucket: ``q``, ``k``, ``v`` ``[seq, heads * d]`` (heads
    contiguous, after norm and rotation), ``true_len`` the prompt's length
    (the state returned is the one after position ``true_len - 1``; rows
    past it are computed and mean nothing)."""
    seq, width = q.shape
    d = width // heads
    chunk = math.gcd(seq, chunk)
    chunks = seq // chunk
    kernel = functools.partial(_prefill_kernel, chunk=chunk, head_dim=d,
                               chunks=chunks)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))}
    rows = pl.BlockSpec((chunk, d), lambda h, c, *_: (c, h))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads, chunks),
            in_specs=[rows, rows, rows],
            out_specs=[rows,
                       pl.BlockSpec((None, d, d), lambda h, c, *_: (h, 0, 0))],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32),
                            pltpu.VMEM((chunk, chunk), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((seq, width), jnp.float32),
                   jax.ShapeDtypeStruct((heads, d, d), jnp.float32)],
        interpret=interpret,
        name="lightning_prefill_scan",
        **params,
    )(jnp.asarray(true_len, jnp.int32).reshape(1),
      jnp.asarray(decay_slopes(heads)), q, k, v)


def _decode_kernel(layer_ref, blocks_ref, q_ref, k_ref, v_ref, decay_ref,
                   state_ref, o_ref, new_ref, *, heads, halves, head_dim):
    del layer_ref, blocks_ref
    for head in range(heads):
        out = None
        for half in range(halves):
            t = head * halves + half
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            tile = state_ref[:, lanes] * decay_ref[:, lanes] \
                + k_ref[:, t:t + 1] * v_ref[:, lanes]
            new_ref[:, lanes] = tile.astype(new_ref.dtype)
            part = (q_ref[:, t:t + 1] * tile).sum(axis=0, keepdims=True)
            out = part if out is None else out + part
        o_ref[head:head + 1, :] = out * (1.0 / math.sqrt(head_dim))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_decode_update(q, k, v, state_cache, block_ids, *, layer,
                            interpret=False):
    """One decode step of every slot: ``(o [slots, heads, d] float32, the
    state buffer updated in place)``.  ``q``, ``k``, ``v`` ``[slots, heads,
    d]`` (after norm and rotation; float32 inside), ``state_cache``
    ``[layers, blocks, block_size, row]`` float32, ``block_ids [slots]``
    each slot's state block (a dead slot's is the null block, whose content
    is scratch), ``layer`` a scalar operand."""
    slots, heads, d = q.shape
    _, _, block_size, row = state_cache.shape
    halves = d // block_size
    assert row == state_row_width(heads, d, block_size), (row, heads, d)
    step_heads = math.gcd(heads, _DECODE_HEADS)
    groups = heads // step_heads
    tiles = step_heads * halves
    lanes = tiles * d

    def columns(x):
        """``[slots, heads, d]`` -> ``[slots, groups, block_size, tiles]``:
        column ``t`` holds the rows of tile ``t`` of the state."""
        return x.astype(jnp.float32).reshape(
            slots, groups, step_heads, halves, block_size).transpose(
                0, 1, 4, 2, 3).reshape(slots, groups, block_size, tiles)

    values = jnp.broadcast_to(
        v.astype(jnp.float32).reshape(slots, groups, step_heads, 1, d),
        (slots, groups, step_heads, halves, d)).reshape(
            slots, groups, 1, lanes)
    decay = jnp.asarray(np.repeat(np.exp(-decay_slopes(heads)),
                                  halves * d).reshape(groups, 1, lanes))
    kernel = functools.partial(_decode_kernel, heads=step_heads,
                               halves=halves, head_dim=d)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))}
    column = pl.BlockSpec((None, None, block_size, tiles),
                          lambda b, g, *_: (b, g, 0, 0))
    state = pl.BlockSpec((None, None, block_size, lanes),
                         lambda b, g, layer, blocks: (layer[0], blocks[b],
                                                      0, g))
    out, state_cache = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, groups),
            in_specs=[column, column,
                      pl.BlockSpec((None, None, 1, lanes),
                                   lambda b, g, *_: (b, g, 0, 0)),
                      pl.BlockSpec((None, 1, lanes),
                                   lambda b, g, *_: (g, 0, 0)),
                      state],
            out_specs=[pl.BlockSpec((None, step_heads, d),
                                    lambda b, g, *_: (b, g, 0)),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((slots, heads, d), jnp.float32),
                   jax.ShapeDtypeStruct(state_cache.shape,
                                        state_cache.dtype)],
        # operand 6 (after the two scalar operands) is the state buffer
        input_output_aliases={6: 1},
        interpret=interpret,
        name="lightning_decode_update",
        **params,
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_ids.astype(jnp.int32),
      columns(q), columns(k), values, decay, state_cache)
    return out, state_cache
