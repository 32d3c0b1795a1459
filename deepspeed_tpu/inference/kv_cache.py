"""Paged cache: preallocated device buffers + a host-side block
allocator (vLLM-style block tables, adapted to XLA static shapes).

The cache is one device array per buffer the served model names
(``inference/model.py``: GPT-2 keeps ``k_cache`` and ``v_cache`` with
rows of ``heads * head_dim``, a latent-attention model ONE buffer whose
row is its compressed key/value and shared rotary key), each of shape

    ``[layers, kv_blocks, kv_block_size, row width]``

allocated ONCE at engine construction.  The layout is the decode
kernels' (``ops/transformer/paged_attention.py``,
``mla_paged_attention.py``): a page is a dense ``[kv_block_size, row]``
tile whatever the head width (with ``head_dim`` 64 as the minor
dimension every page would be padded to 128 lanes: the GPT-2-large
cache measured 1.5x its logical bytes on a v5e in the old ``[..., heads,
head_dim]`` layout, 1.0x in this one), and a token's entry is one row —
what the projection emits, so the append is a row write.  The same block
table serves every buffer of a GROUP (:class:`CacheGroup`): the layers
whose pages cover the same span of a request.  Most models have one group,
every layer caching the whole context.  A model with sliding-window layers
beside full ones names two: the window layers' group holds a fixed number
of pages a request, a RING that position ``p`` enters at page ``(p //
kv_block_size) % pages`` (``ops/transformer/paged_attention.py::
ring_pages``), whatever the request's length — so its buffers are
``slots x pages + 1`` blocks, not the context's.  Each group has its own
pool (:class:`BlockAllocator`) and its own table; a request is granted its
blocks of every group together at admission and gives them back together.
A group need not hold keys and values at all: a linear-attention
layer's per-request STATE is a group of ``pages=1`` whose one block is the
state matrix (``models/minicpm_sala.py``: 32 heads x 128 x 128 float32
values are exactly one block of 64 rows x 8192), in a dtype of its own
(``CacheGroup.dtype``).  Prefill writes that block whole, every decode
step reads and rewrites it in place, a dead slot's table points at the
null block (whose content is scratch), and a requeued request is granted
a block afresh that its new prefill overwrites — nothing is inherited.
Sequences never own contiguous cache
memory: each holds a *block table* (host list of block ids); prefill
scatters whole pages through it, decode scatters one row a slot and the
paged kernel fetches the live pages by id.  Both programs
take the cache arrays as donated arguments and return the updated
arrays, so XLA aliases the output buffer onto the input allocation —
an in-place update, verified as a materialized ``input_output_alias``
by dsverify DSP601 (a silently-copied KV cache is the classic decode
perf bug this subsystem exists to never ship).

Block 0 is reserved as the *null block*: inactive decode slots point
their whole table at it and park their write offset there, so the
fixed-width decode program needs no masking on the write path — dead
slots harmlessly overwrite scratch (two dead slots write the same row
in one scatter: any winner will do).
"""

from typing import NamedTuple, Optional

import jax.numpy as jnp

# block id every table slot starts at (and dead slots stay at): the
# reserved scratch block the allocator never hands out
NULL_BLOCK = 0


class CacheGroup(NamedTuple):
    """Cache buffers that share a block table: ``layers`` planes (the
    buffers' leading dimension: one a layer, or one a (loop step, layer)
    for a model that runs its layers several times over shared weights,
    ``models/ouro.py``), ``buffers`` name -> row width, and the
    span a request holds — ``pages`` blocks whatever its length (a window
    layer's ring, a recurrent layer's state), or None for the whole
    context — and ``dtype``, the buffers' own where it is not the engine's
    serving dtype (a float32 state beside a bfloat16 cache)."""
    name: str
    layers: int
    buffers: dict
    pages: Optional[int] = None
    dtype: Optional[str] = None

    def num_blocks(self, icfg):
        """Blocks of this group's pool, the null block among them: the
        configured ``kv_blocks`` for the whole context, every slot's ring
        otherwise."""
        if self.pages is None:
            return icfg.kv_blocks
        return icfg.max_batch_slots * self.pages + 1

    def table_width(self, icfg):
        return icfg.max_blocks_per_seq if self.pages is None else self.pages


class BlockAllocator:
    """Host-side free list over the preallocated KV blocks of one cache
    group; ``pages_per_request`` is the fixed grant of a ring's pool (None:
    a request is granted its worst-case context).

    Pure Python bookkeeping — nothing here touches the device.  The
    scheduler allocates a sequence's whole worst-case block budget at
    admission (prompt bucket plus the generation cap), which makes
    admission the ONLY place an out-of-blocks condition can surface;
    mid-decode the table is already paid for.
    """

    def __init__(self, num_blocks, pages_per_request=None):
        assert num_blocks > 1, "need at least one block beyond the null block"
        self.num_blocks = int(num_blocks)
        self.pages_per_request = pages_per_request
        # LIFO free list, block 0 excluded (the null block)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        # pool-occupancy high-water mark (allocatable blocks in use at
        # once, across the run) — the capacity-planning receipt
        self.used_peak = 0

    @property
    def capacity(self):
        """Allocatable blocks (the null block is not allocatable)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        return self.capacity - len(self._free)

    def allocate(self, n):
        """``n`` block ids, or None when the pool cannot cover them (the
        caller defers admission; never a partial grant)."""
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        if self.used_blocks > self.used_peak:
            self.used_peak = self.used_blocks
        return taken

    def release(self, blocks):
        for b in blocks:
            assert b != NULL_BLOCK, "the null block is never released"
            self._free.append(int(b))


def init_cache_buffers(num_layers, num_blocks, block_size, row_widths,
                       dtype=jnp.float32):
    """One zero-initialized device buffer ``[layers, blocks, block, row]``
    per entry of ``row_widths``, in the layout the decode kernels read
    (see the module docstring)."""
    return tuple(jnp.zeros((num_layers, num_blocks, block_size, row), dtype)
                 for row in row_widths)


def init_kv_cache(num_layers, num_blocks, block_size, heads, head_dim,
                  dtype=jnp.float32):
    """The (k, v) buffers of a model that caches per-head keys and
    values: two buffers with rows of ``heads * head_dim``."""
    return init_cache_buffers(num_layers, num_blocks, block_size,
                              (heads * head_dim,) * 2, dtype)


def cache_bytes(num_layers, num_blocks, block_size, row_widths,
                dtype=jnp.float32):
    """Footprint of one engine's cache buffers, whatever their rows
    (capacity-planning aid)."""
    return num_layers * num_blocks * block_size * sum(row_widths) \
        * jnp.dtype(dtype).itemsize


def kv_cache_bytes(num_layers, num_blocks, block_size, heads, head_dim,
                   dtype=jnp.float32):
    """:func:`cache_bytes` of K+V buffers with rows of ``heads *
    head_dim``."""
    return cache_bytes(num_layers, num_blocks, block_size,
                       (heads * head_dim,) * 2, dtype)
