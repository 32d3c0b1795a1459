"""Communication backend: named-axis XLA collectives.

The reference scatters ~80 raw ``torch.distributed`` call sites across the
codebase (SURVEY §2.6; e.g. ``deepspeed/runtime/engine.py:836-850``,
``zero/stage2.py:727-738``).  The TPU rebuild routes *every* collective
through this one module, expressed over named mesh axes so XLA lowers them
onto ICI (intra-slice) or DCN (cross-slice) links and overlaps them with
compute via its latency-hiding scheduler — there are no streams or process
groups to manage.

Inside ``shard_map`` these are per-shard collectives over the named axis;
under plain ``jit`` + sharding annotations XLA inserts the equivalents
automatically.  Mapping from the reference's NCCL verbs:

==============================  ==========================================
reference (torch.distributed)   here (jax.lax over a named mesh axis)
==============================  ==========================================
all_reduce                      psum / pmean / pmax
reduce (to owner rank)          psum_scatter (owner = shard index)
reduce_scatter                  psum_scatter
all_gather                      all_gather
broadcast (param sync)          unnecessary under SPMD (same program+init)
broadcast (pipe p2p)            ppermute
all_to_all (sequence parallel)  all_to_all
barrier                         block_until_ready on a psum token
==============================  ==========================================
"""

from jax import lax


def psum(x, axis_name):
    """Sum-allreduce over a mesh axis (reference: dist.all_reduce SUM)."""
    return lax.psum(x, axis_name)


def pmean(x, axis_name):
    """Mean-allreduce (reference: all_reduce followed by /= world_size)."""
    return lax.pmean(x, axis_name)


def pmax(x, axis_name):
    """Max-allreduce (reference: dist.all_reduce MAX, e.g. overflow flags)."""
    return lax.pmax(x, axis_name)


def pmin(x, axis_name):
    return lax.pmin(x, axis_name)


def reduce_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    """Sum-reduce then scatter shards over the axis (reference: dist.reduce_scatter,
    ``zero/stage1.py:572`` / the ZeRO-2 reduce-to-owner pattern ``stage2.py:727``)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def all_gather(x, axis_name, axis=0, tiled=True):
    """Gather shards from every member of the axis (reference: dist.all_gather,
    e.g. ZeRO param reassembly ``stage2.py:1444-1477``)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute(x, axis_name, perm):
    """Point-to-point send/recv ring (reference: pipeline p2p as 2-rank
    broadcast groups, ``runtime/pipe/p2p.py:31-55``)."""
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    """All-to-all (no reference analog; used by Ulysses-style sequence parallelism)."""
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


def axis_index(axis_name):
    """This shard's coordinate along the axis (reference: dist.get_rank(group))."""
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    """Size of the axis (reference: dist.get_world_size(group))."""
    return lax.axis_size(axis_name)
