"""Grouped matrix product over rows sorted by group (Pallas/Mosaic): the
expert layer's ``lhs[rows of group g] @ rhs[g]`` with no capacity and no
padding per group.

The kernel is jax's ``megablox`` grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``): work tiles are laid out
from the group sizes on the device (scalar prefetch), a tile that straddles
two groups is visited once for each with the other's rows masked, and the
grid's length is the number of tiles that hold rows — so the time follows
the rows present, whatever the imbalance.  ``group_sizes`` may name more
groups than ``rhs`` holds: rows of the groups past ``rhs``'s (the experts
other chips hold) are not computed and come back zero.  Off the TPU the
same kernel runs through Pallas' interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm


def _fit(size, tile, unit=128):
    """The largest multiple of ``unit`` up to ``tile`` that divides
    ``size`` (``size`` itself where none does: a block as large as the
    array is always allowed)."""
    for t in range(min(tile, size) // unit * unit, 0, -unit):
        if size % t == 0:
            return t
    return size


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_grouped_matmul(lhs, rhs, group_sizes, *, tiling=(128, 1024, 512),
                       interpret=False):
    """``out[r] = lhs[r] @ rhs[group of row r]`` for ``lhs [m, k]`` whose
    rows are sorted by group, ``rhs [groups held, k, n]`` and
    ``group_sizes [groups]`` (int32; the held groups first).  ``tiling``
    is the (rows, k, n) tile; ``m`` is padded up to whole row tiles here.
    The jitted function's name is the kernel's name in a device trace.
    """
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = tiling[0]
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _megablox_gmm.__wrapped__(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=lhs.dtype,
        tiling=(tm, _fit(k, tiling[1]), _fit(n, tiling[2])),
        group_offset=jnp.int32(0), interpret=interpret)
    return out[:m] if pad else out
