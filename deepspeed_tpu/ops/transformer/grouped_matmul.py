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
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _megablox_tgmm


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

    Differentiable in ``lhs`` and ``rhs`` (``_product``'s rule): d lhs is
    the same kernel on ``rhs`` transposed, d rhs the transposed grouped
    product over the row groups (megablox's ``tgmm``); rows of the groups
    past ``rhs``'s get a zero d lhs and add nothing to d rhs.
    """
    m = lhs.shape[0]
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _product(lhs, rhs, group_sizes.astype(jnp.int32), tiling,
                   interpret)
    return out[:m] if pad else out


def _gmm(lhs, rhs, group_sizes, tiling, interpret, transpose_rhs=False):
    """The kernel's body (its ``jit`` wrapper left off: the caller's is the
    name in a trace) with the k and n tiles fitted to this product."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _megablox_gmm.__wrapped__(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
        tiling=(tiling[0], _fit(k, tiling[1]), _fit(n, tiling[2])),
        group_offset=jnp.int32(0), transpose_rhs=transpose_rhs,
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _product(lhs, rhs, group_sizes, tiling, interpret):
    return _gmm(lhs, rhs, group_sizes, tiling, interpret)


def _product_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return (_gmm(lhs, rhs, group_sizes, tiling, interpret),
            (lhs, rhs, group_sizes))


def _product_bwd(tiling, interpret, res, grad):
    lhs, rhs, group_sizes = res
    return (moe_grouped_matmul_bwd_lhs(grad, rhs, group_sizes, tiling=tiling,
                                       interpret=interpret),
            moe_grouped_matmul_bwd_rhs(lhs, grad, group_sizes, rhs.shape[0],
                                       tiling=tiling, interpret=interpret),
            None)


_product.defvjp(_product_fwd, _product_bwd)


# the backward's two kernels, each under a jit of its own name: a trace
# tells them from the forward's, and one pattern finds all three
@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_grouped_matmul_bwd_lhs(grad, rhs, group_sizes, *, tiling, interpret):
    """``d lhs[r] = grad[r] @ rhs[group of row r]ᵀ``: zero for the rows of
    the groups past ``rhs``'s."""
    return _gmm(grad, rhs, group_sizes, tiling, interpret,
                transpose_rhs=True).astype(grad.dtype)


@functools.partial(jax.jit, static_argnames=("held", "tiling", "interpret"))
def moe_grouped_matmul_bwd_rhs(lhs, grad, group_sizes, held, *, tiling,
                               interpret):
    """``d rhs[g] = lhs[rows of g]ᵀ @ grad[rows of g]`` for the ``held``
    first groups."""
    k, n = lhs.shape[1], grad.shape[1]
    return _megablox_tgmm.__wrapped__(
        lhs.swapaxes(0, 1), grad, group_sizes,
        preferred_element_type=lhs.dtype,
        tiling=(tiling[0], _fit(k, tiling[1]), _fit(n, tiling[2])),
        group_offset=jnp.int32(0), num_actual_groups=held,
        interpret=interpret)
