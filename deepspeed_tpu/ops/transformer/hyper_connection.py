"""Manifold-constrained hyper-connections (mHC): a residual stream that is
``n`` streams, mixed around every sublayer by maps made from the stream
itself (Pallas/Mosaic, and the same equations in plain ``jax.numpy``).

For the stream ``X [tokens, n, C]`` (float32; held as ``[tokens, n C]``, the
``n`` rows side by side along the lanes — a ``[tokens, n, C]`` array is tiled
over its last two dimensions on a TPU, and making tiles of tokens out of it
is a copy of the whole stream) and one sublayer ``F`` with its
``Phi [n C, 2n + n^2]``, ``b [2n + n^2]`` and ``alpha_pre, alpha_post,
alpha_res`` (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606):

1. ``x^ = vec(X) / sqrt(mean(vec(X)^2) + eps)`` — one RMS over all ``n C``
   values, no learned scale;
2. ``[p ; q ; r] = x^ Phi`` (widths ``n``, ``n``, ``n^2``), float32 at
   ``HIGHEST``;
3. ``H_pre = sigmoid(alpha_pre p + b_pre)``, ``H_post = 2 sigmoid(alpha_post
   q + b_post)``;
4. ``R = clip(alpha_res mat(r) + b_res, lo, hi)``, ``M = exp(R)``, then
   ``iters`` times: every row / (its sum + ``sinkhorn_eps``), every column /
   (its sum + ``sinkhorn_eps``); ``H_res = M``, doubly stochastic;
5. ``u = sum_i H_pre[i] X_i``; the caller computes ``y = F(norm(u))``;
6. ``X'_i = sum_j H_res[i, j] X_j + H_post[i] y``.

:func:`mhc_pre_mix` is steps 1-5: ONE pass over the stream, which it reads
once as ``[tokens, n C]`` tiles; the projection is one product on the matrix
unit with the tokens along the lanes of its result, ``Phi^T [rows, n C] . X^T``,
so that the ``n x n`` Sinkhorn iterations are dense vector arithmetic over a
tile's tokens (with a token a sublane row they would fill ``n`` lanes of 128);
the maps are transposed back on the way out, ``u`` is mixed from the tile
still in VMEM, and the maps leave as ``[tokens, 128]`` for
:func:`mhc_post_res_mix`, step 6: one pass that reads ``X``, ``y`` and the
maps and writes ``X'`` over ``X`` (aliased: in place where the caller's
buffer is donated).  Left to XLA the maps are 40 two-line normalisations of
a 4 x 4 matrix a sublayer and the mixes three or more passes over 57 KB a
token.

The weights come PACKED (:func:`pack_maps`, made once by a model's
``prepare_params``): ``phi_t [rows, n C]`` float32 with the map values in
groups of eight rows — group 0 ``H_pre``, group 1 ``H_post``, group ``2 + i``
row ``i`` of ``H_res``, each group's first ``n`` rows used — so that every
slice the kernel takes is whole sublane tiles, and ``affine [rows, 2]``: a
row's ``alpha`` and ``b``.  The maps a token gets have the same layout along
their 128 lanes (:func:`unpack_maps`).

``*_xla`` are the plain forms of the same two steps on the same packed
weights (the CPU tests hold the kernels to them, and a program whose step is
a few rows may take them); off the TPU the kernels run through Pallas'
interpreter.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.logging import logger

_LANES, _SUBLANES = 128, 8
# tokens a grid step mixes: a [128, 4 * 3584] float32 tile is 7.3 MB, twice
# for the pipeline and, in the post mix, twice again for the output
TILE = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def map_rows(n):
    """Rows of the packed projection: ``2 + n`` groups of eight."""
    return _SUBLANES * (2 + n)


def check_tpu_geometry(width, n, tokens=None):
    """Raise for a stream the kernels cannot tile on a TPU: a stream's
    ``width`` fills whole 128-lane tiles, a map's ``n`` values fit one
    group of eight sublanes (their packed rows one lane tile), and — where
    ``tokens`` is given — the tokens fill whole 128-row tiles (the maps are
    transposed in 128 x 128 pieces; a step of a few rows takes the
    ``jax.numpy`` forms)."""
    if width % _LANES or not 1 <= n <= _SUBLANES or map_rows(n) > _LANES:
        raise ValueError(
            f"hyper_connection cannot tile this stream on TPU: width="
            f"{width} must be a multiple of {_LANES} and n={n} at most "
            f"{_SUBLANES}")
    if tokens is not None and tokens % _LANES:
        raise ValueError(
            f"hyper_connection cannot tile this stream on TPU: tokens="
            f"{tokens} must be a multiple of {_LANES} (mhc_pre_mix_xla / "
            f"mhc_post_res_mix_xla take any number)")


def pack_maps(hc, n):
    """``{"phi_t" [rows, n C], "affine" [rows, 2]}`` in float32 from a
    sublayer's ``{"phi" [n C, 2n + n^2], "bias" [2n + n^2], "alpha" [3]}``
    (module docstring: groups of eight rows, ``alpha`` beside ``b``)."""
    phi = hc["phi"].astype(jnp.float32)
    bias = hc["bias"].astype(jnp.float32)
    alpha = hc["alpha"].astype(jnp.float32)
    assert phi.shape[1] == 2 * n + n * n, (phi.shape, n)
    # column k of phi -> its packed row: pre i -> i, post i -> 8 + i,
    # res (i, j) -> 16 + 8 i + j
    group = jnp.concatenate([jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.int32),
                             2 + jnp.repeat(jnp.arange(n), n)])
    row = _SUBLANES * group + jnp.concatenate(
        [jnp.arange(n), jnp.arange(n), jnp.tile(jnp.arange(n), n)])
    phi_t = jnp.zeros((map_rows(n), phi.shape[0]), jnp.float32).at[row].set(
        phi.T)
    affine = jnp.zeros((map_rows(n), 2), jnp.float32).at[row].set(
        jnp.stack([alpha[jnp.minimum(group, 2)], bias], axis=1))
    return {"phi_t": phi_t, "affine": affine}


def unpack_maps(maps, n):
    """``(H_pre [tokens, n], H_post [tokens, n], H_res [tokens, n, n])`` out
    of the ``[tokens, 128]`` maps a pre mix returns."""
    groups = maps[:, :map_rows(n)].reshape(maps.shape[0], 2 + n, _SUBLANES)
    return groups[:, 0, :n], groups[:, 1, :n], groups[:, 2:, :n]


def _sinkhorn(rows, iters, eps):
    """``rows``: the ``n`` rows of ``M``, each ``[8, tokens]`` with the
    columns along the sublanes (zero past ``n``): ``iters`` times every row
    over its sum, then every column over its."""
    def once(_, rows):
        rows = tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps)
                     for r in rows)
        columns = functools.reduce(jnp.add, rows) + eps
        return tuple(r / columns for r in rows)
    return jax.lax.fori_loop(0, iters, once, tuple(rows))


def _pre_kernel(x_ref, phi_ref, affine_ref, u_ref, maps_ref, *, n, width,
                eps, iters, sinkhorn_eps, clamp):
    x = x_ref[...]                                     # [tile, n * width]
    tile = x.shape[0]
    # tokens along the lanes from here: [rows, tile]
    p = jax.lax.dot_general(phi_ref[...], x, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    ss = jnp.sum(x * x, axis=1, keepdims=True)         # [tile, 1]
    ss = jnp.broadcast_to(ss, (tile, _LANES)).T[:1]    # [1, tile]
    r = jax.lax.rsqrt(ss * (1.0 / (n * width)) + eps)
    a = affine_ref[:, 0:1] * (p * r) + affine_ref[:, 1:2]
    used = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0) < n

    def group(g):
        return a[_SUBLANES * g:_SUBLANES * (g + 1)]

    pre = jnp.where(used, jax.nn.sigmoid(group(0)), 0.0)
    post = jnp.where(used, 2.0 * jax.nn.sigmoid(group(1)), 0.0)
    res = _sinkhorn(
        [jnp.where(used, jnp.exp(jnp.clip(group(2 + i), *clamp)), 0.0)
         for i in range(n)], iters, sinkhorn_eps)
    rows = map_rows(n)
    maps = jnp.concatenate(
        [pre, post, *res, jnp.zeros((_LANES - rows, tile), jnp.float32)],
        axis=0).T                                      # [tile, 128]
    maps_ref[...] = maps
    u = maps[:, 0:1] * x[:, :width]
    for i in range(1, n):
        u = u + maps[:, i:i + 1] * x[:, i * width:(i + 1) * width]
    u_ref[...] = u


def _post_kernel(x_ref, y_ref, maps_ref, out_ref, *, n, width):
    """Eight tokens and one lane tile at a time: the ``n`` streams' values,
    ``y``'s and the ``n`` sums of a step are single registers, the map values
    a token one lane broadcast each for all the lane tiles of its row."""
    def tokens(r, carry):
        rows = pl.ds(pl.multiple_of(r * _SUBLANES, _SUBLANES), _SUBLANES)
        maps = maps_ref[rows, :]

        def weight(lane):       # a map value a token, along the lanes
            return jnp.broadcast_to(maps[:, lane:lane + 1],
                                    (_SUBLANES, _LANES))

        post = [weight(_SUBLANES + i) for i in range(n)]
        res = [[weight(_SUBLANES * (2 + i) + j) for j in range(n)]
               for i in range(n)]
        for tile in range(width // _LANES):
            def lanes(stream):
                return pl.ds(stream * width + tile * _LANES, _LANES)
            y = y_ref[rows, lanes(0)]
            streams = [x_ref[rows, lanes(j)] for j in range(n)]
            for i in range(n):
                acc = post[i] * y
                for j in range(n):
                    acc = acc + res[i][j] * streams[j]
                out_ref[rows, lanes(i)] = acc
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // _SUBLANES, tokens, 0)


def _tiles(tokens, tile):
    """``(tile, padded tokens)``: whole 128-row tiles (the maps are
    transposed in 128 x 128 pieces).  Tokens that fill no tile are padded
    under the interpreter alone: compiled, a padded step of 32 rows read
    wrong on the chip (PERF.md section 6, PR 47) and is refused."""
    tile = min(tile, -(-tokens // _LANES) * _LANES)
    assert tile % _SUBLANES == 0, tile     # the post mix steps eight tokens
    return tile, -(-tokens // tile) * tile


def _params(interpret):
    return {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=100 * 1024 * 1024)}


def _log(tokens, n, width, tile, form):
    # once a geometry: the jit's cache answers a model's later sublayers
    logger.info("hyper_connection geometry: tokens=%d n=%d width=%d tile=%s "
                "(%s)", tokens, n, width, tile, form)


@functools.partial(jax.jit, static_argnames=(
    "n", "eps", "sinkhorn_iters", "sinkhorn_eps", "clamp", "tile",
    "interpret"))
def mhc_pre_mix(x, packed, *, n, eps, sinkhorn_iters, sinkhorn_eps, clamp,
                tile=TILE, interpret=False):
    """Steps 1-5 for ``x [tokens, n * width]`` (float32) and a sublayer's
    packed weights: ``(u [tokens, width], maps [tokens, 128])``, both
    float32.  The jitted function's name is the kernel's in a device
    trace."""
    tokens, width = x.shape[0], x.shape[1] // n
    assert x.dtype == jnp.float32 and x.shape[1] == n * width, x.shape
    if not interpret:
        check_tpu_geometry(width, n, tokens)
    tile, padded = _tiles(tokens, tile)
    _log(tokens, n, width, tile, "pallas")
    if padded != tokens:
        x = jnp.pad(x, ((0, padded - tokens), (0, 0)))
    rows = map_rows(n)
    kernel = functools.partial(
        _pre_kernel, n=n, width=width, eps=eps, iters=sinkhorn_iters,
        sinkhorn_eps=sinkhorn_eps, clamp=clamp)
    u, maps = pl.pallas_call(
        kernel,
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
                  pl.BlockSpec((rows, n * width), lambda t: (0, 0)),
                  pl.BlockSpec((rows, 2), lambda t: (0, 0))],
        out_specs=[pl.BlockSpec((tile, width), lambda t: (t, 0)),
                   pl.BlockSpec((tile, _LANES), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded, width), jnp.float32),
                   jax.ShapeDtypeStruct((padded, _LANES), jnp.float32)],
        interpret=interpret, name="mhc_pre_mix", **_params(interpret),
    )(x, packed["phi_t"], packed["affine"])
    return (u, maps) if padded == tokens else (u[:tokens], maps[:tokens])


@functools.partial(jax.jit, static_argnames=("n", "tile", "interpret"))
def mhc_post_res_mix(x, y, maps, *, n, tile=TILE, interpret=False):
    """Step 6: ``X' [tokens, n * width]`` for ``x`` (float32), the
    sublayer's output ``y [tokens, width]`` (float32) and the maps of
    :func:`mhc_pre_mix`; ``x``'s buffer is the result's."""
    tokens, width = y.shape
    assert x.dtype == y.dtype == jnp.float32, (x.dtype, y.dtype)
    assert x.shape == (tokens, n * width), (x.shape, y.shape)
    if not interpret:
        check_tpu_geometry(width, n, tokens)
    tile, padded = _tiles(tokens, tile)
    if padded != tokens:
        pad = ((0, padded - tokens), (0, 0))
        x, y, maps = (jnp.pad(a, pad) for a in (x, y, maps))
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n, width=width),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
                  pl.BlockSpec((tile, width), lambda t: (t, 0)),
                  pl.BlockSpec((tile, _LANES), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((tile, n * width), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, n * width), jnp.float32),
        input_output_aliases={0: 0},
        interpret=interpret, name="mhc_post_res_mix", **_params(interpret),
    )(x, y, maps)
    return out[:tokens] if padded != tokens else out


# -- the same two steps, plain ------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n", "eps", "sinkhorn_iters", "sinkhorn_eps", "clamp"))
def mhc_pre_mix_xla(x, packed, *, n, eps, sinkhorn_iters, sinkhorn_eps,
                    clamp):
    """:func:`mhc_pre_mix` in ``jax.numpy`` on the same packed weights."""
    tokens, width = x.shape[0], x.shape[1] // n
    _log(tokens, n, width, None, "xla")
    x_hat = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    a = jnp.matmul(x_hat, packed["phi_t"].T, precision=_HIGHEST) \
        * packed["affine"][:, 0] + packed["affine"][:, 1]
    groups = a.reshape(tokens, 2 + n, _SUBLANES)[:, :, :n]
    pre = jax.nn.sigmoid(groups[:, 0])
    post = 2.0 * jax.nn.sigmoid(groups[:, 1])
    res = jnp.exp(jnp.clip(groups[:, 2:], *clamp))
    for _ in range(sinkhorn_iters):
        res = res / (res.sum(axis=2, keepdims=True) + sinkhorn_eps)
        res = res / (res.sum(axis=1, keepdims=True) + sinkhorn_eps)
    packed_maps = jnp.pad(
        jnp.concatenate([pre[:, None], post[:, None], res], axis=1),
        ((0, 0), (0, 0), (0, _SUBLANES - n))).reshape(tokens, -1)
    maps = jnp.pad(packed_maps, ((0, 0), (0, _LANES - map_rows(n))))
    # elementwise, so that the mix stays float32 whatever the ambient
    # precision of a product is
    return jnp.sum(pre[:, :, None] * x.reshape(tokens, n, width), axis=1), \
        maps


@functools.partial(jax.jit, static_argnames=("n",))
def mhc_post_res_mix_xla(x, y, maps, *, n):
    """:func:`mhc_post_res_mix` in ``jax.numpy``."""
    _, post, res = unpack_maps(maps, n)
    streams = x.reshape(x.shape[0], 1, n, -1)
    return (jnp.sum(res[:, :, :, None] * streams, axis=2)
            + post[:, :, None] * y[:, None]).reshape(x.shape)
