"""The two stream-mix kernels of ``ops/transformer/hyper_connection.py``
through Pallas' interpreter on the CPU: against their ``jax.numpy`` forms in
the same file, against the benchmark's plain reference (its own code, from
the papers), and the properties the maps must have."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import xing as reference
from deepspeed_tpu.ops.transformer import hyper_connection as hc

N, ITERS, EPS = 4, 20, 1e-6
MAPS = dict(n=N, eps=1e-6, sinkhorn_iters=ITERS, sinkhorn_eps=EPS,
            clamp=(-30.0, 30.0))
CFG = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": ITERS, "hc_eps": EPS,
       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}


def _inputs(tokens, width, seed=0, alpha=0.3, phi=0.05):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    # streams of different sizes, so that a mix that takes the wrong row shows
    x = jax.random.normal(k[0], (tokens, N, width)) \
        * jnp.asarray([1.0, 2.0, 0.5, 3.0])[None, :, None]
    raw = {"phi": phi * jax.random.normal(k[1], (N * width, 2 * N + N * N)),
           "bias": 0.5 * jax.random.normal(k[2], (2 * N + N * N,)),
           "alpha": alpha * (1.0 + 0.2 * jax.random.normal(k[3], (3,)))}
    y = jax.random.normal(k[4], (tokens, width))
    return x, raw, y


@pytest.mark.parametrize("tokens,width,tile", [
    (128, 128, 128), (200, 256, 128), (512, 128, 256), (5, 128, 128),
    (384, 384, 128)])
def test_kernels_are_their_plain_forms(tokens, width, tile):
    """A tile of tokens, tokens that fill no tile (padded inside), two tiles
    a step: ``u``, the maps and ``X'`` to float32 rounding."""
    x, raw, y = _inputs(tokens, width, seed=tokens)
    packed = hc.pack_maps(raw, N)
    flat = x.reshape(tokens, -1)
    u0, m0 = hc.mhc_pre_mix_xla(flat, packed, **MAPS)
    u1, m1 = hc.mhc_pre_mix(flat, packed, tile=tile, interpret=True, **MAPS)
    assert u1.shape == (tokens, width) and m1.shape == (tokens, 128)
    np.testing.assert_allclose(u1, u0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(m1, m0, rtol=2e-5, atol=2e-6)
    x0 = hc.mhc_post_res_mix_xla(flat, y, m0, n=N)
    x1 = hc.mhc_post_res_mix(flat, y, m0, n=N, tile=tile, interpret=True)
    assert x1.shape == flat.shape
    np.testing.assert_allclose(x1, x0, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_both_forms_are_the_references_equations(form):
    """Against ``benchmarks/reference/xing.py``, which shares no code with
    the op: the maps entry for entry, ``u``, and ``X'`` for a given ``y``."""
    tokens, width = 160, 128
    x, raw, y = _inputs(tokens, width, seed=3)
    h_pre, h_post, h_res = reference.connection_maps(raw, x, CFG)
    mixed, _ = reference.connected(raw, x, CFG, lambda u: (y, u))
    mixed = jnp.stack(mixed, axis=1)
    packed = hc.pack_maps(raw, N)
    flat = x.reshape(tokens, -1)
    if form == "pallas":
        u, maps = hc.mhc_pre_mix(flat, packed, interpret=True, **MAPS)
        out = hc.mhc_post_res_mix(flat, y, maps, n=N, interpret=True)
    else:
        u, maps = hc.mhc_pre_mix_xla(flat, packed, **MAPS)
        out = hc.mhc_post_res_mix_xla(flat, y, maps, n=N)
    pre, post, res = hc.unpack_maps(maps, N)
    np.testing.assert_allclose(pre, h_pre, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(post, h_post, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(res, h_res, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        u, jnp.sum(h_pre[:, :, None] * x, axis=1), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out.reshape(x.shape), mixed, rtol=2e-5,
                               atol=2e-5)
    # the maps differ by token, and 2 sigma is not sigma
    assert float(jnp.std(pre, axis=0).min()) > 0.01
    assert float(post.max()) > 1.0 and float(post.min()) > 0.0
    # nothing but the 2n + n^2 values in the 128 lanes
    used = np.zeros(128, bool)
    for g in range(2 + N):
        used[8 * g:8 * g + N] = True
    assert not np.asarray(maps)[:, ~used].any()


@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_h_res_is_doubly_stochastic_after_twenty_iterations(form):
    tokens, width = 256, 128
    x, raw, _ = _inputs(tokens, width, seed=5, alpha=0.2)
    packed = hc.pack_maps(raw, N)
    flat = x.reshape(tokens, -1)
    pre_mix = (lambda **kw: hc.mhc_pre_mix(flat, packed, interpret=True,
                                           **kw)) \
        if form == "pallas" else (lambda **kw: hc.mhc_pre_mix_xla(
            flat, packed, **kw))
    _, _, res = hc.unpack_maps(pre_mix(**MAPS)[1], N)
    assert float(jnp.abs(res.sum(axis=1) - 1.0).max()) < 1e-5
    assert float(jnp.abs(res.sum(axis=2) - 1.0).max()) < 1e-5
    assert float(res.min()) > 0.0
    # one iteration is not there yet: the twenty are all run
    _, _, once = hc.unpack_maps(
        pre_mix(**dict(MAPS, sinkhorn_iters=1))[1], N)
    assert float(jnp.abs(once.sum(axis=2) - 1.0).max()) > 1e-3


@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_the_clamp_keeps_the_exponential_finite(form):
    """``alpha_res`` of 100 puts ``R`` far past 88, where ``exp`` in float32
    is infinite: clamped at +-30 every map is finite and ``H_res`` still
    normalised; without the clamp it is not."""
    tokens, width = 128, 128
    x, raw, _ = _inputs(tokens, width, seed=7)
    raw = dict(raw, alpha=jnp.asarray([0.3, 0.3, 100.0]))
    packed = hc.pack_maps(raw, N)
    flat = x.reshape(tokens, -1)

    def maps(clamp):
        kw = dict(MAPS, clamp=clamp)
        if form == "pallas":
            return hc.mhc_pre_mix(flat, packed, interpret=True, **kw)
        return hc.mhc_pre_mix_xla(flat, packed, **kw)

    u, clamped = maps((-30.0, 30.0))
    assert bool(jnp.isfinite(clamped).all()) and bool(jnp.isfinite(u).all())
    _, _, res = hc.unpack_maps(clamped, N)
    assert float(jnp.abs(res.sum(axis=1) - 1.0).max()) < 1e-3
    _, free = maps((-1e30, 1e30))
    assert not bool(jnp.isfinite(free).all())


def test_packed_weights_hold_every_value_in_its_group():
    x, raw, _ = _inputs(8, 128, seed=9)
    packed = hc.pack_maps(raw, N)
    assert packed["phi_t"].shape == (hc.map_rows(N), N * 128) == (48, 512)
    assert packed["affine"].shape == (48, 2)
    phi_t, affine = np.asarray(packed["phi_t"]), np.asarray(packed["affine"])
    phi, bias, alpha = (np.asarray(raw[k]) for k in ("phi", "bias", "alpha"))
    for i in range(N):
        np.testing.assert_array_equal(phi_t[i], phi[:, i])            # pre
        np.testing.assert_array_equal(phi_t[8 + i], phi[:, N + i])    # post
        assert tuple(affine[i]) == (alpha[0], bias[i])
        assert tuple(affine[8 + i]) == (alpha[1], bias[N + i])
        for j in range(N):
            k = 2 * N + N * i + j
            np.testing.assert_array_equal(phi_t[16 + 8 * i + j], phi[:, k])
            assert tuple(affine[16 + 8 * i + j]) == (alpha[2], bias[k])
    unused = [r for r in range(48) if r % 8 >= N]
    assert not phi_t[unused].any() and not affine[unused].any()
    # bfloat16 weights widen exactly
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), raw)
    again = hc.pack_maps(low, N)
    assert again["phi_t"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(again["phi_t"])[0],
        np.asarray(low["phi"].astype(jnp.float32))[:, 0])


@pytest.mark.parametrize("width,n", [(192, 4), (3584, 9), (100, 4)])
def test_a_stream_that_does_not_tile_is_refused(width, n):
    with pytest.raises(ValueError, match="cannot tile"):
        hc.check_tpu_geometry(width, n)
    hc.check_tpu_geometry(3584, 4)           # the published widths do
    hc.check_tpu_geometry(3584, 4, tokens=8192)
    with pytest.raises(ValueError, match="tokens=32 must be a multiple"):
        hc.check_tpu_geometry(3584, 4, tokens=32)


def test_one_log_line_a_traced_geometry(caplog):
    from deepspeed_tpu.utils.logging import logger
    x, raw, _ = _inputs(40, 128, seed=11)
    packed = hc.pack_maps(raw, N)
    flat = x.reshape(40, -1)
    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            for _ in range(3):      # the jit's cache answers the later calls
                hc.mhc_pre_mix(flat, packed, interpret=True, **MAPS)
            hc.mhc_pre_mix_xla(flat, packed, **MAPS)
    finally:
        logger.propagate = False
    lines = [r.getMessage() for r in caplog.records
             if "hyper_connection geometry" in r.getMessage()]
    assert lines == [
        "hyper_connection geometry: tokens=40 n=4 width=128 tile=128 "
        "(pallas)",
        "hyper_connection geometry: tokens=40 n=4 width=128 tile=None (xla)"]
