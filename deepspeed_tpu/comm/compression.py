"""Gradient-compression collectives: error-feedback 1-bit allreduce.

TPU-native re-design of the reference's MPI/cupy compressed allreduce
(``deepspeed/runtime/fp16/onebit_adam.py:104-228`` ``Compressed_Allreduce``
and ``runtime/custom_collectives.py``).  The algorithm is identical — each
worker sends only the sign of its (error-compensated) buffer plus one
scale; each "server" rank reduces one 1/world chunk and broadcasts the
re-compressed result — but the transport is XLA collectives over a named
mesh axis instead of mpi4py igather/allgather:

    phase 1 (worker→server):  all_to_all of packed sign chunks
                              + all_gather of worker scales
    phase 2 (server→worker):  all_gather of packed server signs + scales

Sign bits are hand-packed 8-per-uint8 before the collectives (the analog of
``cupy.packbits``), so the bytes on the wire are 1/32 of fp32 — this is the
point of the exercise on DCN-bound multi-pod meshes.  Everything is a pure
function usable inside ``shard_map`` and differentiable-free (runs in the
optimizer step, outside autodiff).
"""

import jax
import jax.numpy as jnp
import numpy as np

_BIT_WEIGHTS = np.asarray([128, 64, 32, 16, 8, 4, 2, 1], np.uint8)  # MSB-first


def pack_signs(bits):
    """[n] bool (True = +1) → [n/8] uint8, MSB-first like ``packbits``."""
    n = bits.shape[0]
    assert n % 8 == 0, f"sign buffer length {n} not divisible by 8"
    b = bits.reshape(n // 8, 8).astype(jnp.uint8)
    return (b * jnp.asarray(_BIT_WEIGHTS)).sum(-1).astype(jnp.uint8)


def unpack_signs(packed):
    """[m] uint8 → [m*8] ±1.0 float32, MSB-first."""
    bits = (packed[:, None] // jnp.asarray(_BIT_WEIGHTS, jnp.uint8)) % 2
    return bits.reshape(-1).astype(jnp.float32) * 2.0 - 1.0


def _compress(buf, error):
    """Error-feedback sign compression: returns (sign_bits_bool, scale,
    new_error).  scale = ||buf+err|| / sqrt(n); the quantization residual
    becomes the next round's error (reference ``onebit_adam.py:122-127``)."""
    comp = buf + error
    n = comp.shape[0]
    scale = jnp.linalg.norm(comp) / np.sqrt(n)
    sign_bits = comp >= 0
    signs = sign_bits.astype(jnp.float32) * 2.0 - 1.0
    new_error = comp - scale * signs
    return sign_bits, scale, new_error


def padded_size(n, world):
    """Smallest size >= ``n`` divisible by ``8*world`` — the alignment
    the packed-sign chunking needs (8 signs per uint8, one equal chunk
    per server rank).  Callers allocate their persistent error buffers
    at this size; :func:`compressed_allreduce` pads and trims the data
    buffer internally."""
    q = 8 * int(world)
    return -(-int(n) // q) * q


def compressed_allreduce(buf, worker_error, server_error, axis_name):
    """1-bit error-feedback mean-allreduce of ``buf`` over ``axis_name``.

    Args:
        buf: [n] fp32, ANY size — padded internally to
            ``padded_size(n, world)`` with zeros and trimmed on return
            (real flat-gradient sizes are rarely divisible by 8·world).
        worker_error: [padded_size(n, world)] fp32 worker residual
            (carried across steps; error feedback accumulates on the
            PADDED buffer, so its tail keeps the pad lanes' residual).
        server_error: [padded_size(n, world)/world] fp32 server residual
            for this rank's chunk.
        axis_name: mesh axis to reduce over (must be in manual shard_map).

    Returns ``(out, new_worker_error, new_server_error)`` with ``out``
    the [n] compressed approximation of ``mean(buf)`` — identical on
    all ranks; the error buffers stay padded-size.
    """
    world = jax.lax.axis_size(axis_name)
    n = buf.shape[0]
    n_pad = padded_size(n, world)
    assert worker_error.shape[0] == n_pad, (
        f"worker_error size {worker_error.shape[0]} must be "
        f"padded_size(n={n}, world={world}) = {n_pad}")
    assert server_error.shape[0] * world == n_pad, (
        f"server_error size {server_error.shape[0]} must be "
        f"padded_size(n={n}, world={world})/world = {n_pad // world}")
    if n_pad != n:
        buf = jnp.concatenate(
            [buf, jnp.zeros((n_pad - n,), buf.dtype)])

    # -- worker compression (reference :118-127) --
    sign_bits, worker_scale, new_worker_error = _compress(buf, worker_error)

    # -- phase 1: signs chunked to server ranks (reference igather :146-165) --
    packed = pack_signs(sign_bits)  # [n_pad/8] uint8
    chunks = packed.reshape(world, n_pad // 8 // world)
    # all_to_all: rank r ends up with [world, chunk] = everyone's chunk r
    recv = jax.lax.all_to_all(chunks[None], axis_name, split_axis=1,
                              concat_axis=0, tiled=False)[:, 0]
    scales = jax.lax.all_gather(worker_scale, axis_name)  # [world]

    # -- server reduce + re-compress (reference :174-193) --
    chunk_signs = jax.vmap(unpack_signs)(recv)  # [world, n/world] ±1
    compensated = jnp.einsum("w,wn->n", scales / world, chunk_signs)
    srv_bits, server_scale, new_server_error = _compress(compensated,
                                                         server_error)

    # -- phase 2: broadcast compressed server chunks (reference :202-214) --
    srv_packed = pack_signs(srv_bits)  # [n_pad/8/world] uint8
    all_packed = jax.lax.all_gather(srv_packed, axis_name)  # [world, n_pad/8/world]
    all_scales = jax.lax.all_gather(server_scale, axis_name)  # [world]
    out_signs = jax.vmap(unpack_signs)(all_packed)  # [world, n_pad/world]
    out = (out_signs * all_scales[:, None]).reshape(n_pad)
    return out[:n], new_worker_error, new_server_error


def compressed_allreduce_reference(bufs, worker_errors, server_errors):
    """Host (numpy) simulation of the same algorithm over ``world`` buffers;
    ground truth for tests.  Returns (out, new_worker_errors,
    new_server_errors)."""
    bufs = [np.asarray(b, np.float64) for b in bufs]
    world = len(bufs)
    n = bufs[0].shape[0]
    signs, scales, new_werrs = [], [], []
    for b, e in zip(bufs, worker_errors):
        comp = b + np.asarray(e, np.float64)
        scale = np.linalg.norm(comp) / np.sqrt(n)
        s = np.where(comp >= 0, 1.0, -1.0)
        new_werrs.append(comp - scale * s)
        signs.append(s)
        scales.append(scale)
    chunk = n // world
    outs, new_serrs = [], []
    for r in range(world):
        comp = sum(scales[w] / world * signs[w][r * chunk:(r + 1) * chunk]
                   for w in range(world))
        comp = comp + np.asarray(server_errors[r], np.float64)
        sscale = np.linalg.norm(comp) / np.sqrt(chunk)
        ss = np.where(comp >= 0, 1.0, -1.0)
        new_serrs.append(comp - sscale * ss)
        outs.append(sscale * ss)
    return np.concatenate(outs), new_werrs, new_serrs
