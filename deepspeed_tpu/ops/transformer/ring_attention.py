"""Ring attention: exact attention over sequence-sharded Q/K/V.

First-class long-context support (SURVEY §5.7: the 2020 reference's
long-sequence story was block-sparse attention + activation
checkpointing/offload; ring attention is the TPU-era upgrade called for by
the rebuild plan, SURVEY §7 step 7).  The sequence is sharded over the
``seq`` mesh axis; each device keeps its Q shard resident and the K/V
shards rotate around the ring via ``ppermute`` while a streaming
(flash-style) softmax accumulates the exact result:

    m, l, o ← running row-max, normalizer, unnormalized output
    for step t in 0..N-1:
        attend local Q against the currently-held K/V chunk
        rotate K/V to the next ring neighbor          [ICI ppermute]

Compute is O(s²/N) per device with only O(s/N) resident activations, the
per-chunk matmuls stay MXU-shaped, and XLA overlaps the ppermute with the
chunk compute (the collective-permute latency hides behind the attention
matmuls once chunks are big enough).  Backward is autodiff through the
scan: the K/V rotation transposes to the reverse rotation, giving the
standard ring-attention backward without hand-written communication.

Causality is handled per (q-shard, kv-chunk) pair from global positions:
chunks strictly above the diagonal contribute nothing (masked with a
finite -1e9 so gradients stay NaN-free).
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import SEQ_AXIS

NEG = -1e9


def _ring_attention_local(q, k, v, kpm, axis_name, nshards, causal, scale):
    """Per-shard body (inside shard_map): q/k/v are local chunks
    [b, s_loc, h, d]; kpm an additive [b, s_loc] key-padding-mask chunk
    (or None) that rotates around the ring with its K/V chunk."""
    me = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    qpos = me * s_loc + jnp.arange(s_loc)  # global query positions
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]

    q32 = q.astype(jnp.float32)
    if kpm is None:
        kpm = jnp.zeros((b, s_loc), jnp.float32)

    def step(carry, t):
        k_cur, v_cur, kpm_cur, m, l, o = carry
        src = (me - t) % nshards  # which chunk we hold this step
        kpos = src * s_loc + jnp.arange(s_loc)

        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32))
        scores = scores * scale
        scores = scores + kpm_cur[:, None, None, :].astype(jnp.float32)
        if causal:
            mask = kpos[None, :] <= qpos[:, None]  # [s_q, s_k]
            scores = jnp.where(mask[None, None], scores, NEG)

        chunk_max = jnp.max(scores, axis=-1)  # [b, h, sq]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32))

        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        kpm_next = jax.lax.ppermute(kpm_cur, axis_name, perm)
        return (k_next, v_next, kpm_next, new_m, l_new, o_new), None

    m0 = jnp.full((b, h, s_loc), NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    (_, _, _, m, l, o), _ = jax.lax.scan(step, (k, v, kpm, m0, l0, o0),
                                         jnp.arange(nshards))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [b, s_loc, h, d]


def ring_attention(q, k, v, mesh=None, axis_name=SEQ_AXIS, causal=False,
                   key_padding_mask=None, scale=None):
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    Args:
        q, k, v: ``[batch, seq, heads, head_dim]`` global arrays whose seq
            dim is (or will be) sharded over ``axis_name``.
        mesh: the device mesh (defaults to the engine-registered current
            mesh).
        causal: autoregressive masking using global positions.
        key_padding_mask: additive ``[batch, seq]`` (-inf at masked keys);
            its chunks rotate around the ring alongside K/V.

    Falls back to a single-device dense computation when the axis has size 1.
    """
    if mesh is None:
        from ...parallel.mesh import get_current_mesh

        mesh = get_current_mesh()
        assert mesh is not None, (
            "ring_attention needs a mesh (pass mesh= or initialize the "
            "engine, which registers the current mesh)")
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    nshards = shape.get(axis_name, 1)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    if nshards == 1:
        # single seq shard: same math, dense
        from .attention import reference_attention

        mask4 = (key_padding_mask[:, None, None, :]
                 if key_padding_mask is not None else None)
        # reference_attention hard-codes 1/sqrt(d); fold any custom scale
        # in by pre-scaling q so both paths compute the same scores
        q_eff = q * (scale * math.sqrt(d)) if scale != 1.0 / math.sqrt(d) \
            else q
        return reference_attention(q_eff, k, v, mask=mask4, causal=causal)

    body = partial(_ring_attention_local, axis_name=axis_name,
                   nshards=nshards, causal=causal, scale=scale)
    spec = P(None, axis_name)  # shard the seq dim (axis 1)
    if key_padding_mask is None:
        fn = shard_map(lambda q, k, v: body(q, k, v, None), mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec,
                           axis_names={axis_name}, check_vma=False)
        return fn(q, k, v)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, spec),
                       out_specs=spec, axis_names={axis_name},
                       check_vma=False)
    return fn(q, k, v, key_padding_mask)
