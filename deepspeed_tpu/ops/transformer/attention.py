"""Attention dispatch: Pallas flash attention on TPU, jnp reference elsewhere.

This is the TPU-native stand-in for the reference's fused attention kernel
chain (strided-batch GEMMs + fused scale/mask softmax,
``csrc/transformer/softmax_kernels.cu``, ``ds_transformer_cuda.cpp:145``).
The Pallas path (``ops/transformer/flash_attention.py``) computes attention
blockwise without materializing the [s, s] score matrix (flash-attention
style), which is both the memory story (long sequences) and the HBM-
bandwidth story on TPU.
"""

import math

import jax
import jax.numpy as jnp

from ..op_common import random_keep

# Dispatch policy, measured on v5e (BERT-large shapes, h16 d64):
# - seq >= 512: the tuned-block Pallas kernel wins (seq 512: 5.4 vs 6.8 ms
#   fwd+bwd; seq 2048: 7.3 vs 15.8 ms — see flash_attention._auto_blocks,
#   the authoritative tuning record) AND never materializes the [s, s]
#   score tensor, which is also what lifts the memory ceiling for long
#   sequences;
# - short self-attention (128, 256): the kernels where a grid step can
#   hold two or more of the device's batch rows (``rows_per_step``).  One
#   layer's QKV GEMM + attention + output GEMM, forward and backward, at
#   b112 s128 with mask and dropout 0.1 (builder's microbenchmark, chip
#   runs of PR 36): XLA's batched attention 4.61 ms; the kernels at 1 row
#   a step 3.17, 2 rows 2.88, 4 rows 2.69, 8 rows 2.59, 16 rows 2.57.
#   The cell ``bert_large.seq128``, tokens/s/chip: 61,987 through XLA's
#   attention → 70,276 with eight rows a step looped in the kernel
#   (ledger, PR 36: +13.4% on six pairs, step 231.20 → 203.94 ms) →
#   72,833 with the rows spelled out (chip runs of PR 37, four pairs:
#   +17.5%, step 196.76 ms, ``train_mfu`` 59.2 → 69.5);
# - the rest under 512 stays with XLA's batched attention: one-row calls
#   (every serving prefill bucket), a query-gathered layer (s != kv_len),
#   heads that fill no lane tile (25 heads, key width 192), a data mesh
#   that leaves a device one row.  History: on the old [b·h, s, d] operand
#   layout (a head transpose each way, one head of one row a step) the
#   kernels LOST at seq 128, 344 against 416 samples/s end to end.
PALLAS_MIN_SEQ = 512
PALLAS_MIN_SCORE_BYTES = 2 * 1024 ** 3


def _use_pallas(q, k):
    from ...parallel.mesh import (DATA_AXIS, MODEL_AXIS, current_platform,
                                  get_current_mesh)

    shapes_ok = (current_platform() == "tpu" and q.shape[1] >= 128
                 and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
                 and q.shape[-1] % 64 == 0)
    if q.shape[1] >= PALLAS_MIN_SEQ and k.shape[1] >= PALLAS_MIN_SEQ:
        return shapes_ok
    if not shapes_ok:
        return False
    b, sq, h, d = q.shape
    if sq == k.shape[1]:
        # a short self-attention: the kernels where a grid step can be
        # filled with several of the device's batch rows
        from .flash_attention import rows_per_step

        split = _KernelSplit()
        if rows_per_step(split.local(DATA_AXIS, b), sq,
                         split.local(MODEL_AXIS, h), d) >= 2:
            return True
    score_bytes = 4 * b * h * sq * k.shape[1]
    # shapes here are logical/global; under data-parallel GSPMD each
    # chip materializes 1/dp of the batch — budget the PER-DEVICE size
    mesh = get_current_mesh()
    if mesh is not None:
        score_bytes //= max(mesh.shape.get("data", 1), 1)
    return score_bytes > PALLAS_MIN_SCORE_BYTES


class _KernelSplit:
    """How a kernel call is split over the current mesh: over every axis
    no enclosing ``shard_map`` is manual over yet (``auto``), where the
    axis divides the dimension."""

    def __init__(self):
        from ...parallel.mesh import get_current_mesh

        self.mesh = get_current_mesh()
        self.context = jax.sharding.get_abstract_mesh()
        self.auto = frozenset()
        if self.mesh is not None and self.mesh.size > 1:
            self.auto = frozenset(self.mesh.axis_names) - frozenset(
                () if self.context.empty else self.context.manual_axes)

    def axis_for(self, axis, dim):
        """``axis`` if a dimension of ``dim`` is split over it, else None."""
        if axis not in self.auto:
            return None
        n = self.mesh.shape[axis]
        return axis if n > 1 and dim % n == 0 else None

    def local(self, axis, dim):
        """What one device's call holds of a dimension of ``dim``."""
        return (dim // self.mesh.shape[axis] if self.axis_for(axis, dim)
                else dim)


def shard_kernel_over_mesh(kernel, q, k=None, v=None, kv_mask=None, seed=None,
                           shard_heads=True):
    """``kernel(q, k, v, kv_mask, seed)`` on each device's shard of the
    batch (over ``data``) and of the heads (over ``model``).  ``q`` alone
    (``k`` and ``v`` None) is a fused projection [b, s, 3, h, d].

    XLA cannot partition a Mosaic kernel call: on a mesh of several
    devices it refuses to lower one anywhere a mesh axis is still left to
    GSPMD ("Mosaic kernels cannot be automatically partitioned") — at the
    top level of the step, and just as much inside the engine's
    ``shard_map`` bodies, which are manual over ``data`` alone.  So the
    call is wrapped in a ``shard_map`` over every axis that is not manual
    yet.  Attention is independent per (sample, head), so each shard's
    kernel needs no collective.  A batch or head count the axis does not
    divide stays replicated over it.

    The shards share ``seed``: in-kernel dropout masks repeat across batch
    shards at equal local (sample, head) index — as the replicated key of
    the engine's bucketed exchange already makes XLA dropout do.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import DATA_AXIS, MODEL_AXIS

    split = _KernelSplit()
    mesh, context, auto = split.mesh, split.context, split.auto
    if not auto:
        return kernel(q, k, v, kv_mask, seed)
    batch_axis = split.axis_for(DATA_AXIS, q.shape[0])
    head_axis = (split.axis_for(MODEL_AXIS, q.shape[-2]) if shard_heads
                 else None)
    qkv_spec = P(batch_axis, None, head_axis, None)
    if k is None:
        args, specs = [q], [P(batch_axis, None, None, head_axis, None)]
    else:
        args, specs = [q, k, v], [qkv_spec] * 3
    n_qkv = len(args)
    if kv_mask is not None:
        args.append(kv_mask)
        specs.append(P(batch_axis, None))
    if seed is not None:
        args.append(seed)
        specs.append(P())

    def body(*args):
        qkv, rest = args[:n_qkv] + (None,) * (3 - n_qkv), list(args[n_qkv:])
        mask = rest.pop(0) if kv_mask is not None else None
        return kernel(*qkv, mask, rest.pop(0) if rest else None)

    # nested in one of the engine's shard_maps, the mesh is the context's
    return shard_map(body, mesh=mesh if context.empty else context,
                     in_specs=tuple(specs), out_specs=qkv_spec,
                     axis_names=auto, check_vma=False)(*args)


def key_padding_to_additive(key_padding_mask):
    """[b, s] 1/0 key-padding mask -> additive [b, s] bias (0 / -1e9)."""
    return (1.0 - key_padding_mask.astype(jnp.float32)) * -1e9


def reference_attention(q, k, v, mask=None, causal=False, dropout_rate=0.0,
                        dropout_rng=None, deterministic=True):
    """jnp attention: [b, s, h, d] inputs, fp32 softmax accumulation."""
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((s, k.shape[1]), bool))
        scores = jnp.where(causal_mask[None, None], scores, jnp.float32(-1e9))
    if mask is not None:
        scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if (not deterministic and dropout_rate >= 1.0 / 512.0
            and dropout_rng is not None):
        # one random byte per element in compute dtype (the reference kernel
        # likewise drops the fp16 softmax output, dropout_kernels.cu); rates
        # below the 1/256 quantum pass through, matching layers.dropout
        keep, inv_keep = random_keep(dropout_rng, probs.shape, dropout_rate)
        probs = jnp.where(keep, probs * jnp.asarray(inv_keep, probs.dtype), 0.0)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return ctx


def _kernel_dropout(dropout_rate, dropout_rng, deterministic):
    """(seed, rate) of the flash kernels' in-kernel probs dropout."""
    if (deterministic or dropout_rate < 1.0 / 512.0 or dropout_rng is None):
        return None, 0.0
    # hand the kernel 64 bits of seed material from this call's rng stream
    # (32 bits would birthday-collide across steps after ~65k draws)
    seed = jax.lax.bitcast_convert_type(
        jax.random.bits(dropout_rng, (2,), jnp.uint32), jnp.int32)
    return seed, float(dropout_rate)


def self_attention(qkv, mask=None, key_padding_mask=None, causal=False,
                   dropout_rate=0.0, dropout_rng=None, deterministic=True):
    """``dot_product_attention`` over q, k, v = ``qkv[:, :, 0..2]`` of a
    fused projection [batch, seq, 3, heads, head_dim].  Where the flash
    kernel runs it is handed the one array (no slice is materialised for
    it: ``flash_attention.flash_self_attention``); the result is the same
    either way."""
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if not (_use_pallas(q, k) and mask is None):
        return dot_product_attention(
            q, k, v, mask=mask, key_padding_mask=key_padding_mask,
            causal=causal, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            deterministic=deterministic)
    from .flash_attention import flash_self_attention

    seed, rate = _kernel_dropout(dropout_rate, dropout_rng, deterministic)
    return shard_kernel_over_mesh(
        lambda qkv, _k, _v, kv_mask, seed: flash_self_attention(
            qkv, kv_mask=kv_mask, dropout_seed=seed, causal=causal,
            dropout_rate=rate),
        qkv, kv_mask=key_padding_mask, seed=seed)


def dot_product_attention(q, k, v, mask=None, key_padding_mask=None,
                          causal=False, dropout_rate=0.0,
                          dropout_rng=None, deterministic=True):
    """Multi-head attention on [batch, seq, heads, head_dim] tensors.

    ``mask`` is an additive bias broadcastable to [b, h, q, k] (e.g. a
    padding mask of -1e9 at masked keys), matching the reference layer's
    attention-mask contract (``ops/transformer/transformer.py:155-244``).
    ``key_padding_mask`` is the structured special case the flash kernel
    fuses (reference: fused scale+mask softmax,
    ``csrc/transformer/softmax_kernels.cu``): [b, kv_len] with 1 at visible
    keys, 0 at padding.  Pass one or the other, not both.
    """
    assert mask is None or key_padding_mask is None, (
        "pass either an additive mask or a key_padding_mask, not both")
    if _use_pallas(q, k) and mask is None:
        from .flash_attention import flash_attention

        seed, rate = _kernel_dropout(dropout_rate, dropout_rng, deterministic)
        return shard_kernel_over_mesh(
            lambda q, k, v, kv_mask, seed: flash_attention(
                q, k, v, kv_mask=kv_mask, dropout_seed=seed, causal=causal,
                dropout_rate=rate),
            q, k, v, kv_mask=key_padding_mask, seed=seed)
    if key_padding_mask is not None:
        mask = key_padding_to_additive(key_padding_mask)[:, None, None, :]
    return reference_attention(q, k, v, mask=mask, causal=causal,
                               dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                               deterministic=deterministic)
