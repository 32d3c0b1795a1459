"""Pallas flash attention (forward + backward) for TPU.

TPU-native replacement for the reference's fused attention kernel chain
(QKV strided-batch GEMMs + fused scale/mask softmax + dropout,
``csrc/transformer/ds_transformer_cuda.cpp:145-288``,
``softmax_kernels.cu``).  Instead of materializing the [s, s] score matrix
in HBM, attention is computed blockwise in VMEM with an online softmax
(flash-attention recurrence), so memory is O(s·d) and HBM traffic is one
pass over Q/K/V — this is what buys the "10x longer sequences" capability
the reference got from block-sparse attention (SURVEY §5.7), but for the
dense case.

Layout: inputs are [batch, seq, heads, head_dim]; kernels run on
[batch·heads, seq, head_dim] with a 3-D grid (bh, outer blocks, inner
blocks).  K/V stream through VMEM one block per grid step — VMEM usage is
O(block), not O(seq), so sequence length is bounded by HBM alone — measured
on one v5e chip: BERT-large trains at seq 8192 (1.1 samples/s), 16384, and
32768 (batch 1, per-layer remat), vs the reference's 16x-over-512 best with
block-sparse attention.  Matmul operands stay in the storage
dtype (bf16 runs the MXU at full rate; fp32 operands are several times
slower) with fp32 accumulation; softmax state is fp32 in VMEM scratch.

The backward pass is the standard flash recurrence: recompute P blockwise
from the saved logsumexp, then
``dv += Pᵀ·dO``, ``ds = P∘(dO·Vᵀ − Δ)``, ``dk += dsᵀ·Q``, ``dq += ds·K``
with ``Δ = rowsum(dO ∘ O)``.

In-kernel dropout: keep masks are drawn from the TPU hardware PRNG seeded
by (user seed, tile coordinates), so the backward kernels regenerate the
forward masks bit-for-bit instead of storing an O(s²) mask tensor — the
reference's saved-seed cuRAND trick (``dropout_kernels.cu``) minus the
saved mask.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.logging import logger

_VMEM = pltpu.VMEM

NEG_INF = -1e30
# Running-max floor: keeps exp(NEG_INF - m) == 0 even for rows where every
# key is masked out (m would otherwise be NEG_INF and exp(0) = 1).
MAX_FLOOR = -1e20


def _auto_blocks(s, kv_len, d=64, causal=False):
    """Largest MXU-friendly blocks the sequence lengths divide into.

    Measured on v5e (B·S = 8k tokens, h16 d64): (512, 512) wins at s=512
    (5.4 ms fwd+bwd vs XLA's ~6.8), (512, 2048) at s=2048 (7.3 vs 15.8) —
    128² blocks leave ~2x on the table (pipeline bubbles + sub-MXU dots).
    Bigger k blocks win until the double-buffered K/V block footprint
    presses on scoped VMEM, so block_k·d caps at 128K elements.

    CAUSAL caps block_k at block_q: the skip of above-diagonal work is
    block-granular, so a k block wider than the q block straddles the
    diagonal and executes mostly-masked tiles — kernel-level A/B at
    GPT-2 shape (seq 1024): q512/k1024 10.2 ms fwd+bwd vs q512/k512 7.3.
    End-to-end GPT-2-medium seq-1024 throughput is within noise (attention
    is ~7% of that step); the win grows with seq (more straddling tiles
    avoided) and is free either way.

    Round-4 re-audit (repeated two-point scans, b8 s1024 h16 d64 causal —
    the GPT-2 bench shape, where the step profile puts attention at a
    third of the step): among streamed geometries q512/k512 is stable-best
    at ~3.0 ms fwd+bwd; q256/k512 reads 3.6 ms and q256/k1024 is bistable
    (1.7–3.8 across identical recompiles).  But the SINGLE-TILE path at
    q1024/k1024 beats them all — 2.3–2.6 ms no-dropout, 2.8–3.3 with
    dropout, vs 3.0–3.3 / 3.3–4.3 for the round-3 auto choice — despite
    executing the full (unskipped) score tile: the straight-line softmax
    with no scratch round-trips and full-width PV lanes more than pays for
    the 2x causal MXU waste at this size.  So for causal shapes up to
    s=1024 the auto policy now prefers one full tile; past that the
    streamed q512 geometry still wins (the waste grows quadratically).
    (Also measured, negative: base-2 softmax is a wash — Mosaic's exp
    already costs the same as exp2 — and a masked/unmasked tile split
    gains zero.)
    """
    def pick(n, candidates):
        for c in candidates:
            if n % c == 0:
                return c
        return n

    qcands = (512, 256, 128)
    if (causal and s == kv_len and s <= 1024
            and (128 * 1024) // max(d, 1) >= s):
        # single full tile (see docstring: measured best at the GPT-2
        # shape; n_kb == 1 takes the scratch-free straight-line kernel).
        # The d-gate keeps this to shapes where block_k can also reach s —
        # otherwise the pick would silently swap the measured q512 streamed
        # geometry for an unmeasured q1024 streamed one.
        qcands = (1024,) + qcands
    block_q = pick(s, qcands)
    kmax = max(128, (128 * 1024) // max(d, 1))
    if causal:
        kmax = min(kmax, block_q)
    block_k = pick(kv_len, tuple(
        c for c in (2048, 1024, 512, 256, 128) if c <= kmax))
    return min(block_q, s), min(block_k, kv_len)


def _dropout_thresh(rate):
    """Static uint32 threshold + inverse-keep scale for in-kernel dropout.

    Probabilities come from a 32-bit hardware PRNG draw per score entry:
    drop iff ``bits < thresh``.  Quantization error is < 2^-32, so the
    returned scale is unbiased for all practical purposes.
    """
    # dslint: disable=DSH102 -- rate is a static kernel parameter (functools.partial-bound), never a tracer
    thresh = int(round(float(rate) * float(1 << 32)))
    thresh = min((1 << 32) - 1, max(1, thresh))
    keep_prob = 1.0 - thresh / float(1 << 32)
    return thresh, 1.0 / keep_prob


def _keep_mask(seed_ref, i, j, kb, shape, thresh):
    """Regenerable [Bq, Bk] keep mask for score tile (i, j, kb).

    Seeding the hardware PRNG with (seed words, tile coordinates) makes the
    draw a pure function of the tile, so the backward kernels regenerate the
    exact forward mask.  This Mosaic toolchain accepts AT MOST two seed
    words (a third reliably crashes its compiler — measured), so the 64-bit
    user seed (two int32 words; a single 32-bit per-step seed would
    birthday-collide after ~65k steps) XOR-folds with the coordinates:
    ``bh`` into word 0 and ``(j, kb)`` packed EXACTLY into word 1
    (``j*2^15 + kb`` — both block counts stay far below 2^15 for every
    supported shape), so distinct tiles cannot alias the way a wraparound
    multiplicative hash could.
    """
    tile = jnp.int32(j) * jnp.int32(1 << 15) + jnp.int32(kb)
    pltpu.prng_seed(seed_ref[0] ^ jnp.int32(i), seed_ref[1] ^ tile)
    bits = jax.lax.bitcast_convert_type(
        pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(thresh)


def _scores(q_blk, k_blk, scale, causal, masked, kvm_ref, j, kb, block_q,
            block_k):
    """Scaled [Bq, Bk] score tile + causal/key-padding masking."""
    s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)
    if masked:
        kvm = kvm_ref[0, 0]  # [Bk] fp32 0/1 — this grid step's k block
        s = jnp.where(kvm[None, :] > 0.0, s, NEG_INF)
    return s


def _fwd_kernel(*refs, scale, causal, masked, dropout, single):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kb = pl.num_programs(2)

    if single:
        # one k block: straight-line softmax, no scratch round-trips (the
        # common short-sequence case; ~25% faster than the streamed form)
        s = _scores(q_ref[0], k_ref[0], scale, causal, masked, kvm_ref,
                    j, kb, block_q, block_k)
        m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), MAX_FLOOR)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if dropout:
            thresh, inv_keep = _dropout_thresh(dropout)
            keep = _keep_mask(seed_ref, i, j, kb, (block_q, block_k), thresh)
            p = jnp.where(keep, p * inv_keep, 0.0)
        acc = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l_safe))[:, 0]
        return

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # causal: q rows of block j end at (j+1)·Bq − 1; skip k blocks past
    # them.  The skip saves the compute; the K/V block DMA still happens
    # (BlockSpec fetches are unconditional) — acceptable because K/V bytes
    # are a rounding error next to the score matmuls at these block sizes.
    needed = True if not causal else kb * block_k <= (j + 1) * block_q - 1

    # (round-4 negative result: splitting this step into masked/unmasked
    # variants so fully-below-diagonal tiles skip the causal iota/select
    # measured 3.02 vs 3.00 ms at the GPT-2 shape — Mosaic overlaps that
    # VPU work with the dots already; reverted to the single body)
    @pl.when(needed)
    def _step():
        s = _scores(q_ref[0], k_ref[0], scale, causal, masked, kvm_ref,
                    j, kb, block_q, block_k)
        m, l = m_sc[...], l_sc[...]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=1, keepdims=True)),
                            MAX_FLOOR)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        # l accumulates the UNdropped sum (softmax normalizer); dropout hits
        # only the value accumulation, so out == dropout(softmax(s)) @ v.
        l_sc[...] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        if dropout:
            thresh, inv_keep = _dropout_thresh(dropout)
            keep = _keep_mask(seed_ref, i, j, kb, (block_q, block_k), thresh)
            p = jnp.where(keep, p * inv_keep, 0.0)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l_safe))[:, 0]


def _bwd_dq_kernel(*refs, scale, causal, masked, dropout, single):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    dq_ref, dq_sc = rest

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kb = pl.num_programs(2)

    def tile_dq():
        s = _scores(q_ref[0], k_ref[0], scale, causal, masked, kvm_ref,
                    j, kb, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout:
            thresh, inv_keep = _dropout_thresh(dropout)
            keep = _keep_mask(seed_ref, i, j, kb, (block_q, block_k), thresh)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(k_ref.dtype)
        return jax.lax.dot_general(ds, k_ref[0], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    if single:
        dq_ref[0] = (tile_dq() * scale).astype(dq_ref.dtype)
        return

    @pl.when(kb == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    needed = True if not causal else kb * block_k <= (j + 1) * block_q - 1

    @pl.when(needed)
    def _step():
        dq_sc[...] = dq_sc[...] + tile_dq()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, masked, dropout, single):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    dk_ref, dv_ref, dk_sc, dv_sc = rest

    block_k, d = k_ref.shape[1], k_ref.shape[2]
    block_q = q_ref.shape[1]
    # grid is (bh, k blocks, q blocks): q streams in the inner dimension
    i, kb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_qb = pl.num_programs(2)

    def tile_dkdv():
        s = _scores(q_ref[0], k_ref[0], scale, causal, masked, kvm_ref,
                    j, kb, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [Bq, Bk] fp32
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout:
            thresh, inv_keep = _dropout_thresh(dropout)
            # fwd tile (j, kb) — same seed hash, same mask
            keep = _keep_mask(seed_ref, i, j, kb, (block_q, block_k), thresh)
            p_v = jnp.where(keep, p * inv_keep, 0.0)
            dp_m = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            p_v, dp_m = p, dp
        dv_t = jax.lax.dot_general(
            p_v.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp_m - delta_ref[0, 0][:, None])).astype(q_ref.dtype)
        dk_t = jax.lax.dot_general(ds, q_ref[0], (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return dk_t, dv_t

    if single:
        dk_t, dv_t = tile_dkdv()
        # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk.
        dk_ref[0] = (dk_t * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_t.astype(dv_ref.dtype)
        return

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    # causal: q block j contributes to k block kb iff its last row can see
    # the block's first key
    needed = True if not causal else (j + 1) * block_q - 1 >= kb * block_k

    @pl.when(needed)
    def _step():
        dk_t, dv_t = tile_dkdv()
        dk_sc[...] = dk_sc[...] + dk_t
        dv_sc[...] = dv_sc[...] + dv_t

    @pl.when(j == n_qb - 1)
    def _finalize():
        # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk.
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, scale, causal, masked, dropout):
    """Single-tile fused backward: dq, dk, dv from ONE score
    materialization.  The streamed pair (_bwd_dq_kernel + _bwd_dkv_kernel)
    each recompute the q·kᵀ scores, the softmax exp, the dᵒ·vᵀ dot and —
    under dropout — the PRNG mask; at the single-tile shapes the auto
    policy picks for s ≤ 1024 (GPT-2 s=1024 causal, BERT s=512) the whole
    tile fits VMEM, so one straight-line kernel computes p and ds once
    and feeds all three gradient dots (round-5 follow-up to the round-4b
    single-tile forward: the same win applied to the backward)."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    dq_ref, dk_ref, dv_ref = rest

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    i = pl.program_id(0)

    s = _scores(q_ref[0], k_ref[0], scale, causal, masked, kvm_ref,
                0, 0, block_q, block_k)
    p = jnp.exp(s - lse_ref[0, 0][:, None])  # [Bq, Bk] fp32
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if dropout:
        thresh, inv_keep = _dropout_thresh(dropout)
        keep = _keep_mask(seed_ref, i, 0, 0, (block_q, block_k), thresh)
        p_v = jnp.where(keep, p * inv_keep, 0.0)
        dp = jnp.where(keep, dp * inv_keep, 0.0)
    else:
        p_v = p
    ds = (p * (dp - delta_ref[0, 0][:, None])).astype(q_ref.dtype)
    dq_ref[0] = (jax.lax.dot_general(
        ds, k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale).astype(dq_ref.dtype)
    # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk too
    dk_ref[0] = (jax.lax.dot_general(
        ds, q_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale).astype(dk_ref.dtype)
    dv_ref[0] = jax.lax.dot_general(
        p_v.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)


def _flatten_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unflatten_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention(q, k, v, kv_mask=None, dropout_seed=None, causal=False,
                    block_q=None, block_k=None,
                    interpret=False, dropout_rate=0.0):
    """Flash attention on [b, s, h, d]; returns [b, s, h, d].

    ``kv_mask`` is an optional key-padding mask [b, kv_len] with 1 at
    visible keys and 0 at padding (BERT's ``attention_mask`` contract —
    the reference fuses this into its softmax kernel,
    ``csrc/transformer/softmax_kernels.cu``).  Rows with every key masked
    produce zero output and zero gradients.

    ``dropout_rate`` > 0 applies attention-probability dropout *inside* the
    kernel: keep masks come from the TPU hardware PRNG seeded by
    (``dropout_seed``, tile coordinates) and are regenerated bit-identically
    in the backward kernels (nothing O(s²) is ever stored — the reference's
    fused softmax-dropout capability, ``dropout_kernels.cu``).
    ``dropout_seed`` is a scalar int32 array; vary it per step/layer.
    TPU-only: requires the Mosaic PRNG (not available in interpret mode).
    """
    out, _ = _flash_fwd(q, k, v, kv_mask, dropout_seed, causal, block_q,
                        block_k, interpret, dropout_rate)
    return out


def flash_attention_forward(q, k, v, *, causal, block_q, block_k,
                            interpret=False, name=None):
    """The forward kernel alone, for serving: ``q``, ``k`` [b, s, h, d] and
    ``v`` [b, s, h, dv] with a value width of its own, blocks given by the
    caller (no first-use tuning inside a served program), softmax scale
    1/sqrt(d) (fold any other factor into ``q``).  ``name`` is the kernel's
    name in a device trace."""
    out, _ = _flash_fwd(q, k, v, None, None, causal, block_q, block_k,
                        interpret, 0.0, name=name)
    return out


def _mask_spec(h, block_k):
    # one [1, 1, block_k] mask slice per (batch·head, k block) program:
    # batch = i // h.  The singleton middle axis keeps the block's
    # trailing-two dims Mosaic-tileable.
    return pl.BlockSpec((1, 1, block_k), lambda i, j, kb: (i // h, 0, kb))


def _dropout_ops(dropout_rate, dropout_seed):
    """(operands, specs, active_rate) for the in-kernel dropout seed."""
    if not dropout_rate:
        return (), (), 0.0
    assert dropout_seed is not None, (
        "flash_attention dropout_rate > 0 requires a dropout_seed")
    seed = jnp.asarray(dropout_seed, jnp.int32).reshape(-1)
    if seed.size == 1:  # legacy scalar seed: widen with a zero hi word
        seed = jnp.concatenate([seed, jnp.zeros((1,), jnp.int32)])
    assert seed.size == 2, f"dropout_seed must be 1 or 2 int32 words, got {seed.size}"
    return ((seed,), (pl.BlockSpec(memory_space=pltpu.SMEM),),
            float(dropout_rate))  # dslint: disable=DSH102 -- dropout_rate rides custom_vjp nondiff_argnums: static by construction


@functools.lru_cache(maxsize=None)
def _log_geometry(s, kv_len, d, causal, dropout, block_q, block_k, chosen):
    """One line per distinct kernel geometry per process (traced calls
    repeat per layer and per pass): which blocks a shape ran with, and
    whether the caller, the measured heuristic or the first-use tuner
    chose them — two cold runs of an un-anchored shape may differ."""
    logger.info("flash_attention geometry: s=%d kv=%d d=%d causal=%s "
                "dropout=%s -> block_q=%d block_k=%d (%s)", s, kv_len, d,
                causal, dropout, block_q, block_k, chosen)


def _resolve_blocks(s, kv_len, d, block_q, block_k, causal=False,
                    dropout_rate=0.0):
    auto_q, auto_k = _auto_blocks(s, kv_len, d, causal)
    chosen = "caller"
    if block_q is None and block_k is None:
        # runtime autotune (reference analog: the GEMM algorithm search
        # baked into kernel setup, csrc/includes/gemm_test.h): shapes the
        # hand calibration covers keep the measured heuristic choice;
        # anything else gets a cached first-use micro-search.  tune()
        # calls back into flash_attention with EXPLICIT blocks, so the
        # recursion terminates here.
        from .kernel_tuner import tune
        heuristic = (auto_q, auto_k)
        auto_q, auto_k = tune(s, kv_len, d, causal, dropout_rate,
                              flash_attention, heuristic)
        chosen = "heuristic" if (auto_q, auto_k) == heuristic else "tuned"
    block_q = block_q or auto_q
    block_k = block_k or auto_k
    _log_geometry(s, kv_len, d, causal, dropout_rate, block_q, block_k,
                  chosen)
    # The kernels index K/V in whole blocks; a ragged tail would silently
    # attend over out-of-block garbage.  Dispatchers (attention.py) only
    # route divisible shapes here; direct callers must pad or shrink blocks.
    if s % block_q != 0 or kv_len % block_k != 0:
        raise ValueError(
            f"flash_attention requires seq divisible by block sizes: "
            f"q_len={s} % block_q={block_q}, kv_len={kv_len} % block_k={block_k}")
    return block_q, block_k


def _grid_params(interpret):
    if interpret:
        return {}
    # bh and the outer block dim are parallel; the streamed dim accumulates
    # into VMEM scratch and must run in order.  The raised vmem limit lets
    # XLA keep large kernel outputs in VMEM when it judges that profitable
    # (v5e has 128M; the default 16M scoped limit rejects long-sequence
    # outputs it would otherwise promote).
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)}


def _flash_fwd(q, k, v, kv_mask, dropout_seed, causal, block_q, block_k,
               interpret, dropout_rate, name=None):
    b, s, h, d = q.shape
    kv_len = k.shape[1]
    # the values may be narrower or wider than the keys (latent attention
    # expands keys of 192 beside values of 128): the score tile is q.k over
    # d, the accumulator and the output are dv wide
    dv = v.shape[-1]
    block_q, block_k = _resolve_blocks(s, kv_len, d, block_q, block_k, causal,
                                       dropout_rate)
    masked = kv_mask is not None
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    bh = b * h
    n_qb = pl.cdiv(s, block_q)
    n_kb = pl.cdiv(kv_len, block_k)

    seed_ops, seed_specs, drop = _dropout_ops(dropout_rate, dropout_seed)
    mask_ops, mask_specs = (), ()
    if masked:
        assert kv_mask.shape == (b, kv_len), (
            f"kv_mask must be [batch, kv_len]={b, kv_len}, got {kv_mask.shape}")
        mask_ops = (kv_mask.astype(jnp.float32)[:, None, :],)
        mask_specs = (_mask_spec(h, block_k),)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               masked=masked, dropout=drop,
                               single=(n_kb == 1))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j, kb: (i, kb, 0)),
            *seed_specs,
            *mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((block_q, 1), jnp.float32),   # running max m
            _VMEM((block_q, 1), jnp.float32),   # running sum l
            _VMEM((block_q, dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name=name,
        **_grid_params(interpret),
    )(qf, kf, vf, *seed_ops, *mask_ops)
    outh = _unflatten_heads(out, b, h)
    return outh, (q, k, v, kv_mask, dropout_seed, outh, lse)


def _flash_fwd_rule(q, k, v, kv_mask, dropout_seed, causal, block_q, block_k,
                    interpret, dropout_rate):
    out, res = _flash_fwd(q, k, v, kv_mask, dropout_seed, causal, block_q,
                          block_k, interpret, dropout_rate)
    return out, res


def _flash_bwd_rule(causal, block_q, block_k, interpret, dropout_rate, res, g):
    assert res[2].shape[-1] == res[0].shape[-1], (
        "the flash backward kernels assume values as wide as keys; a "
        "value width of its own is forward-only (flash_attention_forward)")
    q, k, v, kv_mask, dropout_seed, out, lse = res
    b, s, h, d = q.shape
    kv_len = k.shape[1]
    block_q, block_k = _resolve_blocks(s, kv_len, d, block_q, block_k, causal,
                                       dropout_rate)
    masked = kv_mask is not None
    scale = 1.0 / math.sqrt(d)
    bh = b * h

    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    dof = _flatten_heads(g)
    of = _flatten_heads(out)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1,
                    keepdims=True).transpose(0, 2, 1)  # [bh, 1, s]

    n_qb = pl.cdiv(s, block_q)
    n_kb = pl.cdiv(kv_len, block_k)

    seed_ops, seed_specs, drop = _dropout_ops(dropout_rate, dropout_seed)
    mask_ops, mask_specs = (), ()
    if masked:
        mask_ops = (kv_mask.astype(jnp.float32)[:, None, :],)
        mask_specs = (_mask_spec(h, block_k),)

    if n_qb == 1 and n_kb == 1:
        # single-tile fused backward: one kernel, one score pass
        grid_1d = ({} if interpret else
                   {"compiler_params": pltpu.CompilerParams(
                       dimension_semantics=("parallel",),
                       vmem_limit_bytes=100 * 1024 * 1024)})
        fused_seed_specs = seed_specs
        fused_mask_specs = ((pl.BlockSpec((1, 1, block_k),
                                          lambda i: (i // h, 0, 0)),)
                            if masked else ())
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              masked=masked, dropout=drop),
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 1, block_q), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 1, block_q), lambda i: (i, 0, 0)),
                *fused_seed_specs,
                *fused_mask_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                jax.ShapeDtypeStruct((bh, kv_len, d), k.dtype),
                jax.ShapeDtypeStruct((bh, kv_len, d), v.dtype),
            ],
            interpret=interpret,
            **grid_1d,
        )(qf, kf, vf, dof, lse, delta, *seed_ops, *mask_ops)
        dqh = (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
               _unflatten_heads(dv, b, h))
        return dqh + (jnp.zeros_like(kv_mask) if masked else None, None)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          masked=masked, dropout=drop, single=(n_kb == 1)),
        grid=(bh, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            *seed_specs,
            *mask_specs,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **_grid_params(interpret),
    )(qf, kf, vf, dof, lse, delta, *seed_ops, *mask_ops)

    # grid (bh, k blocks, q blocks): mask/seed specs take (i, kb, j) index
    # order, so the kb-indexed mask slice rides program_id(1)
    dkv_mask_specs = ((pl.BlockSpec((1, 1, block_k),
                                    lambda i, kb, j: (i // h, 0, kb)),)
                      if masked else ())
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          masked=masked, dropout=drop, single=(n_qb == 1)),
        grid=(bh, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, kb, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, kb, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, kb, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, kb, j: (i, 0, j)),
            *seed_specs,
            *dkv_mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb, j: (i, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_len, d), v.dtype),
        ],
        scratch_shapes=[
            _VMEM((block_k, d), jnp.float32),
            _VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        **_grid_params(interpret),
    )(qf, kf, vf, dof, lse, delta, *seed_ops, *mask_ops)

    dqh = (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
           _unflatten_heads(dv, b, h))
    return dqh + (jnp.zeros_like(kv_mask) if masked else None, None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
