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

Layout: inputs are [batch, seq, heads, head_dim].  The kernels index them
as [batch, seq, heads·head_dim] — a free reshape of what the QKV projection
wrote — in blocks whose last dimension is whole 128-lane tiles: two 64-wide
heads a block, one head a block where head_dim is a multiple of 128
(``_Operands``), and write their outputs the same way, lane-dense, so no
transpose stands between the GEMMs and the kernels.  A self-attention
layer hands over its fused [b, s, 3, h, d] projection whole
(``flash_self_attention``): q, k and v are the same array at three block
indices, and the fused backward returns the one gradient.  Shapes whose
heads do not fill lane tiles (an odd number of 64-wide heads, key width
192) run on [batch·heads, seq, head_dim] through a transpose each way.  The
grid is (batch rows, blocks a row, outer blocks, inner blocks); where a
batch row is one short tile (seq 128, 256) a step holds several rows, up
to 1024 query rows' worth (``_Operands.tile``).  K/V stream through VMEM
one block per grid step — VMEM usage is
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
saved mask.  With several batch rows a step each row keeps the seed its
own step would have had, so the masks do not depend on the rows a step.

A model calls the kernels once a layer with the same shapes: the calls are
built under an inlined ``jit`` (``_fwd_kernels``, ``_bwd_kernels``) whose
cache answers every layer after the first with the first one's equations,
so a kernel is traced and lowered once a geometry, not once a layer
(``trace_stats()`` counts both; logged with the compile counters).
"""

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.logging import logger

_VMEM = pltpu.VMEM
# largest [s, 3·h·d] output block the fused backward keeps resident (it is
# double-buffered): 16M holds s 1024 at a hidden size of 2,730 in bf16
_ROW_BLOCK_BYTES = 16 * 1024 * 1024
# where one batch row is a single tile shorter than ``_SHORT_SEQ``, a grid
# step holds as many batch rows as make ``_STEP_ROWS`` query rows at most
# (``_Operands.tile``; the readings are in ``_auto_blocks``' docstring).
# From 512 a row's own tile fills a step: one row a step, as measured there
_STEP_ROWS = 1024
_SHORT_SEQ = 512

NEG_INF = -1e30
# Running-max floor: keeps exp(NEG_INF - m) == 0 even for rows where every
# key is masked out (m would otherwise be NEG_INF and exp(0) = 1).
MAX_FLOOR = -1e20


def _auto_blocks(s, kv_len, d=64, causal=False):
    """Largest MXU-friendly blocks the sequence lengths divide into.

    Measured on v5e (B·S = 8k tokens, h16 d64): (512, 512) wins at s=512
    (5.4 ms fwd+bwd vs XLA's ~6.8), (512, 2048) at s=2048 (7.3 vs 15.8) —
    128² blocks leave ~2x on the table (pipeline bubbles + sub-MXU dots).
    Those and the records below were read while every grid step held ONE
    head of [b·h, s, d] operands behind a transpose each way; since the
    kernels index the projection's layout (two d=64 heads a step) the same
    blocks stand — at b32 s512 h16 d64 with mask and dropout, one layer's
    QKV GEMM + attention + output GEMM, forward and backward, fell from
    5.77 to 4.56 ms at (512, 512), the kernels' share of it from 3.19 to
    2.73 (chip runs of PR 30; the geometry was not searched again).
    At s=128 a row is one (128, 128) tile and the loss is the grid's, not
    the block's: with one batch row a step a call is 896 steps of ~0.3 µs
    of work.  The same sandwich at b112 s128 h16 d64 (mask, dropout 0.1;
    builder's microbenchmark, chip runs of PR 36), by batch rows a grid
    step: XLA's attention 4.61 ms; 1 row 3.17; 2 rows 2.88; 4 rows 2.69;
    7 rows 2.60; 8 rows 2.59; 14 and 16 rows 2.57 — so ``_STEP_ROWS`` is
    1024 query rows a step (8 × 128; past it nothing is left to gain).
    In the cell ``bert_large.seq128`` (tokens/s/chip): XLA's attention
    61,987; eight rows a step, looped in the kernel, 70,276 (ledger, PR
    36), spelled out 72,833 (chip runs of PR 37; ``_row_steps``); four
    rows spelled out 71,970 (chip runs of PR 36).
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


def rows_per_step(b, s, h, d):
    """Batch rows a grid step holds for self-attention over [b, s, h, d]
    with the blocks ``_auto_blocks`` picks (``_Operands.tile``): what the
    dispatch asks before it sends a sequence under 512 here."""
    return _Operands(b, h, d, d).tile(s, s, *_auto_blocks(s, s, d)).rows


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
            block_k, window=None, row=0):
    """Scaled [Bq, Bk] score tile + causal/key-padding masking; with
    ``window`` a query sees its last ``window`` keys only, itself among
    them.  ``row`` is the batch row of the step's block the tile is of."""
    s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_idx - k_idx < window, s, NEG_INF)
    if masked:
        kvm = kvm_ref[row, 0]  # [Bk] fp32 0/1 — this grid step's k block
        s = jnp.where(kvm[None, :] > 0.0, s, NEG_INF)
    return s


# -- several heads in one 128-lane block --------------------------------------
#
# An operand block is [rows, G·d]: the lanes of G heads side by side, as the
# QKV projection wrote them (G = 1 where a head fills whole lane tiles, and
# where the operands are flattened).  The per-head [Bq, Bk] tile is computed
# once per head, and nothing is sliced out of a lane tile: an operand with
# the OTHER heads' lanes zeroed (``_own_lanes``) stands for the head.  A
# product that contracts over the lanes (q·kᵀ, dO·vᵀ) then adds exact zeros
# to its sums; a product whose output is lane-wide (p·v, ds·k, dsᵀ·q,
# pᵀ·dO) is zero outside the head's lanes, so the heads' products ADD to
# the output block (the backward kernels), or is taken over all G·d lanes
# of the unmasked operand and selected by lane (``_by_head``: the forward,
# where each head's accumulator has its own normaliser).  On a 128 x 128 MXU
# a contraction of 64 and an output of 64 columns each half-fill a pass, so
# the full-width products cost what the 64-wide ones did.  Measured at the
# seq-512 cell's shape, a layer's forward / fused backward (chip runs of
# PR 30): one head a step on [b·h, s, 64] 0.893 / 1.150 ms; two heads a
# step with q and dO zeroed and every output selected 0.803 / 1.281; with
# k, v (and q, dO for dk, dv) zeroed and the outputs added 0.815 / 1.222;
# static 64-lane slices were slower than either (a layer's attention
# sandwich 4.60 ms against 4.56).  So the forward selects and the backward
# adds.

def _own_lanes(x, g, heads):
    """``x`` [rows, heads·d] with every lane outside head ``g`` zeroed."""
    if heads == 1:
        return x
    d = x.shape[1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    own = (lane >= g * d) & (lane < (g + 1) * d)
    return jnp.where(own, x, jnp.zeros_like(x))


def _by_head(parts, shape):
    """[rows, G·d] that holds ``parts[g]`` in head g's lanes; a part is
    lane-wide already or one column, spread over its head's lanes."""
    if len(parts) == 1:
        return parts[0]
    d = shape[1] // len(parts)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out = parts[-1]
    for g in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (g + 1) * d, parts[g], out)
    return out


def _step_id(lead, rows=1):
    """The step's number: the steps are the grid's ``lead`` leading
    dimensions — ``(b·h,)`` flattened, ``(batch rows, blocks a row)`` in
    the projection's layout — counted in row-major order.  Where a step
    holds ``rows`` batch rows (``_Operands.rows``), the number its first
    row would have with one row a step: the dropout seeds follow it."""
    i = pl.program_id(0)
    if rows > 1:
        i = i * rows
    if lead == 2:
        i = i * pl.num_programs(1) + pl.program_id(1)
    return i


def _row_steps(rows, lead, i):
    """``(r, i_r)`` for each batch row ``r`` of the step's blocks, ``i_r``
    the number the row's step would have with one row a step — the dropout
    seeds follow it, so the masks do not depend on the rows a step holds.
    One row: the step's own ``i``.  The kernels spell the rows out, one
    copy of the row's body each: a loop over them in the kernel
    (``lax.fori_loop``) costs BERT-large's seq-128 cell 3.5% of its tokens
    (70,273 against 72,833 a second a chip; the kernels 27.0 against 19.9
    ms of a step), and since a kernel is traced and lowered once a
    geometry (``_fwd_kernels``) the longer body costs set-up about a
    second of tracing (chip runs of PR 37)."""
    if rows == 1:
        return [(0, i)]
    first = _step_id(lead, rows)
    stride = pl.num_programs(1) if lead == 2 else 1
    return [(0, first)] + [(r, first + r * stride) for r in range(1, rows)]


def _stat_at(ref, r, g):
    """Index of head ``g`` of the block's row ``r`` in a block of per-row
    statistics: ``[G, 1, s]``, or ``[R, G, 1, s]`` with several rows a
    step (``_Operands.row_spec``)."""
    return (g, 0) if len(ref.shape) == 3 else (r, g, 0)


def _grid_ids(lead):
    """``(i, j, kb)`` of a grid ``(*steps, outer blocks, inner blocks)``."""
    return _step_id(lead), pl.program_id(lead), pl.program_id(lead + 1)


def _head_index(i, g, heads):
    """batch·h + head, the index ``_keep_mask`` has always been seeded by:
    step ``i`` holds heads ``i·G .. i·G + G − 1`` in either layout."""
    return i if heads == 1 else i * heads + g


def _fwd_kernel(*refs, scale, causal, masked, dropout, single, heads, lead,
                window=None, rows=1):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    o_ref, lse_ref = rest[:2]
    # per head a running max m and sum l; one lane-wide output accumulator
    m_scs, l_scs = rest[2:2 + heads], rest[2 + heads:2 + 2 * heads]
    acc_sc = rest[2 + 2 * heads]

    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    i, j, kb = _grid_ids(lead)
    n_kb = pl.num_programs(lead + 1)
    wide = o_ref.shape[1:]
    # where the step stands in the streamed dimension, and the k block it
    # holds: the same number, unless the stream is a window's band — then
    # it walks only the last n_kb blocks up to the diagonal (``_band``)
    at = kb
    if window is not None:
        kb = j - (n_kb - 1) + at

    if single:
        # one k block: straight-line softmax, no scratch round-trips (the
        # common short-sequence case; ~25% faster than the streamed form).
        # A step holds ``rows`` batch rows where a row is one tile: the
        # row's body as it is, once a row
        for r, step in _row_steps(rows, lead, i):
            outs, stats = [], []
            for g in range(heads):
                s = _scores(_own_lanes(q_ref[r], g, heads), k_ref[r], scale,
                            causal, masked, kvm_ref, j, kb, block_q, block_k,
                            window, r)
                m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), MAX_FLOOR)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=1, keepdims=True)
                if dropout:
                    thresh, inv_keep = _dropout_thresh(dropout)
                    keep = _keep_mask(seed_ref, _head_index(step, g, heads),
                                      j, kb, (block_q, block_k), thresh)
                    p = jnp.where(keep, p * inv_keep, 0.0)
                acc = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[r],
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
                l_safe = jnp.where(l == 0.0, 1.0, l)
                outs.append(acc / l_safe)
                stats.append((m, l_safe))
            o_ref[r] = _by_head(outs, wide).astype(o_ref.dtype)
            for g, (m, l_safe) in enumerate(stats):
                lse_ref[_stat_at(lse_ref, r, g)] = (m + jnp.log(l_safe))[:, 0]
        return

    @pl.when(at == 0)
    def _init():
        for m_sc, l_sc in zip(m_scs, l_scs):
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # causal: q rows of block j end at (j+1)·Bq − 1; skip k blocks past
    # them.  The skip saves the compute; the K/V block DMA still happens
    # (BlockSpec fetches are unconditional) — acceptable because K/V bytes
    # are a rounding error next to the score matmuls at these block sizes.
    needed = True if not causal else kb * block_k <= (j + 1) * block_q - 1
    if window is not None:
        # every block of the band lies at or below the diagonal; the first
        # query blocks' bands start before the sequence does
        needed = kb >= 0

    # (round-4 negative result: splitting this step into masked/unmasked
    # variants so fully-below-diagonal tiles skip the causal iota/select
    # measured 3.02 vs 3.00 ms at the GPT-2 shape — Mosaic overlaps that
    # VPU work with the dots already; reverted to the single body)
    @pl.when(needed)
    def _step():
        corrs, ps = [], []
        for g, (m_sc, l_sc) in enumerate(zip(m_scs, l_scs)):
            s = _scores(_own_lanes(q_ref[0], g, heads), k_ref[0], scale,
                        causal, masked, kvm_ref, j, kb, block_q, block_k,
                        window)
            m, l = m_sc[...], l_sc[...]
            m_new = jnp.maximum(
                jnp.maximum(m, jnp.max(s, axis=1, keepdims=True)), MAX_FLOOR)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            # l accumulates the UNdropped sum (softmax normalizer); dropout
            # hits only the value accumulation, so out ==
            # dropout(softmax(s)) @ v.
            l_sc[...] = l * corr + jnp.sum(p, axis=1, keepdims=True)
            m_sc[...] = m_new
            if dropout:
                thresh, inv_keep = _dropout_thresh(dropout)
                keep = _keep_mask(seed_ref, _head_index(i, g, heads), j, kb,
                                  (block_q, block_k), thresh)
                p = jnp.where(keep, p * inv_keep, 0.0)
            corrs.append(corr)
            ps.append(p)
        rescaled = acc_sc[...] * _by_head(corrs, wide)
        acc_sc[...] = rescaled + _by_head(
            [jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for p in ps], wide)

    @pl.when(at == n_kb - 1)
    def _finalize():
        l_safes = []
        for l_sc in l_scs:
            l = l_sc[...]
            l_safes.append(jnp.where(l == 0.0, 1.0, l))
        o_ref[0] = (acc_sc[...] / _by_head(l_safes, wide)).astype(o_ref.dtype)
        for g, (m_sc, l_safe) in enumerate(zip(m_scs, l_safes)):
            lse_ref[g, 0] = (m_sc[...] + jnp.log(l_safe))[:, 0]


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _sum(parts):
    return functools.reduce(operator.add, parts)


def _bwd_tile(q, k_g, v_g, do, lse, delta, seed_ref, kvm_ref, head, j, kb, *,
              scale, causal, masked, dropout, want_pv, row=0, window=None):
    """One head's [Bq, Bk] tile of the backward recurrence, recomputed from
    the saved logsumexp: ``ds = p ∘ (dp − Δ)`` and, where the caller forms
    dv, the (dropped) probabilities ``p_v`` that met the values — both in
    the operands' dtype, ready for the gradient products.  ``k_g`` and
    ``v_g`` hold the head's lanes alone; ``lse`` and ``delta`` are its
    [Bq, 1] columns."""
    block_q, block_k = q.shape[0], k_g.shape[0]
    s = _scores(q, k_g, scale, causal, masked, kvm_ref, j, kb, block_q,
                block_k, window, row)
    p = jnp.exp(s - lse)  # [Bq, Bk] fp32
    dp = _dot(do, v_g, ((1,), (1,)))
    p_v = p
    if dropout:
        thresh, inv_keep = _dropout_thresh(dropout)
        # the forward's tile (head, j, kb): same seed words, same mask
        keep = _keep_mask(seed_ref, head, j, kb, (block_q, block_k), thresh)
        if want_pv:
            p_v = jnp.where(keep, p * inv_keep, 0.0)
        dp = jnp.where(keep, dp * inv_keep, 0.0)
    ds = (p * (dp - delta)).astype(q.dtype)
    return (p_v.astype(do.dtype) if want_pv else None), ds


def _bwd_dq_kernel(*refs, scale, causal, masked, dropout, single, heads,
                   lead, window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    dq_ref, dq_sc = rest

    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    i, j, kb = _grid_ids(lead)
    n_kb = pl.num_programs(lead + 1)
    # the place in the stream and the k block held there, as in the forward:
    # a window's stream is its band, which ends at the diagonal
    at = kb
    if window is not None:
        kb = j - (n_kb - 1) + at

    def tile_dq():
        parts = []
        for g in range(heads):
            k_g = _own_lanes(k_ref[0], g, heads)
            _, ds = _bwd_tile(
                q_ref[0], k_g, _own_lanes(v_ref[0], g, heads), do_ref[0],
                lse_ref[g, 0][:, None], delta_ref[g, 0][:, None], seed_ref,
                kvm_ref, _head_index(i, g, heads), j, kb, scale=scale,
                causal=causal, masked=masked, dropout=dropout, want_pv=False,
                window=window)
            parts.append(_dot(ds, k_g, ((1,), (0,))))
        return _sum(parts)

    if single:
        dq_ref[0] = (tile_dq() * scale).astype(dq_ref.dtype)
        return

    @pl.when(at == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    needed = True if not causal else kb * block_k <= (j + 1) * block_q - 1
    if window is not None:
        needed = kb >= 0    # the first query blocks' bands start before 0

    @pl.when(needed)
    def _step():
        dq_sc[...] = dq_sc[...] + tile_dq()

    @pl.when(at == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, masked, dropout, single, heads,
                    lead, window=None, per_head=None, n_qb=None):
    """dk and dv of one k block, the q blocks streamed.  With grouped KV
    heads a step is a KV head and the stream runs over each of its query
    heads in turn, ``per_head`` q blocks each, into the one accumulator:
    the group's sum is formed here.  With a ``window`` a head's stream is
    the band of ``per_head`` q blocks from the diagonal down, ``n_qb`` the
    sequence's (``_bwd_kernels`` maps the blocks the same way)."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    dk_ref, dv_ref, dk_sc, dv_sc = rest

    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    # grid is (steps, k blocks, q blocks): q streams in the inner dimension
    i, kb, at = _grid_ids(lead)
    n_at = pl.num_programs(lead + 1)
    j = at if per_head is None else at % per_head
    if window is not None:
        j = kb + j

    def tile_dkdv():
        dks, dvs = [], []
        for g in range(heads):
            p_v, ds = _bwd_tile(
                q_ref[0], _own_lanes(k_ref[0], g, heads),
                _own_lanes(v_ref[0], g, heads), do_ref[0],
                lse_ref[g, 0][:, None], delta_ref[g, 0][:, None], seed_ref,
                kvm_ref, _head_index(i, g, heads), j, kb, scale=scale,
                causal=causal, masked=masked, dropout=dropout, want_pv=True,
                window=window)
            dvs.append(_dot(p_v, _own_lanes(do_ref[0], g, heads),
                            ((0,), (0,))))
            dks.append(_dot(ds, _own_lanes(q_ref[0], g, heads), ((0,), (0,))))
        return _sum(dks), _sum(dvs)

    if single:
        dk_t, dv_t = tile_dkdv()
        # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk.
        dk_ref[0] = (dk_t * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_t.astype(dv_ref.dtype)
        return

    @pl.when(at == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    # causal: q block j contributes to k block kb iff its last row can see
    # the block's first key
    needed = True if not causal else (j + 1) * block_q - 1 >= kb * block_k
    if window is not None:
        needed = j < n_qb   # the last k blocks' bands run past the sequence

    @pl.when(needed)
    def _step():
        dk_t, dv_t = tile_dkdv()
        dk_sc[...] = dk_sc[...] + dk_t
        dv_sc[...] = dv_sc[...] + dv_t

    @pl.when(at == n_at - 1)
    def _finalize():
        # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk.
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, scale, causal, masked, dropout, heads, lead,
                      fused_out, rows=1):
    """Single-tile fused backward: dq, dk, dv from ONE score
    materialization.  The streamed pair (_bwd_dq_kernel + _bwd_dkv_kernel)
    each recompute the q·kᵀ scores, the softmax exp, the dᵒ·vᵀ dot and —
    under dropout — the PRNG mask; at the single-tile shapes the auto
    policy picks for s ≤ 1024 (GPT-2 s=1024 causal, BERT s=512) the whole
    tile fits VMEM, so one straight-line kernel computes p and ds once
    and feeds all three gradient dots (round-5 follow-up to the round-4b
    single-tile forward: the same win applied to the backward).  It holds
    every row of dO and O, so Δ = rowsum(dO ∘ O) is formed here, as the
    column the tile subtracts, and no pass of XLA's reads the two again.
    With ``rows`` batch rows a step (short sequences), once a row."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref = refs[:6]
    rest = refs[6:]
    seed_ref = rest.pop(0) if dropout else None
    kvm_ref = rest.pop(0) if masked else None
    for r, i in _row_steps(rows, lead, _step_id(lead)):
        _bwd_fused_row(r, i, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                       seed_ref, kvm_ref, rest, scale=scale, causal=causal,
                       masked=masked, dropout=dropout, heads=heads,
                       fused_out=fused_out)


def _bwd_fused_row(r, i, q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, seed_ref,
                   kvm_ref, out_refs, *, scale, causal, masked, dropout, heads,
                   fused_out):
    """Row ``r`` of the step's blocks, step number ``i`` (``_row_steps``)."""
    dqs, dks, dvs = [], [], []
    for g in range(heads):
        k_g = _own_lanes(k_ref[r], g, heads)
        do_g = _own_lanes(do_ref[r], g, heads)
        delta = jnp.sum(do_g.astype(jnp.float32)
                        * o_ref[r].astype(jnp.float32), axis=1, keepdims=True)
        p_v, ds = _bwd_tile(
            q_ref[r], k_g, _own_lanes(v_ref[r], g, heads), do_ref[r],
            lse_ref[_stat_at(lse_ref, r, g)][:, None], delta, seed_ref,
            kvm_ref, _head_index(i, g, heads), 0, 0, scale=scale,
            causal=causal, masked=masked, dropout=dropout, want_pv=True,
            row=r)
        dqs.append(_dot(ds, k_g, ((1,), (0,))))
        dks.append(_dot(ds, _own_lanes(q_ref[r], g, heads), ((0,), (0,))))
        dvs.append(_dot(p_v, do_g, ((0,), (0,))))
    # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk too
    grads = (_sum(dqs) * scale, _sum(dks) * scale, _sum(dvs))
    if not fused_out:
        for ref, grad in zip(out_refs, grads):
            ref[r] = grad.astype(ref.dtype)
        return
    # ONE gradient [b, s, 3·h·d] for a fused projection: the output block
    # is the batch row's whole [s, 3·h·d] (the step's rows'), resident in
    # VMEM while the row's steps each store their three [s, G·d] pieces at
    # their lanes (whole lane tiles, so the stores are aligned), and
    # written to HBM once a row, in full lines.
    dqkv_ref, = out_refs
    width = q_ref.shape[2]
    for third, grad in enumerate(grads):
        at = pl.multiple_of(
            third * (dqkv_ref.shape[2] // 3) + pl.program_id(1) * width, 128)
        dqkv_ref[r, :, pl.ds(at, width)] = grad.astype(dqkv_ref.dtype)


def _flatten_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unflatten_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


class _Operands:
    """How the kernels see the [b, s, h, d] operands; follows from ``h``,
    ``d`` and ``dv`` alone.

    *Projection layout*: ``[b, s, h·d]``, a free reshape of what the QKV
    GEMM wrote, indexed in blocks whose last dimension is whole 128-lane
    tiles — two 64-wide heads a block (grid ``b · h/2``), one head a block
    where the widths are multiples of 128.  Outputs leave in the same
    layout, lane-dense: nothing is transposed around the kernels.  With
    ``fused``, q, k and v are the three thirds of ONE ``[b, s, 3·h·d]``
    array (``flash_self_attention``), told apart by the block index alone.

    *Flattened*: ``[b·h, s, d]`` through a transpose each way, one head a
    block, for shapes whose heads do not fill lane tiles (an odd number of
    64-wide heads, key width 192) — a block narrower than 128 lanes of the
    projection's layout cannot be indexed.

    *Grouped KV heads* (``kv_group`` query heads read one K/V head): k and
    v are ``[b, s, (h / kv_group)·d]`` and query head ``i``'s step indexes
    their block ``i // kv_group`` — nothing is repeated in HBM; one head a
    block, so the widths are multiples of 128.  Forward and dq run a step a
    query head; the dk/dv kernel runs a step a KV head and streams its
    query heads one after another into one accumulator, so the sum over
    the group is formed in VMEM and dk, dv leave ``[b, s, (h / kv_group)·d]``
    (``_bwd_kernels``).

    *Rows a step* (``tile``): where a batch row is ONE tile of the
    projection layout (``s`` = ``kv_len`` = both blocks: the forward's
    straight-line form and the fused backward) and short, a grid step
    holds ``rows`` batch rows — the largest divisor of ``b`` with
    ``rows · s`` ≤ ``_STEP_ROWS`` — so that a step's overhead and DMAs are
    spread over eight 128-row tiles and not one; every block gains that
    leading extent.  1 for every ``s`` ≥ 512 (``_SHORT_SEQ``), streamed
    geometry, grouped KV heads and flattened operand.
    """

    def __init__(self, b, h, d, dv, fused=False, kv_group=1, rows=1):
        self.b, self.h, self.fused, self.kv_group = b, h, fused, kv_group
        self.dims, self.rows = (b, h, d, dv, fused, kv_group), rows
        if d % 128 == 0 and dv % 128 == 0:
            self.heads, self.packed = 1, True
        elif d == dv == 64 and h % 2 == 0:
            self.heads, self.packed = 2, True
        else:
            self.heads, self.packed = 1, False
        assert self.packed or not fused
        assert kv_group == 1 or (self.packed and self.heads == 1
                                 and not fused), (
            "grouped KV heads need head widths that are multiples of 128")
        self.blocks = h // self.heads  # blocks a batch row
        self.name = "flattened" if not self.packed else (
            f"heads/block={self.heads}, projection layout"
            + (", fused qkv" if fused else "")
            + (f", {kv_group} query heads a kv head" if kv_group > 1
               else ""))

    # a static argument of the traced kernel calls (``_fwd_kernels``,
    # ``_bwd_kernels``): equal where everything the specs follow from is
    def __eq__(self, other):
        return (isinstance(other, _Operands)
                and (self.dims, self.rows) == (other.dims, other.rows))

    def __hash__(self):
        return hash((self.dims, self.rows))

    def tile(self, s, kv_len, block_q, block_k):
        """These operands with the ``rows`` a step holds at these lengths
        and blocks."""
        rows = 1
        if (self.packed and self.kv_group == 1 and s < _SHORT_SEQ
                and block_q == s == kv_len == block_k):
            rows = max(r for r in range(1, _STEP_ROWS // s + 1)
                       if self.b % r == 0)
        return self if rows == self.rows else _Operands(*self.dims, rows=rows)

    @property
    def steps(self):
        """The grid's leading dimensions, one step a block of heads (of
        ``rows`` batch rows)."""
        if self.packed:
            return (self.b // self.rows, self.blocks)
        return (self.b * self.h,)

    def to_kernel(self, x):
        if not self.packed:
            return _flatten_heads(x)
        return x.reshape(x.shape[0], x.shape[1], -1)

    def from_kernel(self, x):
        if not self.packed:
            return _unflatten_heads(x, self.b, self.h)
        return x.reshape(self.b, x.shape[1], self.h, -1)

    def shape(self, s, width, dtype):
        """Of a q/k/v-like output, ``width`` lanes a head."""
        if self.packed:
            return jax.ShapeDtypeStruct((self.b, s, self.h * width), dtype)
        return jax.ShapeDtypeStruct((self.b * self.h, s, width), dtype)

    def spec(self, rows, width, seq_block, third=0):
        """Block of a q/k/v-like operand; ``seq_block`` maps the grid ids
        to the block's index along the sequence, ``third`` says which of
        q, k, v a fused array is read as (a key or value of grouped heads
        is indexed by its own head, the query's // ``kv_group``)."""
        if self.kv_group > 1 and third:
            return pl.BlockSpec(
                (1, rows, width),
                lambda *ids: (ids[0], seq_block(*ids),
                              ids[1] // self.kv_group))
        if self.packed:
            first = third * self.blocks if self.fused else 0
            return pl.BlockSpec(
                (self.rows, rows, self.heads * width),
                lambda *ids: (ids[0], seq_block(*ids), first + ids[1]))
        return pl.BlockSpec((1, rows, width),
                            lambda *ids: (ids[0], seq_block(*ids), 0))

    def qkv_specs(self, block_q, block_k, d, dv, at_q, at_k):
        return [self.spec(block_q, d, at_q, 0), self.spec(block_k, d, at_k, 1),
                self.spec(block_k, dv, at_k, 2)]

    def stat_shape(self, s):
        """Of a per-row statistic (logsumexp, Δ): [b·h, 1, s] in either
        layout, the step's heads consecutive there; with several batch
        rows a step the same bytes seen as [b, h, 1, s], so that a block
        takes the heads of each of its rows."""
        if self.rows > 1:
            return (self.b, self.h, 1, s)
        return (self.b * self.h, 1, s)

    def row_spec(self, rows, seq_block):
        """Block of a per-row statistic (``stat_shape``)."""
        if self.rows > 1:
            return pl.BlockSpec(
                (self.rows, self.heads, 1, rows),
                lambda *ids: (ids[0], ids[1], 0, seq_block(*ids)))
        if self.packed:
            return pl.BlockSpec(
                (self.heads, 1, rows),
                lambda *ids: (ids[0] * self.blocks + ids[1], 0,
                              seq_block(*ids)))
        return pl.BlockSpec((self.heads, 1, rows),
                            lambda *ids: (ids[0], 0, seq_block(*ids)))

    def mask_spec(self, rows, seq_block):
        # one [1, 1, rows] slice of the [b, 1, kv_len] key mask per step,
        # the step's batch row's (its ``self.rows`` rows').  The singleton
        # middle axis keeps the block's trailing-two dims Mosaic-tileable.
        if self.packed:
            return pl.BlockSpec((self.rows, 1, rows),
                                lambda *ids: (ids[0], 0, seq_block(*ids)))
        return pl.BlockSpec((1, 1, rows),
                            lambda *ids: (ids[0] // self.h, 0,
                                          seq_block(*ids)))

    def gradient(self, grads):
        """(dq, dk, dv) as the kernels wrote them, or the one gradient of a
        fused projection."""
        return jnp.concatenate(grads, axis=-1) if self.fused else tuple(grads)

    def grid_params(self, interpret, *blocks, rows_in_order=False):
        """Compiler parameters of a grid ``(*steps, *blocks)``, ``blocks``
        the semantics of its trailing dimensions; the steps are parallel
        unless the blocks of a batch row have to run in order.  The raised
        vmem limit lets XLA keep large kernel outputs in VMEM when it
        judges that profitable (v5e has 128M; the default 16M scoped limit
        rejects long-sequence outputs it would otherwise promote)."""
        if interpret:
            return {}
        steps = ("parallel",) * len(self.steps)
        if rows_in_order:
            steps = ("parallel", "arbitrary")
        return {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=steps + blocks,
            vmem_limit_bytes=100 * 1024 * 1024)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, kv_mask=None, dropout_seed=None, causal=False,
                    block_q=None, block_k=None,
                    interpret=False, dropout_rate=0.0, window=None):
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

    ``k`` and ``v`` may hold fewer heads than ``q`` (grouped KV heads, head
    widths multiples of 128: query head ``i`` reads head ``i // (h /
    h_kv)``, and dk, dv come back summed over each group), and with
    ``window`` (causal, ``block_q == block_k``) a query sees its last
    ``window`` keys, itself among them: the blocks outside that band are
    neither fetched nor computed, forward or backward (``_band``).  Neither
    takes a key mask or dropout.  Their kernels carry names of their own in
    a device trace (``_train_name``).
    """
    return _flash_fwd_rule(q, k, v, kv_mask, dropout_seed, causal, block_q,
                           block_k, interpret, dropout_rate, window)[0]


def _train_name(kv_group, window, part):
    """The differentiable kernels' names in a device trace where the heads
    are grouped or windowed; None (Pallas' own) for the others."""
    if window is not None:
        return f"window_train_attention_{part}"
    return f"gqa_train_attention_{part}" if kv_group > 1 else None


def _window_note(window):
    return "" if window is None else f", window {window}"


def flash_attention_forward(q, k, v, *, causal, block_q, block_k,
                            window=None, interpret=False, name=None):
    """The forward kernel alone, for serving: ``q``, ``k`` [b, s, h, d] and
    ``v`` [b, s, h, dv] with a value width of its own, blocks given by the
    caller (no first-use tuning inside a served program), softmax scale
    1/sqrt(d) (fold any other factor into ``q``).  ``name`` is the kernel's
    name in a device trace.

    ``k`` and ``v`` may hold fewer heads than ``q`` (grouped KV heads:
    query head ``i`` reads head ``i // (h / h_kv)``; they are indexed, not
    repeated).  With ``window`` (causal, ``block_q == block_k``) a query
    sees its last ``window`` keys, itself among them, and the blocks
    outside that band are neither computed nor fetched: the streamed grid
    dimension is the band's width (``_band``), not the sequence's."""
    out, _ = _flash_fwd(q, k, v, None, None, causal, block_q, block_k,
                        interpret, 0.0, name=name, window=window)
    return out


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
def _log_geometry(s, kv_len, d, causal, dropout, block_q, block_k, rows,
                  chosen, layout):
    """One line per distinct kernel geometry per process (traced calls
    repeat per layer and per pass): which blocks a shape ran with, how
    many batch rows a grid step holds, whether the caller, the measured
    heuristic or the first-use tuner chose the blocks — two cold runs of
    an un-anchored shape may differ — and which operand layout the kernels
    index (``_Operands``)."""
    logger.info("flash_attention geometry: s=%d kv=%d d=%d causal=%s "
                "dropout=%s -> block_q=%d block_k=%d rows/step=%d (%s; %s)",
                s, kv_len, d, causal, dropout, block_q, block_k, rows, chosen,
                layout)


def _resolve_blocks(ops, s, kv_len, d, block_q, block_k, causal=False,
                    dropout_rate=0.0, note=""):
    """The call's operands, with the batch rows a step holds of them
    (``_Operands.tile``), and its blocks; ``note`` is added to the layout
    in the geometry's log line."""
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
    ops = ops.tile(s, kv_len, block_q, block_k)
    _log_geometry(s, kv_len, d, causal, dropout_rate, block_q, block_k,
                  ops.rows, chosen, ops.name + note)
    # The kernels index K/V in whole blocks; a ragged tail would silently
    # attend over out-of-block garbage.  Dispatchers (attention.py) only
    # route divisible shapes here; direct callers must pad or shrink blocks.
    if s % block_q != 0 or kv_len % block_k != 0:
        raise ValueError(
            f"flash_attention requires seq divisible by block sizes: "
            f"q_len={s} % block_q={block_q}, kv_len={kv_len} % block_k={block_k}")
    return ops, block_q, block_k


# which block along the sequence a grid step reads, by the grid's ids: the
# grids are (*steps, outer blocks, inner blocks), or the steps alone
def _outer(*ids):
    return ids[-2]


def _inner(*ids):
    return ids[-1]


def _first(*ids):
    return 0


def _mask_ops(kv_mask, ops, rows, seq_block):
    """(operands, specs) of the optional [b, kv_len] key mask."""
    if kv_mask is None:
        return (), ()
    return ((kv_mask.astype(jnp.float32)[:, None, :],),
            (ops.mask_spec(rows, seq_block),))


def _band(window, block, n_kb):
    """K blocks a query block's window reaches (``block_q == block_k``):
    its own and as many before it as ``window - 1`` keys span."""
    return min(n_kb, -(-(window - 1) // block) + 1)


# kernel calls asked for, and those of them whose builder ran (the others
# were answered with an earlier trace's equations): ``trace_stats``
_calls = {"asked": 0, "traced": 0}


def trace_stats():
    """How often this process traced a kernel builder and how often the
    cache spared it that (``_fwd_kernels``): BERT-large's training step,
    23 layers' kernels forward and backward, reads 2 and 44 after its
    trace.  Logged with the compile counters (``CompileStats.close``)."""
    return {"geometries_traced": _calls["traced"],
            "calls_from_cache": _calls["asked"] - _calls["traced"]}


def _fwd_call(ops, q, k, v, dims, kv_mask, dropout_seed, causal, block_q,
              block_k, interpret, dropout_rate, name=None, window=None,
              note=""):
    """The forward kernel over operands already in ``ops``' layout (q, k
    and v one array where it is fused); ``dims`` = (s, kv_len, d, dv).
    Returns the output in that layout and the logsumexp
    (``_Operands.stat_shape``)."""
    s, kv_len, d, _ = dims
    ops, block_q, block_k = _resolve_blocks(ops, s, kv_len, d, block_q,
                                            block_k, causal, dropout_rate,
                                            note)
    _calls["asked"] += 1
    return _fwd_kernels(ops, q, k, v, dims, kv_mask, dropout_seed, causal,
                        block_q, block_k, interpret, dropout_rate, name,
                        window)


# An inlined ``jit`` stages no call of a sub-function: it is there for its
# cache, which answers a model's second layer with the first layer's
# equations, so that a kernel call is traced and lowered once a geometry
# (operands, lengths, blocks, mask, dropout: the static arguments and the
# operands' types) and not once a layer.  Host seconds on this sandbox's
# CPU, a step of BERT-large (23 layers' kernels, forward and backward)
# lowered for a described v5e.  seq 512, one row a step: 6.5–7.1 without
# the cache, 3.7–3.8 with it (PR 37).  seq 128, eight rows a step: 2.8–3.1
# with XLA's attention, 18.6 with the rows spelled out in each of 46
# kernel bodies and no cache (PR 36: a ``setup_s`` of 83–87 s on the chip,
# where a set-up lowered the step three times), 3.5–3.7 with the rows
# spelled out in the TWO bodies the cache leaves (PR 37: ``setup_s`` 35.8 s
# beside the 36.0 of XLA's attention; seq 512's 51.3 → 37.8 with the step
# traced once a process, ``engine._step_scalars``).  The cached trace
# shares the helper functions of the caller's module, so a program's text
# differs from the uncached one's in their NAMES alone
# (``test_tpu_compile.py`` holds the one-row programs to PR 35's, names
# normalised).
@functools.partial(jax.jit, inline=True,
                   static_argnums=(0, 4, 7, 8, 9, 10, 11, 12, 13))
def _fwd_kernels(ops, q, k, v, dims, kv_mask, dropout_seed, causal, block_q,
                 block_k, interpret, dropout_rate, name, window):
    _calls["traced"] += 1
    s, kv_len, d, dv = dims
    masked = kv_mask is not None
    n_qb = pl.cdiv(s, block_q)
    n_kb = pl.cdiv(kv_len, block_k)

    at_q, at_k = _outer, _inner  # grid (*steps, q blocks, k blocks)
    if window is not None:
        assert causal and block_q == block_k and not masked, (
            "a window is causal, over square blocks, with no key mask")
        n_kb = _band(window, block_k, n_kb)

        # the band's blocks end at the diagonal; one that would start
        # before the sequence re-reads block 0 and is skipped in the kernel
        def at_k(*ids):
            return jnp.maximum(ids[-2] - (n_kb - 1) + ids[-1], 0)
    seed_ops, seed_specs, drop = _dropout_ops(dropout_rate, dropout_seed)
    if masked:
        assert kv_mask.shape == (ops.b, kv_len), (
            f"kv_mask must be [batch, kv_len]={ops.b, kv_len}, "
            f"got {kv_mask.shape}")
    mask_ops, mask_specs = _mask_ops(kv_mask, ops, block_k, at_k)

    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, masked=masked, dropout=drop,
                               single=(n_kb == 1), heads=ops.heads,
                               lead=len(ops.steps), window=window,
                               rows=ops.rows)
    return pl.pallas_call(
        kernel,
        grid=(*ops.steps, n_qb, n_kb),
        in_specs=[
            *ops.qkv_specs(block_q, block_k, d, dv, at_q, at_k),
            *seed_specs,
            *mask_specs,
        ],
        out_specs=[
            ops.spec(block_q, dv, at_q),
            ops.row_spec(block_q, at_q),
        ],
        out_shape=[
            ops.shape(s, dv, q.dtype),
            jax.ShapeDtypeStruct(ops.stat_shape(s), jnp.float32),
        ],
        scratch_shapes=[
            *[_VMEM((block_q, 1), jnp.float32)] * ops.heads,  # running max m
            *[_VMEM((block_q, 1), jnp.float32)] * ops.heads,  # running sum l
            _VMEM((block_q, ops.heads * dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name=name,
        # steps and the outer block dim are parallel; the streamed dim
        # accumulates into VMEM scratch and must run in order
        **ops.grid_params(interpret, "parallel", "arbitrary"),
    )(q, k, v, *seed_ops, *mask_ops)


def _bwd_call(ops, q, k, v, g, out, lse, dims, kv_mask, dropout_seed, causal,
              block_q, block_k, interpret, dropout_rate, window=None):
    """(dq, dk, dv) in ``ops``' layout from operands, the output and its
    cotangent ``g`` in that layout; ``dims`` = (s, kv_len, d).  Where q,
    k, v are one fused array, its one gradient."""
    ops, block_q, block_k = _resolve_blocks(ops, *dims, block_q, block_k,
                                            causal, dropout_rate,
                                            _window_note(window))
    _calls["asked"] += 1
    return _bwd_kernels(ops, q, k, v, g, out, lse, dims, kv_mask,
                        dropout_seed, causal, block_q, block_k, interpret,
                        dropout_rate, window)


@functools.partial(jax.jit, inline=True,
                   static_argnums=(0, 7, 10, 11, 12, 13, 14, 15))
def _bwd_kernels(ops, q, k, v, g, out, lse, dims, kv_mask, dropout_seed,
                 causal, block_q, block_k, interpret, dropout_rate,
                 window=None):
    _calls["traced"] += 1
    s, kv_len, d = dims
    masked = kv_mask is not None
    n_qb = pl.cdiv(s, block_q)
    n_kb = pl.cdiv(kv_len, block_k)
    # grouped or windowed heads: the streamed pair below, whatever the size
    grouped = ops.kv_group > 1 or window is not None
    if grouped:
        assert not masked and not dropout_rate, (
            "grouped or windowed heads take no key mask and no dropout")

    seed_ops, seed_specs, drop = _dropout_ops(dropout_rate, dropout_seed)
    static = dict(scale=1.0 / math.sqrt(d), causal=causal, masked=masked,
                  dropout=drop, heads=ops.heads, lead=len(ops.steps))

    def in_specs(at_q, at_k, sixth):
        mask_ops, mask_specs = _mask_ops(kv_mask, ops, block_k, at_k)
        return mask_ops, [
            *ops.qkv_specs(block_q, block_k, d, d, at_q, at_k),
            ops.spec(block_q, d, at_q),
            ops.row_spec(block_q, at_q),
            sixth,
            *seed_specs,
            *mask_specs,
        ]

    if n_qb == 1 and n_kb == 1 and not grouped:
        # single-tile fused backward: one kernel, one score pass, Δ formed
        # inside.  A fused projection gets its ONE gradient from it
        # (_bwd_fused_kernel) while a batch row's whole [s, 3·h·d] fits
        # VMEM twice over beside the operands; a row's blocks share that
        # output block, so they run in order
        one_out = ops.fused and (ops.rows * q[0].size * q.dtype.itemsize
                                 <= _ROW_BLOCK_BYTES)
        if one_out:
            out_specs = pl.BlockSpec((ops.rows, s, q.shape[2]),
                                     lambda row, block: (row, 0, 0))
            out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
        else:
            out_specs = [ops.spec(block_q, d, _first),
                         ops.spec(block_k, d, _first),
                         ops.spec(block_k, d, _first)]
            out_shape = [ops.shape(s, d, q.dtype), ops.shape(kv_len, d, k.dtype),
                         ops.shape(kv_len, d, v.dtype)]
        mask_ops, specs = in_specs(_first, _first,
                                   ops.spec(block_q, d, _first))
        grads = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, fused_out=one_out,
                              rows=ops.rows, **static),
            grid=ops.steps,
            in_specs=specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            **ops.grid_params(interpret, rows_in_order=one_out),
        )(q, k, v, g, lse, out, *seed_ops, *mask_ops)
        return grads if one_out else ops.gradient(grads)

    # Δ = rowsum(dO ∘ O) per head, [b·h, 1, s] like the logsumexp
    if ops.packed:
        g4, out4 = ops.from_kernel(g), ops.from_kernel(out)
        delta = jnp.sum(g4.astype(jnp.float32) * out4.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1).reshape(-1, 1, s)
    else:
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True).transpose(0, 2, 1)

    at_q, at_k = _outer, _inner  # grid (*steps, q blocks, k blocks)
    band = n_kb     # k blocks a q block streams, q blocks a k block does
    if window is not None:
        assert causal and block_q == block_k, (
            "a window is causal, over square blocks")
        band = _band(window, block_k, n_kb)

        def at_k(*ids):     # as the forward's: the band ends at the diagonal
            return jnp.maximum(ids[-2] - (band - 1) + ids[-1], 0)
    mask_ops, specs = in_specs(at_q, at_k, ops.row_spec(block_q, at_q))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, single=(band == 1), window=window,
                          **static),
        grid=(*ops.steps, n_qb, band),
        in_specs=specs,
        out_specs=ops.spec(block_q, d, at_q),
        out_shape=ops.shape(s, d, q.dtype),
        scratch_shapes=[_VMEM((block_q, ops.heads * d), jnp.float32)],
        interpret=interpret,
        name=_train_name(ops.kv_group, window, "bwd_dq"),
        **ops.grid_params(interpret, "parallel", "arbitrary"),
    )(q, k, v, g, lse, delta, *seed_ops, *mask_ops)

    if grouped:
        # grid (batch, KV heads, k blocks, the group's query heads x their
        # q blocks): a k block's accumulators take every query head of its
        # group in turn — the group's sum is formed in VMEM, in fp32 — and
        # of each head the q blocks that see the k block: from the diagonal
        # down, the band's width of them under a window
        group, h = ops.kv_group, ops.h

        def q_head(*ids):
            return ids[1] * group + ids[-1] // band

        def q_block(*ids):
            at = ids[-1] % band
            if window is None:
                return at
            return jnp.minimum(ids[-2] + at, n_qb - 1)

        q_spec = pl.BlockSpec((1, block_q, d), lambda *ids: (
            ids[0], q_block(*ids), q_head(*ids)))
        kv_spec = pl.BlockSpec((1, block_k, d), lambda *ids: (
            ids[0], ids[-2], ids[1]))
        stat_spec = pl.BlockSpec((1, 1, block_q), lambda *ids: (
            ids[0] * h + q_head(*ids), 0, q_block(*ids)))
        kv_shape = jax.ShapeDtypeStruct((ops.b, kv_len, h // group * d),
                                        k.dtype)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, single=(group * band == 1),
                              window=window, per_head=band, n_qb=n_qb,
                              **static),
            grid=(ops.b, h // group, n_kb, group * band),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[kv_shape, kv_shape],
            scratch_shapes=[_VMEM((block_k, d), jnp.float32),
                            _VMEM((block_k, d), jnp.float32)],
            interpret=interpret,
            name=_train_name(group, window, "bwd_dkv"),
            **ops.grid_params(interpret, "parallel", "arbitrary"),
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    # grid (*steps, k blocks, q blocks): q streams in the inner dimension
    at_q, at_k = _inner, _outer
    mask_ops, specs = in_specs(at_q, at_k, ops.row_spec(block_q, at_q))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, single=(n_qb == 1), **static),
        grid=(*ops.steps, n_kb, n_qb),
        in_specs=specs,
        out_specs=[
            ops.spec(block_k, d, at_k),
            ops.spec(block_k, d, at_k),
        ],
        out_shape=[
            ops.shape(kv_len, d, k.dtype),
            ops.shape(kv_len, d, v.dtype),
        ],
        scratch_shapes=[
            _VMEM((block_k, ops.heads * d), jnp.float32),
            _VMEM((block_k, ops.heads * d), jnp.float32),
        ],
        interpret=interpret,
        **ops.grid_params(interpret, "parallel", "arbitrary"),
    )(q, k, v, g, lse, delta, *seed_ops, *mask_ops)
    return ops.gradient((dq, dk, dv))


def _flash_fwd(q, k, v, kv_mask, dropout_seed, causal, block_q, block_k,
               interpret, dropout_rate, name=None, window=None, note=""):
    b, s, h, d = q.shape
    # the values may be narrower or wider than the keys (latent attention
    # expands keys of 192 beside values of 128): the score tile is q.k over
    # d, the accumulator and the output are dv wide
    dv = v.shape[-1]
    ops = _Operands(b, h, d, dv, kv_group=h // k.shape[2])
    out, lse = _fwd_call(
        ops, ops.to_kernel(q), ops.to_kernel(k), ops.to_kernel(v),
        (s, k.shape[1], d, dv), kv_mask, dropout_seed, causal, block_q,
        block_k, interpret, dropout_rate, name, window, note)
    out = ops.from_kernel(out)
    return out, (q, k, v, kv_mask, dropout_seed, out, lse)


# what a grouped or windowed forward leaves for its backward, by name: a
# model that recomputes its layers may keep the two (``jax.checkpoint``'s
# ``save_only_these_names``) and spare itself the second forward kernel
SAVED_NAMES = ("flash_attention_out", "flash_attention_lse")


def _flash_fwd_rule(q, k, v, kv_mask, dropout_seed, causal, block_q, block_k,
                    interpret, dropout_rate, window):
    name = _train_name(q.shape[2] // k.shape[2], window, "fwd")
    out, res = _flash_fwd(q, k, v, kv_mask, dropout_seed, causal, block_q,
                          block_k, interpret, dropout_rate, name=name,
                          window=window, note=_window_note(window))
    if name is not None:
        out, lse = (checkpoint_name(x, n) for x, n in zip(
            (out, res[-1]), SAVED_NAMES))
        res = (*res[:-2], out, lse)
    return out, res


def _flash_bwd_rule(causal, block_q, block_k, interpret, dropout_rate, window,
                    res, g):
    assert res[2].shape[-1] == res[0].shape[-1], (
        "the flash backward kernels assume values as wide as keys; a "
        "value width of its own is forward-only (flash_attention_forward)")
    q, k, v, kv_mask, dropout_seed, out, lse = res
    b, s, h, d = q.shape
    ops = _Operands(b, h, d, d, kv_group=h // k.shape[2])
    dq, dk, dv = _bwd_call(
        ops, ops.to_kernel(q), ops.to_kernel(k), ops.to_kernel(v),
        ops.to_kernel(g), ops.to_kernel(out), lse, (s, k.shape[1], d),
        kv_mask, dropout_seed, causal, block_q, block_k, interpret,
        dropout_rate, window)
    if ops.kv_group > 1:    # [b, s, kv heads · d], summed over each group
        dk, dv = dk.reshape(k.shape), dv.reshape(v.shape)
    else:
        dk, dv = ops.from_kernel(dk), ops.from_kernel(dv)
    return (ops.from_kernel(dq), dk, dv,
            None if kv_mask is None else jnp.zeros_like(kv_mask), None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_self_attention(qkv, kv_mask=None, dropout_seed=None, causal=False,
                         block_q=None, block_k=None, interpret=False,
                         dropout_rate=0.0):
    """``flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], ...)`` for
    the fused projection ``qkv`` [b, s, 3, h, d] of a self-attention layer.

    A kernel call wants whole buffers, so three slices of one activation
    are three copies going in and a concatenate coming back.  Where the
    heads fill lane tiles (``_Operands``) the kernels index the ONE array
    three times instead, a third of its last dimension apart; any other
    shape takes the slices."""
    b, s, _, h, d = qkv.shape
    if not _Operands(b, h, d, d).packed:
        return flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                               kv_mask, dropout_seed, causal, block_q,
                               block_k, interpret, dropout_rate)
    return _flash_fused(qkv, kv_mask, dropout_seed, causal, block_q, block_k,
                        interpret, dropout_rate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_fused(qkv, kv_mask, dropout_seed, causal, block_q, block_k,
                 interpret, dropout_rate):
    return _flash_fused_fwd(qkv, kv_mask, dropout_seed, causal, block_q,
                            block_k, interpret, dropout_rate)[0]


def _flash_fused_fwd(qkv, kv_mask, dropout_seed, causal, block_q, block_k,
                     interpret, dropout_rate):
    b, s, _, h, d = qkv.shape
    ops = _Operands(b, h, d, d, fused=True)
    x = qkv.reshape(b, s, 3 * h * d)
    out, lse = _fwd_call(ops, x, x, x, (s, s, d, d), kv_mask, dropout_seed,
                         causal, block_q, block_k, interpret, dropout_rate)
    return ops.from_kernel(out), (qkv, kv_mask, dropout_seed, out, lse)


def _flash_fused_bwd(causal, block_q, block_k, interpret, dropout_rate, res,
                     g):
    qkv, kv_mask, dropout_seed, out, lse = res
    b, s, _, h, d = qkv.shape
    ops = _Operands(b, h, d, d, fused=True)
    x = qkv.reshape(b, s, 3 * h * d)
    grad = _bwd_call(ops, x, x, x, ops.to_kernel(g), out, lse, (s, s, d),
                     kv_mask, dropout_seed, causal, block_q, block_k,
                     interpret, dropout_rate)
    return (grad.reshape(qkv.shape),
            None if kv_mask is None else jnp.zeros_like(kv_mask), None)


_flash_fused.defvjp(_flash_fused_fwd, _flash_fused_bwd)
