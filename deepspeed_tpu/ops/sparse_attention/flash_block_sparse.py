"""Pallas block-sparse flash attention for TPU.

TPU-native analog of the reference's Triton block-sparse kernel stack
(``ops/sparse_attention/matmul.py`` SDD/DSD/DDS + ``softmax.py`` +
``trsrc/*.tr``, with the C++ LUT builder ``csrc/sparse_attention/
utils.cpp``).  The reference compiles look-up tables that map nonzero
layout blocks to kernel work items; here the same LUTs are built host-side
from the ``[H, nb, nb]`` layout and fed to the Mosaic kernel as
scalar-prefetch operands.  Round 5 made the schedule a flattened
WORK LIST (``build_work_luts``): the streaming grid dimension runs one
tick per ACTIVE (q block, k block) pair — a ragged per-row grid padded
every row to the densest row's count, so BigBird's global row (attends
everything) made every row pay a full-density sweep.  Each ``BlockSpec``
index map reads the job arrays to decide which Q and K/V blocks to DMA
next; softmax state opens/closes on first/last-of-row flag bits.
Compute and HBM traffic scale with the number of active blocks — O(s·w)
— while the inner loop is the flash-attention online softmax on
MXU-shaped ``[blk, blk]`` tiles (the dense flash kernel's recurrence,
``ops/transformer/flash_attention.py``, restricted to the layout).

Backward is a SINGLE fused pass over the same row-major work list: dq
accumulates per-row scratch; dk/dv accumulate into full-sequence [s, d]
fp32 VMEM scratch at each job's k-block offset (4 MB per buffer at
seq 16k/d 64), which deletes the transposed-LUT second pass and its
score/softmax recomputation entirely.

No in-kernel dropout (compose ``TransformerLayer``'s output dropout) and
no key-padding mask in v1 — the gather-based ``block_sparse.py`` remains
the fully-general reference implementation and the CPU path.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..transformer.flash_attention import (MAX_FLOOR, NEG_INF, _VMEM,
                                           _flatten_heads, _unflatten_heads,
                                           pltpu)


def build_block_luts(layout):
    """Host-side LUTs from a ``[H, nb, nb]`` 0/1 layout (the analog of the
    reference's ``make_lut``, ``softmax.py:22`` / ``matmul.py:27``).

    Returns ``(lut, cnt, tlut, tcnt)``:
      - ``lut[h, qb, t]``: t-th active key-block for query block qb
        (``cnt[h, qb]`` valid entries, zero-padded);
      - ``tlut[h, kb, t]``: t-th query block attending to key block kb
        (``tcnt[h, kb]`` valid entries) — the transposed layout, for dk/dv.
    """
    layout = np.asarray(layout) != 0
    h, nb, nb2 = layout.shape
    assert nb == nb2, f"layout must be square, got {layout.shape}"
    kmax = max(1, int(layout.sum(-1).max()))
    qmax = max(1, int(layout.sum(-2).max()))
    lut = np.zeros((h, nb, kmax), np.int32)
    cnt = np.zeros((h, nb), np.int32)
    tlut = np.zeros((h, nb, qmax), np.int32)
    tcnt = np.zeros((h, nb), np.int32)
    for hi in range(h):
        for qb in range(nb):
            cols = np.nonzero(layout[hi, qb])[0]
            lut[hi, qb, :len(cols)] = cols
            cnt[hi, qb] = len(cols)
        for kb in range(nb):
            rows = np.nonzero(layout[hi, :, kb])[0]
            tlut[hi, kb, :len(rows)] = rows
            tcnt[hi, kb] = len(rows)
    return lut, cnt, tlut, tcnt


def build_work_luts(layout):
    """Flattened WORK-LIST LUTs: one entry per ACTIVE (q block, k block)
    pair, row-major sorted, plus the k-major transpose for dk/dv.

    Why: the ragged-grid form pads every q row to ``kmax`` ticks, and one
    dense row poisons the whole grid — BigBird's global row attends ALL
    32 key blocks at seq 16k/blk 512 while regular rows attend ~6, so
    every row paid 32 ticks (26 masked).  Work-list ticks equal the
    number of active blocks exactly; the kernel walks jobs and opens/
    closes the softmax state on row-change flags (CSR-style, the same
    reason the reference's Triton kernels iterate ``lut`` rows of raw
    nonzero blocks, ``matmul.py:27``).

    Returns ``(jq, jk, fl)``, each ``[H, T]`` int32: ``jq/jk`` the job's
    q/k block, ``fl`` flag bits (1 = first job of its row, 2 = last job
    of its row, 4 = compute).  Rows with NO active blocks get one
    no-compute job (first|last) so their output window is still
    initialized (zero output, matching the gather reference).  Heads pad
    to a common T with no-op jobs repeating the last position (the
    output window stays put, nothing recomputes).  No transposed list:
    the fused single-pass backward accumulates dk/dv in full-sequence
    VMEM scratch, so the k-major walk no longer exists."""
    layout = np.asarray(layout) != 0
    H, nb, _ = layout.shape

    def one(mat):  # mat[qb, kb] -> row-major job list
        jobs = []
        for qb in range(nb):
            cols = np.nonzero(mat[qb])[0]
            if len(cols) == 0:
                jobs.append((qb, 0, 1 | 2))
            else:
                for t, c in enumerate(cols):
                    fl = 4 | (1 if t == 0 else 0) | (
                        2 if t == len(cols) - 1 else 0)
                    jobs.append((qb, int(c), fl))
        return jobs

    per_head = [one(layout[hi]) for hi in range(H)]
    T = max(len(x) for x in per_head)
    jq = np.zeros((H, T), np.int32)
    jk = np.zeros((H, T), np.int32)
    fl = np.zeros((H, T), np.int32)
    for hi, jobs in enumerate(per_head):
        for t, (q_, k_, fl_) in enumerate(jobs):
            jq[hi, t], jk[hi, t], fl[hi, t] = q_, k_, fl_
        for t in range(len(jobs), T):  # no-op padding
            jq[hi, t], jk[hi, t], fl[hi, t] = jobs[-1][0], jobs[-1][1], 0
    return jq, jk, fl


def _layout_head(i, heads, n_layout_heads):
    """Layout-head index for flat batch·head grid index ``i``."""
    if n_layout_heads == 1:
        return 0
    return jax.lax.rem(i, heads)


def build_super_luts(layout, G):
    """2-D aggregated LUTs: coarsen the layout into ``G×G`` super-tiles so
    the kernel streams MXU-efficient ``[G·blk, G·blk]`` tiles (the fix for
    sub-512 layout blocks starving the MXU: the reference's Triton kernels
    run 16-px blocks natively, but TPU tiles want ~512-wide dots, so a
    super-tile covers a G×G patch of layout blocks and a per-tile BITMASK
    — bit ``row_g·G + col_g`` — keeps masking at the original block
    granularity).  Work scales with SUPER-tile density at the dense
    kernel's per-tile efficiency.

    Returns ``(slut, scnt, smask, stlut, stcnt, stmask)``:
      - ``slut[h, sq, t]``: t-th active super key-column for super q-row
        ``sq`` (``scnt[h, sq]`` valid entries);
      - ``smask[h, sq, t]``: G·G bits of that super-tile's sub-blocks;
      - ``stlut/stcnt/stmask``: the transpose — active super q-rows per
        super key-column (for dk/dv), with the SAME bit convention.
    """
    layout = np.asarray(layout) != 0
    h, nb, nb2 = layout.shape
    assert nb == nb2 and nb % G == 0 and G * G <= 32
    ns = nb // G
    # [h, ns, G, ns, G] → per-super-tile G×G patch
    patch = layout.reshape(h, ns, G, ns, G)
    active = patch.any(axis=(2, 4))                  # [h, ns, ns]
    bitval = (1 << (np.arange(G)[:, None] * G
                    + np.arange(G)[None, :])).astype(np.int64)
    bits = (patch.transpose(0, 1, 3, 2, 4) * bitval).sum((-1, -2))  # [h,ns,ns]
    tmax = max(1, int(active.sum(-1).max()))
    qmax = max(1, int(active.sum(-2).max()))
    slut = np.zeros((h, ns, tmax), np.int32)
    scnt = np.zeros((h, ns), np.int32)
    smask = np.zeros((h, ns, tmax), np.int32)
    stlut = np.zeros((h, ns, qmax), np.int32)
    stcnt = np.zeros((h, ns), np.int32)
    stmask = np.zeros((h, ns, qmax), np.int32)
    for hi in range(h):
        for sq in range(ns):
            cols = np.nonzero(active[hi, sq])[0]
            slut[hi, sq, :len(cols)] = cols
            scnt[hi, sq] = len(cols)
            smask[hi, sq, :len(cols)] = bits[hi, sq, cols]
        for sk in range(ns):
            rows = np.nonzero(active[hi, :, sk])[0]
            stlut[hi, sk, :len(rows)] = rows
            stcnt[hi, sk] = len(rows)
            stmask[hi, sk, :len(rows)] = bits[hi, rows, sk]
    return slut, scnt, smask, stlut, stcnt, stmask


def _super_tile_mask(mask_val, G, blk):
    """[G·blk, G·blk] bool from the G·G-bit super-tile mask: element
    (r, c) active iff bit ``(r//blk)·G + (c//blk)`` is set.  Built from
    two BROADCAST shifts (a [n,1] row shift then a [1,n] column shift) —
    fewer full-tile VPU passes than materializing the 2-D bit index."""
    n = G * blk
    row_sh = (jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) // blk) * G
    col_sh = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) // blk
    shifted = jax.lax.shift_right_logical(
        jax.lax.shift_right_logical(jnp.full((n, 1), mask_val, jnp.int32),
                                    row_sh), col_sh)
    return shifted & 1 > 0


def _tile_scores(q_blk, k_blk, scale, causal, j, kb, blk):
    s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        k_idx = kb * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)
    return s


def _job(jq_ref, jk_ref, fl_ref, lh, t):
    f = fl_ref[lh, t]
    return (jq_ref[lh, t], jk_ref[lh, t],
            (f & 1) != 0, (f & 2) != 0, (f & 4) != 0)


def _fwd_kernel(jq_ref, jk_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, causal, heads, n_layout_heads,
                blk):
    """Work-list forward: grid tick t executes job t — one ACTIVE
    (q block, k block) tile.  Softmax state opens on the job's
    first-of-row flag and the output window closes on last-of-row."""
    i, t = pl.program_id(0), pl.program_id(1)
    lh = _layout_head(i, heads, n_layout_heads)
    j, kb, first, last, valid = _job(jq_ref, jk_ref, fl_ref, lh, t)

    @pl.when(first)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(valid)
    def _step():
        s = _tile_scores(q_ref[0], k_ref[0], scale, causal, j, kb, blk)
        m, l = m_sc[...], l_sc[...]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=1, keepdims=True)),
                            MAX_FLOOR)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        # rows with no active key block (no-compute job, or causal-masked
        # away) produce zero output, matching the gather reference's guard
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l_safe))[:, 0]


def _bwd_fused_kernel(jq_ref, jk_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dq_sc, dk_sc, dv_sc, *, scale, causal, heads,
                      n_layout_heads, blk):
    """Single-pass backward: dq, dk AND dv from one score materialization
    per active tile.  dq accumulates per-row in a [blk, d] scratch (the
    row-major job order closes it on last-of-row); dk/dv accumulate into
    FULL-SEQUENCE [s, d] fp32 VMEM scratch at each job's k-block offset —
    at d=64 that is 4 MB per buffer even at seq 16k, comfortably inside
    VMEM, and it deletes the entire second backward pass (transposed-LUT
    dk/dv kernel) with its score/softmax/dp recomputation and K/V
    re-streaming.  Measured round 5: 1.95x -> ~3x vs dense at the BigBird
    seq-16k bench layout together with the work-list grid."""
    i, t = pl.program_id(0), pl.program_id(1)
    n_t = pl.num_programs(1)
    lh = _layout_head(i, heads, n_layout_heads)
    j, kb, first, last, valid = _job(jq_ref, jk_ref, fl_ref, lh, t)

    @pl.when(t == 0)
    def _zero_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(first)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(valid)
    def _step():
        s = _tile_scores(q_ref[0], k_ref[0], scale, causal, j, kb, blk)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [blk_q, blk_k] fp32
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(k_ref.dtype)
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_blk = jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_blk = jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        off = kb * blk
        dk_sc[pl.ds(off, blk), :] = dk_sc[pl.ds(off, blk), :] + dk_blk
        dv_sc[pl.ds(off, blk), :] = dv_sc[pl.ds(off, blk), :] + dv_blk

    @pl.when(last)
    def _finalize_dq():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)

    @pl.when(t == n_t - 1)
    def _finalize_dkv():
        # s was scaled after the q·kᵀ dot, so the 1/√d factor lands on dk
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _agg_tile_scores(q_tile, k_tile, scale, mask_val, causal, sq, skb, G,
                     blk):
    """[G·blk, G·blk] scores with the super-tile bitmask (and causal)
    applied — inactive sub-blocks mask to -inf exactly like causal
    masking, so the online softmax recurrence is untouched.

    (Round-4 negative result: branching on ``mask_val == full`` with
    ``lax.cond`` to skip the bitmask select on fully-active super-tiles
    measured 5.4–6.8 ms vs 4.3–5.1 unbranched at s4096/blk128 — the
    Mosaic branch costs more than the mask work it skips, consistent
    with the dense kernel's masked/unmasked-split result.)"""
    s = jax.lax.dot_general(q_tile, k_tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    active = _super_tile_mask(mask_val, G, blk)
    if causal:
        n = G * blk
        q_idx = sq * n + jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        k_idx = skb * n + jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        active = jnp.logical_and(active, q_idx >= k_idx)
    return jnp.where(active, s, NEG_INF)


def _fwd_kernel_agg(slut_ref, scnt_ref, smask_ref, q_ref, k_ref, v_ref,
                    o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale, causal,
                    heads, n_layout_heads, blk, G):
    """Forward over 2-D super-tiles: both q and k tiles span G layout
    blocks ([G·blk, d] each) so every dot runs at the dense kernel's tile
    shape; the G·G-bit mask keeps the math at layout-block granularity."""
    i, sq, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_t = pl.num_programs(2)
    lh = _layout_head(i, heads, n_layout_heads)

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(t < scnt_ref[lh, sq])
    def _step():
        skb = slut_ref[lh, sq, t]
        s = _agg_tile_scores(q_ref[0], k_ref[0], scale,
                             smask_ref[lh, sq, t], causal, sq, skb, G, blk)
        m, l = m_sc[...], l_sc[...]
        m_new = jnp.maximum(jnp.maximum(m, jnp.max(s, axis=1, keepdims=True)),
                            MAX_FLOOR)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finalize():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l_safe))[:, 0]


def _bwd_dq_kernel_agg(slut_ref, scnt_ref, smask_ref, q_ref, k_ref, v_ref,
                       do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, scale,
                       causal, heads, n_layout_heads, blk, G):
    i, sq, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_t = pl.num_programs(2)
    lh = _layout_head(i, heads, n_layout_heads)

    @pl.when(t == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(t < scnt_ref[lh, sq])
    def _step():
        skb = slut_ref[lh, sq, t]
        s = _agg_tile_scores(q_ref[0], k_ref[0], scale,
                             smask_ref[lh, sq, t], causal, sq, skb, G, blk)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(k_ref.dtype)
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel_agg(stlut_ref, stcnt_ref, stmask_ref, q_ref, k_ref,
                        v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                        dk_sc, dv_sc, *, scale, causal, heads,
                        n_layout_heads, blk, G):
    """dk/dv: the k/v tiles are fixed per super key-column; super q-rows
    stream via the transposed LUT with the same bit convention."""
    i, sk, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_t = pl.num_programs(2)
    lh = _layout_head(i, heads, n_layout_heads)

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(t < stcnt_ref[lh, sk])
    def _step():
        sqb = stlut_ref[lh, sk, t]
        s = _agg_tile_scores(q_ref[0], k_ref[0], scale,
                             stmask_ref[lh, sk, t], causal, sqb, sk, G, blk)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [G·blk, G·blk]
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(q_ref.dtype)
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _grid_params(interpret, ndims=3):
    if interpret:
        return {}
    sem = ("parallel",) * (ndims - 1) + ("arbitrary",)
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=sem,
        vmem_limit_bytes=100 * 1024 * 1024)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fbs_attention(q, k, v, jq, jk, fl, nb, causal, interpret):
    out, _ = _fbs_fwd(q, k, v, jq, jk, fl, nb, causal, interpret)
    return out


def _fbs_specs(h, H, blk, d):
    def iq(i, t, jq_r, jk_r, fl_r):
        return (i, jq_r[_layout_head(i, h, H), t], 0)

    def ik(i, t, jq_r, jk_r, fl_r):
        return (i, jk_r[_layout_head(i, h, H), t], 0)

    def iq_row(i, t, jq_r, jk_r, fl_r):
        return (i, 0, jq_r[_layout_head(i, h, H), t])

    return iq, ik, iq_row


def _fbs_fwd(q, k, v, jq, jk, fl, nb, causal, interpret):
    b, s, h, d = q.shape
    H, T = jq.shape
    blk = s // nb
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    bh = b * h
    iq, ik, iq_row = _fbs_specs(h, H, blk, d)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               heads=h, n_layout_heads=H, blk=blk)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, T),
            in_specs=[
                pl.BlockSpec((1, blk, d), iq),
                pl.BlockSpec((1, blk, d), ik),
                pl.BlockSpec((1, blk, d), ik),
            ],
            out_specs=[
                pl.BlockSpec((1, blk, d), iq),
                pl.BlockSpec((1, 1, blk), iq_row),
            ],
            scratch_shapes=[
                _VMEM((blk, 1), jnp.float32),
                _VMEM((blk, 1), jnp.float32),
                _VMEM((blk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
        **_grid_params(interpret, ndims=2),
    )(jq, jk, fl, qf, kf, vf)
    outh = _unflatten_heads(out, b, h)
    return outh, (q, k, v, jq, jk, fl, outh, lse)


def _fbs_bwd(nb, causal, interpret, res, g):
    q, k, v, jq, jk, fl, out, lse = res
    b, s, h, d = q.shape
    # the fused backward's dk/dv accumulate in full-sequence fp32 VMEM
    # scratch: ~12·s·d bytes incl. outputs.  Fine through seq 32k/d 64
    # (measured) and ~64k, but past the ~100 MB scoped-VMEM budget the
    # kernel cannot compile — fail with guidance instead of a Mosaic
    # internal error (the gather-based block_sparse_attention has no such
    # ceiling)
    if 12 * s * d > 96 * 1024 * 1024 and not interpret:
        raise ValueError(
            f"flash_block_sparse_attention backward needs ~{12 * s * d >> 20}"
            f" MB of VMEM scratch at seq {s}, head_dim {d} (limit ~96 MB): "
            f"use the gather-based block_sparse_attention for this shape, "
            f"or shard the sequence (ring attention / the seq mesh axis)")
    H, T = jq.shape
    blk = s // nb
    scale = 1.0 / math.sqrt(d)
    bh = b * h

    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    dof, of = _flatten_heads(g), _flatten_heads(out)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1,
                    keepdims=True).transpose(0, 2, 1)  # [bh, 1, s]
    iq, ik, iq_row = _fbs_specs(h, H, blk, d)

    def whole(i, t, jq_r, jk_r, fl_r):
        return (i, 0, 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          heads=h, n_layout_heads=H, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, T),
            in_specs=[
                pl.BlockSpec((1, blk, d), iq),
                pl.BlockSpec((1, blk, d), ik),
                pl.BlockSpec((1, blk, d), ik),
                pl.BlockSpec((1, blk, d), iq),
                pl.BlockSpec((1, 1, blk), iq_row),
                pl.BlockSpec((1, 1, blk), iq_row),
            ],
            out_specs=[
                pl.BlockSpec((1, blk, d), iq),
                pl.BlockSpec((1, s, d), whole),
                pl.BlockSpec((1, s, d), whole),
            ],
            scratch_shapes=[
                _VMEM((blk, d), jnp.float32),
                _VMEM((s, d), jnp.float32),
                _VMEM((s, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        **_grid_params(interpret, ndims=2),
    )(jq, jk, fl, qf, kf, vf, dof, lse, delta)

    return (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
            _unflatten_heads(dv, b, h), None, None, None)


_fbs_attention.defvjp(_fbs_fwd, _fbs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _fbs_attention_agg(q, k, v, slut, scnt, smask, stlut, stcnt, stmask,
                       causal, interpret, G):
    out, _ = _fbs_fwd_agg(q, k, v, slut, scnt, smask, stlut, stcnt, stmask,
                          causal, interpret, G)
    return out


def _fbs_fwd_agg(q, k, v, slut, scnt, smask, stlut, stcnt, stmask, causal,
                 interpret, G):
    b, s, h, d = q.shape
    H, nsq, tmax = slut.shape
    blk = s // (nsq * G)
    scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    bh = b * h

    kernel = functools.partial(_fwd_kernel_agg, scale=scale, causal=causal,
                               heads=h, n_layout_heads=H, blk=blk, G=G)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, nsq, tmax),
            in_specs=[
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, sq, 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sq, t], 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sq, t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, sq, 0)),
                pl.BlockSpec((1, 1, G * blk),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, 0, sq)),
            ],
            scratch_shapes=[
                _VMEM((G * blk, 1), jnp.float32),
                _VMEM((G * blk, 1), jnp.float32),
                _VMEM((G * blk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
        **_grid_params(interpret),
    )(slut, scnt, smask, qf, kf, vf)
    outh = _unflatten_heads(out, b, h)
    return outh, (q, k, v, slut, scnt, smask, stlut, stcnt, stmask, outh, lse)


def _fbs_bwd_agg(causal, interpret, G, res, g):
    (q, k, v, slut, scnt, smask, stlut, stcnt, stmask, out, lse) = res
    b, s, h, d = q.shape
    H, nsq, tmax = slut.shape
    qmax = stlut.shape[-1]
    blk = s // (nsq * G)
    scale = 1.0 / math.sqrt(d)
    bh = b * h

    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    dof, of = _flatten_heads(g), _flatten_heads(out)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1,
                    keepdims=True).transpose(0, 2, 1)  # [bh, 1, s]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_agg, scale=scale, causal=causal,
                          heads=h, n_layout_heads=H, blk=blk, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, nsq, tmax),
            in_specs=[
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, sq, 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sq, t], 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sq, t], 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, sq, 0)),
                pl.BlockSpec((1, 1, G * blk),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, 0, sq)),
                pl.BlockSpec((1, 1, G * blk),
                             lambda i, sq, t, lut_r, cnt_r, msk_r: (i, 0, sq)),
            ],
            out_specs=pl.BlockSpec(
                (1, G * blk, d),
                lambda i, sq, t, lut_r, cnt_r, msk_r: (i, sq, 0)),
            scratch_shapes=[_VMEM((G * blk, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        **_grid_params(interpret),
    )(slut, scnt, smask, qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_agg, scale=scale, causal=causal,
                          heads=h, n_layout_heads=H, blk=blk, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, nsq, qmax),
            in_specs=[
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sk, t], 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r: (i, sk, 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r: (i, sk, 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r:
                             (i, lut_r[_layout_head(i, h, H), sk, t], 0)),
                pl.BlockSpec((1, 1, G * blk),
                             lambda i, sk, t, lut_r, cnt_r, msk_r:
                             (i, 0, lut_r[_layout_head(i, h, H), sk, t])),
                pl.BlockSpec((1, 1, G * blk),
                             lambda i, sk, t, lut_r, cnt_r, msk_r:
                             (i, 0, lut_r[_layout_head(i, h, H), sk, t])),
            ],
            out_specs=[
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r: (i, sk, 0)),
                pl.BlockSpec((1, G * blk, d),
                             lambda i, sk, t, lut_r, cnt_r, msk_r: (i, sk, 0)),
            ],
            scratch_shapes=[
                _VMEM((G * blk, d), jnp.float32),
                _VMEM((G * blk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        **_grid_params(interpret),
    )(stlut, stcnt, stmask, qf, kf, vf, dof, lse, delta)

    return (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
            _unflatten_heads(dv, b, h), None, None, None, None, None, None)


_fbs_attention_agg.defvjp(_fbs_fwd_agg, _fbs_bwd_agg)


def _pick_q_agg(blk, nb, q_agg):
    """2-D aggregation factor: grow super-tiles toward the dense kernel's
    tuned 512 width, bounded by the layout (nb % G == 0) and the 32-bit
    per-tile mask (G·G <= 32 → G <= 5; 4 in practice).  Measured: blk 256
    runs best UNaggregated (the G=2 union overhead beats the tile-shape
    gain), so aggregation engages for blk <= 128 only."""
    if q_agg == "never":
        return 1
    if q_agg in ("auto", None):
        if blk > 128:
            return 1
        G = max(512 // blk, 1)
    else:
        # explicit factor: honored at ANY block size (ablations need it)
        G = int(q_agg)
    requested = G
    G = min(G, nb, 4)
    while G > 1 and nb % G != 0:
        G -= 1
    G = max(G, 1)
    if q_agg not in ("auto", None, "never") and G != requested:
        # an ablation must not silently measure a different kernel than
        # it asked for
        from ...utils.logging import logger

        logger.warning(
            "flash_block_sparse_attention: explicit q_agg=%s clamped to "
            "G=%d (bounds: nb=%d divisibility, mask budget G<=4)",
            q_agg, G, nb)
    return G


def flash_block_sparse_attention(q, k, v, layout, causal=False,
                                 interpret=False, q_agg="auto"):
    """Block-sparse flash attention on ``[b, s, h, d]`` inputs.

    ``layout`` is the ``[H, nb, nb]`` 0/1 block layout (H == heads, or 1 for
    a shared layout) produced by ``sparsity_config.make_layout``.

    Small layout blocks (the reference's Triton kernels run 16-px blocks;
    BERT-scale configs use 128) starve the MXU as bare [blk, blk] tiles —
    measured 0.76× vs dense at block 128 — so for ``blk < 512`` the kernel
    aggregates ``q_agg`` consecutive layout rows per q tile (512 sublanes,
    the dense kernel's tuned shape) and masks inactive (row, key-block)
    pairs via a per-tick bitmask; dk/dv aggregates key rows symmetrically.
    ``q_agg``: "auto" (default), "never", or an explicit factor.

    Requires the Mosaic PRNG-free feature set only.
    """
    b, s, h, d = q.shape
    layout = np.asarray(layout)
    nb = layout.shape[1]
    assert s % nb == 0, f"seq {s} not divisible into {nb} blocks"
    assert layout.shape[0] in (1, h), (
        f"layout heads {layout.shape[0]} incompatible with {h} heads")
    blk = s // nb
    G = _pick_q_agg(blk, nb, q_agg)
    if G > 1:
        luts = tuple(jnp.asarray(a) for a in build_super_luts(layout, G))
        return _fbs_attention_agg(q, k, v, *luts, bool(causal),
                                  bool(interpret), G)
    jq, jk, fl = build_work_luts(layout)
    return _fbs_attention(q, k, v, jnp.asarray(jq), jnp.asarray(jk),
                          jnp.asarray(fl), int(nb), bool(causal),
                          bool(interpret))
