"""O(1)-compile streamed-offload update: one chunk program, scanned.

The round-5 streamed ZeRO-Offload update (``offload.py``,
``OffloadStream._unrolled_update``) unrolls one full update pipeline — host
load, optimizer math, overflow select, host write-back — per chunk into
the fused step.  XLA program size therefore grows linearly with chunk
count (= state bytes / ``offload_chunk_mb``) and compile time grows
super-linearly with program size: in round 5 gpt2-xl (37 chunks)
compiled ~35 min and gpt2-2.7B (>60 chunks) never finished inside
30 min — the capacity ceiling had moved from memory to COMPILE WALL
TIME (VERDICT r5).

This module is the fix: with every chunk padded to ONE uniform
``(chunk_rows, LANES)`` shape, the whole chunk sequence becomes a
``lax.scan`` whose body is traced ONCE — the chunk index and row offset
are *data* (scan xs), not trace-time Python state.  Group membership
(offloaded state over the ~5 GB per-host-buffer toolchain bound is a
tuple of row-group buffers) is handled by ``lax.switch``: the heavy
per-chunk work — the host→device loads, the optimizer math, the
device→host write-back values — is traced once outside the branches,
and each branch contributes only its group's ``dynamic_slice`` /
``dynamic_update_slice`` (a few HLO ops per group).  Lowered program
size is O(groups) with a tiny constant instead of O(chunks) x the full
update body; the program-count test in
``tests/unit/test_offload_stream.py`` pins jaxpr size constant as chunk
count grows.

What the scan form trades away, deliberately:

- **The folded param cast** (``want_cast``).  ``lax.scan`` can only
  return per-chunk outputs as one stacked array — a full flat
  compute-dtype copy on device, exactly the ~2 bytes/param the round-4
  post-mortem showed re-imposes a capacity ceiling.  The scan path
  instead re-reads the master through the (cheap, 2-ops-per-chunk)
  leaf-direct streamed cast, or composes with ZeRO-3 where no resident
  param copy exists at all.

**Double-buffered pipelining** (round 12, ``prefetch_depth >= 2``):
the serialized scan body pays the full host wire as step latency by
construction — iteration *k*'s loads chain behind its own update and
write-back, so the wire sits idle during compute and vice versa.  With
``prefetch_depth = d`` the scan carry additionally holds a queue of
``d-1`` chunks already fetched to device: iteration *k* consumes the
queue head (fetched ``d-1`` iterations ago), ISSUES the fetch of job
``k+d-1``, updates, and writes back — and because the fetch, the
update, and the write-back are mutually independent dataflow within
one loop body, XLA schedules the next chunk's host→device DMA and this
chunk's device→host write-back concurrently with the update compute.
Device peak grows by exactly ``d-1`` chunk states.  The MATH is
untouched: every chunk consumes the same host values (jobs never share
rows, so fetching early reads identical data) with the same
stochastic-rounding tags (keyed by consumed-job index), which is why
the overlapped and serialized schedules are bit-identical — CI-pinned
by ``tests/unit/test_offload_overlap.py``.  The last ``d-1``
iterations have nothing left to prefetch; their fetch is masked by a
``lax.cond`` (false branch: zeros, no host read), so the pipeline
moves exactly one sweep of each buffer per step at every depth —
``host_state_bytes_per_step`` keeps its meaning unchanged.

The three round-4/5 load-bearing invariants survive structurally:
chunks stay CHAINED (the scan carry serializes iterations — XLA cannot
hoist every chunk's loads to once), host buffers stay a tuple of
≤5 GB row-group buffers (the switch addresses them; they are never
concatenated), and the write-back stays in-place
``dynamic_update_slice`` on loop-carried buffers (the classic aliasing
pattern XLA's while-loop buffer forwarding handles in place).

Everything here is placement-agnostic: device/host movement is injected
as ``to_dev`` / ``to_host`` callables (the engine passes
``jax.device_put`` into its device/pinned-host shardings; CPU tests
pass identity), so the numerics are testable on the CPU backend where
``pinned_host`` does not exist.
"""

import jax
import jax.numpy as jnp

# Chunk count at which "auto" switches the streamed update from the
# unrolled round-robin form to the uniform scan form.  Calibration: the
# round-robin build was measured FASTER at gpt2-large (18 chunks,
# 1.30 s/step) and pathological at gpt2-xl (37 chunks: 19.5 s/step
# round-robin, ~35 min compile) — the crossover sits between, and past
# it compile time is the binding constraint, not step time.
UNIFORM_MIN_CHUNKS = 24

# Chunk count past which the UNROLLED streamed update stops round-robin
# interleaving host groups and issues group-sequentially instead.  The
# round-5 capacity ladder measured the pathology this guards (PERF.md):
# round-robin was faster at gpt2-large (18 chunks, 2 groups) but
# collapsed at gpt2-xl (37 chunks: 19.5 s/step vs 5.16 sequential) —
# interleaving spreads each group's in-place DUS write-back chain
# across the whole unrolled program, so past the scheduler's buffer-
# forwarding window XLA materializes host-buffer copies per chunk
# instead of updating in place.  Sequential order keeps each group's
# chain contiguous.  The breakpoint sits between the two measured
# points; tied to UNIFORM_MIN_CHUNKS because the same wall calibrates
# both (past it the scan form is the default anyway — the unrolled
# form only reaches this size under offload_uniform_chunks: false).
ROUND_ROBIN_MAX_CHUNKS = UNIFORM_MIN_CHUNKS


def uniform_chunk_jobs(group_bounds, chunk_rows):
    """Round-robin (group, rel_row, abs_row) job list over uniform chunks.

    Requires every group's row count to be a multiple of ``chunk_rows``
    (the coordinator's uniform alignment); raises otherwise — callers
    fall back to the unrolled path on a False return from
    :func:`uniform_geometry_ok`, never on an exception here.
    """
    per_group = []
    for gr0, grc in group_bounds:
        assert grc % chunk_rows == 0, (grc, chunk_rows)
        per_group.append([(gr0, r0) for r0 in range(0, grc, chunk_rows)])
    jobs, idx = [], [0] * len(per_group)
    while any(idx[gi] < len(per_group[gi]) for gi in range(len(per_group))):
        for gi in range(len(per_group)):
            if idx[gi] < len(per_group[gi]):
                gr0, r0 = per_group[gi][idx[gi]]
                jobs.append((gi, r0, gr0 + r0))
                idx[gi] += 1
    return jobs


def sr_chunk_tags(jobs):
    """Issue-order-invariant stochastic-rounding tags: each job's rank
    among all jobs sorted by absolute row start.  Both streamed forms
    (this scan and the engine's unrolled chunk loop) key their SR
    streams with these, so reordering the ISSUE schedule (round-robin /
    sequential / pipelined) can never change a rounding draw — the
    bit-identical-schedules contract."""
    order = sorted(range(len(jobs)), key=lambda j: jobs[j][-1])
    tags = [0] * len(jobs)
    for rank, j in enumerate(order):
        tags[j] = rank
    return tags


def uniform_geometry_ok(group_bounds, chunk_rows):
    """True when every group tiles exactly into ``chunk_rows`` chunks."""
    if not chunk_rows:
        return False
    return all(grc % chunk_rows == 0 and grc > 0
               for _, grc in group_bounds)


def uniform_scan_update(*, masters, group_leaves, is_flat, opt_treedef,
                        update_fn, hp, overflow, skip_bad, jobs, chunk_rows,
                        lanes, g=None, g_groups=None, coef=None,
                        to_dev=None, to_host=None,
                        quant=None, res_masters=None, res_group_leaves=None,
                        prefetch_depth=1):
    """Scan the uniform-chunk offload update over ``jobs``.

    Args:
      masters: list of per-group ``(rows_g, lanes)`` fp32 host buffers.
      group_leaves: per-group flattened optimizer-state leaves (flat
        ``(rows_g, lanes)`` leaves differ per group; scalar leaves are
        identical across groups — the engine's zeros-init contract).
      is_flat: per-leaf bool mask (flat row buffer vs scalar state).
      opt_treedef: treedef to rebuild the per-chunk optimizer state.
      update_fn: ``(state, p_chunk, g_chunk, hp) -> (new_p, new_state)``
        — an elementwise flat optimizer (Adam family).
      overflow / skip_bad: the fp16/guard skip contract — on overflow
        every chunk keeps its old values (same per-chunk select as the
        unrolled path).
      jobs: ``[(group, rel_row, abs_row)]`` from :func:`uniform_chunk_jobs`.
      g: flat device gradient ``(rows, lanes)`` (pre-unscaled/clipped by
        the caller), or None when ``g_groups`` is given.
      g_groups: per-group HOST gradient buffers (``offload_gradients``);
        ``coef`` then folds unscale+clip into one per-chunk multiply.
      to_dev / to_host: placement callables (device_put into the
        engine's shardings; identity under test).
      quant: optional ``zero.qstate.StateQuant`` — reduced-precision
        host storage.  Chunks load in their storage dtype, upcast to
        fp32 (folding the error-feedback residual when present), update
        in fp32 exactly as the plain path, and downcast on write-back
        (stochastic rounding keyed by (optimizer step, job index), or
        nearest + fresh residual).  ``None`` leaves this function's
        traced program BYTE-IDENTICAL to the fp32-only form — the
        residual placeholders below are empty pytrees contributing no
        ops and no scan inputs.
      res_masters / res_group_leaves: per-group residual buffers for
        the master and for the reduced flat leaves (aligned with
        ``quant.res_leaf_lis``); only with ``quant.error_feedback``.
      prefetch_depth: chunks in flight (see the module docstring).  1 =
        the serialized schedule (fetch -> update -> write-back chained
        per iteration); d >= 2 = software-pipelined double buffering —
        the carry holds d-1 device-resident prefetched chunks, so each
        iteration's fetch/update/write-back are mutually independent
        and the scheduler overlaps wire with compute.  Clamped to the
        job count.  NUMERICS ARE IDENTICAL at every depth.

    Returns ``(new_masters, new_group_leaves, new_scalars[,
    new_res_masters, new_res_group_leaves])`` with the same group
    structure as the inputs (the residual tails only when ``quant``
    carries residuals).
    """
    if to_dev is None:
        to_dev = lambda x: x
    if to_host is None:
        to_host = lambda x: x
    n_g = len(masters)
    assert n_g == len(group_leaves) and n_g >= 1
    g_on_host = g_groups is not None
    assert g_on_host != (g is not None), \
        "exactly one of g / g_groups must be given"

    flat_pos = [li for li, f in enumerate(is_flat) if f]
    scalars0 = [l for l, f in zip(group_leaves[0], is_flat) if not f]

    has_resm = quant is not None and res_masters is not None
    n_resf = (len(res_group_leaves[0])
              if quant is not None and res_group_leaves else 0)
    # flat-leaf slot (fi, counting only is_flat leaves) -> residual slot
    res_slot_by_fi = {}
    if quant is not None:
        for k, li in enumerate(quant.res_leaf_lis):
            res_slot_by_fi[flat_pos.index(li)] = k
    sr_keys = quant is not None and quant._key0 is not None

    n_jobs = len(jobs)
    depth = max(1, min(int(prefetch_depth or 1), n_jobs))

    xs = {"gi": jnp.asarray([j[0] for j in jobs], jnp.int32),
          "r0": jnp.asarray([j[1] for j in jobs], jnp.int32),
          "abs": jnp.asarray([j[2] for j in jobs], jnp.int32)}
    if sr_keys:
        # stochastic-rounding tag: the chunk's CANONICAL rank by
        # absolute row (not the issue-order position), so the pipelined
        # and serialized schedules — and any unrolled-form job order at
        # the same geometry — draw identical rounding directions
        xs["jid"] = jnp.asarray(sr_chunk_tags(jobs), jnp.uint32)
    if depth > 1:
        # prefetch indices: iteration k issues job k+d-1's fetch.  The
        # last d-1 iterations have nothing left to prefetch; their slot
        # is MASKED (pvalid) — a lax.cond whose false branch returns
        # zeros, so the tail issues no host reads at all (a scan body
        # is traced once; peeling the tail would re-trace it, and an
        # unmasked wrap-around fetch would be redundant wire)
        pidx = [min(k + depth - 1, n_jobs - 1) for k in range(n_jobs)]
        xs["pgi"] = jnp.asarray([jobs[p][0] for p in pidx], jnp.int32)
        xs["pr0"] = jnp.asarray([jobs[p][1] for p in pidx], jnp.int32)
        xs["pvalid"] = jnp.asarray(
            [k + depth - 1 < n_jobs for k in range(n_jobs)], bool)

    def fetch(bufs, gi_, r0_):
        """One chunk's host slices -> device: ``(pm, flats, resm, resf,
        gg)`` with empty tuples for absent families.  Reading any job's
        rows commutes with writes to OTHER jobs' rows (jobs never share
        rows), which is what makes early fetch value-identical."""
        masters_x, flats_x, resm_x, resf_x = bufs

        def read(i):
            def branch(r):
                pm = jax.lax.dynamic_slice(
                    masters_x[i], (r, 0), (chunk_rows, lanes))
                fl = tuple(jax.lax.dynamic_slice(
                    flats_x[i][k], (r, 0), (chunk_rows, lanes))
                    for k in range(len(flat_pos)))
                rm = ((jax.lax.dynamic_slice(
                    resm_x[i], (r, 0), (chunk_rows, lanes)),)
                    if has_resm else ())
                rf = tuple(jax.lax.dynamic_slice(
                    resf_x[i][k], (r, 0), (chunk_rows, lanes))
                    for k in range(n_resf))
                gg = ((jax.lax.dynamic_slice(
                    g_groups[i], (r, 0), (chunk_rows, lanes)),)
                    if g_on_host else ())
                return pm, fl, rm, rf, gg
            return branch

        got = jax.lax.switch(gi_, [read(i) for i in range(n_g)], r0_)
        return jax.tree_util.tree_map(to_dev, got)

    def body(carry, xs_c):
        masters_c, flats_c, _, resm_c, resf_c, queue = carry
        gi, r0, r0a = xs_c["gi"], xs_c["r0"], xs_c["abs"]
        jid = xs_c.get("jid")
        bufs = (masters_c, flats_c, resm_c, resf_c)
        if depth > 1:
            # consume the chunk fetched d-1 iterations ago; issue the
            # next fetch NOW — independent of this iteration's update
            # and write-back, so the DMA overlaps the compute.  Tail
            # iterations (pvalid False) skip the host reads entirely
            head = queue[0]
            fetched = jax.lax.cond(
                xs_c["pvalid"],
                lambda: fetch(bufs, xs_c["pgi"], xs_c["pr0"]),
                lambda: jax.tree_util.tree_map(jnp.zeros_like, head))
            queue = queue[1:] + (fetched,)
        else:
            head = fetch(bufs, gi, r0)
        pm_q, chunk_flat_tup, rm_q, rf_q, gg_q = head
        chunk_flat_q = list(chunk_flat_tup)
        if g_on_host:
            gc = gg_q[0] * coef
        else:
            gc = jax.lax.dynamic_slice(g, (r0a, 0), (chunk_rows, lanes))

        if quant is None:
            pm = pm_q
            chunk_flat = chunk_flat_q
        else:
            pm = quant.load(pm_q, rm_q[0] if rm_q else None)
            chunk_flat = [
                quant.load(cq, rf_q[res_slot_by_fi[fi]]
                           if fi in res_slot_by_fi else None)
                for fi, cq in enumerate(chunk_flat_q)]

        leaves, it_f, it_s = [], iter(chunk_flat), iter(scalars0)
        for f in is_flat:
            leaves.append(next(it_f) if f else next(it_s))
        st = jax.tree_util.tree_unflatten(opt_treedef, leaves)
        new_p, new_st = update_fn(st, pm, gc, hp)
        new_leaves = jax.tree_util.tree_leaves(new_st)

        key_base = None
        if sr_keys:
            scalar_vals = [new_leaves[li] for li, f in enumerate(is_flat)
                           if not f]
            key_base = quant.chunk_key(
                scalar_vals[quant.step_scalar_idx], jid)

        if quant is None:
            if skip_bad:
                new_p = jnp.where(overflow, pm, new_p)
            new_p_h = to_host(new_p)
            new_rm_h, new_rf_h = (), {}
        else:
            q_p, r_p = quant.store(
                new_p, quant.master_dtype,
                key=(jax.random.fold_in(key_base, 0) if sr_keys
                     and quant.master_dtype != jnp.float32 else None))
            if skip_bad:
                q_p = jnp.where(overflow, pm_q, q_p)
                if r_p is not None:
                    r_p = jnp.where(overflow, rm_q[0], r_p)
            new_p_h = to_host(q_p)
            new_rm_h = (to_host(r_p),) if has_resm else ()
            new_rf_h = {}
        new_flat_h, new_scalars, fi = [], [], 0
        for li, f in enumerate(is_flat):
            if f:
                nl = new_leaves[li]
                if quant is None:
                    if skip_bad:
                        nl = jnp.where(overflow, chunk_flat[fi], nl)
                else:
                    q_l, r_l = quant.store(
                        nl, quant.leaf_dtypes[li],
                        key=(jax.random.fold_in(key_base, 1 + fi)
                             if sr_keys and quant.leaf_dtypes[li]
                             != jnp.float32 else None))
                    if skip_bad:
                        q_l = jnp.where(overflow, chunk_flat_q[fi], q_l)
                    if fi in res_slot_by_fi:
                        if skip_bad:
                            r_l = jnp.where(overflow,
                                            rf_q[res_slot_by_fi[fi]], r_l)
                        new_rf_h[res_slot_by_fi[fi]] = to_host(r_l)
                    nl = q_l
                new_flat_h.append(to_host(nl))
                fi += 1
            else:
                ns = new_leaves[li]
                if skip_bad:
                    ns = jnp.where(overflow, scalars0[len(new_scalars)], ns)
                new_scalars.append(ns)
        new_rf_h = tuple(new_rf_h[k] for k in range(n_resf))

        def write(i):
            def branch(args):
                r, pm_h, fl_h, rm_h, rf_h = args
                ms = tuple(
                    jax.lax.dynamic_update_slice(m, pm_h, (r, 0))
                    if j == i else m for j, m in enumerate(masters_c))
                fls = tuple(
                    tuple(jax.lax.dynamic_update_slice(
                        flats_c[j][k], fl_h[k], (r, 0))
                        if j == i else flats_c[j][k]
                        for k in range(len(flat_pos)))
                    for j in range(n_g))
                rms = tuple(
                    jax.lax.dynamic_update_slice(m, rm_h[0], (r, 0))
                    if j == i else m
                    for j, m in enumerate(resm_c)) if has_resm else ()
                rfs = tuple(
                    tuple(jax.lax.dynamic_update_slice(
                        resf_c[j][k], rf_h[k], (r, 0))
                        if j == i else resf_c[j][k]
                        for k in range(n_resf))
                    for j in range(n_g)) if n_resf else ()
                return ms, fls, rms, rfs
            return branch

        masters_n, flats_n, resm_n, resf_n = jax.lax.switch(
            gi, [write(i) for i in range(n_g)],
            (r0, new_p_h, tuple(new_flat_h), new_rm_h, new_rf_h))
        return (masters_n, flats_n, tuple(new_scalars), resm_n,
                resf_n, queue), None

    flats0 = tuple(tuple(group_leaves[gi][li] for li in flat_pos)
                   for gi in range(n_g))
    resm0 = tuple(res_masters) if has_resm else ()
    resf0 = (tuple(tuple(res_group_leaves[gi][k] for k in range(n_resf))
                   for gi in range(n_g)) if n_resf else ())
    # pipeline fill: jobs 0..d-2 fetch from the INITIAL buffers before
    # the scan starts (no prior write can touch their rows)
    bufs0 = (tuple(masters), flats0, resm0, resf0)
    queue0 = tuple(
        fetch(bufs0, jnp.int32(jobs[j][0]), jnp.int32(jobs[j][1]))
        for j in range(depth - 1))
    # scalar carry slot: pre-seeded with the originals so an (impossible)
    # empty job list degrades to "no update" rather than garbage
    carry0 = (tuple(masters), flats0, tuple(scalars0), resm0, resf0,
              queue0)
    (masters_n, flats_n, scalars_n, resm_n, resf_n, _), _ = jax.lax.scan(
        body, carry0, xs)

    new_group_leaves = []
    for gi in range(n_g):
        out, fi, si = [], 0, 0
        for f in is_flat:
            if f:
                out.append(flats_n[gi][fi])
                fi += 1
            else:
                out.append(scalars_n[si])
                si += 1
        new_group_leaves.append(out)
    if has_resm or n_resf:
        return (list(masters_n), new_group_leaves, list(scalars_n),
                list(resm_n) if has_resm else None,
                [list(rg) for rg in resf_n] if n_resf else None)
    return list(masters_n), new_group_leaves, list(scalars_n)
