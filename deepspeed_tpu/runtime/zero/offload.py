"""ZeRO-Offload's update: the decisions, the programs, the declared schedule.

Master weights and flat optimizer state live in pinned host memory; on
TPU the compiled programs stream them to device explicitly (XLA requires
uniform memory spaces per op) and the engine's ``out_shardings`` pin the
results back to host.  Reference analog: CPU-resident fp32 master +
DeepSpeedCPUAdam with async GPU copies (stage2.py:326-342,
csrc/adam/cpu_adam.cpp).

One :class:`OffloadStream` an engine, made from explicit inputs, decides
the form ONCE — one-shot, streamed unrolled or streamed ``lax.scan``
(``stream.py``), serialized or double-buffered, fp32 or reduced host
state (``qstate.py``) — and builds from the same fields the traced
functions the engine composes (:meth:`update`, :meth:`cast`,
:meth:`grads_to_host`, :meth:`carve_leaves`) and the schedule it
declares for them (:meth:`schedule`).  :func:`layout_args` is the
layout-time half of the decision, for the ``FlatParamCoordinator``.  The
engine keeps clipping, the loss-scale state, donation, ``out_shardings``.
"""

import jax
import jax.numpy as jnp

from ...ops.op_common import LANES
from ...utils.logging import log_dist, logger
from . import stream
from .coordinator import split_rows
from .qstate import (STATE_DTYPES, build_state_quant,
                     host_state_bytes_per_step)


def chunk_rows(zc):
    """Rows of one streamed chunk: ``offload_chunk_mb`` of fp32 rows
    (None when 0 — each buffer is its own chunk)."""
    chunk_mb = int(getattr(zc, "offload_chunk_mb", 512) or 0)
    return max(1, (chunk_mb << 20) // (LANES * 4)) if chunk_mb else None


def host_state_dtypes(zc):
    """Storage dtype of each host state buffer, by its name in the
    state (``offload_state_dtype``; all fp32 by default)."""
    sd = zc.offload_state_dtype
    return {"master": STATE_DTYPES[sd["master"]],
            "exp_avg": STATE_DTYPES[sd["momentum"]],
            "exp_avg_sq": STATE_DTYPES[sd["variance"]]}


def layout_args(zc):
    """What ``FlatParamCoordinator`` takes from the offload keys.

    Uniform-chunk (O(1)-compile) streaming needs the row layout aligned
    so every chunk of every host group has ONE shape (``stream.py``):
    "auto" aligns past ``UNIFORM_MIN_CHUNKS`` chunks of state, an
    explicit true at any size, false keeps the round-5 layout.  The
    master's storage dtype shapes the coordinator's buffers; the
    residual and gradient buffer FAMILIES count toward the host-buffer
    total the auto group layout must cap (the AOT crash mode)."""
    uniform_cfg = getattr(zc, "offload_uniform_chunks", "auto")
    return dict(
        uniform_chunk_rows=(chunk_rows(zc) if zc.cpu_offload
                            and uniform_cfg is not False else None),
        uniform_min_chunks=(1 if uniform_cfg is True
                            else stream.UNIFORM_MIN_CHUNKS),
        host_families=(3 + (1 if zc.offload_gradients else 0)
                       + getattr(zc, "offload_state_residual_count", 0)),
        master_dtype=(host_state_dtypes(zc)["master"]
                      if getattr(zc, "offload_state_reduced", False)
                      else None))


def _after(token, tree):
    """Data-dependency fence: every producer feeding ``tree`` may
    only be scheduled after ``token`` is computed.  Without this the
    chunk pipelines below are mutually independent and XLA's
    scheduler runs them ALL concurrently — every chunk's fp32 state
    lands on device at once and the peak is the full buffers again
    (measured: 29.3 G at GPT-2-xl, worse than unchunked)."""
    tree, _ = jax.lax.optimization_barrier((tree, token))
    return tree


def _is_grp(x):
    # plain tuple only: NamedTuple optimizer states are pytree
    # NODES, not row-group containers
    return type(x) is tuple


def _split_group_states(opt_state, n_g):
    """Per-group flattened optimizer-state views of a (possibly
    row-grouped) state tree: flat row-buffer leaves differ per
    group, scalar leaves are shared.  Returns (group_leaves,
    is_flat mask, treedef) — the common prologue of both
    streamed update forms."""
    opt_defs = None
    group_leaves, is_flat = [], None
    for gi in range(n_g):
        st_g = jax.tree_util.tree_map(
            lambda l: l[gi] if type(l) is tuple else l,
            opt_state, is_leaf=_is_grp)
        leaves, opt_defs = jax.tree_util.tree_flatten(st_g)
        group_leaves.append(leaves)
        if is_flat is None:
            is_flat = [getattr(l, "ndim", 0) == 2 for l in leaves]
    return group_leaves, is_flat, opt_defs


def _qres_group_bufs(qres):
    """state["qres"] dict -> {name: per-group buffer list}; the
    residual buffers share the master's row-group layout."""
    return {k: (list(v) if type(v) is tuple else [v])
            for k, v in (qres or {}).items()}


class OffloadStream:
    """The offloaded update of one engine: what it decided and the
    traced functions built from it.  ``flat`` gives the host row groups,
    ``uniform_chunk_rows`` and the shardings; ``device`` its
    ``memory_stats()``; ``eager`` offload parks state between steps, so
    nothing streams in-jit; the rest is what the traced functions read
    (``skip_bad``, ``clip``, the compute dtype, the parameter tree)."""

    def __init__(self, zc, flat, segments, optimizer, device, *, offload,
                 eager, host_grads, prng_impl, skip_bad, clip, compute_dtype,
                 param_template, param_shardings):
        injit = offload and not eager
        self.segments, self.optimizer = segments, optimizer
        self.skip_bad, self.clip = skip_bad, clip
        self.compute_dtype = compute_dtype
        self.param_template = param_template
        self.param_shardings = param_shardings
        self.dev_sharding = flat.master_device_sharding
        self.host_big = flat.master_sharding
        self.host_grad_big = flat.grad_host_sharding
        self.rr_disabled_logged = False

        # Chunk plan for streamed offload: the capacity fix for the in-jit
        # path, which otherwise materializes master + m + v on device AT
        # ONCE for the update (measured 21.8 G peak at GPT-2-large — MORE
        # than device-resident training, defeating offload's purpose).
        # Chunked, each program step streams one [chunk, LANES] slice of
        # (p, m, v) host→device, updates, and streams back — measured
        # throughput-equal to the full-buffer form (examples/
        # exp_host_stream.py) with peak HBM of ~one chunk.  Per-tensor
        # trust-ratio optimizers (LAMB) need whole-buffer norms, so only
        # elementwise flat optimizers (Adam family) chunk; the reference
        # has the same constraint (ZeRO-Offload pairs with [CPU]Adam only).
        self.groups = groups = flat.host_group_bounds  # ((r0, rc),) | None
        self.bounds = groups or ((0, segments.rows),)
        chunk_mb = int(getattr(zc, "offload_chunk_mb", 512) or 0)
        self.rows_per_chunk = rows_per_chunk = chunk_rows(zc)
        self.n_chunks = sum(len(self.chunks(grc)) for _, grc in self.bounds)

        # Stream when the full-buffer path would not fit: below the floor
        # the one-shot update is ~15% faster (gpt2-medium measured 738 vs
        # 855 ms/step) because chunk chaining costs overlap.  The floor is
        # the state size whose 3-buffer device peak (+ grads + params)
        # still fit a 16 G chip: medium (1.42 GB/buffer) fits, large
        # (3.09 GB/buffer) OOM'd at 21.8 G.  An explicitly non-default
        # offload_chunk_mb overrides the floor (smaller chips / bigger
        # co-residents); row-grouped state ALWAYS streams — the one-shot
        # path cannot consume tuple-of-group buffers, so with
        # offload_chunk_mb == 0 each group streams as one chunk.
        self.stream_min_bytes = 1792 << 20
        try:
            # derive the floor from real device memory when the backend
            # reports it (~11% of HBM ~= the 1.75G/16G calibration point,
            # applied in BOTH directions so >16G chips keep the faster
            # one-shot path for proportionally bigger state); a backend
            # that reports no limit (the CPU test mesh returns None)
            # keeps the 16G-chip calibration
            ms = device.memory_stats()
            if ms and ms.get("bytes_limit"):
                self.stream_min_bytes = int(ms["bytes_limit"] * 0.11)
        except Exception:  # dslint: disable=DSE502 -- memory_stats is an optional backend API; calibration default applies
            pass
        chunk_mb_forced = (chunk_mb > 0 and getattr(
            zc, "offload_chunk_mb_explicit", False))
        # Reduced-precision host state (qstate.py): quant is None on the
        # fp32 default path, and every insertion below is gated on it —
        # the default-path programs stay byte-identical.
        opt_shape_flat = (jax.eval_shape(
            optimizer.init_state,
            jax.ShapeDtypeStruct(segments.shape, jnp.float32))
            if injit else None)
        self.quant = None
        if getattr(zc, "offload_state_reduced", False):
            self.quant = build_state_quant(
                zc.offload_state_dtype, opt_shape_flat, prng_impl=prng_impl)
        self.stream = bool(
            injit and getattr(optimizer, "name", "") == "adam"
            and (host_grads  # host grads ride the chunk stream
                 or self.quant is not None  # compression rides the stream
                 or groups is not None
                 or (rows_per_chunk is not None
                     and segments.rows > rows_per_chunk
                     and (chunk_mb_forced
                          or segments.rows * LANES * 4
                          > self.stream_min_bytes))))
        self.grads_on_host = bool(host_grads and self.stream)
        if self.stream:
            log_dist(
                f"ZeRO-Offload: streaming update over "
                f"{len(groups) if groups else 1} host group(s) in chunks "
                f"of ≤{chunk_mb} MB", ranks=[0])

        # O(1)-compile uniform-chunk form (stream.py): past
        # UNIFORM_MIN_CHUNKS the unrolled form's compile time — not
        # memory — caps capacity (~35 min at gpt2-xl's 37 chunks,
        # >30 min un-finished at 2.7B; PERF.md "Compile time"), so the
        # chunk loop becomes a lax.scan whose body is traced once.
        self.uniform = False
        if self.stream:
            uniform_cfg = getattr(zc, "offload_uniform_chunks", "auto")
            # The coordinator already decided (it set uniform_chunk_rows
            # iff layout_args allowed it AND the chunk-count threshold
            # was met at layout time): follow the layout actually built
            # rather than re-deriving the threshold from post-padding
            # geometry, which near the boundary could disagree with it.
            want_uniform = (uniform_cfg is True
                            or (uniform_cfg == "auto"
                                and flat.uniform_chunk_rows is not None))
            geom_ok = (rows_per_chunk is not None
                       and flat.uniform_chunk_rows == rows_per_chunk
                       and stream.uniform_geometry_ok(self.bounds,
                                                      rows_per_chunk))
            self.uniform = want_uniform and geom_ok
            if want_uniform and not geom_ok:
                # loud fallback — only reachable when uniform was FORCED
                # (true) but the layout could not be chunk-aligned, e.g.
                # offload_chunk_mb: 0 (one ragged chunk per group)
                logger.warning(
                    "offload_uniform_chunks: chunk geometry is not "
                    "uniform (chunk_rows=%s over groups %s); falling "
                    "back to the unrolled streamed update — compile "
                    "time will scale with chunk count",
                    rows_per_chunk, self.bounds)
            if self.uniform:
                log_dist(
                    f"ZeRO-Offload: uniform-chunk scan update "
                    f"({self.n_chunks} chunks x {chunk_mb} MB, "
                    f"{len(self.bounds)} group(s)) — compile cost is "
                    f"O(groups), not O(chunks)", ranks=[0])

        # Overlapped chunk streaming (round 12): double-buffer the
        # streamed update — prefetch chunk k+1's host state while chunk
        # k updates, overlap write-back with the next fetch (scan form:
        # the carry-held prefetch queue in stream.py; unrolled
        # form: round-robin group interleave + depth-2 tokens).  Same
        # per-chunk math with the same canonical SR tags, so the
        # overlapped and serialized schedules are BIT-IDENTICAL
        # (tests/unit/test_offload_overlap.py); only transfer issue
        # order changes.  "auto" overlaps whenever the update streams;
        # false keeps the serialized schedule as the measured control.
        overlap_cfg = getattr(zc, "offload_overlap", "auto")
        prefetch_cfg = int(getattr(zc, "offload_prefetch_depth", 2) or 2)
        if overlap_cfg is True and prefetch_cfg < 2:
            raise ValueError(
                "offload_overlap: true contradicts offload_prefetch_"
                "depth: 1 (a one-deep pipeline IS the serialized "
                "schedule); raise the depth or drop offload_overlap")
        # depth 1 means serialized — an explicit offload_prefetch_depth:
        # 1 under "auto" selects the serialized control exactly like
        # offload_overlap: false (the documented knob contract)
        self.overlap = (self.stream and overlap_cfg is not False
                        and prefetch_cfg >= 2)
        if overlap_cfg is True and offload and not self.stream:
            raise ValueError(
                "offload_overlap: true but the offloaded update does not "
                "stream (eager-offload or the full-buffer one-shot path) "
                "— there is no chunk pipeline to overlap; drop the key "
                "or set offload_chunk_mb to force streaming")
        self.prefetch_depth = prefetch_cfg if self.overlap else 1
        if self.stream:
            log_dist(
                f"ZeRO-Offload: {'double-buffered' if self.overlap else 'serialized'} "
                f"chunk streaming ({self.n_chunks} chunks, depth "
                f"{self.prefetch_depth}, "
                f"{'scan' if self.uniform else 'unrolled'} form)",
                ranks=[0])

        # Wire-bytes accounting (PERF.md "ZeRO-Offload wire bytes"): the
        # streamed update moves every host state buffer down and back up
        # exactly once per step — a deterministic figure the bench JSON
        # and telemetry carry so reduced-precision claims are auditable.
        self.host_state_bytes_per_step = None
        if injit:
            n_flat_leaves = sum(
                1 for l in jax.tree_util.tree_leaves(opt_shape_flat)
                if getattr(l, "ndim", 0) == 2)
            self.host_state_bytes_per_step = host_state_bytes_per_step(
                segments.rows, LANES, self.quant,
                n_flat_leaves=n_flat_leaves)
            if self.quant is not None:
                log_dist(
                    f"ZeRO-Offload: reduced-precision host state "
                    f"{zc.offload_state_dtype} — "
                    f"{self.host_state_bytes_per_step / 2**30:.2f} GB "
                    f"state wire bytes/step (fp32 layout: "
                    f"{host_state_bytes_per_step(segments.rows, LANES, None, n_flat_leaves=n_flat_leaves) / 2**30:.2f} GB)",
                    ranks=[0])

    def chunks(self, rows_g):
        """Relative chunk bounds within one (group) buffer."""
        return split_rows(rows_g, self.rows_per_chunk)

    def schedule(self):
        """Declared host-stream schedule (profiling/overlap, DSO7xx): the
        CPU-path receipt for the pipeline.  The offload round trips run
        BETWEEN dispatches, invisible in any one program's HLO, so not
        just the wire BYTES (``host_state_bytes_per_step``) are declared
        but the SCHEDULE the functions below trace — chunk count,
        pipeline depth, issue form — and the overlap analyzer prices the
        exposed fraction from that.  None when the update does not
        stream."""
        if not self.stream:
            return None
        out = {"overlap": self.overlap,
               "prefetch_depth": int(self.prefetch_depth),
               "chunks": int(self.n_chunks),
               "groups": len(self.bounds),
               "form": "scan" if self.uniform else "unrolled"}
        if self.grads_on_host:
            # offload_gradients wire: one spill (device->host)
            # during bwd + one reload (host->device) in the update;
            # the spill chunks depend only on the grad leaves they
            # cover, so the backward hides them when overlap is on
            out["grad_wire_bytes"] = int(2 * self.segments.rows * LANES * 4)
        return out

    def _recombine_group_states(self, opt_state, new_sts):
        """Inverse of :func:`_split_group_states`: per-group state
        trees back into the original (grouped or single) layout."""
        if self.groups is None:
            return new_sts[0]
        return jax.tree_util.tree_map(
            lambda orig, *gs: tuple(gs) if type(orig) is tuple
            else gs[0],
            opt_state, *new_sts, is_leaf=_is_grp)

    def _qres_regroup(self, res_bufs, qres):
        """Inverse of :func:`_qres_group_bufs`: per-group lists back
        into the state layout."""
        if not res_bufs:
            return qres
        return {k: (tuple(v) if self.groups is not None else v[0])
                for k, v in res_bufs.items()}

    def carve_leaves(self, chunk_list):
        """In-order device chunks tiling the flat rows → params pytree
        in compute dtype (leaves carved with ordinary device slices;
        see the :meth:`cast` alignment note)."""
        segments = self.segments
        tmpl_leaves, treedef = jax.tree_util.tree_flatten(
            self.param_template)
        offs, rcs, ns = (segments.row_offsets, segments.row_counts,
                         segments.sizes)
        pieces = [[] for _ in tmpl_leaves]
        abs0 = 0
        for chunk in chunk_list:
            end = abs0 + chunk.shape[0]
            for i in range(len(tmpl_leaves)):
                lo = max(offs[i], abs0)
                hi = min(offs[i] + rcs[i], end)
                if lo < hi:
                    pieces[i].append(jax.lax.slice_in_dim(
                        chunk, lo - abs0, hi - abs0))
            abs0 = end
        assert abs0 == segments.rows, (abs0, segments.rows)
        out = []
        for i, tl in enumerate(tmpl_leaves):
            rows = (pieces[i][0] if len(pieces[i]) == 1
                    else jnp.concatenate(pieces[i], axis=0))
            out.append(jax.lax.slice(
                rows.reshape(-1), (0,), (ns[i],)).reshape(tl.shape))
        params = jax.tree_util.tree_unflatten(treedef, out)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            params, self.param_shardings)

    def update(self, master, opt_state, g, hp, overflow, *, qres=None,
               coef=None, g_on_host=False, want_cast=False):
        """The streamed update, scan or unrolled as decided: ``(master,
        opt state, residuals, cast_list)``.

        ``coef`` folds unscale+clip for host-resident gradients
        (``g_on_host``); ``want_cast`` collects updated chunks cast
        to the compute dtype so the caller assembles new params
        (:meth:`carve_leaves`) without re-reading the master from
        host.  The scan form returns no cast list: a scan can only
        stack per-chunk outputs into a full flat compute-dtype array —
        the exact ~2 bytes/param capacity ceiling the round-4
        post-mortem documented — so callers re-read params via the
        leaf-direct streamed :meth:`cast` (2 HLO ops per chunk)."""
        if self.uniform:
            return self._scan_update(master, opt_state, g, hp, overflow,
                                     qres, coef, g_on_host)
        return self._unrolled_update(master, opt_state, g, hp, overflow,
                                     qres, coef, g_on_host, want_cast)

    def _unrolled_update(self, master, opt_state, g, hp, overflow, qres,
                         coef, g_on_host, want_cast):
        """Chunk-streamed offloaded update, ROUND-ROBIN over host
        groups.

        Each chunk's (p, m, v[, g]) slices load from pinned host,
        update on device, and write back in place via
        ``dynamic_update_slice`` (concatenated fresh outputs defeat
        host donation aliasing).
        Within one group the SSA chain serializes chunk k's loads
        behind chunk k-1's write-back — that preserves in-place
        aliasing (reading the ORIGINAL buffer instead measured
        1.62 → 2.23 s/step from the induced host copies) but leaves
        the wire idle during compute.  Round-robin interleaving
        restores the overlap WITHOUT breaking aliasing: group A's
        chunk k+1 only depends on A's chunk k, so its host→device
        DMA streams while group B's chunk updates and writes back,
        and the ``_after`` token (gating loads on the update two
        jobs back) bounds in-flight chunks at two."""
        squant, skip_bad = self.quant, self.skip_bad
        dev_sharding, host_big = self.dev_sharding, self.host_big
        masters = list(master) if type(master) is tuple else [master]
        gb = self.bounds
        n_g = len(gb)
        group_leaves, is_flat, opt_defs = _split_group_states(
            opt_state, n_g)
        scalar_out = [None] * len(is_flat)
        nf = sum(is_flat)
        res_bufs = _qres_group_bufs(qres)
        # residual read/write plan: master first, then reduced flat
        # leaves in leaf order — tags must match the scan form so
        # stochastic-rounding draws agree across the two layouts
        res_items = []
        if squant is not None:
            if "master" in res_bufs:
                res_items.append(("master", None))
            fi_of_li = {}
            fi = 0
            for li, f in enumerate(is_flat):
                if f:
                    fi_of_li[li] = fi
                    fi += 1
            for li in squant.res_leaf_lis:
                res_items.append((squant.leaf_names[li], li))

        per_group = [self.chunks(grc) for _, grc in gb]
        # Issue order: round-robin interleave overlaps group A's DMA
        # with group B's update — but ONLY below the measured scale
        # breakpoint (stream.ROUND_ROBIN_MAX_CHUNKS: gpt2-xl's 37
        # chunks ran 19.5 s/step round-robin vs 5.16 sequential —
        # interleaving spreads each group's in-place DUS chain past
        # XLA's buffer-forwarding window and every write-back
        # becomes a host-buffer copy).  Past the breakpoint, and
        # always under offload_overlap: false (the serialized
        # control schedule), chunks issue group-sequentially.
        round_robin = (self.overlap
                       and self.n_chunks <= stream.ROUND_ROBIN_MAX_CHUNKS)
        if self.overlap and not round_robin and not self.rr_disabled_logged:
            self.rr_disabled_logged = True
            log_dist(
                f"ZeRO-Offload: round-robin group interleave "
                f"auto-disabled at {self.n_chunks} chunks (> "
                f"{stream.ROUND_ROBIN_MAX_CHUNKS}): issuing group-"
                f"sequentially (the measured-faster order at this "
                f"scale — PERF.md capacity ladder)", ranks=[0])
        jobs = []
        if round_robin:
            idx = [0] * n_g
            while any(idx[gi] < len(per_group[gi])
                      for gi in range(n_g)):
                for gi in range(n_g):
                    if idx[gi] < len(per_group[gi]):
                        jobs.append((gi,)
                                    + tuple(per_group[gi][idx[gi]]))
                        idx[gi] += 1
        else:
            for gi in range(n_g):
                jobs.extend((gi,) + tuple(c) for c in per_group[gi])
        # canonical (issue-order-invariant) SR tags, shared with the
        # scan form: rank by absolute row start
        sr_tags = stream.sr_chunk_tags(
            [(gi, r0, gb[gi][0] + r0) for gi, r0, _ in jobs])

        cast_parts = {} if (want_cast and self.compute_dtype) else None
        tok2 = tok1 = jnp.float32(0.0)
        for jn, (gi, r0, rc) in enumerate(jobs):
            gr0, _ = gb[gi]
            master_g = masters[gi]
            leaves = group_leaves[gi]
            slices = [jax.lax.slice_in_dim(master_g, r0, r0 + rc)] + [
                jax.lax.slice_in_dim(l, r0, r0 + rc)
                for l, f in zip(leaves, is_flat) if f]
            for name, _li in res_items:
                slices.append(jax.lax.slice_in_dim(
                    res_bufs[name][gi], r0, r0 + rc))
            if g_on_host:
                g_g = g[gi] if type(g) is tuple else g
                slices.append(jax.lax.slice_in_dim(g_g, r0, r0 + rc))
            # depth-2 token (gate on the update two jobs back)
            # bounds in-flight chunks at two while letting job k+1's
            # DMA stream during job k's update; the serialized
            # control (offload_overlap: false) gates on the
            # IMMEDIATELY previous update — one chunk in flight,
            # wire fully exposed by construction
            host_slices = _after(
                tok2 if self.overlap else tok1, slices)
            pm_q = jax.device_put(host_slices[0], dev_sharding)
            it = iter(host_slices[1:1 + nf])
            chunk_leaves_q = [
                jax.device_put(next(it), dev_sharding) if f else l
                for l, f in zip(leaves, is_flat)]
            res_dev = [jax.device_put(x, dev_sharding)
                       for x in host_slices[1 + nf:1 + nf
                                            + len(res_items)]]
            if squant is None:
                pm, chunk_leaves = pm_q, chunk_leaves_q
            else:
                res_by_li = {li: res_dev[i] for i, (_, li)
                             in enumerate(res_items) if li is not None}
                res_m = (res_dev[0] if res_items
                         and res_items[0][0] == "master" else None)
                pm = squant.load(pm_q, res_m)
                chunk_leaves = [
                    squant.load(cq, res_by_li.get(li))
                    if is_flat[li] else cq
                    for li, cq in enumerate(chunk_leaves_q)]
            st = jax.tree_util.tree_unflatten(opt_defs, chunk_leaves)
            if g_on_host:
                gc_ = jax.device_put(host_slices[-1],
                                     dev_sharding) * coef
            else:
                gc_ = jax.lax.slice_in_dim(g, gr0 + r0, gr0 + r0 + rc)
            new_p, new_st = self.optimizer.update(st, pm, gc_, hp)
            new_leaves = jax.tree_util.tree_leaves(new_st)
            tok2, tok1 = tok1, new_p[0, 0]
            key_base = None
            if squant is not None and squant._key0 is not None:
                scal = [new_leaves[li] for li, f in enumerate(is_flat)
                        if not f]
                key_base = squant.chunk_key(
                    scal[squant.step_scalar_idx],
                    jnp.uint32(sr_tags[jn]))
            if squant is None:
                if skip_bad:
                    new_p = jnp.where(overflow, pm, new_p)
                write_p = new_p
            else:
                q_p, r_p = squant.store(
                    new_p, squant.master_dtype,
                    key=(jax.random.fold_in(key_base, 0)
                         if key_base is not None and squant.master_dtype
                         != jnp.float32 else None))
                if skip_bad:
                    q_p = jnp.where(overflow, pm_q, q_p)
                    if r_p is not None:
                        r_p = jnp.where(overflow, res_m, r_p)
                write_p = q_p
                if r_p is not None:
                    res_bufs["master"][gi] = jax.lax.dynamic_update_slice(
                        res_bufs["master"][gi],
                        jax.device_put(r_p, host_big), (r0, 0))
            if cast_parts is not None:
                # fold the compute-dtype param cast into the update:
                # the new-param chunk is already on device, so the
                # post-update streamed cast's re-download of the
                # whole master disappears.  Under reduced storage the
                # cast derives from the QUANTIZED value, so forward
                # params equal the stored master exactly in both
                # streamed forms
                cast_parts[(gi, r0)] = write_p.astype(self.compute_dtype)
            masters[gi] = jax.lax.dynamic_update_slice(
                master_g, jax.device_put(write_p, host_big), (r0, 0))
            for li, (old_q, new_l) in enumerate(zip(
                    chunk_leaves_q, new_leaves)):
                if is_flat[li]:
                    if squant is None:
                        if skip_bad:
                            new_l = jnp.where(overflow, old_q, new_l)
                    else:
                        q_l, r_l = squant.store(
                            new_l, squant.leaf_dtypes[li],
                            key=(jax.random.fold_in(
                                key_base, 1 + fi_of_li[li])
                                if key_base is not None
                                and squant.leaf_dtypes[li]
                                != jnp.float32 else None))
                        if skip_bad:
                            q_l = jnp.where(overflow, old_q, q_l)
                        if li in res_by_li and r_l is not None:
                            if skip_bad:
                                r_l = jnp.where(overflow,
                                                res_by_li[li], r_l)
                            nm = squant.leaf_names[li]
                            res_bufs[nm][gi] = \
                                jax.lax.dynamic_update_slice(
                                    res_bufs[nm][gi],
                                    jax.device_put(r_l, host_big),
                                    (r0, 0))
                        new_l = q_l
                    leaves[li] = jax.lax.dynamic_update_slice(
                        leaves[li], jax.device_put(new_l, host_big),
                        (r0, 0))
                elif scalar_out[li] is None:
                    # non-flat state (the step counter): identical per
                    # chunk; the overflow pick applies as in the full
                    # path
                    scalar_out[li] = (jnp.where(overflow, leaves[li],
                                                new_l)
                                      if skip_bad else new_l)

        cast_list = None
        if cast_parts is not None:
            cast_list = [cast_parts[k] for k in sorted(cast_parts)]
        new_sts = []
        for gi in range(n_g):
            out_leaves = [group_leaves[gi][li] if is_flat[li]
                          else scalar_out[li]
                          for li in range(len(is_flat))]
            new_sts.append(jax.tree_util.tree_unflatten(opt_defs,
                                                        out_leaves))
        new_opt = self._recombine_group_states(opt_state, new_sts)
        new_qres = self._qres_regroup(res_bufs, qres)
        if self.groups is None:
            return masters[0], new_opt, new_qres, cast_list
        return tuple(masters), new_opt, new_qres, cast_list

    def _scan_update(self, master, opt_state, g, hp, overflow, qres, coef,
                     g_on_host):
        """The O(1)-compile streamed update: same per-chunk math and
        group structure as :meth:`_unrolled_update`, but the chunk
        loop is a ``lax.scan`` over (group, row) index data
        (``stream.py``) instead of an unrolled trace."""
        squant = self.quant
        masters = list(master) if type(master) is tuple else [master]
        gb = self.bounds
        n_g = len(gb)
        group_leaves, is_flat, opt_defs = _split_group_states(
            opt_state, n_g)
        g_groups = gg = None
        if g_on_host:
            g_groups = list(g) if type(g) is tuple else [g]
        else:
            gg = g
        res_bufs = _qres_group_bufs(qres)
        res_masters = res_bufs.get("master")
        res_names = ([squant.leaf_names[li]
                      for li in squant.res_leaf_lis]
                     if squant is not None else [])
        res_group_leaves = ([[res_bufs[nm][gi] for nm in res_names]
                             for gi in range(n_g)]
                            if res_names else None)
        out = stream.uniform_scan_update(
            masters=masters, group_leaves=group_leaves,
            is_flat=is_flat, opt_treedef=opt_defs,
            update_fn=self.optimizer.update, hp=hp, overflow=overflow,
            skip_bad=self.skip_bad,
            jobs=stream.uniform_chunk_jobs(gb, self.rows_per_chunk),
            chunk_rows=self.rows_per_chunk, lanes=LANES,
            g=gg, g_groups=g_groups, coef=coef,
            to_dev=lambda x: jax.device_put(x, self.dev_sharding),
            to_host=lambda x: jax.device_put(x, self.host_big),
            quant=squant, res_masters=res_masters,
            res_group_leaves=res_group_leaves,
            prefetch_depth=self.prefetch_depth)
        if len(out) == 5:
            (new_masters, new_group_leaves, _, new_resm,
             new_resf) = out
            if new_resm is not None:
                res_bufs["master"] = list(new_resm)
            for k, nm in enumerate(res_names):
                res_bufs[nm] = [new_resf[gi][k] for gi in range(n_g)]
        else:
            new_masters, new_group_leaves, _ = out
        new_qres = self._qres_regroup(res_bufs, qres)
        new_sts = [jax.tree_util.tree_unflatten(opt_defs, gl)
                   for gl in new_group_leaves]
        new_opt = self._recombine_group_states(opt_state, new_sts)
        if self.groups is None:
            return new_masters[0], new_opt, new_qres, None
        return tuple(new_masters), new_opt, new_qres, None

    def grads_to_host(self, grads, hostg):
        """Write the flat fp32 gradient into the donated pinned-host
        buffer chunk-by-chunk, iterating chunks in REVERSE row order
        (≈ the backward's production order: later tree leaves — later
        layers and the LM head — produce their gradients first), so
        each grad leaf's device lifetime ends at its host write and
        the full 4 bytes/param gradient never sits in HBM (reference
        analog: ZeRO-Offload moves averaged gradients to CPU as the
        backward frees them, stage2.py:622-668).  Squared norm and
        finiteness accumulate on device during the pass — clipping
        and fp16 overflow detection would otherwise cost a second
        streamed read of the host buffer."""
        segments = self.segments
        leaves = jax.tree_util.tree_leaves(grads)
        hostgs = list(hostg) if type(hostg) is tuple else [hostg]
        bounds = self.bounds
        offs, rcs, ns = (segments.row_offsets, segments.row_counts,
                         segments.sizes)
        sq = jnp.float32(0.0)
        finite = jnp.asarray(True)
        # Spill token chains: depth-2 PER GROUP under overlap — each
        # group's host gradient buffer then depends only on its own
        # spill writes (plus the grad leaves it covers), so the
        # streamed update's reads of group g can be scheduled as
        # soon as g's spill drains, while other groups are still
        # spilling mid-backward: the optimizer stream starts hot.
        # (When clipping or fp16 overflow detection is on, the
        # global sq/finite reductions below re-impose the full
        # drain — a mathematical barrier, not a scheduling one.)
        # The serialized control keeps ONE global depth-2 chain.
        toks = {gi: (jnp.float32(0.0), jnp.float32(0.0))
                for gi in range(len(bounds))}
        glob = (jnp.float32(0.0), jnp.float32(0.0))
        for gi in reversed(range(len(bounds))):
            gr0, grc = bounds[gi]
            for r0, rc in reversed(self.chunks(grc)):
                abs0 = gr0 + r0
                end = abs0 + rc
                parts, cursor = [], abs0
                for i in range(len(leaves)):
                    lo = max(offs[i], abs0)
                    hi = min(offs[i] + rcs[i], end)
                    if lo >= hi:
                        continue
                    if lo > cursor:  # inter-leaf padding rows
                        parts.append(jnp.zeros(
                            ((lo - cursor) * LANES,), jnp.float32))
                    el_lo = (lo - offs[i]) * LANES
                    el_hi = (hi - offs[i]) * LANES
                    flat_leaf = leaves[i].reshape(-1).astype(jnp.float32)
                    take_hi = min(el_hi, ns[i])
                    if el_lo < take_hi:
                        parts.append(jax.lax.slice(
                            flat_leaf, (el_lo,), (take_hi,)))
                    if take_hi < el_hi:  # leaf's own row-tail padding
                        parts.append(jnp.zeros(
                            (el_hi - take_hi,), jnp.float32))
                    cursor = hi
                if cursor < end:  # trailing dp-padding rows
                    parts.append(jnp.zeros(
                        ((end - cursor) * LANES,), jnp.float32))
                tok2, tok1 = (toks[gi] if self.overlap
                              else glob)
                parts = _after(tok2, parts)
                chunk = (parts[0] if len(parts) == 1
                         else jnp.concatenate(parts)).reshape(rc, LANES)
                if self.clip > 0.0:
                    sq = sq + jnp.sum(chunk ** 2)
                if self.skip_bad:
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(chunk)))
                if self.overlap:
                    toks[gi] = (tok1, chunk[0, 0])
                else:
                    glob = (tok1, chunk[0, 0])
                hostgs[gi] = jax.lax.dynamic_update_slice(
                    hostgs[gi], jax.device_put(chunk, self.host_grad_big),
                    (r0, 0))
        out = tuple(hostgs) if type(hostg) is tuple else hostgs[0]
        return out, sq, finite

    def cast(self, master):
        """Leaf-direct streamed cast: parameter leaves materialize
        from chunk-aligned host reads — the full flat compute-dtype
        buffer never exists on device, so cast peak is the bf16 leaves
        plus ~two fp32 chunks.  (The round-4 parts+concat+unflatten
        form peaked at ~4 bytes/param — flat bf16 AND the leaves —
        re-imposing a ~2B capacity ceiling the update stream had
        removed.)  Load-bearing detail: host-space slice offsets must
        stay CHUNK-ALIGNED — per-leaf (unaligned) host reads silently
        corrupted the whole fused step in round 4 (master write-back
        lost, cast returned zeros), so each aligned chunk loads to
        device whole and leaves are carved out with ordinary device
        slices."""
        masters = master if type(master) is tuple else (master,)
        tok2 = tok1 = jnp.float32(0.0)  # depth-2: see the update loop
        chunk_list = []
        for gi, (gr0, grc) in enumerate(self.bounds):
            for r0, rc in self.chunks(grc):
                src = _after(tok2, jax.lax.slice_in_dim(
                    masters[gi], r0, r0 + rc))
                chunk = jax.device_put(src, self.dev_sharding).astype(
                    self.compute_dtype)
                tok2, tok1 = tok1, chunk[0, 0].astype(jnp.float32)
                chunk_list.append(chunk)
        return self.carve_leaves(chunk_list)
