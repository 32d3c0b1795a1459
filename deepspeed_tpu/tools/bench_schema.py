"""Schema of the driver-bench JSON records (``bench.py``'s one line and
``__graft_entry__.dryrun_multichip``'s one line).

The standing measurement rule (ROADMAP) is that every README/PERF
headline quotes a driver artifact — which only works if the artifact's
fields are stable and auditable.  This module is the registry: every
field the drivers may emit, its type, and its unit, plus
:func:`validate_record` which the drivers run over their records before
printing (fail-soft: schema drift is reported to stderr, never allowed
to lose a measured record).

Three field families are pattern-based rather than enumerated:

- ``offload_<row>_*`` — one group per offload bench row (``gpt2_large``,
  ``gpt2_large_bf16``, ``gpt2_xl``, ...).  Since round 6 every row
  carries ``host_state_dtype`` and ``host_state_bytes_per_step`` so the
  reduced-precision wire-bytes claim is checkable from the JSON alone;
  since round 8 each adds ``comm_wire_bytes_per_step``.
- ``leg_<name>_*`` — one group per multichip-dryrun leg (``zero2``,
  ``pipe``, ``pipe_3d``, ...): per-leg status, losses, the dp=1
  parity-reference loss, and the compile-time comm receipts — the
  structured replacement for the old ``{n_devices, rc, ok, tail}``
  MULTICHIP blob (``tools/bench_diff.py --self-check`` gates the
  ``MULTICHIP_r0*.json`` history with these).
- ``*_exc`` / ``*_error`` — per-row failure strings (a secondary row
  failure must never lose the validated primary metric).
"""

import numbers
import re

# exact field name -> (type, unit/notes)
FIELDS = {
    "metric": (str, "primary metric name"),
    "value": (numbers.Real, "samples/s"),
    "unit": (str, "unit of value"),
    "vs_baseline": (numbers.Real, "ratio vs reference V100 baseline"),
    "model_tflops_per_sec": (numbers.Real, "TFLOP/s"),
    "mfu": (numbers.Real, "model-FLOPs utilisation, 0..1"),
    "chip_peak_tflops": (numbers.Real, "bf16 peak TFLOP/s"),
    "loss": (numbers.Real, "final step loss"),
    "batch": (numbers.Integral, "primary row batch size"),
    "dropout": (numbers.Real, "dropout probability"),
    "device": (str, "device_kind"),
    "error": (str, "primary-metric failure"),
    "seq512_batch": (numbers.Integral, ""),
    "seq512_samples_per_sec": (numbers.Real, "samples/s"),
    "seq512_vs_baseline": (numbers.Real, ""),
    "seq512_mfu": (numbers.Real, ""),
    "gpt2_medium_seq1024_samples_per_sec": (numbers.Real, "samples/s"),
    "gpt2_medium_tokens_per_sec": (numbers.Real, "tokens/s"),
    "gpt2_mfu": (numbers.Real, ""),
    "gpt2_batch": (numbers.Integral, ""),
    "sparse_attn_seq": (numbers.Integral, "sequence length"),
    "sparse_attn_dense_ms": (numbers.Real, "ms, min of repeats"),
    "sparse_attn_sparse_ms": (numbers.Real, "ms, min of repeats"),
    "sparse_attn_speedup_vs_dense": (numbers.Real, "ratio"),
    "sparse_attn_repeats": (numbers.Integral,
                            "interleaved timing repeats (min-aggregated)"),
    "offload_xl_note": (str, ""),
    "compile_cache_hits": (numbers.Integral, ""),
    "compile_cache_misses": (numbers.Integral, ""),
    "compile_seconds_cold": (numbers.Real, "s, cache-miss compile wall"),
    "compile_seconds_warm": (numbers.Real, "s, cache-hit retrieval wall"),
    "compile_programs": (numbers.Integral, ""),
    "compile_cache_dir": (str, ""),
    # memory receipts (round 7, profiling/memory): live watermark after
    # the primary row + the compiled train-step program's own
    # memory_analysis figures — "did the step fit, and by how much" is
    # checkable from the JSON alone
    "peak_hbm_bytes": (numbers.Integral,
                       "peak_bytes_in_use summed over local devices"),
    "predicted_temp_bytes": (numbers.Integral,
                             "train_step memory_analysis temp bytes"),
    # communication receipts (round 8, profiling/comm): the compiled
    # step program's collective count and predicted wire bytes from the
    # compile-time HLO walk — the static comm receipt next to the
    # memory one (dp=1 single-chip rows legitimately read 0)
    "comm_collectives_per_step": (numbers.Integral,
                                  "collective ops in the step program"),
    "comm_wire_bytes_per_step": (numbers.Integral,
                                 "predicted wire bytes per step"),
    # overlap receipts (round 11, profiling/overlap): the static
    # critical-path analysis' statement of which predicted wire seconds
    # the compiled schedules actually pay as latency — the metric the
    # overlapped-streaming work (ROADMAP item 2) must drive down
    "exposed_wire_seconds": (numbers.Real,
                             "predicted un-overlapped (exposed) wire "
                             "seconds per step"),
    "overlap_fraction": (numbers.Real,
                         "hidden/total wire seconds, 0..1 (1.0 = fully "
                         "hidden or no wire)"),
    # attribution receipts (round 13, profiling/attribution): the
    # reconciled step budget — the predicted step seconds the compiled
    # programs + declared streams + driver account for, and the
    # fraction of the MEASURED step the model cannot explain.  The
    # doctor CLI replays the same reconciliation offline
    "predicted_step_seconds": (numbers.Real,
                               "attribution budget: compute + exposed "
                               "wire + host stream + driver, s/step"),
    "step_unexplained_fraction": (numbers.Real,
                                  "(measured p50 - predicted)/measured "
                                  "(negative = model over-predicts)"),
    # program-verification receipt (round 10, profiling/verify +
    # tools/dslint/programs): unsuppressed DSP6xx violations over every
    # compiled engine program — donation aliases materialized,
    # collectives on the right mesh axes.  0 at HEAD; any regression
    # gates via bench_diff
    "dsp_violations": (numbers.Integral,
                       "ERROR-severity DSP6xx program-verifier findings "
                       "(gated at zero; heuristic warnings report via "
                       "dsp_warnings, which has no ratchet to need)"),
    "dsp_warnings": (numbers.Integral,
                     "warning-severity DSP6xx findings (informational, "
                     "never gated — no ratchet exists on this surface)"),
    "dsp_downgraded": (numbers.Integral,
                       "DSP602 downgraded verdicts (alias bytes "
                       "unverifiable: warm-cache/absent/partial)"),
    # sharding residency receipt (round 17, profiling/sharding +
    # DSS8xx): the compiled train step's MATERIALIZED per-device
    # parameter bytes from its entry-layout sharding annotations — the
    # bench half of ROADMAP item 2's parameter-memory ÷ dp criterion.
    # Gated lower-is-better: re-replicated parameters show here before
    # they OOM anything
    "param_bytes_per_device": (numbers.Integral,
                               "materialized per-device parameter "
                               "bytes (entry-layout ÷shard receipt)"),
    # ZeRO-2 bucketed-collective A/B row (round 14, bench.py
    # _measure_zero2_overlap): overlap_comm on (the headline) vs off
    # (the serialized control) on a data mesh of every local chip, with
    # both schedules' static exposed-wire receipts; left out with fewer
    # than two chips
    "zero2_overlap_ms_per_step": (numbers.Real, "ms, overlap_comm on"),
    "zero2_serial_ms_per_step": (numbers.Real,
                                 "ms, serialized control (info)"),
    "zero2_overlap_exposed_wire_seconds": (numbers.Real,
                                           "declared-schedule exposure"),
    "zero2_serial_exposed_wire_seconds": (numbers.Real,
                                          "control exposure (info)"),
    "zero2_overlap_fraction": (numbers.Real, "hidden/total, 0..1"),
    "zero2_overlap_buckets": (numbers.Integral,
                              "reduce buckets in the schedule"),
    "zero2_overlap_dp": (numbers.Integral, "data-parallel degree"),
    "zero2_overlap_note": (str, ""),
    # multichip-dryrun record envelope (dryrun_multichip's one line;
    # legacy blobs keep n_devices/rc/ok/skipped readable)
    "multichip_schema_version": (numbers.Integral, ""),
    "n_devices": (numbers.Integral, "virtual device count"),
    "axes": (str, "mesh axes exercised"),
    "legs_ok": (numbers.Integral, "legs that passed"),
    "legs_failed": (numbers.Integral, "legs that failed"),
    "legs_skipped": (numbers.Integral, ""),
    "rc": (numbers.Integral, "legacy driver wrapper exit code"),
    "ok": (bool, "legacy driver wrapper flag"),
    "skipped": (bool, "legacy driver wrapper flag"),
    # fleet integrity receipt (round 15): seeded SDC faults the
    # integrity leg injected MINUS the ones the fingerprint consensus
    # caught — 0 is the receipt that nothing silent went undetected
    "integrity_violations": (numbers.Integral,
                             "seeded integrity faults left undetected"),
    # serving receipts (round 16, inference/engine via
    # examples/bench_serving.py): the continuous-batching serve's
    # latency/throughput record — every README serving headline quotes
    # these fields, and the dsp receipt pins the KV-cache donation
    "serving_requests": (numbers.Integral, "finished requests"),
    "serving_generated_tokens": (numbers.Integral, ""),
    "serving_decode_iterations": (numbers.Integral,
                                  "continuous-batch decode dispatches"),
    "serving_per_token_p50_seconds": (numbers.Real,
                                      "s, decode per-token latency"),
    "serving_per_token_p99_seconds": (numbers.Real,
                                      "s, tail (includes TTFT stalls)"),
    "serving_ttft_p50_seconds": (numbers.Real, "s, time to first token"),
    "serving_tokens_per_second_per_chip": (numbers.Real, "tokens/s/chip"),
    "serving_programs_compiled": (numbers.Integral,
                                  "compiled serve programs (bounded by "
                                  "len(prefill_buckets) + 1)"),
    "serving_dsp_violations": (numbers.Integral,
                               "DSP6xx errors over the serve programs "
                               "(gated at zero: the KV-cache donation "
                               "receipt)"),
    # serving memory receipts (round 17): the HBM receipt every
    # training row carries, via the same bench.memory_receipts() path
    # (decode-program temp bytes; pinned-host registry usually absent)
    "serving_peak_hbm_bytes": (numbers.Integral,
                               "peak_bytes_in_use summed over local "
                               "devices after the serve"),
    "serving_predicted_temp_bytes": (numbers.Integral,
                                     "serve_decode memory_analysis "
                                     "temp bytes"),
    "serving_host_buffer_bytes": (numbers.Integral,
                                  "pinned-host registry bytes (serving "
                                  "rows normally omit this)"),
    # serving sharding receipt (round 17, DSS8xx): decode-program
    # weights + paged KV residency per device
    "serving_param_bytes_per_device": (numbers.Integral,
                                       "materialized per-device weight "
                                       "bytes of the decode program"),
    # serving resilience receipts (round 18, inference/frontend via
    # examples/bench_serving.py): the self-healing plane's ledger —
    # requeues after replica death, sheds at the admission bound,
    # expired deadlines, and the worst-case re-serve latency
    "serving_requeued_requests": (numbers.Integral,
                                  "requests re-served after a replica "
                                  "death (exactly-once requeue)"),
    "serving_shed_requests": (numbers.Integral,
                              "submits refused at max_queue_depth"),
    "serving_deadline_expired": (numbers.Integral,
                                 "requests finished by deadline expiry"),
    "serving_recovery_latency_seconds": (numbers.Real,
                                         "worst replica-death -> last "
                                         "requeued-result latency"),
    # serving observability receipts (round 19,
    # inference/observability via engine.serving_receipt()): goodput
    # vs raw throughput, SLO attainment, and the efficiency gauges the
    # continuous-batching claim rests on
    "serving_goodput_tokens_per_second_per_chip": (
        numbers.Real, "tokens/s/chip counting only SLO-conformant "
        "tokens (raw throughput minus tail misses)"),
    "serving_slo_attainment": (numbers.Real,
                               "fraction of generated tokens within "
                               "the inference.slo targets"),
    "serving_batch_occupancy_mean": (numbers.Real,
                                     "mean active/max_batch_size over "
                                     "decode iterations"),
    "serving_kv_block_occupancy_peak": (numbers.Real,
                                        "allocator used-block high "
                                        "water / capacity"),
    "serving_padding_waste_fraction": (numbers.Real,
                                       "padded-prefill tokens wasted "
                                       "by bucket rounding"),
}

# multichip leg fields: leg_<name>_<field>
_LEG_FIELDS = {
    "status": str,                       # ok | failed | skipped
    "loss": numbers.Real,                # first-step loss
    "loss2": numbers.Real,               # post-update second-step loss
    "parity_ref_loss": numbers.Real,     # dp=1 reference, same batches
    "comm_collectives": numbers.Integral,
    "comm_payload_bytes": numbers.Integral,
    "comm_wire_bytes": numbers.Integral,
    # elastic leg (round 9): the kill-and-resize transition the leg
    # proved — world size before/after and the step the resized fleet
    # resumed from
    "resized_from": numbers.Integral,
    "resized_to": numbers.Integral,
    "resume_step": numbers.Integral,
    # program-verification receipt (round 10): DSP6xx violations over
    # the leg engine's compiled programs
    "dsp_violations": numbers.Integral,
    # sharding residency receipt (round 17, DSS8xx)
    "param_bytes_per_device": numbers.Integral,
    # stage-3 ÷dp receipt (round 20): the global parameter bytes the
    # per-device residency divides out of, and the shard divisor the
    # leg proved (== dp under zero_optimization.stage 3)
    "param_bytes_global": numbers.Integral,
    "shard_divisor": numbers.Integral,
    # overlap receipts (round 11)
    "exposed_wire_seconds": numbers.Real,
    "overlap_fraction": numbers.Real,
    # attribution receipts (round 13)
    "predicted_step_seconds": numbers.Real,
    "step_unexplained_fraction": numbers.Real,
    # onebit leg (round 14): the compressed step's wire bytes next to
    # the fp32 flat buffer and the dense-allreduce ratio (~1/32 — the
    # 1-bit claim as an asserted receipt, not prose)
    "compressed_wire_bytes": numbers.Integral,
    "flat_fp32_bytes": numbers.Integral,
    "compressed_wire_ratio": numbers.Real,
    # zero2_overlap leg (round 14): the serialized control's exposure
    # next to the leg's own exposed_wire_seconds (strictly lower,
    # asserted in the leg)
    "serial_exposed_wire_seconds": numbers.Real,
    # integrity leg (round 15): the aimed-recovery transition the leg
    # proved — which rank the fingerprint consensus indicted, the
    # consensus verdict that did it, and the fleet size the eviction
    # resize landed on
    "evicted_rank": numbers.Integral,
    "verdict": str,
    # serving leg (round 16): the 2-replica CPU-mesh continuous-batching
    # serve — request/token counts and greedy-decode parity receipts
    # (mismatches vs the naive full-forward reference, pinned at 0),
    # plus the latency fields shared with the top-level serving_* family
    "requests": numbers.Integral,
    "generated_tokens": numbers.Integral,
    "decode_iterations": numbers.Integral,
    "parity_mismatches": numbers.Integral,
    "per_token_p50_seconds": numbers.Real,
    "tokens_per_second_per_chip": numbers.Real,
    "programs_compiled": numbers.Integral,
    # serving_chaos leg (round 18): the in-process self-healing receipt
    # — requests re-served exactly-once after the seeded eviction, the
    # consensus verdicts that fired, and the completed-set size
    "requeued_requests": numbers.Integral,
    "integrity_violations": numbers.Integral,
    "completed_requests": numbers.Integral,
    "recovery_latency_seconds": numbers.Real,
    # serving observability receipts (round 19): the serving leg's
    # goodput/SLO/occupancy record, mirroring the top-level
    # serving_* observability family
    "goodput_tokens_per_second_per_chip": numbers.Real,
    "slo_attainment": numbers.Real,
    "batch_occupancy_mean": numbers.Real,
    "kv_block_occupancy_peak": numbers.Real,
    "padding_waste_fraction": numbers.Real,
    "error": str,
    "note": str,
}
_LEG_RE = re.compile(
    r"^leg_(?P<leg>[a-z0-9_]+?)_(?P<field>%s)$"
    % "|".join(sorted(_LEG_FIELDS, key=len, reverse=True)))

# offload row fields: offload_<row>_<field>
_OFFLOAD_ROW_FIELDS = {
    "ms_per_step": numbers.Real,
    "params_b": numbers.Real,
    # reduced-precision receipts (round 6): storage dtype and the wire
    # bytes one update moves for host state — "bf16 ≈ half the fp32
    # row" is asserted against these, not prose
    "host_state_dtype": str,
    "host_state_bytes_per_step": numbers.Integral,
    "host_groups": numbers.Integral,
    # memory receipts (round 7): per-row watermark + compile-time
    # prediction + pinned-host registry total
    "peak_hbm_bytes": numbers.Integral,
    "predicted_temp_bytes": numbers.Integral,
    "host_buffer_bytes": numbers.Integral,
    # comm receipts (round 8)
    "comm_collectives_per_step": numbers.Integral,
    "comm_wire_bytes_per_step": numbers.Integral,
    # program-verification receipt (round 10)
    "dsp_violations": numbers.Integral,
    # sharding residency receipt (round 17, DSS8xx)
    "param_bytes_per_device": numbers.Integral,
    # overlap receipts (round 11)
    "exposed_wire_seconds": numbers.Real,
    "overlap_fraction": numbers.Real,
    # attribution receipts (round 13)
    "predicted_step_seconds": numbers.Real,
    "step_unexplained_fraction": numbers.Real,
    "error": str,
    "note": str,
}
_OFFLOAD_RE = re.compile(
    r"^offload_(?P<row>[a-z0-9_]+?)_(?P<field>%s)$"
    % "|".join(sorted(_OFFLOAD_ROW_FIELDS, key=len, reverse=True)))
# per-row failure strings: `<row>_exc` (guarded-retry exceptions) and
# `<row>_error` (invalid-measurement reports, e.g. gpt2_error,
# seq512_error) — both carry prose, never metrics
_EXC_RE = re.compile(r"^[a-z0-9_]+_(exc|error)$")


# -- regression-gate thresholds (tools/bench_diff.py) -----------------------
#
# field -> (direction, rel_tol).  direction "higher" = bigger is better
# (throughput, MFU), "lower" = smaller is better (step time, bytes);
# a change against the direction by more than rel_tol of the old value
# is a REGRESSION.  Fields absent here (and (None, None) entries) are
# informational: diffed, never gated — loss wobbles, device strings,
# cold-compile walls that legitimately differ between cold/warm runs.
THRESHOLDS = {
    "value": ("higher", 0.05),
    "vs_baseline": ("higher", 0.05),
    "model_tflops_per_sec": ("higher", 0.05),
    "mfu": ("higher", 0.05),
    "batch": ("higher", 0.0),            # a downgraded-batch retry must show
    "seq512_batch": ("higher", 0.0),
    "gpt2_batch": ("higher", 0.0),
    "seq512_samples_per_sec": ("higher", 0.05),
    "seq512_vs_baseline": ("higher", 0.05),
    "seq512_mfu": ("higher", 0.05),
    "gpt2_medium_seq1024_samples_per_sec": ("higher", 0.05),
    "gpt2_medium_tokens_per_sec": ("higher", 0.05),
    "gpt2_mfu": ("higher", 0.05),
    "sparse_attn_speedup_vs_dense": ("higher", 0.10),
    "compile_seconds_warm": ("lower", 0.50),
    "peak_hbm_bytes": ("lower", 0.10),
    "predicted_temp_bytes": ("lower", 0.10),
    # a step program that starts moving substantially more wire bytes
    # is a sharding/collective regression even before it shows up in
    # step time (generous tol: XLA is free to re-split collectives)
    "comm_wire_bytes_per_step": ("lower", 0.25),
    # exposure must not creep back once overlap lands; the fraction is
    # gated loosely (model-derived, scheduler-version sensitive) and
    # the absolute exposed seconds generously for the same reason
    "exposed_wire_seconds": ("lower", 0.25),
    "overlap_fraction": ("higher", 0.10),
    # attribution quality is CI-ratcheted like exposure: a predicted
    # step that grows is a budget regression (generous tol: the figure
    # is roofline-table sensitive), and the unexplained fraction is a
    # SIGNED optimum-at-zero metric (negative = over-prediction), so it
    # gates on magnitude with an absolute band — direction "zero",
    # wide (measured-latency noisy; DSO705's baseline ratchet is the
    # tighter per-program gate)
    "predicted_step_seconds": ("lower", 0.25),
    "step_unexplained_fraction": ("zero", 0.25),
    # any new program-verifier violation is a gated regression (zero
    # tolerance: the receipt exists to pin this at 0)
    "dsp_violations": ("lower", 0.0),
    # resident parameter bytes per device must only shrink (sharding
    # landing) — growth past the dtype/padding band is re-replication
    # (the DSS801/DSS803 bug class on the bench surface)
    "param_bytes_per_device": ("lower", 0.10),
    # multichip: device-count or passing-leg shrinkage must show
    "n_devices": ("higher", 0.0),
    "legs_ok": ("higher", 0.0),
    "legs_failed": ("lower", 0.0),
    # any seeded integrity fault the consensus missed is a gated
    # regression (zero tolerance: the receipt exists to pin this at 0)
    "integrity_violations": ("lower", 0.0),
    # zero-2 bucketed-collective A/B (round 14): the overlapped row's
    # step time and exposure are the gated headline; the serialized
    # control rows are informational (they exist to be worse)
    "zero2_overlap_ms_per_step": ("lower", 0.25),
    "zero2_overlap_exposed_wire_seconds": ("lower", 0.25),
    "zero2_overlap_fraction": ("higher", 0.10),
    # serving bench (round 16): throughput gated like the training
    # headline; latency percentiles informational (single-run tails);
    # the donation receipt and the compile bound pinned exactly
    "serving_tokens_per_second_per_chip": ("higher", 0.25),
    "serving_programs_compiled": ("lower", 0.0),
    "serving_dsp_violations": ("lower", 0.0),
    # serving memory + residency receipts (round 17): gated like the
    # training rows' equivalents
    "serving_peak_hbm_bytes": ("lower", 0.10),
    "serving_predicted_temp_bytes": ("lower", 0.10),
    "serving_param_bytes_per_device": ("lower", 0.10),
    # serving resilience receipts (round 18): counters are
    # informational (they scale with the bench's injected faults, not
    # with code quality); the exactly-once property itself is gated in
    # the serving_chaos leg via parity_mismatches
    # serving observability (round 19): goodput is the gated headline
    # (same tol as raw serving throughput — a goodput drop is either a
    # throughput or a tail-latency regression); attainment and the
    # occupancy/waste gauges are informational (they move with bench
    # load shape, not code quality)
    "serving_goodput_tokens_per_second_per_chip": ("higher", 0.25),
}

# thresholds for the pattern-based leg_<name>_<field> family
_LEG_FIELD_THRESHOLDS = {
    "comm_wire_bytes": ("lower", 0.25),
    "dsp_violations": ("lower", 0.0),
    "param_bytes_per_device": ("lower", 0.10),
    # stage-3 ÷dp receipt (round 20): the divisor can only grow (a drop
    # back to 1 is the sharding silently un-landing); global bytes are
    # informational (they track the dryrun model, not code quality)
    "shard_divisor": ("higher", 0.0),
    "exposed_wire_seconds": ("lower", 0.25),
    "overlap_fraction": ("higher", 0.10),
    # informational since round 16: the dryrun legs' predicted step
    # seconds come from roofline tables evaluated on whatever CPU the
    # dryrun ran on, and history shows >25% run-to-run wobble with no
    # code change — a noise class, not a regression signal.  The
    # STRUCTURAL receipts stay gated (comm_wire_bytes, dsp_violations,
    # exposure); the top-level bench predicted_step_seconds (measured
    # on the bench box) keeps its gate too
    "predicted_step_seconds": (None, None),
    "step_unexplained_fraction": ("zero", 0.25),
    # serving leg (round 16): parity mismatches are the token-identical
    # receipt (pinned at zero); latency fields stay informational on
    # the virtual-CPU dryrun mesh
    "parity_mismatches": ("lower", 0.0),
    "requests": ("higher", 0.0),
    # serving_chaos leg (round 18): an undetected seeded fault is a
    # regression (the in-leg assert already pins the exact counts)
    "integrity_violations": ("lower", 0.0),
    # onebit compressed-path receipts (round 14): more wire (or a
    # grown ratio) = the compression is leaking dense collectives
    "compressed_wire_bytes": ("lower", 0.25),
    "compressed_wire_ratio": ("lower", 0.25),
    # serving observability (round 19): goodput gated like the
    # top-level field; occupancy/attainment/waste informational on the
    # virtual-CPU dryrun mesh
    "goodput_tokens_per_second_per_chip": ("higher", 0.25),
}

# thresholds for the pattern-based offload_<row>_<field> family
_OFFLOAD_FIELD_THRESHOLDS = {
    "ms_per_step": ("lower", 0.10),
    "host_state_bytes_per_step": ("lower", 0.01),
    "peak_hbm_bytes": ("lower", 0.10),
    "predicted_temp_bytes": ("lower", 0.10),
    "host_buffer_bytes": ("lower", 0.10),
    "comm_wire_bytes_per_step": ("lower", 0.25),
    "dsp_violations": ("lower", 0.0),
    "param_bytes_per_device": ("lower", 0.10),
    "exposed_wire_seconds": ("lower", 0.25),
    "overlap_fraction": ("higher", 0.10),
    "predicted_step_seconds": ("lower", 0.25),
    "step_unexplained_fraction": ("zero", 0.25),
}


def threshold_for(key):
    """(direction, rel_tol) for a record key; (None, None) =
    informational (never gated)."""
    if key in THRESHOLDS:
        return THRESHOLDS[key]
    m = _OFFLOAD_RE.match(key)
    if m:
        return _OFFLOAD_FIELD_THRESHOLDS.get(m.group("field"),
                                             (None, None))
    m = _LEG_RE.match(key)
    if m:
        return _LEG_FIELD_THRESHOLDS.get(m.group("field"), (None, None))
    return (None, None)


def field_type(key):
    """Expected python type for a record key, or None if unknown."""
    if key in FIELDS:
        return FIELDS[key][0]
    m = _OFFLOAD_RE.match(key)
    if m:
        return _OFFLOAD_ROW_FIELDS[m.group("field")]
    m = _LEG_RE.match(key)
    if m:
        return _LEG_FIELDS[m.group("field")]
    if _EXC_RE.match(key):
        return str
    return None


def validate_record(record):
    """Return a list of problem strings (empty = schema-clean).

    Booleans are rejected where numbers are expected (bool is an int
    subclass — a True smuggled into a metric field is a bug; the two
    declared-bool legacy wrapper flags are the only exception)."""
    problems = []
    for key, value in record.items():
        want = field_type(key)
        if want is None:
            problems.append(f"unknown bench field {key!r}")
            continue
        ok = isinstance(value, want) and not (
            want not in (str, bool) and isinstance(value, bool))
        if not ok:
            problems.append(
                f"bench field {key!r} expected {want.__name__}, got "
                f"{type(value).__name__} ({value!r})")
    return problems
