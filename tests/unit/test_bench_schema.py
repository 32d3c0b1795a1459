"""Driver-bench JSON schema (``tools/bench_schema.py``).

The standing ROADMAP rule — every README/PERF headline quotes a driver
artifact — needs the artifact's fields to be stable; this suite pins
the registry against the round-5 record and the round-6 fields
(reduced-precision ``host_state_dtype`` / ``host_state_bytes_per_step``).
"""

from deepspeed_tpu.tools.bench_schema import field_type, validate_record

# the round-5 bench record, as bench.py printed it on an earlier
# attachment (the BENCH_r05.json artifact itself is no longer in the tree)
ROUND5_RECORD = {
    "metric": "bert_large_seq128_samples_per_sec_per_chip",
    "value": 477.34, "unit": "samples/s", "vs_baseline": 1.755,
    "model_tflops_per_sec": 111.6, "mfu": 0.5664, "chip_peak_tflops": 197.0,
    "loss": 8.5964, "batch": 112, "dropout": 0.1, "device": "TPU v5 lite",
    "seq512_batch": 32, "seq512_samples_per_sec": 104.13,
    "seq512_vs_baseline": 2.002, "seq512_mfu": 0.5237,
    "gpt2_medium_seq1024_samples_per_sec": 38.56,
    "gpt2_medium_tokens_per_sec": 39487.0, "gpt2_mfu": 0.4554,
    "gpt2_batch": 8, "sparse_attn_seq": 16384,
    "sparse_attn_dense_ms": 74.78, "sparse_attn_sparse_ms": 28.24,
    "sparse_attn_speedup_vs_dense": 2.65,
    "offload_gpt2_large_ms_per_step": 1292.0,
    "offload_gpt2_large_params_b": 0.77,
    "offload_xl_note": "opt-in",
}


def test_round5_record_validates():
    assert validate_record(ROUND5_RECORD) == []


def test_round6_reduced_precision_fields():
    """The new offload rows must carry auditable wire-bytes receipts."""
    record = {
        "offload_gpt2_large_ms_per_step": 1292.0,
        "offload_gpt2_large_params_b": 0.77,
        "offload_gpt2_large_host_state_dtype": "fp32",
        "offload_gpt2_large_host_state_bytes_per_step": 18598986752,
        "offload_gpt2_large_bf16_ms_per_step": 880.0,
        "offload_gpt2_large_bf16_params_b": 0.77,
        "offload_gpt2_large_bf16_host_state_dtype": "bf16",
        "offload_gpt2_large_bf16_host_state_bytes_per_step": 9299493376,
        "offload_gpt2_xl_host_groups": 2,
        "sparse_attn_repeats": 3,
    }
    assert validate_record(record) == []
    # the dtype/bytes pattern applies to ANY row name, not a fixed list
    assert field_type("offload_gpt2_27b_host_state_bytes_per_step")
    assert field_type("offload_gpt2_27b_host_state_dtype") is str


def test_round12_overlap_row_validates_and_gates():
    """The overlap-mode record (``bench_offload_capacity.py overlap``):
    the new ``gpt2_large_overlap`` row rides the existing
    ``offload_<row>_<field>`` pattern, so its ms/step and exposed-wire
    receipts are schema-legal AND regression-gated by ``bench_diff``
    with the standard offload thresholds — a future change that slows
    the overlapped row or re-grows its exposure trips CI."""
    from deepspeed_tpu.tools.bench_schema import threshold_for

    record = {
        "metric": "offload_overlap",
        "device": "cpu",
        "offload_gpt2_large_ms_per_step": 660.0,
        "offload_gpt2_large_exposed_wire_seconds": 0.66,
        "offload_gpt2_large_overlap_fraction": 0.0,
        "offload_gpt2_large_overlap_ms_per_step": 480.0,
        "offload_gpt2_large_overlap_exposed_wire_seconds": 0.012,
        "offload_gpt2_large_overlap_overlap_fraction": 0.98,
        "offload_gpt2_large_overlap_host_state_bytes_per_step":
            9299493376,
        "offload_gpt2_large_overlap_note": "dryrun",
    }
    assert validate_record(record) == []
    # the bench_diff gate rows the satellite asked for
    assert threshold_for("offload_gpt2_large_overlap_ms_per_step") == (
        "lower", 0.10)
    assert threshold_for(
        "offload_gpt2_large_overlap_exposed_wire_seconds") == (
        "lower", 0.25)
    assert threshold_for(
        "offload_gpt2_large_overlap_overlap_fraction") == ("higher", 0.10)


def test_round15_integrity_leg_fields_validate_and_gate():
    """The multichip integrity leg's receipts: which rank the
    fingerprint consensus indicted, the verdict, and the resized fleet
    — plus the fleet-wide ``integrity_violations`` pinned at 0 by
    ``bench_diff`` (any seeded fault the consensus misses is a gated
    regression)."""
    from deepspeed_tpu.tools.bench_schema import threshold_for

    record = {
        "metric": "dryrun_multichip",
        "leg_integrity_status": "ok",
        "leg_integrity_evicted_rank": 2,
        "leg_integrity_verdict": "outlier",
        "leg_integrity_resized_to": 2,
        "leg_integrity_resume_step": 1,
        "integrity_violations": 0,
    }
    assert validate_record(record) == []
    assert threshold_for("integrity_violations") == ("lower", 0.0)
    # leg-pattern fields stay informational unless listed; the verdict
    # and rank are identity fields, never gated numerically
    assert field_type("leg_integrity_verdict") is str
    assert validate_record({"leg_integrity_evicted_rank": "two"}) != []


def test_round18_serving_resilience_fields_validate_and_gate():
    """The self-healing serving receipts: the serving_chaos leg's
    exactly-once requeue counts, plus the top-level requeue/shed/
    recovery fields ``bench_serving`` quotes.  An undetected seeded
    fault (``leg_*_integrity_violations``) is a gated regression; the
    raw counters stay informational — they scale with how much chaos
    the bench injects, not with code quality."""
    from deepspeed_tpu.tools.bench_schema import threshold_for

    record = {
        "metric": "dryrun_multichip",
        "leg_serving_chaos_status": "ok",
        "leg_serving_chaos_evicted_rank": 1,
        "leg_serving_chaos_requeued_requests": 3,
        "leg_serving_chaos_completed_requests": 9,
        "leg_serving_chaos_parity_mismatches": 0,
        "leg_serving_chaos_integrity_violations": 0,
        "leg_serving_chaos_recovery_latency_seconds": 0.011,
        "serving_requeued_requests": 3,
        "serving_shed_requests": 2,
        "serving_deadline_expired": 0,
        "serving_recovery_latency_seconds": 0.007,
    }
    assert validate_record(record) == []
    assert threshold_for(
        "leg_serving_chaos_integrity_violations") == ("lower", 0.0)
    assert threshold_for(
        "leg_serving_chaos_parity_mismatches") == ("lower", 0.0)
    # counters are informational: never gated numerically
    assert threshold_for("serving_requeued_requests") == (None, None)
    assert threshold_for(
        "leg_serving_chaos_requeued_requests") == (None, None)
    assert validate_record(
        {"serving_recovery_latency_seconds": "slow"}) != []


def test_unknown_and_mistyped_fields_are_flagged():
    probs = validate_record({
        "offload_gpt2_large_host_state_bytes_per_step": "lots",
        "made_up_field": 1,
        "mfu": True,  # bool smuggled into a metric
    })
    assert len(probs) == 3
    assert any("made_up_field" in p for p in probs)


def test_failure_strings_allowed_per_row():
    assert validate_record({
        "offload_xl_exc": "xl run failed (try 2): ...",
        "seq512_exc": "secondary run failed (try 1): ...",
        "offload_gpt2_large_bf16_error": "non-finite loss nan",
    }) == []
