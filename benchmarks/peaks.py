"""Published peaks of one chip, keyed by ``device_kind``, and the
utilisation arithmetic built on them.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect; the
45 GB/s per ICI link is the figure ``deepspeed_tpu/profiling/utilization.py``
carries (that file is the program's; this one is the yardstick's copy).
A device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 45e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    """The peaks of ``device_kind``; ``KeyError`` for a kind the table
    does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"benchmarks/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None


def mfu_percent(tokens_per_s_per_chip, flops_per_token, device_kind):
    """Model FLOP/s utilisation of one chip, in percent: the operations
    the forward and backward passes require per token (recomputation not
    counted) times tokens per second per chip, over the bf16 peak."""
    return (100.0 * tokens_per_s_per_chip * flops_per_token
            / peaks_for(device_kind)["bf16_flops"])


def roofline_percent(flops, bytes_moved, seconds, device_kind):
    """Share of the roofline a kernel reached: the least time the chip
    could take — the larger of operations over peak FLOP/s and bytes over
    peak bytes/s — over the time it took.  Returns (percent, which bound)."""
    peak = peaks_for(device_kind)
    t_flops = flops / peak["bf16_flops"]
    t_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
