"""Chip peak FLOP/s table + model-FLOPs-utilisation (MFU) math.

The ONE implementation shared by the flops profiler and the capacity
planner — utilisation numbers must not drift between reporters because
each carried its own peak table.

A TPU whose ``device_kind`` is not in the tables is an ERROR, not a
default: a utilisation against a guessed peak is a wrong number under a
device metric's name.  A non-TPU device (the CPU test mesh) has no peak
and gets no MFU; only the static analysers, which price program text
and never report a utilisation, get stand-in link speeds for it.
"""

# bf16 peak TFLOP/s per chip, by device_kind substring (published
# per-chip figures).
PEAK_TFLOPS = {
    "v5 lite": 197.0,  # TPU v5e
    "v5e": 197.0,
    "v4": 275.0,
    "v5p": 459.0,
    "v6": 918.0,  # Trillium
}

# Bandwidth tables for the overlap analyzer's roofline/wire costing
# (GB/s, by the same device_kind substrings as PEAK_TFLOPS).
# ``hbm_gbps`` is stream bandwidth, ``ici_gbps`` one-direction per-link
# interconnect — both conservative public figures, same spirit as the
# peak-TFLOPs table.
CHIP_BANDWIDTHS = {
    "v5 lite": {"hbm_gbps": 819.0, "ici_gbps": 45.0},
    "v5e": {"hbm_gbps": 819.0, "ici_gbps": 45.0},
    "v4": {"hbm_gbps": 1228.0, "ici_gbps": 50.0},
    "v5p": {"hbm_gbps": 2765.0, "ici_gbps": 90.0},
    "v6": {"hbm_gbps": 1640.0, "ici_gbps": 90.0},
}
# Stand-ins the STATIC analysers (profiling/overlap, the attribution
# flops cross-check) use for a device that is not a TPU — the CPU test
# mesh.  Fast links on purpose: small predicted windows, so the analyser
# under-claims rather than inventing findings.  Never an MFU denominator.
ANALYSER_PEAK_TFLOPS = 990.0
ANALYSER_HBM_GBPS = 3000.0
ANALYSER_ICI_GBPS = 100.0
# host<->device DMA: ~14 GB/s effective, measured in round 5 on an
# earlier attachment (PERF.md "ZeRO-Offload wire bytes" accounting) and
# not re-measured since
DEFAULT_HOST_GBPS = 14.0


def _lookup(table, device_kind):
    kind = (device_kind or "").lower()
    for key, val in table.items():
        if key in kind:
            return val
    if "tpu" in kind:
        raise ValueError(
            f"no peak figures for TPU device_kind {device_kind!r}: add it "
            f"to profiling/utilization.py (known: {sorted(table)})")
    return None


def chip_specs(device_kind=""):
    """Roofline/wire constants for one ``device_kind`` string:
    ``{device_kind, peak_tflops, hbm_gbps, ici_gbps, host_gbps}`` — the
    static analysers' price list.  An unknown TPU kind raises; a non-TPU
    kind (the CPU test mesh, or none) gets the analyser stand-ins."""
    peak = _lookup(PEAK_TFLOPS, device_kind)
    bw = _lookup(CHIP_BANDWIDTHS, device_kind) or {}
    return {"device_kind": device_kind or "",
            "peak_tflops": peak if peak is not None else ANALYSER_PEAK_TFLOPS,
            "hbm_gbps": bw.get("hbm_gbps", ANALYSER_HBM_GBPS),
            "ici_gbps": bw.get("ici_gbps", ANALYSER_ICI_GBPS),
            "host_gbps": DEFAULT_HOST_GBPS}


def chip_peak_tflops(device):
    """bf16 peak TFLOP/s for one jax device (by ``device_kind``); None
    for a device that is not a TPU (no MFU there), an error for a TPU
    kind the table does not know."""
    if getattr(device, "platform", "") != "tpu":
        return None
    return _lookup(PEAK_TFLOPS, getattr(device, "device_kind", "tpu"))


def achieved_tflops(samples_per_sec, flops_per_sample):
    """Model TFLOP/s actually sustained."""
    return samples_per_sec * flops_per_sample / 1e12


def model_flops_utilization(samples_per_sec, flops_per_sample,
                            peak_tflops):
    """MFU in [0, 1] (values > 1 mean the caller measured nothing)."""
    return achieved_tflops(samples_per_sec, flops_per_sample) / peak_tflops
