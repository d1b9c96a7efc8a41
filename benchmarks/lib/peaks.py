"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a share of a peak taken against the wrong chip means nothing.

Copied from ``fedml_tpu/core/perf.py``'s ``PEAKS`` (the program may
change its table; the yardstick keeps its own)."""

from __future__ import annotations

# device_kind -> (bf16 FLOP/s, HBM bytes/s, HBM bytes, source)
PEAKS = {
    "TPU v5 lite": (
        197e12, 819e9, 16e9,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM2e at 819 GB/s per chip",
    ),
    "TPU v5e": (
        197e12, 819e9, 16e9,
        "Google Cloud documentation, 'TPU v5e' (same chip, other name)",
    ),
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/lib/peaks.py: "
            "add its published peaks with their source before measuring on it"
        )
    flops, bw, hbm, source = PEAKS[device_kind]
    return {"flops_per_s": flops, "bytes_per_s": bw, "hbm_bytes": hbm,
            "source": source}
