"""Published per-chip peaks, keyed by jax's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. (Copied from
``bench.py DEVICE_PEAKS``, PR 22; the original is listed for deletion in
PERF.md.) A device that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(PEAKS)}); add its published peaks with their "
            "source in a benchmark PR")
    return PEAKS[device_kind]
