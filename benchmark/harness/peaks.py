"""Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.

A device that is not in the table is an error, never a default.
Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/harness/peaks.py; "
            "add it with its published source before measuring on it")
    return PEAKS[device_kind]
