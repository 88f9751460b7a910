"""The chip's published peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
in bfloat16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM. JAX names that
chip "TPU v5 lite". A kind that is not in the table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None
