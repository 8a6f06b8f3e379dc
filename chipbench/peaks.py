"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 819 GB/s HBM bandwidth,
16 GB HBM (Google Cloud documentation, "TPU v5e").  A device that is not
in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]
