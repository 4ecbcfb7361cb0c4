"""Published peaks of the chips the benchmark may run on.

Keyed by the exact `device_kind` JAX reports. A kind that is not here is
an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]
