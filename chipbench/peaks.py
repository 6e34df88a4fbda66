"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s
in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A device that is not in the table is an error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"chipbench has no published peaks for device kind {device_kind!r}: "
                       "add it to chipbench/peaks.py with its source") from None
