"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600
Gbit/s of chip-to-chip interconnect. A kind not in the table is an error.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
