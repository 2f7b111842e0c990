"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" is the TPU v5e.  Source: Google Cloud documentation, "TPU
v5e" (system architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.  A device
missing here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}
SOURCE = "Google Cloud documentation, 'TPU v5e'"


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
