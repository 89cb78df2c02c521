"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in the table is an error, never a
default: a roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

#: device_kind -> peaks of one chip. Source: Google Cloud documentation,
#: "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
