"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s, bf16 matrix units
        "hbm_bytes_per_s": 819e9,      # HBM bandwidth
        "hbm_bytes": 16e9,             # HBM capacity
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` of HBM traffic: the larger of the two roofline bounds."""
    p = peaks(device_kind)
    return max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
