"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never
a default: a utilization over a guessed peak is not a measurement.
The table is the yardstick itself, so only a ``benchmark`` PR adds to it.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e", system architecture: per chip
# 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect.
_V5E = {
    "chip": "TPU v5e",
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, 'TPU v5e', system architecture",
}
PEAKS = {"TPU v5 lite": _V5E}


class UnknownDevice(KeyError):
    """``device_kind`` is not in the table of peaks."""


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the benchmark's "
            f"table of peaks ({sorted(PEAKS)}); add it with its source "
            f"before measuring on it")
    return PEAKS[device_kind]
