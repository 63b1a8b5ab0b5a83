"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it.  A device that is not here is an error, never a default, and no
environment variable overrides a figure."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add it with its source before measuring")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, hbm_bytes: float, peaks: dict):
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = hbm_bytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")


def share_pct(least: float, measured: float, what: str) -> float:
    """``least / measured`` as a percentage.  A share of a peak cannot
    pass 100%: one that does means the operations or bytes are counted
    too high or the time leaves out part of the work, so it raises
    instead of printing."""
    if measured <= 0:
        raise ValueError(f"{what}: measured time {measured} is not positive")
    pct = 100.0 * least / measured
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.2f}% of the peak (least {least:.6g} s over "
            f"measured {measured:.6g} s) cannot be right")
    return pct
