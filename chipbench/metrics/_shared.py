"""Helpers the per-layer readers share: the roofline's least time."""
from __future__ import annotations


def least_seconds(ops: float, nbytes: float, peaks) -> float:
    """The least time the chip needs for ``ops`` operations and ``nbytes``
    bytes of memory traffic: the larger of the two bounds."""
    return max(ops / peaks["flops"], nbytes / peaks["hbm_bytes_per_s"])


def per_round(records, seconds):
    """Seconds over the fit's rounds, or None without a fit or a time."""
    if records.fit is None or seconds is None:
        return None
    return seconds / records.fit["rounds"]
