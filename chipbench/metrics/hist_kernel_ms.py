"""hist_kernel_ms: device milliseconds per round of the step-① histogram
kernel (``kernels/histogram.py``), from the trace of the window."""
from __future__ import annotations

from chipbench.metrics._shared import per_round

KERNEL = "histogram_pallas"  # the kernel's op name in the device trace


def seconds(records):
    if records.trace is None:
        return None
    return records.trace.device_seconds(KERNEL)


def read(records):
    s = per_round(records, seconds(records))
    return None if s is None else s * 1e3
