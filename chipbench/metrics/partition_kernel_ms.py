"""partition_kernel_ms: device milliseconds per round of the step-③
partition kernel (``kernels/partition.py``), from the trace."""
from __future__ import annotations

from chipbench.metrics._shared import per_round

KERNEL = "partition_pallas"  # vmap_jit_partition_pallas__.<n>


def read(records):
    if records.trace is None:
        return None
    s = per_round(records, records.trace.device_seconds(KERNEL))
    return None if s is None else s * 1e3
