"""loadgen_late_ms: the 99th percentile of how late the harness's load
generator sent a request against its due time, in milliseconds; a late
generator would otherwise read as a fast server."""
from __future__ import annotations

from chipbench.harness import quantile


def read(records):
    serve = records.serve
    if serve is None or not serve["late_s"]:
        return None
    return quantile(serve["late_s"], 0.99) * 1e3
