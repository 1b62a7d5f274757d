"""serve_idle_share: percent of the serving window in which no operation
ran on the device (trace busy union over the traced window)."""
from __future__ import annotations


def read(records):
    if records.trace is None or records.serve is None:
        return None
    return 100.0 * records.trace.idle_share
