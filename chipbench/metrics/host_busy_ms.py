"""host_busy_ms: milliseconds per round in which the host round loop
(``core/gbdt.py`` ``train``) did its own work: the measured round less
the ``repro.sync`` spans inside it (``step_times["sync_wait"]``), the
round span's self time on the host.  None for a program without that
counter."""
from __future__ import annotations

from chipbench.metrics.host_wait_ms import sync_wait_s


def read(records):
    fit = records.fit
    wait = None if fit is None else sync_wait_s(fit)
    if wait is None:
        return None
    return (fit["window_s"] - wait) * 1e3 / fit["rounds"]
