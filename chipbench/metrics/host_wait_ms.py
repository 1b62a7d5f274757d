"""host_wait_ms: milliseconds per round in which the host round loop
(``core/gbdt.py`` ``train``) blocked on the device: the timed fit's
``step_times["sync_wait"]``, the sum of its ``repro.sync`` spans, over
its rounds.  None for a program without that counter."""
from __future__ import annotations


def sync_wait_s(fit):
    """The fit's ``sync_wait`` seconds, or None."""
    try:
        from repro.tracing import SYNC_WAIT
    except ImportError:            # a program without the tracing module
        return None
    return fit["step_times"].get(SYNC_WAIT)


def read(records):
    fit = records.fit
    wait = None if fit is None else sync_wait_s(fit)
    return None if wait is None else wait * 1e3 / fit["rounds"]
