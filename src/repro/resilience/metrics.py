"""Process-wide resilience counters.

The perf gate needs to distinguish "slow" from "spent the round budget
recovering" — so every resilience event increments a named process-wide
counter here, and the benchmark harness snapshots the counters around
each lane (``benchmarks/run.py`` records per-lane ``recoveries`` in the
emitted JSON).

``"recoveries"`` counts every trainer recovery branch that fired
(transient replay, OOM degradation, divergence rollback — streaming or
distributed).  A failing Pallas kernel is not an event here: it raises.

Counters are cumulative per process; use :func:`snapshot` around a
region to attribute events to it.  Thread-safe (the serving daemon and
prefetch threads may record concurrently).
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Dict

_lock = threading.Lock()
_counts: Counter = Counter()


def record(kind: str, n: int = 1) -> None:
    """Increment the ``kind`` counter by ``n``."""
    with _lock:
        _counts[kind] += int(n)


def counts() -> Dict[str, int]:
    """A copy of every counter (cumulative since process start/reset)."""
    with _lock:
        return dict(_counts)


def snapshot() -> Dict[str, int]:
    """Alias of :func:`counts` — pair two calls to diff a region."""
    return counts()


def delta(before: Dict[str, int]) -> Dict[str, int]:
    """Counters accumulated since ``before`` (a :func:`snapshot`)."""
    now = counts()
    keys = set(now) | set(before)
    return {k: now.get(k, 0) - before.get(k, 0) for k in keys
            if now.get(k, 0) - before.get(k, 0)}


def reset() -> Dict[str, int]:
    """Zero every counter; returns the pre-reset values."""
    with _lock:
        old = dict(_counts)
        _counts.clear()
        return old
