"""What every cell's driver shares: the cell's files, the compile clock,
host spans, device facts and the records the per-layer readers read."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
SPAN_PREFIX = "chipbench."


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Clock:
    """Counts XLA compiles (JAX's backend-compile events) and their
    seconds, so set-up can report its compile time and the window can
    show that nothing compiled inside it."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)

    @contextlib.contextmanager
    def phase(self, name: str, phases: Dict[str, Dict[str, float]]):
        """Time one set-up phase; its seconds and compile seconds go to
        ``phases`` and to standard error."""
        t0, c0 = time.perf_counter(), self.compile_s
        with span(name):
            yield
        s, c = time.perf_counter() - t0, self.compile_s - c0
        phases[name] = {"s": s, "compile_s": c}
        log(f"setup phase {name}: {s:.3f} s, of which {c:.3f} s compile")


def span(name: str):
    """A host span in the profiler's trace (a no-op when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest chip the cell uses."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile (0 <= q <= 1), interpolated between order
    statistics as ``statistics.quantiles(method="inclusive")`` does.  An
    infinite value (a request that never met any limit) stays infinite
    where it is reached, instead of turning the interpolation into NaN."""
    vals = sorted(values)
    if not vals:
        raise ValueError("quantile of no values")
    pos = q * (len(vals) - 1)
    lo = int(pos)
    frac = pos - lo
    if frac == 0.0 or lo + 1 >= len(vals):
        return vals[lo]
    return vals[lo] + frac * (vals[lo + 1] - vals[lo])


@dataclasses.dataclass
class Records:
    """What a run leaves for the per-layer readers: the cell's files, the
    device's peaks, the reduced trace (traced runs only) and each
    driver's own readings."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    peaks: Dict[str, float]
    trace: Any = None                  # trace.Summary of the window
    fit: Optional[Dict[str, Any]] = None
    serve: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class Check:
    """One number compared and its limit: it passes at or below it."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit         # NaN fails


@dataclasses.dataclass
class Outcome:
    """A driver's result: end-to-end readings, checks, work counts."""

    metrics: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    records: Records
