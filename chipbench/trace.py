"""Reduction of a JAX profiler trace to the numbers the readers use.

A trace is the ``*.xplane.pb`` that ``jax.profiler.trace`` writes.  The
device's operations are the events of the ``XLA Ops`` lines of the
``/device:*`` planes; a CPU backend runs its operations on host threads,
where they are the events that carry an ``hlo_op`` statistic (that is how
the recorded CPU fixture of the tests holds them).  Host spans are the
harness's ``jax.profiler.TraceAnnotation`` events, named ``chipbench.*``.

From those, over the measured window (the ``chipbench.measured`` span):

* busy seconds: the union of the operations' intervals, per device,
  averaged over the devices that ran any;
* device seconds per operation name, and of the operations whose own
  name contains a given text (a kernel);
* the idle gaps between busy intervals, each named by the innermost
  harness span around its middle and by the innermost other host event
  there (what the host was doing), summed per name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench.harness import SPAN_PREFIX

WINDOW = SPAN_PREFIX + "measured"
TOP = 10


@dataclasses.dataclass
class Event:
    name: str                  # the op's own name: "histogram_pallas.11"
    start: int                 # ns
    end: int                   # ns
    stats: Dict = dataclasses.field(default_factory=dict, repr=False)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(name: str) -> str:
    """An XLA op event is named by its HLO text, ``%histogram_pallas.11 =
    f32[...] custom-call(%pad.46, ...)``; its own name is what precedes
    `` = `` (the operands name other ops)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(op_name(e.name), start,
                         start + int(e.duration_ns), dict(e.stats)))
    return out


def read_events(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device ops by device, host events) of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [e for line in plane.lines if line.name == "XLA Ops"
                   for e in _events(line)]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if "hlo_op" in e.stats:
                        cpu_ops.append(e)
                    else:
                        host.append(e)
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return devices, host


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(events: List[Event], points: List[int]
               ) -> Dict[int, Optional[Event]]:
    """For each time point, the shortest event around it (a sweep)."""
    evs = sorted(events, key=lambda e: e.start)
    out: Dict[int, Optional[Event]] = {}
    active: List[Event] = []
    i = 0
    for t in sorted(set(points)):
        while i < len(evs) and evs[i].start <= t:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e.end > t]
        out[t] = min(active, key=lambda e: e.end - e.start, default=None)
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: Dict[str, List[Event]]          # device -> ops inside the window
    gaps: List[Tuple[str, float]]        # (name, idle seconds), summed

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_seconds(self, text: str) -> Optional[float]:
        """Device seconds of the ops whose own name contains ``text``,
        averaged over devices; None when no op matches."""
        per_dev = []
        for ops in self.ops.values():
            hits = [e for e in ops if text in e.name]
            if hits:
                per_dev.append(sum(e.end - e.start for e in hits) / 1e9)
        return sum(per_dev) / len(per_dev) if per_dev else None

    def top_ops(self, k: int = TOP) -> List[Tuple[str, float]]:
        per_name: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for e in ops:
                per_name[e.name] += (e.end - e.start) / 1e9 / len(self.ops)
        return sorted(per_name.items(), key=lambda kv: -kv[1])[:k]


def reduce(path: str) -> Summary:
    devices, host = read_events(path)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    others = [e for e in host if not e.name.startswith(SPAN_PREFIX)]
    windows = [e for e in spans if e.name == WINDOW]
    if windows:
        w0, w1 = windows[0].start, windows[0].end
    else:
        every = [e for ops in devices.values() for e in ops]
        w0, w1 = min(e.start for e in every), max(e.end for e in every)
    clipped: Dict[str, List[Event]] = {}
    busy = []
    idle: List[Tuple[int, int]] = []
    for dev, ops in devices.items():
        inside = [dataclasses.replace(e, start=max(e.start, w0),
                                      end=min(e.end, w1))
                  for e in ops if e.end > w0 and e.start < w1]
        if not inside:
            continue
        clipped[dev] = inside
        merged = _union([(e.start, e.end) for e in inside])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        idle += [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    if not clipped:
        raise ValueError(f"{path}: no device operation inside the window")
    mids = [(s + e) // 2 for s, e in idle]
    where = _innermost([x for x in spans if x.name != WINDOW], mids)
    doing = _innermost(others, mids)
    gaps: Dict[str, float] = defaultdict(float)
    for (s, e), mid in zip(idle, mids):
        name = (where[mid].name[len(SPAN_PREFIX):] if where[mid]
                else "measured")
        if doing[mid] is not None:
            name += " / " + doing[mid].name
        gaps[name] += (e - s) / 1e9 / len(clipped)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / len(busy), ops=clipped,
                   gaps=top_gaps)
