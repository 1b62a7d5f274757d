#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python chipbench/run.py --workload higgs1m-fit --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``: the configuration's file, ``chipbench/traffic/<mix>.json``
(which names its driver, ``chipbench/drivers/<driver>.py``), the limits of
its correctness checks, ``chipbench/limits/<cell>.json``, and the chip's
peaks, ``chipbench/peaks.json``.  A per-layer metric is read by
``chipbench/metrics/<metric>.py``.

The run needs the chips the cell asks for: without them it exits with
code 3 and prints no result.  It then times set-up (from process start to
the window), measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line last on standard
output.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
records a profiler trace of the window and reports its per-layer metrics.
The numbers compared, each with its limit, are the last lines on standard
error and the last key of the JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.harness import (BENCH_DIR, Clock, Outcome, load_json,  # noqa: E402
                               log, memory_peak_bytes)

NO_CHIP = 3
TRACE_ROOT = ROOT / ".chipbench"


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, its run arguments, the plan
    the program runs under, and the harness's clock and window."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    peaks: Dict[str, float]
    seed: int
    seconds: float
    chips: int
    plan: Any
    clock: Clock
    trace_dir: Optional[Path] = None
    phases: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    window_start: Optional[float] = None

    @contextlib.contextmanager
    def window(self):
        """Set-up ends here; the profiler traces what follows when asked.
        The driver marks the measured part with a ``measured`` span."""
        import jax
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        try:
            self.window_start = time.perf_counter()
            yield
        finally:
            if self.trace_dir is not None:
                jax.profiler.stop_trace()

    def memory_peak(self) -> Optional[int]:
        return memory_peak_bytes(self.chips)


def cell_files(bench: Dict, name: str, bench_dir: Path = BENCH_DIR,
               root: Path = ROOT):
    """(cell, configuration, traffic, limits) of the cell ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    return cell, config, traffic, limits


def metrics_of(bench: Dict, cell: Dict, kind: str):
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def read_metric(name: str, records):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(records)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def device_facts() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def chips_present(chips: int) -> Optional[Dict[str, Any]]:
    """The device facts, or None (and a message) without enough chips."""
    facts = device_facts()
    if facts["platform"] != "tpu" or facts["count"] < chips:
        log(f"needs {chips} TPU chip(s); JAX sees platform="
            f"{facts['platform']} device_kind={facts['kind']} "
            f"count={facts['count']}: nothing was run")
        return None
    return facts


def main(argv=None, *, bench_path: Path = ROOT / "BENCHMARK.json",
         bench_dir: Path = BENCH_DIR, root: Path = ROOT,
         need_chip: bool = True, plan=None, peaks_path: Optional[Path] = None,
         compile_cache: bool = True, t_start: float = T_START,
         out=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = out or sys.stdout

    bench = load_json(bench_path)
    cell, config, traffic, limits = cell_files(bench, args.workload,
                                               bench_dir, root)
    chips = int(cell["chips"])
    facts = chips_present(chips) if need_chip else device_facts()
    if facts is None:
        return NO_CHIP
    peak_table = load_json(peaks_path or bench_dir / "peaks.json")
    if facts["kind"] not in peak_table["devices"]:
        log(f"no peaks for device kind {facts['kind']!r} in peaks.json "
            f"(known: {sorted(peak_table['devices'])})")
        return NO_CHIP
    log(f"device: platform={facts['platform']} "
        f"device_kind={facts['kind']} count={facts['count']}")

    import jax
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # every program, however quick to compile, is kept: the served
        # path alone compiles thousands of small ones
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if plan is None:
        from repro.api import ExecutionPlan
        plan = ExecutionPlan.auto()
    log(f"plan: {plan.resolved().describe()}")

    trace_dir = None
    if args.trace:
        trace_dir = TRACE_ROOT / f"trace-{args.workload}-{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, config=config, traffic=traffic, limits=limits,
                  peaks=peak_table["devices"][facts["kind"]],
                  seed=args.seed, seconds=args.seconds, chips=chips,
                  plan=plan, clock=Clock(), trace_dir=trace_dir)
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    outcome: Outcome = driver.run(ctx)
    setup_s = ctx.window_start - t_start
    log(f"setup: {setup_s:.3f} s; phases "
        + json.dumps({k: {a: round(b, 3) for a, b in v.items()}
                      for k, v in ctx.phases.items()}))

    device = dict(facts, memory_peak_bytes=outcome.memory_peak_bytes)
    result: Dict[str, Any] = {}
    if args.trace:
        from chipbench import trace as trace_mod
        summary = trace_mod.reduce(trace_mod.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        outcome.records.trace = summary
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        metrics = {}
        for m in metrics_of(bench, cell, "per_layer"):
            value = read_metric(m["name"], outcome.records)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.gaps}
    else:
        values = dict(outcome.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": _finite(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_of(bench, cell, "end_to_end")}

    correct = all(c.ok for c in outcome.checks)
    for c in outcome.checks:
        log(f"check {c.name}: {c.value!r} limit <= {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                      for c in outcome.checks}
    out.write(json.dumps(line) + "\n")
    out.flush()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
