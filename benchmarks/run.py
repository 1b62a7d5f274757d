"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figures covered:
  Fig 6  step breakdown            bench_breakdown
  Fig 7  machine/strategy speedups bench_training
  Fig 9  optimization isolation    bench_opts
  Fig 12 dataset-size sensitivity  bench_scaling
  Fig 13 batch inference           bench_inference
  (out-of-core)                    bench_streaming
The roofline table (EXPERIMENTS.md §Roofline) is produced by the dry-run
artifacts via ``python -m repro.launch.report``.

``--smoke`` is the CI lane: tiny scales, every bench family exercised,
and ``--json BENCH_ci.json`` captures the rows (plus wall time and
failure state per bench) as the machine-readable perf-trajectory
artifact that CI uploads on every push.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.3,
                    help="dataset scale vs the (already scaled-down) specs")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke lane: minimal scales, all benches")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact")
    args = ap.parse_args()
    scale = 0.05 if args.smoke else args.scale

    # the distributed lane shards over the devices this process has; on a
    # CPU host give it eight (the flag only shapes the host platform, so a
    # chip is untouched).  Must precede the first jax import.
    flag = "--xla_force_host_platform_device_count"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" {flag}=8").strip()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_breakdown, bench_inference,
                            bench_multiclass, bench_opts, bench_scaling,
                            bench_streaming, bench_training)
    from repro.resilience import metrics as rmetrics
    benches = {
        "breakdown": lambda: bench_breakdown.run(scale=scale),
        "training": lambda: bench_training.run(scale=scale),
        "opts": lambda: bench_opts.run(scale=scale),
        "scaling": lambda: bench_scaling.run(base_scale=scale),
        "inference": lambda: bench_inference.run(
            n=max(2000, int(20000 * scale))),
        "multiclass": lambda: bench_multiclass.run(scale=scale),
        "streaming": lambda: bench_streaming.run(
            scale=scale, n_fields=16 if args.smoke else 64,
            n_trees=3 if args.smoke else 5),
    }
    selected = (args.only.split(",") if args.only else list(benches))
    report = {"smoke": args.smoke, "scale": scale,
              "python": platform.python_version(), "benches": {}}
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        t0 = time.time()
        entry = {"rows": [], "seconds": None, "error": None}
        report["benches"][name] = entry
        before = rmetrics.snapshot()
        try:
            if name not in benches:
                raise KeyError(
                    f"unknown bench {name!r}; available: "
                    f"{','.join(benches)}")
            for row in benches[name]():
                print(row)
                sys.stdout.flush()
                cells = row.split(",", 2)
                try:
                    us = float(cells[1]) if len(cells) > 1 else 0.0
                except ValueError:
                    us = 0.0    # malformed timing cell must not kill the lane
                entry["rows"].append({
                    "name": cells[0],
                    "us_per_call": us,
                    "derived": cells[2] if len(cells) > 2 else ""})
        except Exception as e:  # noqa: BLE001 — keep the artifact complete
            # record the failure in the JSON (with context: how far the
            # lane got, and a short traceback), keep running the rest
            print(f"{name}_FAILED,0,{e!r}")
            entry["error"] = repr(e)
            entry["failed_after_rows"] = len(entry["rows"])
            entry["traceback"] = traceback.format_exc(limit=6)
            failures.append(name)
        entry["seconds"] = round(time.time() - t0, 2)
        # "slow" vs "spent rounds recovering": a lane whose trainer
        # replayed or rolled back says so in the artifact
        fired = rmetrics.delta(before)
        entry["resilience"] = {"recoveries": fired.get("recoveries", 0)}
        print(f"# {name} done in {entry['seconds']:.1f}s", file=sys.stderr)

    if args.json is not None:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        print(f"# FAILED benches ({len(failures)}/{len(selected)}): "
              f"{', '.join(failures)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
