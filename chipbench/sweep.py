#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate at which the
backlog does not grow over a window and the 99th percentile latency stays
under the traffic's ``latency_limit_ms``.

    python chipbench/sweep.py --workload <serving cell> --seed 5 --seconds 20 \\
        --rates 500,1000,2000,4000

Set-up is made once; each rate then runs one open-loop window of the
traffic mix at that rate.  One JSON line per rate: the p99, the requests
still unanswered at the close and the rows queued then, how late the
load generator ran, and the rows answered per second.  The cell's rate is
then written into its traffic file as a number; runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import run  # noqa: E402
from chipbench.harness import Clock, load_json, quantile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic, _ = run.cell_files(bench, args.workload)
    if run.chips_present(int(cell["chips"])) is None:
        return run.NO_CHIP
    import jax
    from repro.api import ExecutionPlan
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Server

    from chipbench.drivers import serve
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = Clock()
    registry, pool, _, _ = serve.prepare(config, traffic, args.seed,
                                         ExecutionPlan.auto(), clock, {})
    server = Server(registry, **traffic["server"])
    try:
        serve.warm(server, registry, pool, traffic)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(traffic, rate_per_s=rate)
            m = serve.measure(server, pool, mix, args.seconds, args.seed,
                              clock=clock)
            p99 = quantile(m["latency_s"].tolist(), 0.99) * 1e3
            print(json.dumps({
                "rate_per_s": rate, "requests": len(m["sizes"]),
                "p99_ms": p99 if p99 != float("inf") else None,
                "p50_ms": quantile(m["latency_s"].tolist(), 0.5) * 1e3,
                "unanswered_at_close": m["backlog"],
                "queue_rows_at_close": m["queue_at_close"],
                "failed": m["failed"],
                "late_p99_ms": quantile(m["late_s"].tolist(), 0.99) * 1e3,
                "rows_per_s": m["answered_rows"] / args.seconds,
                "rows_per_flush": m["rows"] / max(m["flushes"], 1),
                "compiles_in_window": m["compiles_in_window"],
                "limit_ms": traffic["latency_limit_ms"]}), flush=True)
    finally:
        server.stop(timeout=traffic["result_wait_s"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
