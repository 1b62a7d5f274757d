"""Run a cell of the test-only benchmark (``fixtures/bench.json``) in this
process on the CPU, past the harness's look for a chip."""
from __future__ import annotations

import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

FIXTURES = Path(__file__).parent / "fixtures"
SEED = 2 ** 33 + 17          # wider than 32 bits, as the driver's are


def interpret_plan():
    from repro.api import ExecutionPlan
    return ExecutionPlan.auto(hist_strategy="pallas_grouped",
                              partition_strategy="pallas",
                              traversal_strategy="pallas")


def run_cell(workload: str, trace: int = 0, plan=None, seed: int = SEED,
             seconds: float = 2.0):
    """(exit code, the JSON line or None) of one run."""
    from chipbench import run
    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  bench_path=FIXTURES / "bench.json", bench_dir=FIXTURES,
                  need_chip=False, plan=plan,
                  peaks_path=FIXTURES / "peaks.json", compile_cache=False,
                  t_start=time.perf_counter(), out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
