#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python chipbench/control.py --workload higgs1m-fit --seeds 1,2,3

For each seed this makes the cell's inputs as a run does, puts the plain
reference in the program's place, and prints what the cell's comparison
reads for:

* ``control``: the reference in the next lower precision (bfloat16
  statistics and bfloat16 features for a fit; bfloat16 features and
  leaves for serving), which has to fail the comparison;
* for a fit, ``code_mismatch`` of the program's own binning, as a run
  reads it;
* for a fit, each fault a training run can have: margins never updated
  (``unchanged``), half of the records left out and the rest counted twice
  (``half_batch``), one leaf off by 1% (``altered_leaf``).

The benchmark's own runs never run this.  It needs the chips the cell asks
for, like a run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import run  # noqa: E402
from chipbench.harness import Clock, load_json, log  # noqa: E402

FIT_VARIANTS = (("control", {"stats": "bfloat16"}),
                ("unchanged", {"fault": "unchanged"}),
                ("half_batch", {"fault": "half_batch"}),
                ("altered_leaf", {"fault": "altered_leaf"}))


def fit_readings(config, traffic, seed, clock):
    from chipbench.drivers import fit
    from chipbench.reference import fit_ref
    _, codes, y = fit.prepare(config, traffic, seed, clock, {})
    out = {"program": {"code_mismatch": fit.code_mismatch(
        config, traffic, seed, codes)}}
    rounds = traffic["compared_rounds"]
    t0 = time.perf_counter()
    ref = fit.reference_rounds(config, codes, y, rounds)
    out["reference_s"] = time.perf_counter() - t0
    for name, kw in FIT_VARIANTS:
        got = fit.reference_rounds(config, codes, y, rounds, **kw)
        out[name] = fit_ref.compare(got["trees"], got["losses"], ref)
    out["control"]["code_mismatch"] = fit.code_mismatch(
        config, traffic, seed, None, precision="bfloat16")
    return out


def serve_readings(config, traffic, seed, seconds, clock):
    from chipbench.drivers import serve
    _, pool, sample, trees = serve.prepare(config, traffic, seed, None,
                                           clock, {})
    _, sizes, offsets = serve.schedule(traffic, seconds, seed,
                                       pool.shape[0])
    from chipbench.reference import binning_ref, serve_ref
    tables = binning_ref.fit_edges(sample, set(range(
        config["dataset"]["numeric_fields"], pool.shape[1])),
        config["model"]["max_bins"])
    host = {f: getattr(trees, f) for f in trees._fields}
    out = {}
    answers = [None] * len(sizes)
    for k in serve.sampled_requests(sizes, traffic, seed):
        o = offsets[k]
        answers[k] = serve_ref.predict(pool[o:o + sizes[k]], host,
                                       config["model"]["base_margin"],
                                       tables, precision="bfloat16")
    m = {"sizes": sizes, "offsets": offsets, "answers": answers}
    return {"control": {"pred_gap": serve.compare(m, pool, sample, trees,
                                                  config, traffic, seed)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window whose requests are compared (serving); "
                         "default: BENCHMARK.json's run_seconds")
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic, _ = run.cell_files(bench, args.workload)
    if run.chips_present(int(cell["chips"])) is None:
        return run.NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = Clock()
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["driver"] == "fit":
            out = fit_readings(config, traffic, seed, clock)
        else:
            out = serve_readings(config, traffic, seed,
                                 args.seconds or bench["run_seconds"], clock)
        log(f"seed {seed}: {json.dumps(out)}")
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
