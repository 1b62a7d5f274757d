"""A cell is data: a test-only configuration and traffic mix, found by
name from a test-only ``bench.json``, run end to end on the CPU with the
Pallas kernels in interpret mode; and a run without a chip prints
nothing."""
from __future__ import annotations

import io
import os
import shutil
import subprocess
import sys

import pytest

from cells import FIXTURES, ROOT, interpret_plan, run_cell

E2E = {"tiny-higgs-fit": {"setup_s", "fit_round_s"},
       "tiny-flight-serve": {"setup_s", "serve_p50_ms", "serve_rows_per_s"}}
PER_LAYER = {"tiny-higgs-fit": {"fit_mfu", "fit_idle_share", "grow_s",
                                "margin_update_s"},
             "tiny-flight-serve": {"serve_idle_share", "serve_rows_per_flush",
                                   "loadgen_late_ms"}}


@pytest.mark.parametrize("workload", sorted(E2E))
def test_cell_runs_end_to_end(workload):
    rc, line = run_cell(workload, plan=interpret_plan())
    assert rc == 0
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == E2E[workload]
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", sorted(PER_LAYER))
def test_traced_cell_reports_its_layers(workload):
    rc, line = run_cell(workload, trace=1)
    assert rc == 0 and line["correct"] is True
    # the kernels' readers find no Pallas kernel on the CPU's jnp path and
    # stay silent; the others read
    assert PER_LAYER[workload] <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


def test_metric_without_workloads_follows_what_it_moves():
    from chipbench import run
    bench = {"end_to_end": [
        {"name": "setup_s"},
        {"name": "fit_round_s", "workloads": ["a"]},
        {"name": "serve_p50_ms", "workloads": ["b"]}],
        "per_layer": [
        {"name": "idle.fit", "moves": "fit_round_s"},
        {"name": "flush_rows", "moves": "serve_p50_ms", "workloads": ["b"]}]}
    names = {c: [m["name"] for m in run.metrics_of(bench, {"name": c},
                                                   "per_layer")]
             for c in "ab"}
    assert names == {"a": ["idle.fit"], "b": ["flush_rows"]}


def test_no_chip_no_result(capsys):
    from chipbench import run
    out = io.StringIO()
    rc = run.main(["--workload", "tiny-higgs-fit", "--seed", "1",
                   "--seconds", "1"], bench_path=FIXTURES / "bench.json",
                  bench_dir=FIXTURES, out=out)
    assert rc == run.NO_CHIP
    assert out.getvalue() == ""
    assert "platform=cpu" in capsys.readouterr().err


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "higgs1m-fit",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
