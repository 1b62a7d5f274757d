"""The required-work counts of ``hist_roofline`` and ``fit_mfu`` against a
count made by hand at the higgs shape: n = 10M records, F = 28 fields,
NB = 256 bins, depth 6 (levels of 1, 2, ..., 32 nodes: 63 in all)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.harness import load_json  # noqa: E402
from chipbench.metrics import fit_mfu, hist_roofline  # noqa: E402
from chipbench.metrics._shared import least_seconds  # noqa: E402

N, F, NB, D = 10_000_000, 28, 256, 6


def test_histogram_level_by_hand():
    # root: 280 MB of codes, 80 MB of g and h, 40 MB of node ids, and one
    # node's 28 x 256 (g, h) float32 sums; one add per code per statistic
    ops, nbytes = hist_roofline.level_work(N, F, NB, 1)
    assert ops == 560_000_000
    assert nbytes == 280_000_000 + 80_000_000 + 40_000_000 + 57_344


def test_histogram_round_by_hand():
    ops, nbytes = hist_roofline.round_work(N, F, NB, D)
    assert ops == 6 * 560_000_000
    assert nbytes == 6 * 400_000_000 + 63 * 57_344 == 2_403_612_672


def test_fit_round_by_hand():
    ops, nbytes = fit_mfu.round_work(N, F, NB, D)
    # histograms, then g/h 16n, partition 6 x 9n, leaf sums 12n,
    # margin update (8 + 6)n, loss 8n: 104n bytes beyond the histograms
    assert nbytes == 2_403_612_672 + 104 * N
    assert ops == 6 * 560_000_000 + (6 + 6 + 2 + 6 + 3) * N


def test_least_time_at_v5e_peaks():
    peaks = load_json(Path(__file__).resolve().parents[1] / "peaks.json")
    v5e = peaks["devices"]["TPU v5 lite"]
    ops, nbytes = fit_mfu.round_work(N, F, NB, D)
    # memory-bound: 3.44 GB at 819 GB/s is 4.2 ms; 3.6 G adds at
    # 197 T/s would be 18 us
    assert least_seconds(ops, nbytes, v5e) == pytest.approx(
        3_443_612_672 / 819e9)
