"""The readers of the program's own counters (``repro.tracing``), on
hand-built fit records: each reads what is there, per round, and returns
None where nothing is."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.harness import Records  # noqa: E402
from chipbench.metrics import host_busy_ms, host_wait_ms  # noqa: E402
from repro import tracing  # noqa: E402


def _records(rounds=2, window_s=0.1, step_times=None):
    fit = {"rounds": rounds, "window_s": window_s,
           "step_times": step_times or {}}
    return Records(cell={}, config={}, traffic={}, peaks={}, fit=fit)


NO_FIT = Records(cell={}, config={}, traffic={}, peaks={})


def test_host_wait_ms():
    times = {tracing.BINNING_SPLIT: 0.08, tracing.SYNC_WAIT: 0.06}
    assert host_wait_ms.read(_records(step_times=times)) == pytest.approx(30)
    assert host_wait_ms.read(
        _records(rounds=3, step_times=times)) == pytest.approx(20)
    # a program without the counter, and a run without a fit
    assert host_wait_ms.read(_records(
        step_times={tracing.BINNING_SPLIT: 0.08})) is None
    assert host_wait_ms.read(NO_FIT) is None


def test_host_busy_ms():
    times = {tracing.SYNC_WAIT: 0.06}
    # the 0.1 s window less 0.06 s of waits, over 2 and 4 rounds
    assert host_busy_ms.read(_records(step_times=times)) == pytest.approx(20)
    assert host_busy_ms.read(
        _records(rounds=4, step_times=times)) == pytest.approx(10)
    assert host_busy_ms.read(_records(step_times={})) is None
    assert host_busy_ms.read(NO_FIT) is None


@pytest.mark.parametrize("reader", [host_wait_ms, host_busy_ms])
def test_a_program_without_the_tracing_module_reads_nothing(reader,
                                                            monkeypatch):
    """The benchmark's files also run over an older program, which has
    no ``repro.tracing``: the readers then return None and raise
    nothing."""
    records = _records(step_times={tracing.SYNC_WAIT: 0.01})
    assert reader.read(records) is not None
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader.read(records) is None
