"""The trace reduction on a small trace recorded on the CPU.

The fixture ``fixtures/cpu_trace.xplane.pb`` was recorded by
``record_fixture`` below: a jitted matrix product run once before the
measured span (outside the window), then three times inside it, each in
its own ``chipbench.round.<i>`` span followed by a 20 ms sleep (an idle
gap inside that round's span).  Regenerate it with
``python chipbench/tests/test_trace.py``."""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "cpu_trace.xplane.pb"
SLEEP_S = 0.02


def record_fixture(dest: Path = FIXTURE) -> None:
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        f(x).block_until_ready()                       # outside the window
        with jax.profiler.TraceAnnotation("chipbench.measured"):
            for i in range(3):
                with jax.profiler.TraceAnnotation(f"chipbench.round.{i}"):
                    f(x).block_until_ready()
                    time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(tmp), dest)


@pytest.fixture(scope="module")
def raw():
    """Device ops and spans read straight from the file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    ops, spans = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                s, d = int(e.start_ns), int(e.duration_ns)
                if "hlo_op" in stats:
                    ops.append((e.name, s, s + d))
                elif e.name.startswith("chipbench."):
                    spans[e.name] = (s, s + d)
    return ops, spans


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(FIXTURE))


def test_window_is_the_measured_span(summary, raw):
    _, spans = raw
    s, e = spans["chipbench.measured"]
    assert summary.window_s == pytest.approx((e - s) / 1e9, abs=1e-9)
    assert summary.window_s > 3 * SLEEP_S


def test_busy_is_the_union_of_ops_inside_the_window(summary, raw):
    ops, spans = raw
    w0, w1 = spans["chipbench.measured"]
    # the union by brute force over microseconds
    covered = set()
    for _, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        covered.update(range(s // 1000, -(-e // 1000)) if e > s else ())
    assert summary.busy_s == pytest.approx(len(covered) / 1e6, abs=2e-4)
    assert 0 < summary.busy_s < summary.window_s - 3 * SLEEP_S * 0.9
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)


def test_kernel_time_by_name(summary, raw):
    ops, spans = raw
    w0, w1 = spans["chipbench.measured"]
    want = sum(min(e, w1) - max(s, w0) for name, s, e in ops
               if name.startswith("dot") and e > w0 and s < w1) / 1e9
    assert want > 0
    assert summary.device_seconds("dot") == pytest.approx(want, rel=1e-9)
    assert summary.device_seconds("no such kernel") is None
    names = [name for name, _ in summary.top_ops()]
    assert any(n.startswith("dot") for n in names)
    # the op before the window is left out: three products, not four
    inside = [o for o in ops if o[0].startswith("dot") and o[2] > w0
              and o[1] < w1]
    assert len(inside) == 3


def test_gaps_are_named_by_the_span_they_fall_in(summary):
    gaps = dict(summary.gaps)
    per_round = {name.split(" / ")[0]: 0.0 for name in gaps}
    for name, s in gaps.items():
        per_round[name.split(" / ")[0]] += s
    for i in range(3):
        assert per_round.get(f"round.{i}", 0.0) >= 0.9 * SLEEP_S
    assert sum(gaps.values()) <= summary.window_s - summary.busy_s + 1e-9


if __name__ == "__main__":
    record_fixture()
    print(f"wrote {FIXTURE}")
