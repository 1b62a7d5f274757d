"""Seeded chaos suite (PR 9): training and data-path resilience.

Everything here is DETERMINISTIC — fault schedules are seeded or pinned
to exact step indices, so the assertions are exact (bit-equal models,
exact recovery counters), never probabilistic.  The matching serving
chaos tests (bounded-queue shedding, deadline expiry, dispatcher crash
supervision) live in ``tests/test_serving.py``.

The headline invariants:

  * a streamed fit under injected IO errors, one device OOM, and one
    mid-round preemption produces the SAME model as the fault-free fit
    (chunked accumulation is chunk-size-invariant; rounds commit
    atomically and replay under per-round RNG keys);
  * checkpoint-restore recovery reproduces tree structure bit-exactly
    and leaf values to float tolerance (restored margins are recomputed
    by streamed inference);
  * corruption is LOUD: a flipped byte in a staged shard raises
    ``ShardCorruptionError`` instead of feeding garbage into a fit, and
    is never retried.
"""
import hashlib
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.api import (BoosterRegressor, ExecutionPlan, NpzShardSource,
                       RecoveryPolicy, RetryPolicy, RetryingSource,
                       write_npz_shards)
from repro.core.binning import StreamingBinner
from repro.core.gbdt import GBDTConfig, train_streaming
from repro.data.pipeline import BinnedShardSource, write_binned_shards
from repro.data.synthetic import SyntheticSource
from repro.distributed import checkpoint as ckpt
from repro.resilience import (DeviceOOMError, FaultSchedule, FaultySource,
                              GracefulShutdown, NumericalDivergenceError,
                              Preemption, ShardCorruptionError,
                              TrainingInterrupted, TransientIOError,
                              corrupt_file, seeded_schedule)

N, F, CHUNK = 1200, 5, 256
NO_BACKOFF = RetryPolicy(base_delay_s=0.0, max_delay_s=0.0, jitter=0.0)


def _materialize(src, n):
    xs, ys = zip(*src.chunks(n))
    return np.concatenate(xs), np.concatenate(ys)


def _fresh_source():
    return SyntheticSource(N, F, seed=7)


def _assert_trees_equal(a, b, *, leaf_rtol=None):
    """Bit-equal forests; with ``leaf_rtol`` the structure stays
    bit-strict but leaf values compare to float tolerance (the
    checkpoint-restore path recomputes margins by streamed inference)."""
    for field, u, v in zip(a.trees._fields, a.trees, b.trees):
        u, v = np.asarray(u), np.asarray(v)
        if field == "leaf_value" and leaf_rtol is not None:
            np.testing.assert_allclose(u, v, rtol=leaf_rtol, atol=1e-6,
                                       err_msg=field)
        else:
            np.testing.assert_array_equal(u, v, err_msg=field)


@pytest.fixture(scope="module")
def base():
    """Fault-free reference fit (shared: every chaos run compares to it)."""
    src = _fresh_source()
    X, y = _materialize(src, N)
    binner = StreamingBinner(max_bins=32, sketch_size=4096).fit(X)
    cfg = GBDTConfig(n_trees=6, max_depth=3, learning_rate=0.3,
                     objective="reg:squarederror")
    res = train_streaming(cfg, src, binner, y, chunk_rows=CHUNK)
    return {"X": X, "y": y, "binner": binner, "cfg": cfg, "res": res}


# --------------------------------------------------------------------------
# streaming training under injected faults
# --------------------------------------------------------------------------
def test_seeded_io_errors_absorbed_bit_equal(base):
    """A seeded storm of transient read errors, fully absorbed by
    RetryingSource: the trainer never notices, the model is bit-equal."""
    sched = seeded_schedule(123, "source", 120, rate=0.15)
    assert sched.pending() > 0
    flaky = RetryingSource(FaultySource(_fresh_source(), sched), NO_BACKOFF)
    res = train_streaming(base["cfg"], flaky, base["binner"], base["y"],
                          chunk_rows=CHUNK)
    assert flaky.stats["retries"] > 0          # the storm actually hit
    assert all(kind == "error" for _, _, kind in sched.fired)
    assert res.stats["recoveries"] == 0        # absorbed below the trainer
    _assert_trees_equal(res.model, base["res"].model)
    np.testing.assert_array_equal(res.history["train_loss"],
                                  base["res"].history["train_loss"])


def test_oom_degrades_chunk_and_preserves_model(base):
    """A device OOM mid-round halves chunk_rows and retries the round;
    chunk-size-invariant accumulation keeps the model bit-equal."""
    sched = FaultSchedule().add("source", 7, exc=DeviceOOMError)
    faulty = FaultySource(_fresh_source(), sched)
    res = train_streaming(base["cfg"], faulty, base["binner"], base["y"],
                          chunk_rows=CHUNK,
                          recovery=RecoveryPolicy(min_chunk_rows=64))
    assert res.stats["oom_halvings"] == 1
    assert res.stats["chunk_rows"] == CHUNK // 2
    assert sched.fired == [("source", 7, "error")]
    _assert_trees_equal(res.model, base["res"].model)
    np.testing.assert_array_equal(res.history["train_loss"],
                                  base["res"].history["train_loss"])


def test_oom_budget_exhaustion_propagates(base):
    """min_chunk_rows == chunk_rows leaves no room to degrade: the OOM
    must propagate instead of looping."""
    sched = FaultSchedule().add("source", 3, exc=DeviceOOMError)
    faulty = FaultySource(_fresh_source(), sched)
    with pytest.raises(DeviceOOMError):
        train_streaming(base["cfg"], faulty, base["binner"], base["y"],
                        chunk_rows=CHUNK,
                        recovery=RecoveryPolicy(min_chunk_rows=CHUNK))


def test_midround_preemption_replays_in_memory(base):
    """No checkpoint_dir: a transient failure mid-round replays the round
    from the end-of-previous-round in-memory state, bit-equal (rounds
    commit atomically; the round RNG is keyed by (seed, round))."""
    sched = FaultSchedule().add("source", 50, exc=Preemption)
    faulty = FaultySource(_fresh_source(), sched)
    res = train_streaming(base["cfg"], faulty, base["binner"], base["y"],
                          chunk_rows=CHUNK, recovery=RecoveryPolicy())
    assert res.stats["recoveries"] == 1
    assert res.stats["replayed_rounds"] == 0   # in-memory, no restore
    _assert_trees_equal(res.model, base["res"].model)
    np.testing.assert_array_equal(res.history["train_loss"],
                                  base["res"].history["train_loss"])


def test_recovery_budget_exhaustion_propagates(base):
    sched = (FaultSchedule()
             .add("source", 30, exc=Preemption)
             .add("source", 45, exc=Preemption))   # fires during the replay
    faulty = FaultySource(_fresh_source(), sched)
    with pytest.raises(Preemption):
        train_streaming(base["cfg"], faulty, base["binner"], base["y"],
                        chunk_rows=CHUNK,
                        recovery=RecoveryPolicy(max_recoveries=1))


def test_preemption_restores_from_checkpoint(base, tmp_path):
    """With checkpoint_dir set, a late preemption restores the newest
    save_named bundle and replays only the lost rounds: tree structure is
    bit-equal; leaf values match to float tolerance (restored margins are
    recomputed via streamed inference)."""
    sched = FaultSchedule().add("source", 100, exc=Preemption)  # round 5
    faulty = FaultySource(_fresh_source(), sched)
    res = train_streaming(
        base["cfg"], faulty, base["binner"], base["y"], chunk_rows=CHUNK,
        recovery=RecoveryPolicy(checkpoint_dir=str(tmp_path),
                                checkpoint_every=2))
    assert res.stats["recoveries"] == 1
    assert res.stats["replayed_rounds"] == 1   # restored round 4, lost 5
    assert res.model.n_trees == base["res"].model.n_trees
    _assert_trees_equal(res.model, base["res"].model, leaf_rtol=1e-5)


def test_combined_chaos_matches_fault_free(base):
    """The acceptance scenario: seeded IO errors + one device OOM + one
    mid-round preemption in a single fit — every recovery layer fires,
    and the final model is bit-equal to the fault-free run."""
    io_sched = seeded_schedule(5, "source", 120, rate=0.1)
    io_sched.add("source", 33, exc=DeviceOOMError)       # not retryable
    inner = RetryingSource(FaultySource(_fresh_source(), io_sched),
                           NO_BACKOFF)
    preempt = FaultSchedule().add("source", 70, exc=Preemption)
    outer = FaultySource(inner, preempt)    # above the retry wrapper: the
    res = train_streaming(                  # trainer must handle this one
        base["cfg"], outer, base["binner"], base["y"], chunk_rows=CHUNK,
        recovery=RecoveryPolicy(min_chunk_rows=64, max_recoveries=2))
    assert inner.stats["retries"] > 0                    # IO storm absorbed
    assert res.stats["oom_halvings"] == 1                # chunk degraded
    assert res.stats["recoveries"] == 1                  # round replayed
    assert ("source", 70, "error") in preempt.fired
    _assert_trees_equal(res.model, base["res"].model)
    np.testing.assert_array_equal(res.history["train_loss"],
                                  base["res"].history["train_loss"])


def test_estimator_recovery_end_to_end():
    """The same invariant through the public estimator surface:
    fit(data=RetryingSource(...), recovery=...) under seeded faults
    predicts identically to the fault-free fit."""
    src = SyntheticSource(1500, 6, seed=9)
    X, _ = _materialize(src, 1500)
    plan = ExecutionPlan(chunk_bytes=12_000)
    kw = dict(n_trees=5, max_depth=3, learning_rate=0.3, max_bins=32)
    clean = BoosterRegressor(**kw).fit(data=src, plan=plan)
    sched = seeded_schedule(11, "source", 200, rate=0.1)
    flaky = RetryingSource(
        FaultySource(SyntheticSource(1500, 6, seed=9), sched), NO_BACKOFF)
    rec = BoosterRegressor(**kw).fit(data=flaky, plan=plan,
                                     recovery=RecoveryPolicy())
    assert flaky.stats["retries"] > 0
    np.testing.assert_array_equal(np.asarray(clean.predict(X)),
                                  np.asarray(rec.predict(X)))


# --------------------------------------------------------------------------
# RetryingSource unit behavior
# --------------------------------------------------------------------------
def test_retry_budget_exhaustion_raises():
    sched = FaultSchedule()
    for step in range(3):                       # 3 consecutive failures
        sched.add("source", step, exc=TransientIOError)
    src = RetryingSource(
        FaultySource(SyntheticSource(400, 3, seed=1), sched),
        RetryPolicy(max_retries=2, base_delay_s=0.0, jitter=0.0))
    with pytest.raises(TransientIOError):
        list(src.chunks(200))
    assert src.stats["retries"] == 2


def test_corruption_is_never_retried():
    sched = FaultSchedule().add("source", 1, exc=ShardCorruptionError)
    src = RetryingSource(
        FaultySource(SyntheticSource(400, 3, seed=1), sched), NO_BACKOFF)
    with pytest.raises(ShardCorruptionError):
        list(src.chunks(200))
    assert src.stats["retries"] == 0


def test_hung_read_times_out_and_retries():
    """A latency spike past chunk_timeout_s surfaces as a (transient)
    ChunkTimeoutError; the pass re-opens and the stream stays identical."""
    plain = np.concatenate(
        [x for x, _ in SyntheticSource(400, 3, seed=1).chunks(100)])
    sched = FaultSchedule().add("source", 0, kind="latency", delay_s=0.6)
    src = RetryingSource(
        FaultySource(SyntheticSource(400, 3, seed=1), sched),
        RetryPolicy(chunk_timeout_s=0.1, base_delay_s=0.0, jitter=0.0))
    got = np.concatenate([x for x, _ in src.chunks(100)])
    assert src.stats["timeouts"] == 1 and src.stats["retries"] == 1
    np.testing.assert_array_equal(got, plain)


def test_seeded_schedule_is_deterministic():
    a = seeded_schedule(42, "source", 100, rate=0.2, latency_rate=0.1)
    b = seeded_schedule(42, "source", 100, rate=0.2, latency_rate=0.1)
    assert a.pending() == b.pending() > 0
    c = seeded_schedule(43, "source", 100, rate=0.2, latency_rate=0.1)
    assert {k for k in a._pending} != {k for k in c._pending}


# --------------------------------------------------------------------------
# shard corruption: crc32 manifests
# --------------------------------------------------------------------------
def test_corrupt_shard_detected_on_read(tmp_path):
    paths = write_npz_shards(str(tmp_path), SyntheticSource(600, 4, seed=3),
                             rows_per_shard=200)
    assert os.path.exists(tmp_path / "manifest.json")
    corrupt_file(paths[1], seed=0)             # flip bytes mid-directory
    src = NpzShardSource(str(tmp_path))        # shard 0 verifies fine
    with pytest.raises(ShardCorruptionError, match="crc32"):
        list(src.chunks(250))


def test_corrupt_first_shard_detected_at_open(tmp_path):
    paths = write_npz_shards(str(tmp_path), SyntheticSource(300, 4, seed=3),
                             rows_per_shard=200)
    corrupt_file(paths[0], seed=1)
    with pytest.raises(ShardCorruptionError, match="crc32"):
        NpzShardSource(str(tmp_path))


def test_corrupt_binned_shard_detected(tmp_path):
    src = SyntheticSource(500, 4, seed=5)
    X, _ = _materialize(src, 500)
    binner = StreamingBinner(max_bins=16, sketch_size=1024).fit(X)
    paths = write_binned_shards(str(tmp_path), src, binner,
                                rows_per_shard=200)
    corrupt_file(paths[-1], seed=2)
    with pytest.raises(ShardCorruptionError, match="crc32"):
        list(BinnedShardSource(str(tmp_path)).chunks(128))


def test_unmanifested_directory_still_loads(tmp_path):
    """Back-compat: shard directories that predate checksumming (or had
    the manifest deleted) load without verification."""
    write_npz_shards(str(tmp_path), SyntheticSource(300, 4, seed=3),
                     rows_per_shard=200)
    plain = np.concatenate(
        [x for x, _ in NpzShardSource(str(tmp_path)).chunks(100)])
    os.remove(tmp_path / "manifest.json")
    back = NpzShardSource(str(tmp_path))
    assert back.manifest is None
    got = np.concatenate([x for x, _ in back.chunks(100)])
    np.testing.assert_array_equal(got, plain)


def test_foreign_shard_rejected_by_manifest(tmp_path):
    """A file that appeared after export is not silently mixed into the
    dataset — the manifest is the directory's source of truth."""
    write_npz_shards(str(tmp_path), SyntheticSource(300, 4, seed=3),
                     rows_per_shard=200)
    np.savez(tmp_path / "zz_foreign.npz", X=np.zeros((4, 4), np.float32))
    with pytest.raises(ShardCorruptionError, match="manifest"):
        list(NpzShardSource(str(tmp_path)).chunks(100))


# --------------------------------------------------------------------------
# checkpoint torn-step fallback (satellite)
# --------------------------------------------------------------------------
def test_restore_named_falls_back_past_torn_step(tmp_path):
    """A step whose payload passes sha validation but cannot be loaded
    (torn write where the manifest was re-stamped) warns and falls back
    to the next-newest valid step instead of crashing the restore."""
    ckpt.save_named(str(tmp_path), {"a": np.arange(3)}, 1)
    ckpt.save_named(str(tmp_path), {"a": np.arange(5)}, 2)
    payload_path = tmp_path / "step_2" / "arrays.npz"
    torn = payload_path.read_bytes()[:20]       # truncated npz: unloadable
    payload_path.write_bytes(torn)
    manifest_path = tmp_path / "step_2" / "manifest.json"
    manifest = json.loads(manifest_path.read_text())
    manifest["sha256"] = hashlib.sha256(torn).hexdigest()
    manifest_path.write_text(json.dumps(manifest))
    with pytest.warns(RuntimeWarning, match="step_2"):
        arrays, step, _ = ckpt.restore_named(str(tmp_path))
    assert step == 1
    np.testing.assert_array_equal(arrays["a"], np.arange(3))


def test_restore_named_ignores_partial_dirs(tmp_path):
    """Crash debris — a stray ``step_N.tmp`` from an interrupted write,
    or a step directory with no payload — is skipped, not fatal."""
    ckpt.save_named(str(tmp_path), {"a": np.arange(2)}, 1)
    os.makedirs(tmp_path / "step_9.tmp")        # two-phase write, torn
    os.makedirs(tmp_path / "step_3")            # dir exists, no payload
    arrays, step, _ = ckpt.restore_named(str(tmp_path))
    assert step == 1
    np.testing.assert_array_equal(arrays["a"], np.arange(2))


# --------------------------------------------------------------------------
# estimator fit input validation (satellite)
# --------------------------------------------------------------------------
def _xy(n=32, f=3):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, f)).astype(np.float32),
            rng.normal(size=n).astype(np.float32))


def test_fit_rejects_nan_labels():
    X, y = _xy()
    y[5] = np.nan
    with pytest.raises(ValueError, match="non-finite.*row 5"):
        BoosterRegressor(n_trees=1).fit(X, y)


def test_fit_rejects_inf_labels():
    X, y = _xy()
    y[-1] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        BoosterRegressor(n_trees=1).fit(X, y)


def test_fit_rejects_mismatched_lengths():
    X, y = _xy()
    with pytest.raises(ValueError, match="row-for-row"):
        BoosterRegressor(n_trees=1).fit(X, y[:-3])


def test_fit_rejects_empty_dataset():
    with pytest.raises(ValueError, match="empty dataset"):
        BoosterRegressor(n_trees=1).fit(np.zeros((0, 3), np.float32),
                                        np.zeros(0, np.float32))


def test_fit_rejects_non_2d_features():
    with pytest.raises(ValueError, match="2-D"):
        BoosterRegressor(n_trees=1).fit(np.zeros(8, np.float32),
                                        np.zeros(8, np.float32))


def test_fit_validates_eval_set():
    X, y = _xy()
    X_val, y_val = _xy(8)
    y_val[0] = np.nan
    with pytest.raises(ValueError, match="eval_set"):
        BoosterRegressor(n_trees=1).fit(X, y, eval_set=(X_val, y_val))


def test_fit_validates_streamed_labels():
    from repro.api import ArraySource
    X, y = _xy(200)
    y[77] = np.nan
    with pytest.raises(ValueError, match="streamed labels"):
        BoosterRegressor(n_trees=1).fit(
            data=ArraySource(X, y), plan=ExecutionPlan(chunk_bytes=2_000))


def test_recovery_accepted_on_every_fit_path():
    """PR 10: recovery= is no longer streaming-only — the in-memory fit
    arms the divergence sentinels (and the mesh path the full distributed
    recovery ladder) instead of rejecting the policy."""
    X, y = _xy(64)
    est = BoosterRegressor(n_trees=2, max_depth=2).fit(
        X, y, recovery=RecoveryPolicy())
    assert est.n_trees_ == 2


def test_recovery_policy_validates():
    with pytest.raises(ValueError, match="checkpoint_every"):
        RecoveryPolicy(checkpoint_every=0)
    with pytest.raises(ValueError, match="budgets"):
        RecoveryPolicy(max_recoveries=-1)
    with pytest.raises(ValueError, match="min_chunk_rows"):
        RecoveryPolicy(min_chunk_rows=0)


# --------------------------------------------------------------------------
# PR 10 — numerical divergence sentinels
# --------------------------------------------------------------------------
def test_divergence_sentinel_raises_typed():
    """An absurd learning rate overflows squared-error margins to inf in
    the first round; with a recovery policy armed the host loop raises the
    TYPED error (with the round index) instead of silently boosting NaNs."""
    X, y = _xy(64)
    with pytest.raises(NumericalDivergenceError) as ei:
        BoosterRegressor(n_trees=3, max_depth=2, learning_rate=1e20).fit(
            X, y, recovery=RecoveryPolicy(max_divergence_rollbacks=0))
    assert ei.value.round_index >= 0


def test_divergence_fused_rollback_budget_exhausts():
    """The depthwise round loop rolls back and halves the LR on a
    divergence trip; a persistently-diverging config exhausts
    max_divergence_rollbacks and the typed error propagates (never an
    unbounded retry loop)."""
    X, y = _xy(64)
    with pytest.raises(NumericalDivergenceError):
        BoosterRegressor(n_trees=4, max_depth=2, learning_rate=1e30,
                         log_every=1).fit(
            X, y, recovery=RecoveryPolicy(max_divergence_rollbacks=2))


def test_divergence_without_recovery_is_legacy_silent():
    """No recovery policy → the sentinel stays unarmed and legacy behavior
    (a NaN-loss model, caller's responsibility) is preserved."""
    X, y = _xy(64)
    est = BoosterRegressor(n_trees=2, max_depth=2, learning_rate=1e20).fit(
        X, y)
    assert not np.isfinite(est.history_["train_loss"][-1])


# --------------------------------------------------------------------------
# PR 10 — graceful shutdown: typed resumable interrupts, resume equality
# --------------------------------------------------------------------------
def test_shutdown_interrupts_host_and_fused_and_resumes_bit_equal(tmp_path):
    """sd.request() after round 2 interrupts BOTH single-process round
    loops (lossguide's host loop and the depthwise one-program round)
    after the commit; the partial model stays fitted state and a resume
    from the checkpoint lands on the bit-identical final ensemble."""
    X, y = _xy(256)
    for i, policy in enumerate(("lossguide", "depthwise")):
        kw = dict(n_trees=6, max_depth=3, max_bins=32, seed=3,
                  grow_policy=policy)
        gold = BoosterRegressor(**kw).fit(X, y)
        ckdir = str(tmp_path / f"ck{i}")
        est = BoosterRegressor(**kw)
        sd = GracefulShutdown()

        def cb(t_idx, model):
            if t_idx == 2:
                sd.request("SIGTERM")

        with pytest.raises(TrainingInterrupted) as ei:
            est.fit(X, y, checkpoint_dir=ckdir, checkpoint_every=2,
                    callback=cb, shutdown=sd)
        assert ei.value.rounds_done == 3
        assert ei.value.result.stats["interrupted"]
        assert est.is_fitted and est.n_trees_ == 3   # partial model kept
        res = BoosterRegressor(**kw).fit(X, y, checkpoint_dir=ckdir)
        _assert_trees_equal(res.model_, gold.model_)


def test_streaming_sigterm_delivers_typed_interrupt(base, tmp_path):
    """A REAL SIGTERM (os.kill) mid-streaming-fit: the handler finishes
    the in-flight round, commits a checkpoint, and raises the typed
    resumable error naming the signal."""
    sd = GracefulShutdown()

    def cb(t_idx, model):
        if t_idx == 2:
            os.kill(os.getpid(), signal.SIGTERM)

    with sd:
        with pytest.raises(TrainingInterrupted) as ei:
            train_streaming(
                base["cfg"], _fresh_source(), base["binner"], base["y"],
                chunk_rows=CHUNK, callback=cb, shutdown=sd,
                recovery=RecoveryPolicy(checkpoint_dir=str(tmp_path),
                                        checkpoint_every=2))
    stop = ei.value
    assert stop.signal_name == "SIGTERM"
    assert stop.rounds_done == 3
    assert stop.checkpoint_dir == str(tmp_path)
    from repro.api import serialize
    assert serialize.has_checkpoint(str(tmp_path))


def test_streaming_sigterm_resume_bit_equal(tmp_path):
    """Acceptance: SIGTERM mid-fit + resume == uninterrupted fit, bit-for-
    bit, through the public streaming estimator surface."""
    from repro.api import ArraySource
    src = SyntheticSource(1500, 6, seed=9)
    X, y = _materialize(src, 1500)
    plan = ExecutionPlan(chunk_bytes=12_000)
    kw = dict(n_trees=6, max_depth=3, learning_rate=0.3, max_bins=32)
    gold = BoosterRegressor(**kw).fit(data=ArraySource(X, y), plan=plan)
    ckdir = str(tmp_path / "ck")
    est = BoosterRegressor(**kw)
    sd = GracefulShutdown()

    def cb(t_idx, model):
        if t_idx == 2:
            os.kill(os.getpid(), signal.SIGTERM)

    with sd:
        with pytest.raises(TrainingInterrupted):
            est.fit(data=ArraySource(X, y), plan=plan, checkpoint_dir=ckdir,
                    checkpoint_every=2, callback=cb,
                    recovery=RecoveryPolicy(), shutdown=sd)
    assert est.n_trees_ == 3
    res = BoosterRegressor(**kw).fit(data=ArraySource(X, y), plan=plan,
                                     checkpoint_dir=ckdir)
    _assert_trees_equal(res.model_, gold.model_)
    np.testing.assert_array_equal(np.asarray(res.predict(X)),
                                  np.asarray(gold.predict(X)))


def test_graceful_shutdown_restores_prior_handlers():
    before = signal.getsignal(signal.SIGTERM)
    with GracefulShutdown() as sd:
        assert signal.getsignal(signal.SIGTERM) is not before
        assert not sd.requested
    assert signal.getsignal(signal.SIGTERM) is before


# --------------------------------------------------------------------------
# kernel failures raise; interpret mode never runs on a TPU
# --------------------------------------------------------------------------
def test_explicit_pallas_kernel_failure_raises(monkeypatch):
    """A Pallas histogram kernel that fails makes the fit fail: nothing
    swaps in a jnp strategy behind the plan."""
    from repro.kernels import histogram as hist_k

    def boom(*a, **k):
        raise RuntimeError("injected kernel launch failure")

    monkeypatch.setattr(hist_k, "histogram_pallas", boom)
    X, y = _xy(203, 5)      # a shape no other test traces with this plan
    with pytest.raises(RuntimeError, match="injected kernel launch"):
        BoosterRegressor(n_trees=2, max_depth=3, max_bins=32).fit(
            X, y, plan=ExecutionPlan(hist_strategy="pallas_grouped",
                                     interpret=True))


def test_interpret_plan_refused_on_tpu(monkeypatch):
    from repro.api import plan as plan_mod
    monkeypatch.setattr(plan_mod, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="interpret=True is refused"):
        ExecutionPlan(interpret=True).resolved()
    with pytest.raises(ValueError, match="interpret=True is refused"):
        ExecutionPlan.auto(hist_strategy="pallas_grouped", interpret=True)


def test_auto_plan_on_tpu_elects_compiled_kernels(monkeypatch):
    """On a TPU every "auto" step resolves to its Pallas kernel, compiled,
    with no availability probe in between."""
    from repro.api import plan as plan_mod
    monkeypatch.setattr(plan_mod, "_on_tpu", lambda: True)
    plan = ExecutionPlan.auto()
    assert (plan.hist_strategy, plan.partition_strategy,
            plan.traversal_strategy, plan.interpret) == (
        "pallas_grouped", "pallas", "pallas", False)


# --------------------------------------------------------------------------
# PR 10 — RetryingSource lifecycle
# --------------------------------------------------------------------------
def test_retrying_source_close_is_idempotent():
    src = RetryingSource(SyntheticSource(400, 3, seed=1), NO_BACKOFF)
    list(src.chunks(200))
    src.close()
    src.close()                                    # second close: no-op
    with RetryingSource(SyntheticSource(400, 3, seed=1), NO_BACKOFF) as s2:
        assert len(list(s2.chunks(200))) == 2
    assert s2._closed


def test_train_streaming_closes_source_on_every_exit(base):
    """Both the success and the failure exit path of train_streaming
    release the RetryingSource watchdog."""
    ok = RetryingSource(FaultySource(_fresh_source(), FaultSchedule()),
                        NO_BACKOFF)
    train_streaming(base["cfg"], ok, base["binner"], base["y"],
                    chunk_rows=CHUNK)
    assert ok._closed
    sched = FaultSchedule().add("source", 3, exc=DeviceOOMError)
    bad = RetryingSource(FaultySource(_fresh_source(), sched), NO_BACKOFF)
    with pytest.raises(DeviceOOMError):
        train_streaming(base["cfg"], bad, base["binner"], base["y"],
                        chunk_rows=CHUNK,
                        recovery=RecoveryPolicy(min_chunk_rows=CHUNK))
    assert bad._closed


# --------------------------------------------------------------------------
# PR 10 — deprecated distributed.fault shim
# --------------------------------------------------------------------------
def test_distributed_fault_shim_warns_once_per_access():
    from repro.distributed import fault as dfault
    from repro.resilience import faults as rfaults
    with pytest.warns(DeprecationWarning, match="resilience.faults"):
        assert dfault.FaultInjector is rfaults.FaultInjector
    with pytest.warns(DeprecationWarning):
        assert dfault.FaultSchedule is rfaults.FaultSchedule
    # the names that genuinely live there import warning-free
    assert dfault.StepJournal is not None
    with pytest.raises(AttributeError):
        dfault.NoSuchThing


# --------------------------------------------------------------------------
# PR 10 — distributed chaos matrix (in-process, D=1)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dist_base():
    """Fault-free distributed reference fit on the in-process device set
    (D=1 under plain pytest; the D∈{2,8} points run in subprocesses)."""
    import jax
    from repro.core import bin_dataset
    from repro.distributed.trainer import (data_parallel_mesh,
                                           train_distributed)
    rng = np.random.default_rng(2)
    X = rng.normal(size=(1024, 5))
    y = (rng.integers(-8, 9, 1024) * 0.25).astype(np.float32)
    data = bin_dataset(X, max_bins=32)
    cfg = GBDTConfig(n_trees=6, max_depth=3, subsample=0.8, seed=11,
                     hist_strategy="scatter")
    mesh = data_parallel_mesh(jax.devices())
    gold = train_distributed(cfg, data, y, mesh=mesh)
    return dict(data=data, y=y, cfg=cfg, mesh=mesh, gold=gold)


def _dist_run(dist_base, sched, *, dist_kw=None, recovery=None):
    from repro.distributed.trainer import (DistributedConfig,
                                           train_distributed)
    dist = DistributedConfig(fault_schedule=sched, **(dist_kw or {}))
    return train_distributed(dist_base["cfg"], dist_base["data"],
                             dist_base["y"], mesh=dist_base["mesh"],
                             dist=dist,
                             recovery=recovery or RecoveryPolicy())


def test_distributed_transient_retried_bit_equal(dist_base):
    """A transient IO error post-dispatch is retried on the SAME mesh
    (the round never committed) — no remesh, bit-equal trajectory."""
    sched = FaultSchedule().add("round", 2, exc=TransientIOError)
    res = _dist_run(dist_base, sched)
    assert res.stats["recoveries"] == 1
    assert res.stats["restarts"] == 0
    assert not sched.pending()
    _assert_trees_equal(res.model, dist_base["gold"].model)


def test_distributed_oom_subbatches_bit_equal(dist_base):
    """Device OOM doubles hist_slices (sub-batched accumulation) and
    retries; zero-stat padding keeps histograms — and therefore the whole
    model — bit-equal to the monolithic path."""
    sched = FaultSchedule().add("round", 3, exc=DeviceOOMError)
    res = _dist_run(dist_base, sched)
    assert res.stats["oom_halvings"] == 1
    assert res.stats["hist_slices"] == 2
    _assert_trees_equal(res.model, dist_base["gold"].model)


def test_distributed_injected_nan_round_replays_bit_equal(dist_base):
    """A divergence trip rolls the round back; the first replay runs at
    the SAME learning rate, so a one-shot NaN round replays bit-equal."""
    sched = FaultSchedule().add("round", 4, exc=NumericalDivergenceError)
    res = _dist_run(dist_base, sched)
    assert res.stats["divergence_rollbacks"] == 1
    _assert_trees_equal(res.model, dist_base["gold"].model)


def test_distributed_divergence_budget_exhausts(dist_base):
    sched = FaultSchedule().add("round", 2, exc=NumericalDivergenceError)
    with pytest.raises(NumericalDivergenceError):
        _dist_run(dist_base, sched,
                  recovery=RecoveryPolicy(max_divergence_rollbacks=0))


def test_distributed_preemption_restores_and_replays(dist_base, tmp_path):
    """Preemption re-meshes onto the survivors (the sole in-process device
    keeps itself), restores the newest named checkpoint, and replays —
    structure bit-equal, leaves to float tolerance."""
    sched = FaultSchedule().add("elastic", 4, exc=Preemption)
    res = _dist_run(dist_base, sched,
                    dist_kw=dict(checkpoint_dir=str(tmp_path),
                                 checkpoint_every=2),
                    recovery=RecoveryPolicy(checkpoint_dir=str(tmp_path),
                                            checkpoint_every=2))
    assert res.stats["restarts"] == 1
    assert res.stats["n_shards"] == 1
    assert res.model.n_trees == dist_base["cfg"].n_trees
    _assert_trees_equal(res.model, dist_base["gold"].model, leaf_rtol=1e-5)


def test_distributed_shutdown_interrupts_after_commit(dist_base):
    sd = GracefulShutdown()

    def cb(t_idx, model):
        if t_idx == 2:
            sd.request("SIGTERM")

    from repro.distributed.trainer import train_distributed
    with pytest.raises(TrainingInterrupted) as ei:
        train_distributed(dist_base["cfg"], dist_base["data"],
                          dist_base["y"], mesh=dist_base["mesh"],
                          callback=cb, shutdown=sd)
    assert ei.value.rounds_done == 3
    assert ei.value.result.stats["interrupted"]
    assert ei.value.result.stats["distributed"]


# --------------------------------------------------------------------------
# PR 10 — distributed chaos matrix (subprocess, D ∈ {2, 8})
# --------------------------------------------------------------------------
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_with_devices(code: str, n_devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=_ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


_STORM_CHILD = r"""
import numpy as np, jax, tempfile
from repro.core import GBDTConfig, bin_dataset
from repro.distributed.trainer import (DistributedConfig, data_parallel_mesh,
                                       train_distributed)
from repro.resilience import (DeviceOOMError, FaultSchedule,
                              NumericalDivergenceError, Preemption,
                              RecoveryPolicy, TransientIOError)

rng = np.random.default_rng(0)
n, F = 4096, 6
X = rng.normal(size=(n, F))
y = (rng.integers(-8, 9, n) * 0.25).astype(np.float32)
data = bin_dataset(X, max_bins=32)
cfg = GBDTConfig(n_trees=8, max_depth=3, subsample=0.8, seed=11,
                 hist_strategy="scatter")
mesh = data_parallel_mesh(jax.devices())
gold = train_distributed(cfg, data, y, mesh=mesh)

# the acceptance storm: IO + OOM + one injected NaN round + a preemption
sched = (FaultSchedule()
         .add("round", 2, exc=TransientIOError)
         .add("round", 3, exc=DeviceOOMError)
         .add("round", 4, exc=NumericalDivergenceError)
         .add("elastic", 6, exc=Preemption))
with tempfile.TemporaryDirectory() as d:
    res = train_distributed(
        cfg, data, y, mesh=mesh,
        dist=DistributedConfig(checkpoint_dir=d, checkpoint_every=1,
                               fault_schedule=sched),
        recovery=RecoveryPolicy(checkpoint_dir=d, checkpoint_every=1))
st = res.stats
assert st["recoveries"] == 1, st
assert st["oom_halvings"] == 1 and st["hist_slices"] == 2, st
assert st["divergence_rollbacks"] == 1, st
assert st["restarts"] == 1, st
assert not sched.pending()
assert res.model.n_trees == cfg.n_trees
for nm in ("feature", "threshold", "is_cat", "default_left"):
    np.testing.assert_array_equal(
        np.asarray(getattr(res.model.trees, nm)),
        np.asarray(getattr(gold.model.trees, nm)), err_msg=nm)
np.testing.assert_allclose(np.asarray(res.model.trees.leaf_value),
                           np.asarray(gold.model.trees.leaf_value),
                           rtol=1e-5, atol=1e-6)
print("DIST_STORM_OK", st["n_shards"])
"""


@pytest.mark.slow
@pytest.mark.parametrize("n_devices", [2, 8])
def test_distributed_chaos_storm_matrix(n_devices):
    """Acceptance: a seeded storm (transient IO + device OOM + one NaN
    round + a worker preemption) at D shards produces a model bit-equal
    in structure and rtol=1e-5 in leaves to the fault-free run, with
    every recovery reported in stats."""
    out = _run_with_devices(_STORM_CHILD, n_devices)
    assert f"DIST_STORM_OK {n_devices - 1}" in out   # preemption: D-1 left


_SIGTERM_CHILD = r"""
import os, signal, tempfile
import numpy as np, jax
from repro.api import (BoosterRegressor, GracefulShutdown, RecoveryPolicy,
                       TrainingInterrupted, data_parallel_mesh)

rng = np.random.default_rng(1)
X = rng.normal(size=(2048, 6))
y = rng.normal(size=2048).astype(np.float32)
mesh = data_parallel_mesh(jax.devices())
kw = dict(n_trees=8, max_depth=3, max_bins=32, seed=4)
gold = BoosterRegressor(**kw).fit(X, y, mesh=mesh)

with tempfile.TemporaryDirectory() as d:
    est = BoosterRegressor(**kw)

    def cb(t_idx, model):
        if t_idx == 3:
            os.kill(os.getpid(), signal.SIGTERM)    # real delivery

    try:
        with GracefulShutdown() as sd:
            est.fit(X, y, mesh=mesh, checkpoint_dir=d, checkpoint_every=2,
                    callback=cb, recovery=RecoveryPolicy(), shutdown=sd)
        raise AssertionError("fit survived SIGTERM")
    except TrainingInterrupted as stop:
        assert stop.signal_name == "SIGTERM", stop.signal_name
        assert stop.rounds_done == 4, stop.rounds_done
        assert est.n_trees_ == 4
    # recovery= exposes the trainer's named round checkpoint, whose EXACT
    # live margins make the D>1 resume bit-equal (a host-side margin
    # replay can differ from the fused sharded step in the last ulp)
    res = BoosterRegressor(**kw).fit(X, y, mesh=mesh, checkpoint_dir=d,
                                     recovery=RecoveryPolicy())

for a, b in zip(res.model_.trees, gold.model_.trees):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("DIST_SIGTERM_RESUME_OK")
"""


@pytest.mark.slow
def test_distributed_sigterm_resume_bit_equal():
    """Acceptance: SIGTERM mid-distributed-fit commits the in-flight
    round; resuming from the checkpoint yields the bit-identical final
    ensemble."""
    out = _run_with_devices(_SIGTERM_CHILD, 2)
    assert "DIST_SIGTERM_RESUME_OK" in out
