"""Fig 7 analog — training speedups across machines and strategies.

Two complementary reproductions:
  (a) *measured*: wall-time of the software histogram strategies on this
      host (scatter = multicore-style RMW, privatized replicas = GPU
      shared-memory style, sort+segment-sum = GPU-alternative, blocked
      one-hot einsum = the Booster kernel's XLA twin);
  (b) *modeled*: the paper's ideal-machine model (see benchmarks.common)
      evaluated per dataset: Ideal-32-core, Ideal-GPU (2x parallelism),
      Inter-record (histogram replicas eat on-chip capacity), Booster
      (3200-way, memory-bound).  Expected structure: GPU ≈ 1.6–1.9x,
      Booster ~5–30x, larger datasets -> larger speedups.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (BOOSTER, IDEAL_CPU, IDEAL_GPU, csv_row,
                               host_step2_time, machine_step1_time,
                               machine_step3_time, machine_step5_time,
                               strategy_plans, time_call)
from repro.api import ExecutionPlan
from repro.core import GBDTConfig, bin_dataset, train
from repro.data import make_tabular, paper_dataset
from repro.distributed.trainer import data_parallel_mesh, train_distributed
from repro.kernels import ops
from repro.resilience import RecoveryPolicy

STRATS = ("scatter", "scatter_private", "sort", "onehot")


def modeled_training_time(machine, n, F, depth=6, n_trees=1,
                          column_major=None, frac_active=1.0,
                          n_bins=256):
    """Per-tree time under the paper's machine model.  ``column_major``
    defaults to Booster-only (its redundant representation).  Step ② runs
    on the host for EVERY machine (§IV adds it to all systems) — it is the
    Amdahl residual that caps speedups on small datasets (Mq2008)."""
    if column_major is None:
        column_major = machine["name"] == "booster"
    t = 0.0
    for level in range(depth):
        active = n * (frac_active ** level)
        t += machine_step1_time(machine, active, F)
        t += machine_step3_time(machine, active, F, column_major)
        t += host_step2_time(2 ** level, F, n_bins)
    t += machine_step5_time(machine, n, F, depth, min(2 ** depth - 1, F),
                            column_major)
    return t * n_trees


def run_e2e(scale: float = 1.0, depth: int = 6, n_trees: int = 5):
    """End-to-end depth-6 training rows/sec: the pre-PR path (direct
    histograms, host-driven loop) vs hist-subtraction + fused rounds —
    the acceptance comparison for the device-resident trainer (subtraction
    halves step-① work at levels > 0, fused rounds drop the per-round
    host syncs)."""
    n = max(4000, int(40000 * scale))
    X, y, cats = make_tabular(n, 20, 4, n_cats=10, task="regression",
                              seed=0)
    data = bin_dataset(X, max_bins=64, categorical_fields=cats)
    rows = []
    rps = {}
    lanes = {
        "direct": (ExecutionPlan(hist_strategy="scatter").resolved(), False),
        "subfused": (ExecutionPlan(hist_strategy="scatter",
                                   hist_subtraction=True).resolved(), True),
    }
    for name, (plan, fused) in lanes.items():
        cfg = GBDTConfig(n_trees=n_trees, max_depth=depth,
                         learning_rate=0.3, fused_rounds=fused)
        t = time_call(lambda cfg=cfg, plan=plan: train(cfg, data, y,
                                                       plan=plan),
                      repeat=2)
        rps[name] = n * n_trees / t
        rows.append(csv_row(f"train_e2e_d{depth}_{name}", t * 1e6,
                            f"rows_per_sec={rps[name]:.0f};n={n};"
                            f"n_trees={n_trees}"))
    rows.append(csv_row(f"train_e2e_d{depth}_speedup", 0.0,
                        f"x={rps['subfused'] / rps['direct']:.2f}"))
    return rows


def run_packed_hist(scale: float = 1.0):
    """Packed vs unpacked Pallas histogram rows/sec (ISSUE 7).  Both
    lanes run the same ``pallas_grouped`` kernel on the same 16-bin
    data; the packed lane feeds 4-bit nibble codes and unpacks them
    in-VMEM, halving the HBM traffic the kernel is bound by — the
    acceptance criterion is packed beating unpacked."""
    from repro.core.binning import PackedCodes, pack_nibbles_np

    n = max(20000, int(200000 * scale))
    n_cols, n_bins = 28, 16
    rng = np.random.default_rng(0)
    codes_np = rng.integers(0, n_bins, (n, n_cols), dtype=np.uint8)
    codes = jnp.asarray(codes_np)
    packed = PackedCodes(jnp.asarray(pack_nibbles_np(codes_np)), n_cols)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones((n,), jnp.float32)
    nid = jnp.asarray(rng.integers(0, 8, n), jnp.int32)
    plan = ExecutionPlan(hist_strategy="pallas_grouped").resolved()

    rows, rps = [], {}
    for tag, data in (("unpacked", codes), ("packed", packed)):
        t = time_call(lambda data=data: ops.build_histogram(
            data, g, h, nid, n_nodes=8, n_bins=n_bins, plan=plan))
        rps[tag] = n / t
        rows.append(csv_row(f"hist_pallas_{tag}", t * 1e6,
                            f"rows_per_sec={rps[tag]:.0f};n={n};"
                            f"fields={n_cols};bins={n_bins}"))
    rows.append(csv_row("hist_pallas_packed_speedup", 0.0,
                        f"x={rps['packed'] / rps['unpacked']:.2f}"))
    return rows


def _timed_distributed(n: int, n_trees: int, depth: int) -> dict:
    """Fit timings of ``train_distributed`` on a one-device mesh and on a
    mesh over every device this process has — in this process: a child
    could not reach a chip the bench already holds."""
    X, y, cats = make_tabular(n, 20, 0, task="regression", seed=0)
    data = bin_dataset(X, max_bins=64)
    cfg = GBDTConfig(n_trees=n_trees, max_depth=depth, learning_rate=0.3)

    def one(cfg, **kw):
        t0 = time.perf_counter()
        train_distributed(cfg, data, y, **kw)
        return time.perf_counter() - t0

    out = {}
    meshes = {"1shard": jax.devices()[:1]}
    if len(jax.devices()) > 1:
        meshes["all"] = jax.devices()
    for tag, devs in meshes.items():
        mesh = data_parallel_mesh(devs)
        one(cfg, mesh=mesh)                  # warm: step cached by mesh
        # min-of-2 squeezes scheduler noise out of both sides
        out[tag] = min(one(cfg, mesh=mesh) for _ in range(2))
    # fault-free fit with the recovery machinery armed (divergence
    # sentinels + checkpointable round loop): the wrapper's overhead when
    # nothing fails.  Longer fits raise the per-round signal; plain and
    # guarded reps interleave on the warm full mesh, so each ratio
    # compares adjacent timings under the same host load
    rec = RecoveryPolicy()
    cfg = GBDTConfig(n_trees=n_trees * 3, max_depth=depth, learning_rate=0.3)
    one(cfg, mesh=mesh)
    one(cfg, mesh=mesh, recovery=rec)
    plain, guarded = [], []
    for _ in range(5):
        plain.append(one(cfg, mesh=mesh))
        guarded.append(one(cfg, mesh=mesh, recovery=rec))
    out["recovery"] = min(guarded)
    ratios = sorted(g / p for g, p in zip(guarded, plain))
    out["overhead"] = ratios[len(ratios) // 2]
    out["recovery_trees"] = n_trees * 3
    return out


def run_distributed(scale: float = 1.0, depth: int = 5, n_trees: int = 4):
    """End-to-end ``train_distributed`` rows/sec on a 1-shard mesh and,
    when the process has more than one device, on a ``("data",)`` mesh
    over all of them (``train_dist_all``, its shard count in ``derived``).
    On a CPU host (``benchmarks/run.py`` gives it eight host devices) the
    "devices" share the same cores, so the scaling row measures the psum
    + shard_map overhead rather than real speedup — the rows/sec lanes
    are what the perf gate tracks."""
    n = max(4000, int(40000 * scale))
    timed = _timed_distributed(n, n_trees, depth)
    rows, rps = [], {}
    for tag in ("1shard", "all"):
        if tag not in timed:            # one device: no wider mesh
            continue
        t = timed[tag]
        rps[tag] = n * n_trees / t
        shards = len(jax.devices()) if tag == "all" else 1
        rows.append(csv_row(f"train_dist_{tag}", t * 1e6,
                            f"rows_per_sec={rps[tag]:.0f};n={n};"
                            f"n_trees={n_trees};shards={shards}"))
    if "all" in rps:
        rows.append(csv_row("train_dist_scaling", 0.0,
                            f"x={rps['all'] / rps['1shard']:.2f}"))
    # fault-free recovery-armed fit on the same mesh: the self-healing
    # wrapper (divergence sentinels, checkpointable rounds) must stay
    # within 5% of the plain engine.  The gate is the median of paired
    # plain/guarded ratios from interleaved reps — robust to host drift
    t_rec = timed["recovery"]
    overhead = timed["overhead"]
    rows.append(csv_row("train_dist_recovery", t_rec * 1e6,
                        f"rows_per_sec={n * timed['recovery_trees'] / t_rec:.0f};"
                        f"overhead_vs_plain={overhead:.3f}"))
    if overhead > 1.05:
        raise RuntimeError(
            f"recovery-armed distributed fit is {overhead:.3f}x the plain "
            f"fit (gate: 1.05) — the fault-free path must stay cheap")
    return rows


def run(scale: float = 1.0, max_bins: int = 128):
    rows = []
    geo = {m["name"]: [] for m in (IDEAL_GPU, BOOSTER)}
    for name in ("iot", "higgs", "allstate", "mq2008", "flight"):
        X, y, cats, spec = paper_dataset(name, scale=scale)
        data = bin_dataset(X, max_bins=max_bins, categorical_fields=cats)
        n, F = data.codes.shape
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=n), jnp.float32)
        h = jnp.ones((n,), jnp.float32)
        nid = jnp.asarray(rng.integers(0, 8, n), jnp.int32)

        # (a) measured software strategies
        times = {}
        for s, plan in strategy_plans(STRATS).items():
            times[s] = time_call(
                lambda plan=plan: ops.build_histogram(
                    data.codes, g, h, nid, n_nodes=8, n_bins=data.n_bins,
                    plan=plan))
        base = times["scatter"]
        rows.append(csv_row(
            f"hist_strategies_{name}", base * 1e6,
            ";".join(f"{s}_x={base/times[s]:.2f}" for s in STRATS)))

        # (b) the paper's ideal-machine model at the FULL Table-III sizes
        # (analytic — no memory cost); categorical datasets behave
        # "smaller" (lopsided splits shrink per-level work, §IV)
        n_full = spec.n_records * 1000      # specs are 1000x scaled down
        frac = 0.55 if spec.n_categorical else 1.0
        # IoT's many shallow trees raise step-①'s share (paper §IV)
        depth = 3 if name == "iot" else 6
        t_cpu = modeled_training_time(IDEAL_CPU, n_full, F,
                                      depth=depth, frac_active=frac)
        t_gpu = modeled_training_time(IDEAL_GPU, n_full, F,
                                      depth=depth, frac_active=frac)
        t_boo = modeled_training_time(BOOSTER, n_full, F,
                                      depth=depth, frac_active=frac)
        su_gpu, su_boo = t_cpu / t_gpu, t_cpu / t_boo
        geo["ideal_gpu"].append(su_gpu)
        geo["booster"].append(su_boo)
        rows.append(csv_row(
            f"modeled_speedup_{name}", t_cpu * 1e6,
            f"ideal_gpu_x={su_gpu:.2f};booster_x={su_boo:.2f};"
            f"records={n_full};fields={F}"))
    for k, v in geo.items():
        rows.append(csv_row(f"modeled_geomean_{k}", 0.0,
                            f"x={float(np.exp(np.mean(np.log(v)))):.2f}"))
    # (c) packed vs unpacked Pallas histogram kernel (4-bit nibble codes)
    rows.extend(run_packed_hist(scale=scale))
    # (d) end-to-end depth-6 trainer: direct vs subtraction + fused rounds
    rows.extend(run_e2e(scale=scale))
    # (e) the distributed engine: 1-shard vs all-device data mesh
    rows.extend(run_distributed(scale=scale))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
