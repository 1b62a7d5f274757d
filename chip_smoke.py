#!/usr/bin/env python3
"""Bring-up check: the GBDT fit and the serving daemon on a TPU chip.

    python chip_smoke.py                # one chip: fit, reference checks, serving
    python chip_smoke.py --four-chip    # data-parallel fit on 4 chips vs 1 device

One chip.  Generates the paper's higgs-shaped workload (10M records x 28
fields, Table III) from ``--seed``, fits it through ``BoosterClassifier``
at 256 bins and depth 6 with the plan ``ExecutionPlan.auto()`` resolves
on the chip (the Pallas kernels, compiled), and checks on the chip
against the plain float32 jnp paths (scatter histogram, reference
partition and traversal):

  * the step-① histogram at the root level and at a 32-node level;
  * the first tree's split features and thresholds;
  * the ensemble's predictions;

then publishes the model into a ``ModelRegistry``, warms it, and serves
ragged requests through ``Server.submit``: every response must match the
reference traversal, with zero drops and zero retraces after warmup.

Four chips.  ``train_distributed`` on a ``("data",)`` mesh over 4 chips
against the same fit on a one-device mesh: tree structure bit-equal and
leaves within rtol 1e-5, atol 1e-6 (docs/api.md, "Distributed training").

Any failed check or exception exits non-zero.  Timings are host
wall-clock.  The last line of a passing run is the JSON object
``{"ok": true, "device": {...}}``; a run without a TPU exits 2 before
doing any work.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# -- tolerances --------------------------------------------------------------
# Histogram cells, each statistic x in {g, h}, against the exact (float64
# host) sums of the same float32 stats:
#     |got - exact| <= HIST_TOL_RMS * sqrt(sum x^2) + HIST_TOL_SUM * sum |x|
# At 10M records on a v5e, at the root, the Pallas kernel read 0.635 of
# this bound; a single bfloat16 pass, which rounds every term to 8
# mantissa bits, 15.5.  The float32 scatter reference sums each bin's ~39K
# records in sequence and read 3.18 of it, the Pallas kernel against it
# 2.65, so that cross-check allows HIST_SCATTER_SLACK bounds, which the
# bfloat16 pass must fail as well.
HIST_TOL_RMS = 1e-3
HIST_TOL_SUM = 1e-4
HIST_SCATTER_SLACK = 4.0
# First tree: split features, thresholds, categorical and default-left
# flags equal; leaf values within this relative tolerance.
LEAF_RTOL = 1e-4
# Margins and served probabilities: a sum of <= 20 float32 leaves in
# another order; a bfloat16 leaf would be off by ~1e-3 of its value.
PRED_RTOL = 1e-5
PRED_ATOL = 1e-6

ROUNDS = 20
DEPTH = 6
BINS = 256
SERVE_SIZES = (1, 7, 64, 333, 1000, 2048, 4095, 4096, 17, 2500)


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}")


class Clock:
    """Host wall-clock of a phase, and the seconds XLA spent compiling
    inside it (JAX's backend-compile events)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)

    @contextlib.contextmanager
    def phase(self, name):
        t0, c0 = time.perf_counter(), self.compile_s
        print(f"[{name}]", flush=True)
        yield
        print(f"[{name}] {time.perf_counter() - t0:.3f} s host wall-clock, "
              f"of which {self.compile_s - c0:.3f} s XLA compile", flush=True)


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def make_data(n: int, seed: int):
    from repro.api import paper_dataset
    X, y, cats, spec = paper_dataset("higgs", n_override=n, seed=seed)
    print(f"  records={X.shape[0]} fields={X.shape[1]} "
          f"(higgs, Table III: {spec.comment})")
    return X, y


def pallas_and_reference_plans():
    from repro.api import ExecutionPlan
    plan = ExecutionPlan.auto()
    ref = ExecutionPlan.auto(hist_strategy="scatter",
                             partition_strategy="reference",
                             traversal_strategy="reference")
    print(f"  plan: {plan.describe()}")
    print(f"  reference plan: {ref.describe()}")
    check((plan.hist_strategy, plan.partition_strategy,
           plan.traversal_strategy, plan.interpret)
          == ("pallas_grouped", "pallas", "pallas", False),
          "plan resolves to the compiled Pallas kernels")
    return plan, ref


PREDICT_ROWS = 1 << 20    # rows per predict call: a (rows, 28) float32
                          # block pads its 28 lanes to 128 on the chip


def predict_margins(est, X, plan):
    import numpy as np
    return np.concatenate([np.asarray(est.predict_margin(
        X[lo:lo + PREDICT_ROWS], plan=plan))
        for lo in range(0, X.shape[0], PREDICT_ROWS)])


def exact_hist(codes, nid, n_nodes, n_bins, stats):
    """float64 host sums: (n_nodes, F, n_bins) per statistic in ``stats``."""
    import numpy as np
    base = nid.astype(np.int64) * n_bins
    out = [np.empty((codes.shape[1], n_nodes * n_bins)) for _ in stats]
    for f in range(codes.shape[1]):
        key = base + codes[:, f]
        for o, w in zip(out, stats):
            o[f] = np.bincount(key, weights=w, minlength=n_nodes * n_bins)
    return [o.reshape(-1, n_nodes, n_bins).transpose(1, 0, 2) for o in out]


def check_histograms(codes, g, h, n_bins, plan, ref, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    n = codes.shape[0]
    codes_np = np.asarray(codes)
    g_np = np.asarray(g, np.float64)
    h_np = np.asarray(h, np.float64)
    g16 = np.asarray(g.astype(jnp.bfloat16), np.float64)
    h16 = np.asarray(h.astype(jnp.bfloat16), np.float64)
    levels = ((1, np.zeros((n,), np.int32)),
              (32, np.asarray(jax.random.randint(
                  jax.random.PRNGKey(seed), (n,), 0, 32, jnp.int32))))
    for nn, nid in levels:
        def kernel(p):
            return np.asarray(ops.build_histogram(
                codes, g, h, jnp.asarray(nid), n_nodes=nn, n_bins=n_bins,
                plan=p), np.float64)
        got, scat = kernel(plan), kernel(ref)
        sg, sg2, sga, sh, sh2, sg16, sh16 = exact_hist(
            codes_np, nid, nn, n_bins,
            (g_np, g_np ** 2, np.abs(g_np), h_np, h_np ** 2, g16, h16))
        tol = np.stack([HIST_TOL_RMS * np.sqrt(sg2) + HIST_TOL_SUM * sga,
                        HIST_TOL_RMS * np.sqrt(sh2) + HIST_TOL_SUM * sh],
                       axis=-1)
        exact = np.stack([sg, sh], axis=-1)
        one_pass = np.stack([sg16, sh16], axis=-1)

        def worst(err, bound):        # max error as a fraction of the bound
            return float(np.max(np.where(bound > 0, err / np.where(
                bound > 0, bound, 1.0), err)))

        e_got = worst(np.abs(got - exact), tol)
        e_scat = worst(np.abs(scat - exact), tol)
        e_pair = worst(np.abs(got - scat), tol)
        e_bf16 = worst(np.abs(one_pass - exact), tol)
        print(f"  histogram {nn} node(s), error in bounds: Pallas {e_got:.3e}, "
              f"float32 scatter {e_scat:.3e}, Pallas vs scatter {e_pair:.3e}, "
              f"one bfloat16 pass {e_bf16:.3e}")
        check(got.shape == (nn, codes.shape[1], n_bins, 2)
              and bool(np.isfinite(got).all()),
              f"histogram shape and finite values at {nn} node(s)")
        check(e_got <= 1.0,
              f"Pallas histogram within the bound at {nn} node(s)")
        check(e_pair <= HIST_SCATTER_SLACK,
              f"Pallas histogram within {HIST_SCATTER_SLACK:g} bounds of the "
              f"float32 scatter at {nn} node(s)")
        check(e_bf16 > HIST_SCATTER_SLACK,
              f"one bfloat16 pass fails both checks at {nn} node(s)")


def one_chip(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api import BoosterClassifier, ModelRegistry, Server
    from repro.serving import warmup_buckets

    clock = Clock()
    dev = jax.devices()[0]
    with clock.phase("data"):
        X, y = make_data(args.records, args.seed)
    plan, ref = pallas_and_reference_plans()
    params = dict(n_trees=args.rounds, max_depth=DEPTH, max_bins=BINS,
                  seed=args.seed)

    with clock.phase("fit"):
        est = BoosterClassifier(**params).fit(X, y, plan=plan)
        jax.block_until_ready(est.model_.trees)
        check(est.n_trees_ == args.rounds, f"{args.rounds} rounds fitted")
        loss = est.history_["train_loss"]
        print(f"  train loss {loss[0]:.6f} -> {loss[-1]:.6f}")
        check(bool(np.isfinite(loss).all()) and loss[-1] < loss[0],
              "train loss finite and falling")
    print(f"  peak device bytes after fit: {peak_bytes(dev)}")

    with clock.phase("reference: first tree"):
        ref_est = BoosterClassifier(**{**params, "n_trees": 1}).fit(
            X, y, plan=ref)
        a = [np.asarray(x[0]) for x in est.model_.trees]
        b = [np.asarray(x[0]) for x in ref_est.model_.trees]
        names = est.model_.trees._fields
        for name, u, v in zip(names, a, b):
            if name == "leaf_value":
                check(np.allclose(u, v, rtol=LEAF_RTOL, atol=0.0),
                      f"first tree {name} within rtol {LEAF_RTOL:g}")
            else:
                check(np.array_equal(u, v), f"first tree {name} equal")

    with clock.phase("reference: predictions"):
        got, want = (predict_margins(est, X, p) for p in (plan, ref))
        err = float(np.max(np.abs(got - want)))
        print(f"  margins: max abs diff {err:.3e} over {got.shape[0]} rows")
        check(got.shape == (X.shape[0],) and bool(np.isfinite(got).all()),
              "margins finite, one per record")
        check(np.allclose(got, want, rtol=PRED_RTOL, atol=PRED_ATOL),
              "Pallas traversal == reference traversal")

    with clock.phase("reference: histograms"):
        data = est.binner_.transform(X)
        p = jax.nn.sigmoid(jnp.asarray(want, jnp.float32))
        yy = jnp.asarray(y, jnp.float32)
        check_histograms(data.codes, p - yy, p * (1.0 - p), data.n_bins,
                         plan, ref, args.seed)
        del data
    print(f"  peak device bytes: {peak_bytes(dev)}")

    with clock.phase("serving"):
        pipe = est.to_pipeline()
        registry = ModelRegistry(plan=plan)
        registry.publish("higgs", pipe)
        server = Server(registry, max_batch=max(SERVE_SIZES))
        try:
            warm = server.warmup("higgs")
            print(f"  warmup: {warm} traces over buckets "
                  f"{warmup_buckets(max(SERVE_SIZES))}")
            traces0 = server.stats()["higgs"]["traces"]
            rng = np.random.default_rng(args.seed)
            batches = [X[o:o + k] for k in SERVE_SIZES
                       for o in [int(rng.integers(0, X.shape[0] - k))]]
            reqs = [server.submit("higgs", b) for b in batches]
            for b, r in zip(batches, reqs):
                got = r.result(timeout=300)
                want = np.asarray(pipe.predict(b, plan=ref, mode="direct"))
                check(got.shape == (b.shape[0],) and np.allclose(
                    got, want, rtol=PRED_RTOL, atol=PRED_ATOL),
                    f"{b.shape[0]}-row response == reference traversal")
            st = server.stats()["higgs"]
            print(f"  served {st['requests']} requests, {st['rows']} rows "
                  f"in {st['flushes']} flushes; p50 {st['p50_ms']:.3f} ms, "
                  f"p99 {st['p99_ms']:.3f} ms (host wall-clock)")
            check(st["requests"] == len(SERVE_SIZES),
                  "every request answered")
            check(st["dropped"] + st["shed"] + st["deadline_failures"] == 0,
                  "zero drops")
            check(st["traces"] == traces0, "zero retraces after warmup")
        finally:
            server.stop(timeout=60)


def first_tree_exact(tree, data, y, cfg):
    """float64 host weights of the first tree's bottom slots, from the
    float32 round-0 statistics: ``(reached, weights)``, where ``reached``
    marks the slots that hold records."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import losses
    from repro.kernels.ref import TreeArrays, traverse_ref

    loss = losses.get_loss(cfg.objective, cfg.n_classes)
    m0 = jnp.full((len(y),), float(loss.base_margin(y)), jnp.float32)
    g, h = loss.grad_hess(m0, jnp.asarray(y, jnp.float32))
    first = TreeArrays(*[jnp.asarray(np.asarray(a)[0]) for a in tree])
    n_leaf = first.leaf_value.shape[0]
    slot = np.asarray(traverse_ref(
        first._replace(leaf_value=jnp.arange(n_leaf, dtype=jnp.int32)),
        data.codes, data.missing_bin))
    G = np.bincount(slot, np.asarray(g, np.float64), n_leaf)
    H = np.bincount(slot, np.asarray(h, np.float64), n_leaf)
    reached = np.bincount(slot, minlength=n_leaf) > 0
    return reached, -G / (H + cfg.lambda_) * cfg.learning_rate


def four_chip(args) -> None:
    import jax
    import numpy as np
    from repro.api import ExecutionPlan
    from repro.core import GBDTConfig, bin_dataset
    from repro.distributed.trainer import data_parallel_mesh, train_distributed

    devs = jax.devices()
    check(len(devs) == 4, f"four devices visible (got {len(devs)})")
    clock = Clock()
    with clock.phase("data"):
        X, y = make_data(args.records, args.seed)
        data = bin_dataset(X, max_bins=BINS)
    cfg = GBDTConfig(n_trees=args.rounds, max_depth=DEPTH,
                     objective="binary:logistic", seed=args.seed)
    results = {}
    # the 4-way fit runs first, so each device's peak shows its own shard
    for tag, mesh_devs in (("4 chips", devs), ("1 device", devs[:1])):
        mesh = data_parallel_mesh(mesh_devs)
        with clock.phase(f"fit on {tag}"):
            res = train_distributed(cfg, data, y, mesh=mesh,
                                    plan=ExecutionPlan.auto(mesh=mesh))
            jax.block_until_ready(res.model.trees)
        ran = res.stats["kernel_plan"]
        print(f"  kernels ran: {ran}")
        check(ran.startswith("ExecutionPlan(hist=pallas_grouped, "
                             "partition=pallas, traversal=pallas, "
                             "interpret=False"),
              f"the {tag} fit ran the compiled Pallas kernels")
        for d in devs:
            print(f"  {d}: peak device bytes {peak_bytes(d)}")
        results[tag] = res
    a, b = results["4 chips"].model.trees, results["1 device"].model.trees
    for name in ("feature", "threshold", "is_cat", "default_left"):
        check(np.array_equal(np.asarray(getattr(a, name)),
                             np.asarray(getattr(b, name))),
              f"{name} bit-equal across 4 chips and 1 device")
    la, lb = np.asarray(a.leaf_value), np.asarray(b.leaf_value)
    print(f"  leaf values: max abs diff {float(np.max(np.abs(la - lb))):.3e}"
          f"; per tree {np.max(np.abs(la - lb), axis=1).tolist()}")
    reached, exact = first_tree_exact(a, data, y, cfg)
    for tag, lv in (("4 chips", la), ("1 device", lb)):
        err = np.abs(lv[0] - exact)[reached]
        print(f"  first tree, {tag}, against float64 sums: max abs error "
              f"{float(err.max()):.3e}, max relative error "
              f"{float((err / np.abs(exact[reached])).max()):.3e}")
    check(np.allclose(la, lb, rtol=1e-5, atol=1e-6),
          "leaf values within rtol 1e-5 (atol 1e-6) across 4 chips and "
          "1 device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="only the data-parallel fit on 4 chips and the "
                         "one-device fit it is compared with")
    ap.add_argument("--records", type=int, default=10_000_000,
                    help="higgs records (Table III: 10M)")
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = enable_compile_cache()
    info = device_info()
    print(f"device: {json.dumps(info)}")
    print(f"compile cache: {cache}")
    if info["platform"] != "tpu":
        print("no TPU visible to JAX: nothing was run", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    (four_chip if args.four_chip else one_chip)(args)
    print(f"total {time.perf_counter() - t0:.3f} s host wall-clock")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
