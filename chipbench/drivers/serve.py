"""The ``serve`` driver: an open loop of requests through ``Server.submit``.

Set-up makes a pool of records on the device from the seed, fits the
program's ``Binner`` on a sample of them, makes the served ensemble from
the seed in the program's ``TreeArrays`` layout, publishes it into a
``ModelRegistry`` and starts a ``Server`` with the traffic's settings.
``Server.warmup`` compiles every row bucket; the harness then sends one
batch of every size a flush can have through the very call a flush makes,
because the served path still compiles per flush size (the result slice
and the sigmoid run eagerly on the exact row count).  Requests come in
whole multiples of the traffic's ``rows_granule``, so a flush has at most
``max_batch / rows_granule`` sizes.

The arrivals are Poisson at the traffic's rate: every seed gets the same
multiset of gaps and of sizes, in its own order, and its own rows.  One
thread sends each request at its due time; latency runs from the due time
to the result, so a late send counts against the server.  After the
window every request is awaited (up to ``result_wait_s`` past the close),
and a sample drawn from the seed, with the largest requests in it, is
compared with the reference.
"""
from __future__ import annotations

import contextlib
import inspect
import math
import threading
import time

import numpy as np

from chipbench import data as data_mod
from chipbench.harness import Check, Outcome, Records, log, quantile, span
from chipbench.reference import binning_ref, serve_ref

MODEL = "served"
LARGEST = 20                 # the largest requests always join the sample


def schedule(traffic: dict, seconds: float, seed: int, pool_rows: int):
    """(due offsets in s, rows per request, pool offsets) of the window."""
    rate = traffic["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(traffic["schedule_seed"])
    gaps = fixed.exponential(1.0 / rate, n)
    unit = traffic["rows_granule"]
    lo, hi = traffic["rows_min"] // unit, traffic["rows_max"] // unit
    sizes = unit * np.clip(np.floor(np.exp(fixed.uniform(
        math.log(lo), math.log(hi + 1), n))).astype(np.int64), lo, hi)
    rng = np.random.default_rng(seed)
    gaps, sizes = gaps[rng.permutation(n)], sizes[rng.permutation(n)]
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * n / (n + 1)
    offsets = rng.integers(0, pool_rows - sizes + 1)
    return due, sizes, offsets


def _max_batch(server_kw: dict) -> int:
    from repro.serving import Server
    default = inspect.signature(Server).parameters["max_batch"].default
    return int(server_kw.get("max_batch", default))


def prepare(config: dict, traffic: dict, seed: int, plan, clock, phases):
    """The pool of rows, the binner's sample, the served trees and a
    registry that serves them under ``MODEL``."""
    import jax
    import jax.numpy as jnp
    from repro.core.binning import Binner
    from repro.core.gbdt import GBDTModel
    from repro.core.inference import GBDTPipeline
    from repro.kernels.ref import TreeArrays
    from repro.serving import ModelRegistry

    ds, model = config["dataset"], config["model"]
    n_num = ds["numeric_fields"]
    F = n_num + ds["categorical_fields"]
    key = data_mod.seed_key(seed)
    with clock.phase("data", phases):
        X, _ = data_mod.tabular(jax.random.fold_in(key, 0),
                                n=traffic["pool_rows"], n_numeric=n_num,
                                n_categorical=ds["categorical_fields"],
                                n_cats=ds["categories"],
                                missing_rate=ds["missing_rate"])
        pool = np.asarray(X)
    sample = pool[:traffic["binner_sample_rows"]]
    with clock.phase("model", phases):
        binner = Binner(max_bins=model["max_bins"],
                        categorical_fields=list(range(n_num, F))).fit(sample)
        layout = binner.transform(sample[:1])
        trees = TreeArrays(*data_mod.ensemble(
            jax.random.fold_in(key, 1), layout.is_categorical,
            jnp.asarray(layout.n_value_bins, jnp.int32),
            n_trees=model["trees"], depth=model["max_depth"],
            leaf_scale=model["leaf_scale"]))
        gbdt = GBDTModel(trees=trees, base_margin=model["base_margin"],
                         objective=model["objective"],
                         missing_bin=model["max_bins"] - 1, n_fields=F,
                         max_depth=model["max_depth"])
        registry = ModelRegistry(plan=plan)
        registry.publish(MODEL, GBDTPipeline(binner=binner, model=gbdt))
    return registry, pool, sample, trees


def warm(server, registry, pool, traffic: dict) -> None:
    """Every row bucket, then every flush size through the flush's call:
    flushes coalesce whole requests, so their sizes are the multiples of
    the traffic's row granule up to ``max_batch``."""
    server.warmup(MODEL)
    entry = registry.entry(MODEL)
    unit = traffic["rows_granule"]
    for rows in range(unit, _max_batch(traffic["server"]) + 1, unit):
        np.asarray(entry.pipeline.predict(
            pool[:rows], plan=registry.plan, mode="cached",
            cache=entry.cache))
    for rows in (unit, traffic["rows_max"]):
        server.submit(MODEL, pool[:rows], slack_ms=0).result(60)


def measure(server, pool, traffic: dict, seconds: float, seed: int,
            window=contextlib.nullcontext, clock=None):
    """One open-loop window; every request awaited after it closes."""
    due, sizes, offsets = schedule(traffic, seconds, seed, pool.shape[0])
    n = len(due)
    sent = np.zeros(n)
    reqs = [None] * n
    errors = []

    def send(t0):
        try:
            for k in range(n):
                wait = t0 + due[k] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                sent[k] = time.monotonic()
                o = offsets[k]
                reqs[k] = server.submit(MODEL, pool[o:o + sizes[k]])
        except Exception as exc:       # noqa: BLE001 — raised below
            errors.append(exc)

    before = server.stats()[MODEL]
    c0 = clock.compiles if clock is not None else 0
    with window():
        t0 = time.monotonic()
        sender = threading.Thread(target=send, args=(t0,),
                                  name="chipbench-loadgen")
        with span("measured"):
            sender.start()
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            t_close = time.monotonic()
        at_close = server.stats()[MODEL]
        sender.join(traffic["result_wait_s"])
    in_window = (clock.compiles - c0) if clock is not None else None
    if errors:
        raise errors[0]

    done_at = np.full(n, np.inf)
    answers = [None] * n
    give_up = t_close + traffic["result_wait_s"]
    for k, req in enumerate(reqs):
        if req is None:
            continue
        try:
            answers[k] = np.asarray(req.result(
                max(0.0, give_up - time.monotonic())))
            done_at[k] = req.submitted_at + req.latency_s
        except Exception as exc:       # noqa: BLE001 — counted as failed
            log(f"request {k} failed: {type(exc).__name__}: {exc}")
    latency = done_at - (t0 + due)
    in_time = done_at <= t_close
    return {"due": due, "sizes": sizes, "offsets": offsets,
            "answers": answers, "latency_s": latency,
            "late_s": sent - (t0 + due),
            "failed": int(np.sum(~np.isfinite(latency))),
            "backlog": int(np.sum(~in_time)),
            "queue_at_close": at_close["queue_depth"],
            "rows": at_close["rows"] - before["rows"],
            "flushes": at_close["flushes"] - before["flushes"],
            "answered_rows": float(np.sum(sizes[in_time])),
            "compiles_in_window": in_window}


def sampled_requests(sizes, traffic: dict, seed: int):
    """The requests compared: drawn from the seed, with the largest."""
    rng = np.random.default_rng(seed)
    n = len(sizes)
    pick = set(rng.choice(n, size=min(n, traffic["sample_requests"]),
                          replace=False).tolist())
    return sorted(pick | set(np.argsort(sizes)[-LARGEST:].tolist()))


def compare(m: dict, pool, sample, trees, config: dict, traffic: dict,
            seed: int, precision: str = "float32") -> float:
    """The widest gap between a sampled answer and the reference's; a
    sampled answer of the wrong length reads infinite."""
    model = config["model"]
    cat_ids = set(range(config["dataset"]["numeric_fields"],
                        pool.shape[1]))
    sizes, offsets, answers = m["sizes"], m["offsets"], m["answers"]
    pick = sampled_requests(sizes, traffic, seed)
    tables = binning_ref.fit_edges(sample, cat_ids, model["max_bins"])
    host_trees = {f: np.asarray(getattr(trees, f)) for f in trees._fields}
    rows = np.concatenate([pool[offsets[k]:offsets[k] + sizes[k]]
                           for k in pick])
    want = serve_ref.predict(rows, host_trees, model["base_margin"], tables,
                             precision=precision)
    gap, lo = 0.0, 0
    for k in pick:
        ref = want[lo:lo + sizes[k]]
        lo += sizes[k]
        got = answers[k]
        if got is None:
            continue                   # counted under unanswered
        if got.shape != ref.shape:
            return math.inf
        gap = max(gap, float(np.max(np.abs(got.astype(np.float64) - ref))))
    return gap


def run(ctx) -> Outcome:
    from repro.serving import Server

    cfg, traffic, limits = ctx.config, ctx.traffic, ctx.limits
    registry, pool, sample, trees = prepare(cfg, traffic, ctx.seed, ctx.plan,
                                            ctx.clock, ctx.phases)
    server = Server(registry, **traffic["server"])
    try:
        with ctx.clock.phase("warmup", ctx.phases):
            warm(server, registry, pool, traffic)
        m = measure(server, pool, traffic, ctx.seconds, ctx.seed,
                    window=ctx.window, clock=ctx.clock)
    finally:
        server.stop(timeout=traffic["result_wait_s"])
    peak = ctx.memory_peak()
    log(f"window: {len(m['sizes'])} requests, {int(m['sizes'].sum())} rows, "
        f"{m['failed']} failed, {m['backlog']} unanswered at the close, "
        f"queue at the close {m['queue_at_close']} rows, "
        f"{m['compiles_in_window']} compiles inside it")
    gap = compare(m, pool, sample, trees, cfg, traffic, ctx.seed)
    checks = [Check("unanswered", float(m["failed"]), limits["unanswered"]),
              Check("pred_gap", gap, limits["pred_gap"])]
    records = Records(cell=ctx.cell, config=cfg, traffic=traffic,
                      peaks=ctx.peaks,
                      serve={"requests": len(m["sizes"]),
                             "window_s": ctx.seconds,
                             "late_s": m["late_s"].tolist(),
                             "rows": m["rows"], "flushes": m["flushes"],
                             "backlog": m["backlog"],
                             "compiles_in_window": m["compiles_in_window"]})
    lat = m["latency_s"].tolist()
    log(f"latency from due time: p50 {quantile(lat, 0.5) * 1e3:.3f} ms, "
        f"p99 {quantile(lat, 0.99) * 1e3:.3f} ms")
    metrics = {"serve_p50_ms": quantile(lat, 0.5) * 1e3,
               "serve_rows_per_s": m["answered_rows"] / ctx.seconds}
    return Outcome(metrics=metrics, checks=checks,
                   attempted=len(m["sizes"]), failed=m["failed"],
                   memory_peak_bytes=peak, records=records)
