"""Batched serving drivers.

Two entry modes:
  * ``--mode gbdt`` (default) — a thin CLI over the serving daemon
    (``repro.serving``): it publishes ``--models`` demo tenants into a
    :class:`ModelRegistry`, warms every reachable power-of-two flush
    bucket, then drives a mixed multi-model load of ragged request sizes
    through :class:`Server.submit` — with a mid-run hot-swap republishing
    tenant 0 at a new version.  All queueing, deadline batching, metric
    and retrace accounting lives in the daemon; the driver only
    generates traffic and prints the final ``stats()`` snapshot.  A warm
    server must show ZERO predict-cache retraces and ZERO dropped
    requests across the swap.  When no bundles exist under
    ``--model-dir`` small demo models are trained and saved first, so
    the driver is self-contained.
  * ``--mode lm --arch <id>`` — the assigned-architecture LM stack at
    smoke scale: one prefill, then jit'd single-token decode steps against
    the (ring-buffered where SWA) KV/SSM caches.  ``--no-greedy`` samples
    from the softmax at ``--temperature`` instead of argmax decoding.

    PYTHONPATH=src python -m repro.launch.serve --mode gbdt --batch 4096
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --arch mixtral-8x22b --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import functools
import os
import time

import numpy as np
import jax
import jax.numpy as jnp


def request_sizes(batch: int):
    """The ragged request-size mix (real traffic) — ONE definition shared
    by the measured loop and the warmup-coverage check below, so the two
    can never drift apart (the pre-daemon driver derived them separately
    and the zero-retrace check could pass vacuously)."""
    return [max(1, batch), max(1, batch // 2), max(1, (3 * batch) // 4),
            max(1, batch // 3)]


def _demo_bundle(path: str, plan, task: str, seed: int, n_trees: int = 100,
                 learning_rate: float = 0.2) -> str:
    """Train + save a small demo tenant at ``path`` unless one exists."""
    from repro.api import BoosterClassifier, BoosterRegressor, make_tabular

    if os.path.isdir(path):
        return path
    print(f"[serve] no bundle at {path}; training demo model ({task})")
    X, y, cats = make_tabular(20_000, 20, 8, n_cats=12, task=task,
                              seed=seed)
    cls = BoosterClassifier if task == "binary" else BoosterRegressor
    est = cls(n_trees=n_trees, max_depth=6, learning_rate=learning_rate,
              max_bins=64, categorical_fields=cats, seed=seed)
    est.fit(X, y, plan=plan)
    est.save(path)
    return path


def run_gbdt(args):
    from repro.api import (ExecutionPlan, ModelRegistry, Server, load,
                           warmup_buckets)
    from repro.core.inference import ROW_BUCKET_FLOOR, bucket_pow2
    from repro.serving import (DeadlineExceededError, DispatcherCrashError,
                               QueueFullError)

    plan = ExecutionPlan.auto()
    registry = ModelRegistry(plan)
    tasks = ["binary", "regression"]
    names = []
    for i in range(max(1, args.models)):
        task = tasks[i % len(tasks)]
        name = f"m{i}_{task}"
        path = _demo_bundle(os.path.join(args.model_dir, name), plan,
                            task, seed=i)
        registry.publish(name, path)
        est = load(path)
        print(f"[serve] published {name} v1: {type(est).__name__} with "
              f"{est.n_trees_} trees")
        names.append(name)
    n_fields = registry.pipeline(names[0]).model.n_fields
    print(f"[serve] {plan.describe()}")

    sizes = request_sizes(args.batch)
    mb = args.microbatch or max(sizes)
    bounded = (args.max_queue_rows is not None
               or args.timeout_ms is not None)
    server = Server(registry, max_batch=mb,
                    default_slack_ms=args.slack_ms,
                    log_every_s=args.log_every_s,
                    max_queue_rows=args.max_queue_rows,
                    timeout_ms=args.timeout_ms)

    # every flush the daemon can assemble holds <= max_batch rows, so the
    # warmup bucket set is a strict SUPERSET of what the measured mix can
    # reach — assert that from the same helpers rather than trusting it
    reachable = {bucket_pow2(min(s, mb) if lo + mb >= s else mb,
                             ROW_BUCKET_FLOOR)
                 for s in sizes for lo in range(0, s, mb)}
    assert reachable <= set(warmup_buckets(mb)), (reachable, mb)
    for name in names:
        traces = server.warmup(name)
        print(f"[serve] warmed {name}: buckets {warmup_buckets(mb)} "
              f"({traces} traces)")
    warm_traces = {name: server.stats()[name]["traces"] for name in names}

    # the mixed multi-model measured loop, with a mid-run hot-swap: a new
    # version of tenant 0 (same tree count -> same shape buckets) lands
    # while requests are in flight; the daemon must drop nothing and
    # retrace nothing
    rng = np.random.default_rng(0)
    swap_at = args.requests // 2
    pending = []
    t_loop = time.perf_counter()
    for i in range(args.requests):
        if i == swap_at:
            v2 = _demo_bundle(os.path.join(args.model_dir,
                                           names[0] + "_v2"), plan,
                              tasks[0], seed=100, learning_rate=0.15)
            version = registry.publish(names[0], v2)
            print(f"[serve] hot-swapped {names[0]} -> v{version} mid-run")
        n_rows = sizes[i % len(sizes)]
        Xb = rng.normal(size=(n_rows, n_fields))
        Xb[rng.random(Xb.shape) < 0.02] = np.nan     # missing values
        pending.append(server.submit(names[i % len(names)], Xb))
    # zero SILENT drops: every submitted request must resolve — either
    # with rows or with one of the typed overload/crash failures
    served = total = 0
    typed = {"shed": 0, "deadline": 0, "crash": 0}
    for req in pending:
        try:
            req.result(timeout=600)
            served += 1
            total += req.n_rows
        except QueueFullError:
            typed["shed"] += 1
        except DeadlineExceededError:
            typed["deadline"] += 1
        except DispatcherCrashError:
            typed["crash"] += 1
    wall = time.perf_counter() - t_loop

    stats = server.stats()
    health = server.health()
    server.stop()
    print(f"[serve] sustained: {total / wall:.0f} records/s over "
          f"{args.requests} requests, {len(names)} models "
          f"(max_batch {mb}, slack {args.slack_ms} ms)")
    ok = True
    for name in names:
        s = stats[name]
        print(f"[serve]   {name} v{s['version']}: {s['requests']} req, "
              f"p50 {s['p50_ms']:.1f} ms, p99 {s['p99_ms']:.1f} ms, "
              f"fill {s['batch_fill']:.2f}, dropped {s['dropped']}, "
              f"shed {s['shed']}, expired {s['deadline_failures']}, "
              f"retraces after warmup {s['traces'] - warm_traces[name]}")
        ok &= s["traces"] == warm_traces[name]
        if not bounded:
            ok &= s["dropped"] == 0
    accounted = served + sum(typed.values())
    ok &= accounted == len(pending)
    print(f"[serve] health: alive={health.alive} ready={health.ready} "
          f"restarts={health.dispatcher_restarts} "
          f"typed_failures={health.failed_requests}")
    print(f"[serve] accounting: {served} served + {typed['shed']} shed + "
          f"{typed['deadline']} expired + {typed['crash']} crash-failed "
          f"= {accounted}/{len(pending)} (zero silent drops: "
          f"{'OK' if accounted == len(pending) else 'VIOLATED'})")
    print(f"[serve] zero retraces across hot-swap: "
          f"{'OK' if ok else 'UNEXPECTED'}")


def run_lm(args):
    from repro.configs import get_smoke
    from repro.models import lm

    cfg = get_smoke(args.arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = args.batch, args.prompt_len

    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    if cfg.mrope:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros((B, 4, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["audio_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_len, cfg.d_model)), jnp.float32)

    t0 = time.perf_counter()
    logits, cache = lm.prefill(cfg, params, batch,
                               max_len=S + args.gen,
                               cache_dtype=jnp.float32)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"[serve] prefill {B}x{S}: {t_prefill*1e3:.1f} ms "
          f"({B*S/t_prefill:.0f} tok/s)")

    decode = jax.jit(functools.partial(lm.decode_step, cfg))
    key = jax.random.PRNGKey(args.seed)

    def pick(logits, key):
        """Greedy argmax, or temperature sampling with --no-greedy."""
        if args.greedy:
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        scaled = logits / max(args.temperature, 1e-6)
        return jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)[:, None]

    key, sub = jax.random.split(key)
    tok = pick(logits, sub)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, tok,
                               jnp.asarray(S + i, jnp.int32))
        key, sub = jax.random.split(key)
        tok = pick(logits, sub)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    mode = ("greedy" if args.greedy
            else f"sampled@T={args.temperature:g}")
    print(f"[serve] decoded {args.gen - 1} steps x {B} seqs ({mode}): "
          f"{t_dec*1e3:.1f} ms ({B*(args.gen-1)/t_dec:.0f} tok/s)")
    print(f"[serve] first sequence: {gen[0][:16].tolist()} ...")


def main():
    from repro.configs import ARCH_IDS
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="gbdt", choices=["gbdt", "lm"])
    # gbdt serving
    ap.add_argument("--model-dir", default="/tmp/repro_serve_bundle")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--microbatch", type=int, default=0,
                    help="daemon flush capacity in rows (0 = the largest "
                         "request size)")
    ap.add_argument("--models", type=int, default=2,
                    help="demo tenants published into the registry")
    ap.add_argument("--max-queue-rows", type=int, default=None,
                    help="per-model queue bound; overload is shed with "
                         "typed QueueFullError futures (default unbounded)")
    ap.add_argument("--timeout-ms", type=float, default=None,
                    help="hard queue deadline; expired requests fail with "
                         "DeadlineExceededError (default none)")
    ap.add_argument("--slack-ms", type=float, default=20.0,
                    help="per-request deadline slack (queue-wait budget)")
    ap.add_argument("--log-every-s", type=float, default=None,
                    help="daemon stats log-line cadence (default: silent)")
    # lm serving
    ap.add_argument("--arch", default="qwen3-14b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=None,
                    help="records per request (gbdt, default 4096) or "
                         "sequences (lm, default 4)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    # BooleanOptionalAction: the old action="store_true", default=True
    # combination made --greedy a no-op (it could never be False)
    ap.add_argument("--greedy", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="argmax decoding; --no-greedy samples at "
                         "--temperature")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.batch is None:
        args.batch = 4096 if args.mode == "gbdt" else 4
    (run_gbdt if args.mode == "gbdt" else run_lm)(args)


if __name__ == "__main__":
    main()
