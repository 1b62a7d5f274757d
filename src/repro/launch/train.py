"""Production training launcher.

Two entry modes:
  * ``--mode gbdt``  (default) — the paper's workload: distributed GBDT
    training with checkpoint/restart and journaling.
  * ``--mode lm --arch <id>``  — the assigned-architecture LM stack at
    smoke scale (full scale is exercised via launch.dryrun).

Run under a real multi-host TPU runtime this driver would be started once
per host (jax.distributed.initialize); on this container it runs single
process.  Mesh construction, shardings, checkpoint cadence and recovery
are identical in both settings.
"""
from __future__ import annotations

import argparse
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache


def run_gbdt(args):
    from repro.api import (BoosterClassifier, BoosterRegressor,
                           ExecutionPlan, GracefulShutdown, RecoveryPolicy,
                           TrainingInterrupted, paper_dataset)
    from repro.api import serialize
    from repro.distributed.fault import StepJournal
    from repro.launch.mesh import make_mesh

    if args.resume and not serialize.has_checkpoint(args.ckpt_dir):
        raise SystemExit(f"--resume: no checkpoint found under "
                         f"{args.ckpt_dir!r} — nothing to resume from")

    X, y, cats, spec = paper_dataset(args.dataset,
                                     n_override=args.records)
    klass = BoosterClassifier if spec.task == "binary" else BoosterRegressor
    est = klass(n_trees=args.trees, max_depth=args.depth,
                learning_rate=args.lr, max_bins=args.max_bins,
                categorical_fields=cats, seed=args.seed)
    journal = StepJournal(os.path.join(args.ckpt_dir, "journal.jsonl"))

    def cb(t_idx, model):
        if (t_idx + 1) % args.ckpt_every == 0:
            journal.append(t_idx, {})

    # --data-shards N shards records over an N-way ("data",) mesh and the
    # fit runs through the distributed engine (per-shard histograms + one
    # psum per level); N must divide the available device count
    mesh = None
    if args.data_shards > 1:
        if args.stream:
            raise SystemExit("--stream (out-of-core) and --data-shards "
                             "(in-memory distributed) cannot combine")
        n_dev = len(jax.devices())
        if args.data_shards > n_dev:
            raise SystemExit(
                f"--data-shards {args.data_shards} exceeds the "
                f"{n_dev} visible devices (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N to emulate)")
        mesh = make_mesh((args.data_shards,), ("data",),
                         devices=jax.devices()[:args.data_shards])

    plan = ExecutionPlan.auto(hist_strategy=args.strategy)
    recovery = RecoveryPolicy(checkpoint_dir=args.ckpt_dir,
                              checkpoint_every=args.ckpt_every)
    source = None
    # SIGTERM/SIGINT finish the in-flight round, commit it atomically and
    # surface a typed, resumable TrainingInterrupted; a later run with
    # --resume restores from the committed checkpoint and grows only the
    # remaining trees — identical final ensemble (deterministic replay)
    try:
        with GracefulShutdown() as sd:
            if args.stream:
                # resilient out-of-core path: stage the dataset once as
                # crc32-manifested npz shards, stream it back through a
                # self-healing RetryingSource, and fit under a
                # RecoveryPolicy — transient mid-round failures replay
                # from the newest checkpoint, device OOM degrades the
                # chunk size instead of dying
                from repro.api import (ArraySource, NpzShardSource,
                                       RetryPolicy, RetryingSource,
                                       write_npz_shards)
                shard_dir = os.path.join(args.ckpt_dir, "shards")
                write_npz_shards(shard_dir, ArraySource(X, y),
                                 rows_per_shard=max(1024,
                                                    args.records // 8))
                source = RetryingSource(NpzShardSource(shard_dir),
                                        RetryPolicy(chunk_timeout_s=60.0))
                est.fit(data=source, plan=plan,
                        checkpoint_dir=args.ckpt_dir,
                        checkpoint_every=args.ckpt_every, callback=cb,
                        verbose=True, recovery=recovery, shutdown=sd)
            else:
                # checkpoint_dir resumes from the newest valid step and
                # keeps writing atomic, sha-verified bundles every
                # --ckpt-every trees; the recovery policy arms divergence
                # sentinels and (with mesh) preemption/OOM self-healing
                est.fit(X, y, plan=plan, mesh=mesh,
                        checkpoint_dir=args.ckpt_dir,
                        checkpoint_every=args.ckpt_every,
                        callback=cb, verbose=True, recovery=recovery,
                        shutdown=sd)
    except TrainingInterrupted as stop:
        print(f"[train] interrupted ({stop.signal_name}) after "
              f"{stop.rounds_done} committed rounds; checkpoint in "
              f"{stop.checkpoint_dir or args.ckpt_dir} — rerun with "
              f"--resume to finish the remaining trees")
        raise SystemExit(75)  # EX_TEMPFAIL: resumable, not a failure
    loss = est.history_.get("train_loss") or [float("nan")]
    shards = est.stats_.get("n_shards", 1)
    print(f"[train] done: {est.n_trees_} trees, loss {loss[-1]:.5f}, "
          f"shards {shards}")
    if args.stream:
        st = est.stats_
        print(f"[train] resilience: {st.get('recoveries', 0)} recoveries, "
              f"{st.get('oom_halvings', 0)} OOM halvings, "
              f"{source.stats['retries']} source retries "
              f"(chunk_rows {st.get('chunk_rows')})")


def run_lm(args):
    from repro.configs import get_smoke
    from repro.models import lm, optim

    cfg = get_smoke(args.arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = optim.adamw_init(params)
    step = jax.jit(lm.make_train_step(cfg, base_lr=args.lr or 3e-3,
                                      warmup=20, total_steps=args.trees))
    rng = np.random.default_rng(args.seed)
    for i in range(args.trees):
        seqs = rng.integers(0, cfg.vocab, (8, 33))
        batch = {"tokens": jnp.asarray(seqs[:, :-1], jnp.int32),
                 "labels": jnp.asarray(seqs[:, 1:], jnp.int32)}
        if cfg.mrope:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(32)[None, None], (3, 8, 32)).astype(jnp.int32)
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros((8, 4, cfg.d_model))
        if cfg.family == "encdec":
            batch["audio_embeds"] = jnp.zeros(
                (8, cfg.frontend_len, cfg.d_model))
        params, opt, m = step(params, opt, batch)
        if i % 20 == 0:
            print(f"[lm] step {i} loss {float(m['loss']):.4f}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="gbdt", choices=["gbdt", "lm"])
    ap.add_argument("--dataset", default="higgs")
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--records", type=int, default=20_000)
    ap.add_argument("--trees", type=int, default=100,
                    help="boosting rounds (gbdt) or steps (lm)")
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--max-bins", type=int, default=128)
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="data-parallel shards for distributed GBDT "
                         "training (1 = single device)")
    ap.add_argument("--stream", action="store_true",
                    help="resilient out-of-core path: stage checksummed "
                         "npz shards, stream through RetryingSource and "
                         "auto-recover mid-round failures from checkpoints")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true",
                    help="continue an interrupted fit from the newest "
                         "checkpoint under --ckpt-dir (fails if none "
                         "exists); the finished ensemble is identical to "
                         "an uninterrupted run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    (run_gbdt if args.mode == "gbdt" else run_lm)(args)


if __name__ == "__main__":
    main()
