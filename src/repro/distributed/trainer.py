"""Data-parallel distributed GBDT training with elastic fault tolerance.

The paper's §III-B decomposition, wired into an actual fit: records are
partitioned across the mesh's data axes, each shard runs the class-batched
histogram kernel over its local records, and the per-shard histograms are
reduced with ONE psum at the end of step ① per level — O(nodes·F·bins)
bytes per level crossing the interconnect instead of the record stream.
Everything downstream of the reduction (step ② split decisions, the tree
tables) is replicated math on the psum'd histogram, so every shard grows
the *same* tree; step ③ partitions each shard's records locally.

A whole boosting round stays on-device per host, as in the single-device
depthwise fit (``core.gbdt.train``): gradients, the per-round stochastic filters, the sharded
grower, leaf shrinkage, the margin refresh and the loss reduction compile
into one jitted step dispatched once per round.

Determinism contract (see docs/api.md "Distributed training"):

  * the per-round RNG stream is ``fold_in(PRNGKey(seed), round)`` and all
    stochastic filters (GOSS, subsample, colsample) are computed on the
    GLOBAL statistics before sharding — the draws are identical for any
    shard count, so tree *structure* differences across meshes can only
    come from float reassociation in the histogram psum;
  * D=1 is bit-equal to the single-device trainer (padding rows carry
    zero statistics, contributing exactly +0.0);
  * for D>1 every histogram cell is a psum of per-shard partial sums —
    exact whenever the per-cell sums are exactly representable (integer
    counts always; dyadic gradient values too), otherwise within the
    documented float tolerance.

Elasticity and fault tolerance (``DistributedConfig``): a worker failure
mid-round surfaces as an exception from the round dispatch; recovery
re-meshes onto the surviving devices, restores the newest
``checkpoint.save_named`` step and deterministically replays the in-flight
tree — the fit never restarts.  A grow event (devices arriving) re-meshes
back up between rounds; training state is mesh-agnostic so a re-mesh is a
re-placement, not a restore.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.plan import ExecutionPlan
from repro.core import gbdt as gbdt_mod
from repro.core import losses as losses_mod
from repro.core import splits as splits_mod
from repro.core import tree as tree_mod
from repro.core.binning import BinnedDataset, PackedCodes
from repro.core.gbdt import (GBDTConfig, GBDTModel, TrainResult, _as_model,
                             _round_stats, _unstack_forests,
                             model_from_meta)
from repro.distributed import checkpoint as ckpt
from repro.distributed.sharding import padded_record_count
from repro.kernels import ops
from repro.kernels.ref import TreeArrays
from repro.launch.mesh import data_axes, make_mesh, n_data_shards
from repro.resilience import metrics as _metrics
from repro.resilience.errors import (NumericalDivergenceError, Preemption,
                                     TrainingInterrupted)
from repro.resilience.recovery import RecoveryPolicy, classify
from repro.resilience.shutdown import GracefulShutdown


@dataclasses.dataclass
class DistributedConfig:
    """Elasticity / fault-tolerance policy for :func:`train_distributed`.

    checkpoint_dir:     where ``checkpoint.save_named`` steps land; None
                        disables checkpointing (a failure then replays the
                        whole fit from round 0 on the surviving devices)
    checkpoint_every:   save cadence in completed rounds
    keep_last:          checkpoint GC horizon
    max_restarts:       failures tolerated before the exception propagates
    fault_injector:     any object with ``check(round)`` raising to
                        simulate a worker loss (``fault.FaultInjector``);
                        checked after the round dispatch, before commit —
                        the in-flight tree is the one replayed
    fault_schedule:     a :class:`repro.resilience.FaultSchedule` driving
                        chaos at the trainer's named sites: ``"round"``
                        fires after the round dispatch before commit
                        (same spot as ``fault_injector``, which it
                        generalizes), ``"elastic"`` fires just before the
                        between-round device poll
    available_devices:  optional ``round -> device list`` callable polled
                        between rounds; a changed list re-meshes the fit
                        up or down (elastic grow/shrink without failure)
    survivors:          maps the failed mesh's device list to the
                        surviving one; default drops the last device
                        (keeps the mesh when only one device remains)
    """

    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 25
    keep_last: int = 3
    max_restarts: int = 2
    fault_injector: Optional[object] = None
    fault_schedule: Optional[object] = None
    available_devices: Optional[Callable[[int], Sequence]] = None
    survivors: Optional[Callable[[Sequence], Sequence]] = None


def data_parallel_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A ``("data",)`` mesh over ``devices`` (default: every device)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return make_mesh((len(devs),), ("data",), devices=devs)


def _check_data_parallel(mesh: Mesh) -> Tuple[str, ...]:
    """The trainer shards records only; a real model axis is not supported."""
    da = data_axes(mesh)
    if "model" in mesh.axis_names and mesh.shape["model"] != 1:
        raise ValueError(
            "train_distributed is data-parallel: the mesh's 'model' axis "
            f"must have size 1, got {mesh.shape['model']} (use "
            "distributed_fit_tree for field sharding)")
    if not da:
        raise ValueError("mesh has no data axes to shard records over")
    return da


def _trainer_kernel_plan(plan: ExecutionPlan) -> ExecutionPlan:
    """The plan the kernels inside the sharded step see: mesh/data-axis
    routing stripped (the step IS the mesh program), chunking dropped from
    the jit key, and the step-② host offload disabled (a host round-trip
    cannot live inside one jit)."""
    return plan.replace(mesh=None, data_axes=None, chunk_bytes=None,
                        host_offload_split=False).resolved()


# --------------------------------------------------------------------------
# the sharded grower: per-shard histograms + one psum per level
# --------------------------------------------------------------------------
def _grow_forest_sharded(mesh: Mesh, da: Tuple[str, ...], *, depth: int,
                         n_bins: int, lambda_: float, gamma: float,
                         min_child_weight: float, plan: ExecutionPlan,
                         cm_packed: bool = False, hist_slices: int = 1):
    """Build the shard_map'd level-wise grower for ``mesh``.

    Returns ``fn(codes, codes_cm, g2, h2, is_cat_field, field_mask) ->
    (TreeArrays with (K, ...) axes, node_ids (K, n_pad))`` where codes is
    (n_pad, F) sharded over the data axes — a plain uint8 matrix or a
    :class:`PackedCodes` (its record axis shards cleanly; the histogram
    dispatch unpacks or consumes nibbles per strategy) — codes_cm its
    (F, n_pad) column-major copy, and g2/h2 the (K, n_pad) per-class
    statistics (padding rows MUST carry zero stats).  With
    ``cm_packed`` the column-major operand arrives as RAW nibble-packed
    bytes (F, n_pad // 2): the record axis is the packed axis, so it is
    shipped as bytes (half the cross-shard placement traffic), sharded
    on whole bytes (``_place_dataset`` pads records so every shard gets
    an even count), and only the <= 2^level gathered split rows are
    unpacked per level inside the local function.  The returned node ids
    are the records' final bottom-leaf slots — step ⑤ is a leaf-value
    lookup, no traversal pass (the streaming trainer's trick, reused
    verbatim).

    ``hist_slices`` is the device-OOM degradation knob: each shard's
    step-① accumulation is split into that many record sub-batches,
    accumulated sequentially so only one sub-batch's scatter
    intermediates are live at a time (the distributed analog of the
    streaming trainer's chunk-rows halving).  Zero-stat padding rows
    contribute exactly +0.0 per cell, so a degraded round reproduces the
    undegraded histogram by the same split-invariance argument the
    streaming accumulation relies on.
    """
    missing_bin = n_bins - 1
    n_int, n_leaf = 2 ** depth - 1, 2 ** depth

    def local(codes_l, codes_cm_l, g_l, h_l, is_cat_field, field_mask):
        K, n_loc = g_l.shape
        state = (jnp.full((K, n_int), -1, jnp.int32),      # feature
                 jnp.zeros((K, n_int), jnp.int32),         # threshold
                 jnp.zeros((K, n_int), jnp.int32),         # is_cat
                 jnp.zeros((K, n_int), jnp.int32))         # default_left
        node_ids = jnp.zeros((K, n_loc), jnp.int32)
        part = jax.vmap(functools.partial(ops.partition_level,
                                          missing_bin=missing_bin,
                                          plan=plan))

        def acc_hist(nn, g_a, h_a, nid):
            """Per-shard step-① accumulation, split into ``hist_slices``
            record sub-batches when OOM degradation demands it (zero-stat
            padding keeps every sub-batching bit-for-bit aligned with the
            monolithic accumulation)."""
            zero = jnp.zeros((K, nn, is_cat_field.shape[0], n_bins, 2),
                             jnp.float32)
            if hist_slices <= 1:
                return ops.accumulate_histogram(zero, codes_l, g_a, h_a,
                                                nid, n_nodes=nn,
                                                n_bins=n_bins, plan=plan)
            sz = -(-n_loc // hist_slices)
            pad = sz * hist_slices - n_loc
            if isinstance(codes_l, PackedCodes):
                cd = jnp.pad(codes_l.data, ((0, pad), (0, 0)))
                parts = [PackedCodes(cd[s * sz:(s + 1) * sz], codes_l.n)
                         for s in range(hist_slices)]
            else:
                cd = jnp.pad(codes_l, ((0, pad), (0, 0)))
                parts = [cd[s * sz:(s + 1) * sz]
                         for s in range(hist_slices)]
            g_p = jnp.pad(g_a, ((0, 0), (0, pad)))
            h_p = jnp.pad(h_a, ((0, 0), (0, pad)))
            nid_p = jnp.pad(nid, ((0, 0), (0, pad)))
            acc = zero
            for s in range(hist_slices):
                sl = slice(s * sz, (s + 1) * sz)
                acc = ops.accumulate_histogram(
                    acc, parts[s], g_p[:, sl], h_p[:, sl], nid_p[:, sl],
                    n_nodes=nn, n_bins=n_bins, plan=plan)
            return acc

        prev_hist = None
        for level in range(depth):
            nn = 2 ** level
            # step ① — local class-batched accumulation, then the paper's
            # end-of-step-① reduction across record partitions.  The local
            # pass reuses ``accumulate_histogram`` (the chunked trainers'
            # reduction unit), so every step-① entry point in the repo
            # dispatches through one jit.
            if plan.hist_subtraction and level > 0:
                # smaller-child masking per shard (paper §II-A): selection
                # uses psum'd *record counts* — integer sums are exact, so
                # every shard (and every shard count) picks the same child
                ones = jnp.ones((n_loc,), jnp.int32)
                counts = jax.lax.psum(
                    jax.vmap(lambda nid: jax.ops.segment_sum(
                        ones, nid, nn))(node_ids), da)
                is_small = tree_mod._child_is_smaller(
                    counts[:, 0::2] <= counts[:, 1::2])        # (K, nn)
                w = jax.vmap(lambda m, nid: m[nid])(
                    is_small, node_ids).astype(jnp.float32)
                small = jax.lax.psum(
                    acc_hist(nn, g_l * w, h_l * w, node_ids), da)
                hist = tree_mod._combine_sibling_hist(prev_hist, small,
                                                      is_small)
            else:
                hist = jax.lax.psum(acc_hist(nn, g_l, h_l, node_ids), da)
            prev_hist = hist
            # step ② — replicated math on the reduced histogram: every
            # shard takes the same decisions and grows the same tree
            state, best, do_split = tree_mod._decide_level(
                hist, level, state, is_cat_field, field_mask,
                lambda_, gamma, min_child_weight,
                splits_mod.find_best_splits)
            # step ③ — route the local records only
            codes_lvl = codes_cm_l[jnp.where(do_split, best.feature, 0)]
            if cm_packed:      # unpack just the gathered rows, in-shard
                b = codes_lvl
                codes_lvl = jnp.stack([b & 0xF, b >> 4], axis=-1).reshape(
                    b.shape[0], b.shape[1], -1)
            node_ids = part(
                node_ids, codes_lvl.transpose(0, 2, 1),
                jnp.where(do_split,
                          jnp.broadcast_to(jnp.arange(nn, dtype=jnp.int32),
                                           (K, nn)), -1),
                best.threshold, best.is_cat, best.default_left)

        feature, threshold, is_cat, default_left = state
        # step ④ — leaf weights from psum'd per-shard G/H sums
        Gb = jax.lax.psum(tree_mod.leaf_sums(g_l, node_ids, n_leaf), da)
        Hb = jax.lax.psum(tree_mod.leaf_sums(h_l, node_ids, n_leaf), da)
        leaf_value = tree_mod.settle_leaves(Gb, Hb, feature, lambda_)
        return (feature, threshold, is_cat, default_left, leaf_value,
                node_ids)

    # tree tables are replicated by VALUE (identical psum'd inputs on every
    # shard), which varying-manual-axes inference cannot prove — turn the
    # static check off, as the other shard_map paths in sharding.py do
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(da), P(None, da), P(None, da), P(None, da), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(None, da)),
        check_vma=False)

    def grow(codes, codes_cm, g2, h2, is_cat_field, field_mask):
        feature, threshold, is_cat, default_left, leaf_value, node_ids = fn(
            codes, codes_cm, g2, h2, is_cat_field, field_mask)
        tree = TreeArrays(feature=feature, threshold=threshold,
                          is_cat=is_cat, default_left=default_left,
                          leaf_value=leaf_value)
        return tree, node_ids

    return grow


# --------------------------------------------------------------------------
# one boosting round as a single jitted dispatch (as core.gbdt.train's)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def _distributed_round_step(config: GBDTConfig, plan: ExecutionPlan,
                            mesh: Mesh, da: Tuple[str, ...], n: int,
                            n_pad: int, F: int, n_bins: int,
                            n_eval: Optional[int],
                            cm_packed: bool = False,
                            hist_slices: int = 1):
    """Compile one distributed boosting round: global gradients + RNG
    filters (shard-count invariant), the sharded grower, leaf shrinkage,
    the leaf-lookup margin refresh and the loss reduction — one dispatch
    per round per host.  Cached per (fused-style config key, kernel plan,
    mesh, shapes, hist_slices): an elastic re-mesh or an OOM degradation
    compiles a new step, a replay on the same mesh reuses the old one.
    """
    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs
    Kb = K or 1
    grow = _grow_forest_sharded(
        mesh, da, depth=config.max_depth, n_bins=n_bins,
        lambda_=config.lambda_, gamma=config.gamma,
        min_child_weight=config.min_child_weight, plan=plan,
        cm_packed=cm_packed, hist_slices=hist_slices)

    def body(margins, y, tkey, codes, codes_cm, is_cat_field):
        g, h = loss.grad_hess(margins, y)
        g, h, field_mask = _round_stats(config, tkey, g, h, n, F, K)
        g2 = g.T if K is not None else g[None]                  # (Kb, n)
        h2 = h.T if K is not None else h[None]
        # padding rows carry zero statistics: exactly +0.0 per histogram
        # cell and leaf sum, so D=1 stays bit-equal to the monolithic path
        g2 = jnp.pad(g2, ((0, 0), (0, n_pad - n)))
        h2 = jnp.pad(h2, ((0, 0), (0, n_pad - n)))
        forest, node_ids = grow(codes, codes_cm, g2, h2, is_cat_field,
                                field_mask)
        forest = gbdt_mod.shrink(forest, config.learning_rate)
        # step ⑤ for free: final node ids ARE bottom-leaf slots
        delta = jax.vmap(lambda v, i: v[i])(forest.leaf_value,
                                            node_ids)[:, :n]   # (Kb, n)
        margins = margins + (delta.T if K is not None else delta[0])
        tree = (forest if K is not None
                else TreeArrays(*[a[0] for a in forest]))
        return margins, tree, jnp.mean(loss.value(margins, y))

    if n_eval is None:
        step = body
    else:
        def step(margins, ev_margins, y, y_ev, tkey, codes, codes_cm,
                 ev_codes, ev_codes_cm, is_cat_field):
            margins, tree, train_loss = body(margins, y, tkey, codes,
                                             codes_cm, is_cat_field)
            ev_data = BinnedDataset(ev_codes, ev_codes_cm, is_cat_field,
                                    n_bins, None, None)
            ev_delta = (gbdt_mod._predict_forest(tree, ev_data, plan)
                        if K is not None
                        else gbdt_mod._predict_one_tree(tree, ev_data,
                                                        plan))
            ev_margins = ev_margins + ev_delta
            return (margins, ev_margins, tree, train_loss,
                    jnp.mean(loss.value(ev_margins, y_ev)))

    return jax.jit(step)


# --------------------------------------------------------------------------
# placement + checkpoint plumbing
# --------------------------------------------------------------------------
def _place_dataset(data: BinnedDataset, mesh: Mesh, da: Tuple[str, ...]):
    """Pad records to divide the data axes and device_put both layouts.
    Pad rows replicate the edge record; training neutralizes them with
    zero gradient statistics inside the round step.

    Nibble-packed datasets (``n_bins <= 16``) ship packed: the row-major
    layout stays a :class:`PackedCodes` (records shard on axis 0, the
    packed field axis is shard-local), the column-major layout ships as
    RAW packed bytes (F, n_pad // 2) — half the placement traffic of the
    uint8 twin.  The packed cm form requires an even per-shard record
    count (a byte must not straddle shards); ``n_pad`` is NEVER adjusted
    for it — that would change the psum reduction shapes and cost the
    bit-equality guarantee against the uint8 path — so when the count
    comes out odd the cm copy falls back to plain uint8.  Pad-row code
    values are immaterial (only their zero statistics matter), so
    byte-level edge replication is as good as record-level.  Returns
    ``(codes, codes_cm, n_pad, cm_packed)``.
    """
    n = data.codes.shape[0]
    n_pad = padded_record_count(n, mesh)
    rm_packed = isinstance(data.codes, PackedCodes)
    cm_packed = isinstance(data.codes_cm, PackedCodes)
    if cm_packed:
        shards = int(np.prod([mesh.shape[a] for a in da])) if da else 1
        cm_packed = (n_pad // shards) % 2 == 0
    if rm_packed:
        d = jnp.pad(data.codes.data, ((0, n_pad - n), (0, 0)), mode="edge")
        codes = jax.device_put(PackedCodes(d, data.codes.n),
                               NamedSharding(mesh, P(da)))
    else:
        codes = jnp.pad(data.codes, ((0, n_pad - n), (0, 0)), mode="edge")
        codes = jax.device_put(codes, NamedSharding(mesh, P(da)))
    if cm_packed:
        d = data.codes_cm.data                       # (F, ceil(n / 2))
        codes_cm = jnp.pad(d, ((0, 0), (0, n_pad // 2 - d.shape[1])),
                           mode="edge")
    else:
        cm = data.codes_cm
        cm = cm.unpack() if isinstance(cm, PackedCodes) else cm
        codes_cm = jnp.pad(cm, ((0, 0), (0, n_pad - n)), mode="edge")
    codes_cm = jax.device_put(codes_cm, NamedSharding(mesh, P(None, da)))
    return codes, codes_cm, n_pad, cm_packed


def _replicate(mesh: Mesh, *arrays):
    sh = NamedSharding(mesh, P())
    out = tuple(None if a is None else jax.device_put(a, sh)
                for a in arrays)
    return out if len(out) > 1 else out[0]


def _save_round_checkpoint(dist: DistributedConfig, config: GBDTConfig,
                           trees, base_margin, margins, eval_margins,
                           history, missing_bin: int, F: int,
                           rounds_done: int) -> None:
    model = _as_model(trees, base_margin, config, missing_bin, F)
    arrays = {f"trees/{f}": np.asarray(getattr(model.trees, f))
              for f in TreeArrays._fields}
    arrays["margins"] = np.asarray(margins)
    arrays["train_loss"] = np.asarray(history["train_loss"], np.float32)
    if eval_margins is not None:
        arrays["eval_margins"] = np.asarray(eval_margins)
        arrays["eval_loss"] = np.asarray(history["eval_loss"], np.float32)
    ckpt.save_named(_round_ckpt_dir(dist), arrays, step=rounds_done,
                    keep_last=dist.keep_last,
                    extra_meta={"round": rounds_done,
                                "model": model.meta()})


def _round_ckpt_dir(dist: DistributedConfig) -> str:
    # namespaced under checkpoint_dir so the estimator's serialized
    # bundles (which share the step_<k> layout) never collide with the
    # trainer's round snapshots in the same directory
    return os.path.join(dist.checkpoint_dir, "rounds")


def _restore_round_checkpoint(dist: DistributedConfig, K: Optional[int]):
    """Newest valid step -> (trees list, margins, eval_margins, history
    arrays, rounds_done); None when no checkpoint exists (replay from 0)."""
    if dist.checkpoint_dir is None:
        return None
    try:
        arrays, step, meta = ckpt.restore_named(_round_ckpt_dir(dist))
    except FileNotFoundError:
        return None
    stacked = TreeArrays(*[np.asarray(arrays[f"trees/{f}"])
                           for f in TreeArrays._fields])
    model = model_from_meta(stacked, meta["model"])
    if K is not None:
        trees = _unstack_forests(model.trees, model.n_rounds, K)
    else:
        trees = [TreeArrays(*[a[i] for a in model.trees])
                 for i in range(model.n_trees)]
    margins = jnp.asarray(arrays["margins"])
    eval_margins = (jnp.asarray(arrays["eval_margins"])
                    if "eval_margins" in arrays else None)
    history = {"train_loss": [float(v) for v in arrays["train_loss"]]}
    if "eval_loss" in arrays:
        history["eval_loss"] = [float(v) for v in arrays["eval_loss"]]
    return trees, margins, eval_margins, history, int(meta["round"])


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------
def train_distributed(config: GBDTConfig, data: BinnedDataset, y, *,
                      mesh: Optional[Mesh] = None,
                      dist: Optional[DistributedConfig] = None,
                      eval_set: Optional[Tuple[BinnedDataset, jax.Array]]
                      = None,
                      init_model: Optional[GBDTModel] = None,
                      callback: Optional[Callable[[int, GBDTModel], None]]
                      = None,
                      verbose: bool = False,
                      plan: Optional[ExecutionPlan] = None,
                      recovery: Optional[RecoveryPolicy] = None,
                      shutdown: Optional[GracefulShutdown] = None
                      ) -> TrainResult:
    """Fit a GBDT ensemble data-parallel across ``mesh`` (see module doc).

    ``mesh`` defaults to ``plan.mesh``; one of the two must be set.  The
    result's ``stats`` records the distributed evidence: final shard
    count, restarts survived, and every re-mesh event as
    ``(kind, round, n_shards)`` tuples.

    ``recovery`` (a :class:`repro.resilience.RecoveryPolicy`) arms typed
    recovery on the round dispatch — the same policy object the streaming
    trainer takes, with the distributed semantics:

      * :class:`Preemption` re-meshes onto the survivors, restores the
        newest ``checkpoint.save_named`` step, and deterministically
        replays (the legacy catch-all path, now reserved for actual
        preemptions);
      * other transient failures retry the round on the SAME mesh after
        ``retry_delay_s`` (state is uncommitted and valid — no restore
        needed), bounded by ``max_recoveries``;
      * a device OOM doubles the per-shard histogram sub-batch count
        (``hist_slices``) and retries bit-equally, bounded by
        ``max_oom_halvings``;
      * a :class:`NumericalDivergenceError` — raised by the per-round
        finiteness sentinel on (loss, margins), which costs nothing extra
        because the loop syncs the loss scalar at commit anyway — replays
        the uncommitted round at the original learning rate first,
        backing off by ``divergence_backoff`` only when the SAME round
        diverges twice, bounded by ``max_divergence_rollbacks``.

    Without a policy the legacy behavior is preserved exactly: ANY
    dispatch failure re-meshes and restores, ``dist.max_restarts`` times.
    ``shutdown`` (a :class:`repro.resilience.GracefulShutdown`) finishes
    the in-flight round on a delivered signal, commits it plus a final
    checkpoint, and raises :class:`TrainingInterrupted` carrying the
    partial result.
    """
    if plan is None:
        plan = ExecutionPlan.from_config(config)
    if mesh is None:
        mesh = plan.mesh
    if mesh is None:
        raise ValueError("train_distributed needs a mesh (argument or "
                         "plan.mesh)")
    _check_data_parallel(mesh)
    kernel_plan = _trainer_kernel_plan(plan)
    dist = dist or DistributedConfig()
    if (recovery is not None and recovery.checkpoint_dir is not None
            and dist.checkpoint_dir is None):
        # one policy object drives both trainers: its checkpoint knobs
        # map onto the distributed trainer's save_named plumbing
        dist = dataclasses.replace(dist,
                                   checkpoint_dir=recovery.checkpoint_dir,
                                   checkpoint_every=recovery.checkpoint_every)
    if config.grow_policy != "depthwise":
        raise ValueError("distributed training supports only the depthwise "
                         "grow_policy")

    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs
    y = jnp.asarray(y, jnp.float32)
    if K is not None:
        gbdt_mod._validate_multiclass_labels(
            K, y, eval_set[1] if eval_set is not None else None)
    n, F = data.codes.shape
    n_eval = None if eval_set is None else int(eval_set[1].shape[0])
    y_ev = (jnp.asarray(eval_set[1], jnp.float32)
            if eval_set is not None else None)
    cfg_key = gbdt_mod._fused_step_key(config)

    # -- initial state (identical to core.gbdt.train) ----------------------
    trees: List[TreeArrays] = []
    if init_model is not None:
        if K is not None:
            trees = _unstack_forests(init_model.trees, init_model.n_rounds,
                                     K)
        else:
            trees = [TreeArrays(*[a[i] for a in init_model.trees])
                     for i in range(init_model.n_trees)]
        base_margin = init_model.base_margin
        # per-round sequential seeding (not one batched predict) so a
        # checkpoint resume replays the interrupted fit bit-exactly on a
        # single shard; when a matching named round checkpoint exists it
        # carries the EXACT live margins (the sharded step's fused
        # scale-and-add can differ from any host recomputation in the
        # last ulp), so that wins
        margins = eval_margins = None
        snap = _restore_round_checkpoint(dist, K)
        if snap is not None and snap[4] == init_model.n_rounds and all(
                np.array_equal(np.asarray(u), np.asarray(v))
                for a, b in zip(snap[0], trees) for u, v in zip(a, b)):
            margins, eval_margins = snap[1], snap[2]
        if margins is None:
            margins = gbdt_mod._replay_margins(init_model, data,
                                               kernel_plan)
        if eval_set is not None and eval_margins is None:
            eval_margins = gbdt_mod._replay_margins(init_model,
                                                    eval_set[0],
                                                    kernel_plan)
        if eval_set is None:
            eval_margins = None
    elif K is not None:
        base_margin = np.asarray(loss.base_margin(y), np.float32)
        margins = jnp.broadcast_to(jnp.asarray(base_margin), (n, K))
        eval_margins = (jnp.broadcast_to(jnp.asarray(base_margin),
                                         (n_eval, K))
                        if eval_set is not None else None)
    else:
        base_margin = float(loss.base_margin(y))
        margins = jnp.full((n,), base_margin, jnp.float32)
        eval_margins = (jnp.full((n_eval,), base_margin)
                        if eval_set is not None else None)
    init_margins, init_eval_margins = margins, eval_margins

    history: Dict[str, List[float]] = {"train_loss": []}
    if eval_set is not None:
        history["eval_loss"] = []
    step_times = {"fused_rounds": 0.0}
    key = jax.random.PRNGKey(config.seed)
    start = len(trees)
    end = start + config.n_trees

    devices = list(mesh.devices.flat)
    events: List[Tuple[str, int, int]] = []
    restarts = 0
    hist_slices = 1                    # OOM degradation state (doubles)
    diverged_at = -1                   # round of the last sentinel trip
    rstats = {"recoveries": 0, "oom_halvings": 0, "replayed_rounds": 0,
              "divergence_rollbacks": 0}

    def _mkstats(**extra):
        return {"n_rows": n, "distributed": True,
                "kernel_plan": kernel_plan.describe(),
                "n_shards": n_data_shards(mesh), "restarts": restarts,
                "remesh_events": events, "hist_slices": hist_slices,
                **rstats, **extra}

    def place(new_mesh):
        nonlocal mesh, da, codes, codes_cm, n_pad, margins, eval_margins
        nonlocal y, y_ev, is_cat, ev_codes, ev_codes_cm, cm_packed
        mesh = new_mesh
        # the plan's data-axis spec wins while it matches the live mesh;
        # an elastic re-mesh always lands on a plain ("data",) topology
        if (plan.data_axes
                and set(plan.data_axes) <= set(mesh.axis_names)):
            da = tuple(plan.data_axes)
        else:
            da = data_axes(mesh)
        codes, codes_cm, n_pad, cm_packed = _place_dataset(data, mesh, da)
        y = _replicate(mesh, y)
        margins = _replicate(mesh, margins)
        is_cat = _replicate(mesh, data.is_categorical)
        if eval_set is not None:
            ev_codes, ev_codes_cm = _replicate(mesh, eval_set[0].codes,
                                               eval_set[0].codes_cm)
            y_ev = _replicate(mesh, y_ev)
            eval_margins = _replicate(mesh, eval_margins)

    codes = codes_cm = is_cat = ev_codes = ev_codes_cm = None
    n_pad, da, cm_packed = 0, (), False
    place(mesh)

    t_loop = time.perf_counter()
    t_idx = start
    while t_idx < end:
        try:
            # elastic grow/shrink between rounds: a changed device list
            # re-places the (mesh-agnostic) training state, no restore
            if dist.fault_schedule is not None:
                dist.fault_schedule.apply("elastic", t_idx)
            if dist.available_devices is not None:
                want = list(dist.available_devices(t_idx))
                if [d.id for d in want] != [d.id for d in devices]:
                    kind = "grow" if len(want) > len(devices) else "shrink"
                    devices = want
                    place(data_parallel_mesh(devices))
                    events.append((kind, t_idx, n_data_shards(mesh)))
                    if verbose:
                        print(f"[dist] {kind} -> {n_data_shards(mesh)} "
                              f"shards at round {t_idx}")
            step = _distributed_round_step(cfg_key, kernel_plan, mesh,
                                           tuple(da), n, n_pad, F,
                                           data.n_bins, n_eval, cm_packed,
                                           hist_slices)
            tkey = jax.random.fold_in(key, t_idx)  # mesh-invariant stream
            if eval_set is None:
                new_margins, tree, tl = step(margins, y, tkey, codes,
                                             codes_cm, is_cat)
                new_eval = ev = None
            else:
                new_margins, new_eval, tree, tl, ev = step(
                    margins, eval_margins, y, y_ev, tkey, codes, codes_cm,
                    ev_codes, ev_codes_cm, is_cat)
            jax.block_until_ready(new_margins)
            if dist.fault_injector is not None:
                dist.fault_injector.check(t_idx)   # worker dies mid-round
            if dist.fault_schedule is not None:
                dist.fault_schedule.apply("round", t_idx)
            # numerical divergence sentinel at log_every cadence (same
            # as the fused engine): a NaN-max reduction over the new
            # margins — max |x| propagates NaN and saturates at inf, so
            # the single fused reduction is an exact finiteness probe
            if (recovery is not None
                    and (t_idx % config.log_every == 0
                         or t_idx == end - 1)
                    and not bool(jnp.isfinite(
                        jnp.maximum(jnp.max(jnp.abs(new_margins)),
                                    jnp.abs(tl))))):
                raise NumericalDivergenceError(
                    f"non-finite loss/margins at round {t_idx}",
                    round_index=t_idx, what="loss/margins")
        except Exception as e:  # noqa: BLE001 — classified below
            action = classify(e) if recovery is not None else "remesh"
            if action == "transient" and isinstance(e, Preemption):
                action = "remesh"      # preemptions re-mesh; others retry
            if action == "divergence":
                if (rstats["divergence_rollbacks"]
                        >= recovery.max_divergence_rollbacks):
                    raise
                rstats["divergence_rollbacks"] += 1
                _metrics.record("recoveries")
                if diverged_at == t_idx:
                    # the same round diverged on its replay: genuine
                    # divergence — shrink the steps (recompiles the round)
                    cfg_key = dataclasses.replace(
                        cfg_key,
                        learning_rate=(cfg_key.learning_rate
                                       * recovery.divergence_backoff))
                    if verbose:
                        print(f"[dist] round {t_idx} diverged twice; "
                              f"learning_rate -> "
                              f"{cfg_key.learning_rate:g}")
                elif verbose:
                    print(f"[dist] divergence at round {t_idx}; replaying "
                          "from the last finite round")
                diverged_at = t_idx
                continue   # the round is uncommitted: replay = rollback
            if action == "oom":
                if rstats["oom_halvings"] >= recovery.max_oom_halvings:
                    raise
                rstats["oom_halvings"] += 1
                _metrics.record("recoveries")
                hist_slices *= 2
                if verbose:
                    print(f"[dist] device OOM at round {t_idx}: "
                          f"hist_slices -> {hist_slices}; retrying round")
                continue
            if action == "transient":
                if rstats["recoveries"] >= recovery.max_recoveries:
                    raise
                rstats["recoveries"] += 1
                _metrics.record("recoveries")
                if recovery.retry_delay_s:
                    time.sleep(recovery.retry_delay_s)
                if verbose:
                    print(f"[dist] transient failure at round {t_idx} "
                          f"({type(e).__name__}: {e}); retrying on the "
                          "same mesh")
                continue
            if action == "fatal":
                raise
            # preemption (or any failure under the legacy no-policy
            # contract): re-mesh onto the survivors, restore the newest
            # checkpoint, deterministically replay
            restarts += 1
            if restarts > dist.max_restarts:
                raise
            if recovery is not None:
                _metrics.record("recoveries")
            surv = (dist.survivors(devices) if dist.survivors is not None
                    else (devices[:-1] if len(devices) > 1 else devices))
            devices = list(surv)
            place(data_parallel_mesh(devices))
            events.append(("shrink", t_idx, n_data_shards(mesh)))
            if verbose:
                print(f"[dist] fault at round {t_idx} ({e}); resuming on "
                      f"{n_data_shards(mesh)} shards")
            t_before = t_idx
            restored = _restore_round_checkpoint(dist, K)
            if restored is None:       # no checkpoint yet: replay the fit
                trees = list(trees[:start])
                margins, eval_margins = init_margins, init_eval_margins
                history = {k: [] for k in history}
                t_idx = start
            else:
                trees, margins, eval_margins, history, t_idx = restored
            rstats["replayed_rounds"] += max(0, t_before - t_idx)
            margins = _replicate(mesh, margins)
            if eval_margins is not None:
                eval_margins = _replicate(mesh, eval_margins)
            continue                    # deterministic replay from t_idx

        # -- commit the round ---------------------------------------------
        margins, eval_margins = new_margins, new_eval
        # committed trees go to host memory: the ensemble must outlive any
        # mesh (an elastic re-mesh would otherwise mix device assemblies
        # when the final model stacks rounds from different meshes)
        trees.append(TreeArrays(*[np.asarray(a) for a in tree]))
        history["train_loss"].append(float(tl))
        if eval_set is not None:
            history["eval_loss"].append(float(ev))
        rounds_done = t_idx + 1
        if (dist.checkpoint_dir is not None
                and rounds_done % dist.checkpoint_every == 0):
            _save_round_checkpoint(dist, config, trees, base_margin,
                                   margins, eval_margins, history,
                                   data.missing_bin, F, rounds_done)
        if verbose and (t_idx % config.log_every == 0 or t_idx == end - 1):
            print(f"[dist] round {t_idx:4d}  "
                  f"train_loss={history['train_loss'][-1]:.6f}  "
                  f"shards={n_data_shards(mesh)}")
        if callback is not None:
            callback(t_idx, _as_model(trees, base_margin, config,
                                      data.missing_bin, F))
        if shutdown is not None and shutdown.requested:
            # the in-flight round is committed; persist the exact
            # resumable state, then exit with a typed status
            if (dist.checkpoint_dir is not None
                    and rounds_done % dist.checkpoint_every):
                _save_round_checkpoint(dist, config, trees, base_margin,
                                       margins, eval_margins, history,
                                       data.missing_bin, F, rounds_done)
            step_times["fused_rounds"] = time.perf_counter() - t_loop
            partial = TrainResult(
                model=_as_model(trees, base_margin, config,
                                data.missing_bin, F),
                history=history, step_times=step_times,
                stats=_mkstats(interrupted=True))
            raise TrainingInterrupted(
                f"shutdown ({shutdown.signal_name}) after round {t_idx}",
                rounds_done=len(trees), signal_name=shutdown.signal_name,
                checkpoint_dir=dist.checkpoint_dir, result=partial)
        t_idx += 1

    step_times["fused_rounds"] = time.perf_counter() - t_loop
    return TrainResult(
        model=_as_model(trees, base_margin, config, data.missing_bin, F),
        history=history, step_times=step_times, stats=_mkstats())
