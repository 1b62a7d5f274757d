"""Distributed GBDT: the paper's parallel decomposition at pod scale.

Paper §III-B: "the records can be partitioned among the clusters so that
each cluster generates a set of histograms which are reduced at the end of
the step" — inter-record parallelism → the ``("pod", "data")`` mesh axes.
The group-by-field mapping (§III-A) lifts to the chip level: fields (and
their histogram slabs) are sharded across ``"model"`` — intra-record
parallelism.  Cross-shard traffic per level is then

  * one histogram psum over the data axes (O(nodes·local_fields·bins), ≪
    record traffic — the paper's cluster reduction), and
  * one tiny per-node argmax combine across field shards (step ②).

``distributed_histogram`` / ``distributed_fit_tree_shardmap`` make these
collectives *explicit* with shard_map; ``pjit_fit_tree`` lowers the whole
unmodified ``core.tree.fit_tree`` under GSPMD and lets XLA place the same
collectives (the two paths are tested equal).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.plan import ExecutionPlan
from repro.core import splits as splits_mod
from repro.core import tree as tree_mod
from repro.core.binning import PackedCodes, as_unpacked
from repro.kernels import ops
from repro.kernels.ref import TreeArrays
from repro.launch.mesh import data_axes


def _warn_loose_strategy(hist_strategy: Optional[str]) -> None:
    """One release path for the distributed growers' loose hist kwarg —
    the defaulting itself now lives in ``ExecutionPlan.from_config``."""
    if hist_strategy is not None and hist_strategy != "auto":
        warnings.warn(
            "legacy strategy-string kwargs are deprecated; pass "
            "plan=ExecutionPlan(hist_strategy=...) instead",
            DeprecationWarning, stacklevel=3)


def gbdt_shardings(mesh: Mesh):
    """NamedShardings for the GBDT training inputs on ``mesh``."""
    da = data_axes(mesh)
    return {
        "codes": NamedSharding(mesh, P(da, "model")),     # records x fields
        "codes_cm": NamedSharding(mesh, P("model", da)),  # fields x records
        "per_record": NamedSharding(mesh, P(da)),         # g, h, node_ids, y
        "per_field": NamedSharding(mesh, P(None, "model")),
        "replicated": NamedSharding(mesh, P()),
    }


def padded_record_count(n: int, mesh: Mesh) -> int:
    """Records padded up to a multiple of the data-axis product (elastic
    re-meshing can land on shard counts that do not divide n)."""
    n_da = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    return -(-n // n_da) * n_da


def shard_dataset(data, mesh: Mesh):
    """device_put the binned dataset onto the mesh (records x fields grid).

    Records are padded (edge-replicated) to divide the data axes; padded
    rows must carry g = h = 0 in training (no histogram contribution) and
    their predictions are sliced off by callers.
    """
    sh = gbdt_shardings(mesh)
    n = data.codes.shape[0]
    n_pad = padded_record_count(n, mesh) - n
    # the mesh grid shards BOTH axes of each layout; a nibble-packed axis
    # cannot be split mid-byte, so distributed placement uses the plain
    # uint8 layouts (single-device training keeps the packed halving)
    codes = jnp.pad(as_unpacked(data.codes), ((0, n_pad), (0, 0)),
                    mode="edge")
    codes_cm = jnp.pad(as_unpacked(data.codes_cm), ((0, 0), (0, n_pad)),
                       mode="edge")
    return data.__class__(
        codes=jax.device_put(codes, sh["codes"]),
        codes_cm=jax.device_put(codes_cm, sh["codes_cm"]),
        is_categorical=jax.device_put(data.is_categorical, sh["replicated"]),
        n_bins=data.n_bins, bin_edges=data.bin_edges,
        n_value_bins=data.n_value_bins)


# --------------------------------------------------------------------------
# explicit shard_map path — the paper's communication schedule, verbatim
# --------------------------------------------------------------------------
def distributed_histogram(mesh: Mesh, codes, g, h, node_ids, *,
                          n_nodes: int, n_bins: int,
                          plan: Optional[ExecutionPlan] = None,
                          strategy: Optional[str] = None):
    """Step ① with explicit collectives.

    Local kernel on (records/D, fields/M) shard, then one psum over the data
    axes.  Returns the histogram sharded over fields on "model"
    (group-by-field at chip granularity): (n_nodes, F, n_bins, 2).
    """
    da = data_axes(mesh)
    _warn_loose_strategy(strategy)
    plan = ExecutionPlan.from_config(base=plan, hist_strategy=strategy)
    if isinstance(codes, PackedCodes):
        codes = codes.unpack()     # the field axis is sharded mid-byte

    def local(codes_l, g_l, h_l, node_l):
        hist_l = ops.build_histogram(codes_l, g_l, h_l, node_l,
                                     n_nodes=n_nodes, n_bins=n_bins,
                                     plan=plan)
        # the paper's end-of-step-① reduction across record partitions
        return jax.lax.psum(hist_l, da)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(da, "model"), P(da), P(da), P(da)),
                       out_specs=P(None, "model"))
    return fn(codes, g, h, node_ids)


def distributed_split_combine(mesh: Mesh, hist, is_cat_field, field_mask,
                              lambda_, gamma, min_child_weight, n_fields: int):
    """Step ② across field shards: local best per shard, tiny global argmax.

    hist is field-sharded (model); each shard evaluates its own fields and
    contributes one candidate per node; the cross-shard combine moves only
    O(nodes x shards x 6) scalars — the paper's 'bins ≪ records' argument.
    """
    m_size = mesh.shape["model"]
    f_local = n_fields // m_size

    def local(hist_l, cat_l, mask_l):
        best = splits_mod.find_best_splits(hist_l, cat_l, mask_l, lambda_,
                                           gamma, min_child_weight)
        shard = jax.lax.axis_index("model")
        cand = jnp.stack([
            best.gain,
            (best.feature + shard * f_local).astype(jnp.float32),
            best.threshold.astype(jnp.float32),
            best.is_cat.astype(jnp.float32),
            best.default_left.astype(jnp.float32),
            best.node_g, best.node_h, best.left_h], axis=-1)  # (NN, 8)
        allc = jax.lax.all_gather(cand, "model")              # (M, NN, 8)
        win = jnp.argmax(allc[..., 0], axis=0)                # (NN,)
        sel = jnp.take_along_axis(allc, win[None, :, None], axis=0)[0]
        return sel

    # the post-all_gather argmax is replicated across "model" by value, which
    # varying-manual-axes inference cannot prove — disable the static check
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, "model"), P("model"), P("model")),
                       out_specs=P(), check_vma=False)
    sel = fn(hist, is_cat_field, field_mask)
    return splits_mod.SplitDecision(
        gain=sel[:, 0], feature=sel[:, 1].astype(jnp.int32),
        threshold=sel[:, 2].astype(jnp.int32),
        is_cat=sel[:, 3].astype(jnp.int32),
        default_left=sel[:, 4].astype(jnp.int32),
        node_g=sel[:, 5], node_h=sel[:, 6], left_h=sel[:, 7])


def distributed_partition_bits(mesh: Mesh, node_ids, codes_cm, feat, thr,
                               cat, dl, *, missing_bin: int, n_fields: int):
    """Step ③ with owner-evaluates semantics (paper §III-B adapted).

    Instead of gathering each level's predicate columns to every data
    shard (O(nn x records) cross-chip bytes), the model shard that OWNS a
    node's split field evaluates the predicate locally and contributes a
    2-bit verdict; one int8 psum over "model" (O(records) bytes) routes
    every record — the TPU analog of Booster streaming pointer lists
    instead of record fields.
    """
    import jax.numpy as jnp
    da = data_axes(mesh)
    m_size = mesh.shape["model"]
    f_local = n_fields // m_size
    if isinstance(codes_cm, PackedCodes):
        codes_cm = codes_cm.unpack()   # the record axis is sharded mid-byte

    def local(codes_cm_l, node_l):
        rank = jax.lax.axis_index("model")
        owns = (feat >= 0) & (feat // f_local == rank)          # (nn,)
        local_idx = jnp.clip(feat - rank * f_local, 0, f_local - 1)
        codes_sel = codes_cm_l[local_idx]                       # (nn, n_l)
        n_l = codes_sel.shape[1]
        code = codes_sel[node_l, jnp.arange(n_l)].astype(jnp.int32)
        t, c, d = thr[node_l], cat[node_l], dl[node_l]
        left = jnp.where(c == 1, code == t, code <= t)
        left = jnp.where(code == missing_bin, d == 1, left)
        verdict = jnp.where(owns[node_l],
                            jnp.where(left, 2, 1), 0).astype(jnp.int8)
        # psum stays int8: exactly one owner contributes, max total == 2
        total = jax.lax.psum(verdict, "model")
        go_left = total != 1          # 0 == pass-through -> left
        return 2 * node_l + (1 - go_left.astype(jnp.int32))

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("model", da), P(da)),
                         out_specs=P(da), check_vma=False)(codes_cm, node_ids)


def distributed_fit_tree(mesh: Mesh, codes, codes_cm, g, h, *, depth: int,
                         n_bins: int, missing_bin: int, is_cat_field,
                         field_mask, lambda_: float, gamma: float,
                         min_child_weight: float,
                         plan: Optional[ExecutionPlan] = None,
                         hist_strategy: Optional[str] = None,
                         hist_dtype=None, partition_bits: bool = False):
    """Level-wise grower with the paper's EXPLICIT communication schedule.

    Per level: local histograms -> one psum over the data axes (cast to
    ``hist_dtype`` first when set — bf16 halves the only cross-pod
    collective, the gradient-compression knob of DESIGN.md §6) -> per-shard
    split finding on local fields -> tiny cross-shard argmax -> partition.
    Returns the same TreeArrays as ``core.tree.fit_tree``.
    """
    import jax.numpy as jnp
    from repro.kernels.ref import TreeArrays
    from repro.core.splits import leaf_weight

    _warn_loose_strategy(hist_strategy)
    plan = ExecutionPlan.from_config(base=plan, hist_strategy=hist_strategy,
                                     distributed=True)
    da = data_axes(mesh)
    codes = as_unpacked(codes)         # both shard grids split mid-byte
    codes_cm = as_unpacked(codes_cm)
    F = codes.shape[1]
    n = codes.shape[0]
    n_int, n_leaf = 2 ** depth - 1, 2 ** depth

    feature = jnp.full((n_int,), -1, jnp.int32)
    threshold = jnp.zeros((n_int,), jnp.int32)
    is_cat = jnp.zeros((n_int,), jnp.int32)
    default_left = jnp.zeros((n_int,), jnp.int32)
    value_bottom = jnp.zeros((n_leaf,), jnp.float32)
    value_set = jnp.zeros((n_leaf,), bool)
    node_ids = jnp.zeros((n,), jnp.int32)

    def local_hist(codes_l, g_l, h_l, node_l, nn):
        hist_l = ops.build_histogram(codes_l, g_l, h_l, node_l, n_nodes=nn,
                                     n_bins=n_bins, plan=plan)
        if hist_dtype is not None:      # compress the cross-shard reduction
            hist_l = hist_l.astype(hist_dtype)
        return jax.lax.psum(hist_l, da).astype(jnp.float32)

    for level in range(depth):
        nn = 2 ** level
        off = nn - 1
        reps = 2 ** (depth - level)
        hist = jax.shard_map(
            functools.partial(local_hist, nn=nn), mesh=mesh,
            in_specs=(P(da, "model"), P(da), P(da), P(da)),
            out_specs=P(None, "model"))(codes, g, h, node_ids)
        best = distributed_split_combine(mesh, hist, is_cat_field,
                                         field_mask, lambda_, gamma,
                                         min_child_weight, F)
        resolved = value_set[jnp.arange(nn) * reps]
        do_split = (best.gain > 0.0) & (~resolved)
        w = leaf_weight(best.node_g, best.node_h, lambda_)
        newly = (~do_split) & (~resolved)
        mask_b = jnp.repeat(newly, reps)
        value_bottom = jnp.where(mask_b & (~value_set),
                                 jnp.repeat(w, reps), value_bottom)
        value_set = value_set | mask_b
        feature = jax.lax.dynamic_update_slice(
            feature, jnp.where(do_split, best.feature, -1), (off,))
        threshold = jax.lax.dynamic_update_slice(threshold, best.threshold,
                                                 (off,))
        is_cat = jax.lax.dynamic_update_slice(is_cat, best.is_cat, (off,))
        default_left = jax.lax.dynamic_update_slice(
            default_left, best.default_left, (off,))
        if partition_bits:
            node_ids = distributed_partition_bits(
                mesh, node_ids, codes_cm,
                jnp.where(do_split, best.feature, -1), best.threshold,
                best.is_cat, best.default_left,
                missing_bin=missing_bin, n_fields=F)
        else:
            codes_lvl = codes_cm[jnp.where(do_split, best.feature, 0)]
            node_ids = ops.partition_level(
                node_ids, codes_lvl.T,
                jnp.where(do_split, jnp.arange(nn, dtype=jnp.int32), -1),
                best.threshold, best.is_cat, best.default_left,
                missing_bin=missing_bin, plan=plan)

    Gb = jax.ops.segment_sum(g.astype(jnp.float32), node_ids, n_leaf)
    Hb = jax.ops.segment_sum(h.astype(jnp.float32), node_ids, n_leaf)
    wb = leaf_weight(Gb, Hb, lambda_)
    value_bottom = jnp.where(value_set, value_bottom, wb)
    return TreeArrays(feature=feature, threshold=threshold, is_cat=is_cat,
                      default_left=default_left, leaf_value=value_bottom)


# --------------------------------------------------------------------------
# GSPMD path — unmodified core grower under pjit
# --------------------------------------------------------------------------
def pjit_fit_tree(mesh: Mesh, *, depth: int, n_bins: int, missing_bin: int,
                  lambda_: float, gamma: float, min_child_weight: float,
                  plan: Optional[ExecutionPlan] = None,
                  hist_strategy: Optional[str] = None,
                  donate: bool = False):
    """jit the unmodified level-wise grower with mesh shardings.

    Works on any mesh (including the 512-chip production mesh in the
    dry-run); GSPMD inserts the same psum/all-gather schedule the explicit
    path spells out.
    """
    sh = gbdt_shardings(mesh)
    _warn_loose_strategy(hist_strategy)
    plan = ExecutionPlan.from_config(base=plan, hist_strategy=hist_strategy,
                                     distributed=True)

    fn = functools.partial(
        tree_mod.fit_tree, depth=depth, n_bins=n_bins,
        missing_bin=missing_bin, lambda_=lambda_, gamma=gamma,
        min_child_weight=min_child_weight, plan=plan)

    def wrapped(codes, codes_cm, g, h, is_cat_field, field_mask):
        return fn(codes, codes_cm, g, h, is_cat_field=is_cat_field,
                  field_mask=field_mask)

    return jax.jit(
        wrapped,
        in_shardings=(sh["codes"], sh["codes_cm"], sh["per_record"],
                      sh["per_record"], sh["replicated"], sh["replicated"]),
        out_shardings=NamedSharding(mesh, P()),
    )
