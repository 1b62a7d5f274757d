"""Tree growing — steps ①–④ of the paper's training algorithm.

Two growers, matching the two configurations described in §II-A:

  * ``fit_tree``          — the *level-by-level* configuration ("streams in
    all the input records and histogram-bins the relevant records at each
    vertex ... maintains a separate histogram per vertex").  This is the
    fixed-shape, fully jittable primary path: every record carries a
    level-local node id; one histogram pass per level computes all vertex
    histograms at once; the partition kernel routes records to children.
    One full-data scan per level by default; with
    ``ExecutionPlan.hist_subtraction`` levels > 0 bin only the smaller
    child of every split parent (a compacted half-stream pass) and derive
    the sibling as ``parent − smaller`` — the paper's §II-A trick applied
    level-synchronously.

  * ``fit_tree_lossguide`` — the *vertex-by-vertex* (leaf-wise, best-first)
    configuration with the paper's step-① optimization applied literally:
    bin only the smaller child and derive the sibling by subtracting from
    the parent's histogram ("without any explicit binning at the other
    child", §II-A).  Host-driven control flow (a gain heap), device math.

Both emit the same fixed-shape ``TreeArrays`` (complete binary tree with
pass-through nodes), so every downstream consumer (partition, traversal,
inference, checkpointing, sharding) is grower-agnostic.
"""
from __future__ import annotations

import functools
import heapq
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.plan import ExecutionPlan, resolve_plan
from repro.core import splits as splits_mod
from repro.core.binning import PackedCodes
from repro.kernels import ops
from repro.kernels.ref import TreeArrays


def _lift_loose_kwargs(plan: Optional[ExecutionPlan],
                       **loose) -> ExecutionPlan:
    """Resolve the growers' plan, lifting any legacy per-step loose kwargs
    (``hist_strategy=`` etc.) into it with a deprecation warning — one
    release path before the growers take ``plan=`` only."""
    passed = sorted(k for k, v in loose.items()
                    if v is not None and v != "auto" and v is not False)
    if passed:
        warnings.warn(
            "legacy strategy-string kwargs are deprecated; pass "
            f"plan=ExecutionPlan({', '.join(f'{k}=...' for k in passed)}) "
            "instead", DeprecationWarning, stacklevel=3)
    return resolve_plan(plan, **loose)


def _gather_fields(codes_cm, idx):
    """Leading-axis (field) gather from the column-major copy, unpacked.

    ``codes_cm`` is (F, n) — plain uint8 or :class:`PackedCodes` over the
    record axis.  Packed rows are selected WITHOUT unpacking the full
    matrix; only the gathered level rows expand to uint8."""
    if isinstance(codes_cm, PackedCodes):
        return codes_cm[idx].unpack()
    return codes_cm[idx]


def fit_tree(codes, codes_cm, g, h, *, depth: int, n_bins: int,
             missing_bin: int, is_cat_field, field_mask,
             lambda_: float, gamma: float, min_child_weight: float,
             plan: Optional[ExecutionPlan] = None,
             hist_strategy: Optional[str] = None,
             partition_strategy: Optional[str] = None,
             host_offload_split: Optional[bool] = None) -> TreeArrays:
    """Grow one depth-``depth`` tree level-by-level (fixed shapes, jittable).

    codes: (n, F) uint8 row-major (step-① input);
    codes_cm: (F, n) uint8 column-major redundant copy (step-③ input);
    g, h: (n,) float32 gradient statistics.  ``plan`` selects the kernel
    strategies (the legacy per-step string kwargs are deprecated — they
    still lift into the plan, with a ``DeprecationWarning``, for one
    release).

    The scalar grower IS the K=1 slice of ``fit_forest`` — one body to
    maintain; the class axis costs nothing at K=1 (same kernels, same
    matmul shapes, bit-identical results).
    """
    plan = _lift_loose_kwargs(plan, hist_strategy=hist_strategy,
                              partition_strategy=partition_strategy,
                              host_offload_split=host_offload_split)
    forest = _fit_forest_impl(codes, codes_cm, g[None], h[None],
                              depth=depth, n_bins=n_bins,
                              missing_bin=missing_bin,
                              is_cat_field=is_cat_field,
                              field_mask=field_mask, lambda_=lambda_,
                              gamma=gamma,
                              min_child_weight=min_child_weight, plan=plan)
    return TreeArrays(*[a[0] for a in forest])


# --------------------------------------------------------------------------
# class-batched grower: K per-class trees per round (multi-class boosting)
# --------------------------------------------------------------------------
def fit_forest(codes, codes_cm, g, h, *, depth: int, n_bins: int,
               missing_bin: int, is_cat_field, field_mask,
               lambda_: float, gamma: float, min_child_weight: float,
               plan: Optional[ExecutionPlan] = None,
               hist_strategy: Optional[str] = None,
               partition_strategy: Optional[str] = None,
               host_offload_split: Optional[bool] = None) -> TreeArrays:
    """Grow K trees level-synchronously (one per class, shared code stream).

    g, h: (K, n) per-class gradient statistics.  Every per-node array of
    ``fit_tree`` gains a leading class axis; the step-① histogram is built
    ONCE per level for all classes (the class-batched ``build_histogram``),
    so the record/code stream is read once per level regardless of K.
    Returns TreeArrays with leading (K, ...) axes.

    The loose ``hist_strategy=`` / ``partition_strategy=`` /
    ``host_offload_split=`` kwargs are deprecated (lifted into the plan
    with a warning, OUTSIDE the jit so the warning actually fires on
    every call rather than only at trace time).
    """
    plan = _lift_loose_kwargs(plan, hist_strategy=hist_strategy,
                              partition_strategy=partition_strategy,
                              host_offload_split=host_offload_split)
    return _fit_forest_impl(codes, codes_cm, g, h, depth=depth,
                            n_bins=n_bins, missing_bin=missing_bin,
                            is_cat_field=is_cat_field, field_mask=field_mask,
                            lambda_=lambda_, gamma=gamma,
                            min_child_weight=min_child_weight, plan=plan)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "n_bins", "missing_bin", "plan"))
def _fit_forest_jit(codes, codes_cm, g, h, *, depth: int, n_bins: int,
                    missing_bin: int, is_cat_field, field_mask,
                    lambda_: float, gamma: float, min_child_weight: float,
                    plan: ExecutionPlan) -> TreeArrays:
    n, F = codes.shape
    K = g.shape[0]
    n_int = 2 ** depth - 1

    feature = jnp.full((K, n_int), -1, jnp.int32)
    threshold = jnp.zeros((K, n_int), jnp.int32)
    is_cat = jnp.zeros((K, n_int), jnp.int32)
    default_left = jnp.zeros((K, n_int), jnp.int32)

    node_ids = jnp.zeros((K, n), jnp.int32)        # per-class vertex ids
    find = (splits_mod.find_best_splits_host if plan.host_offload_split
            else splits_mod.find_best_splits)

    part = jax.vmap(functools.partial(ops.partition_level,
                                      missing_bin=missing_bin, plan=plan))

    state = (feature, threshold, is_cat, default_left)
    prev_hist = None
    for level in range(depth):
        nn = 2 ** level

        # step ① — one batched pass covers all K class partitions; with
        # plan.hist_subtraction, levels > 0 bin only the smaller child of
        # each parent and derive the sibling from the previous level's hist
        if plan.hist_subtraction and level > 0:
            hist = _subtract_level_hist(codes, g, h, node_ids, prev_hist,
                                        n_nodes=nn, n_bins=n_bins, plan=plan)
        else:
            hist = ops.build_histogram(codes, g, h, node_ids, n_nodes=nn,
                                       n_bins=n_bins, plan=plan)
        prev_hist = hist                                      # (K,nn,F,NB,2)
        # step ② — split decisions + tree-table updates (shared with the
        # chunked grower, which accumulates the same hist across chunks)
        state, best, do_split = _decide_level(
            hist, level, state, is_cat_field, field_mask, lambda_,
            gamma, min_child_weight, find)

        # step ③ — per-class predicate columns from the column-major copy
        codes_lvl = _gather_fields(
            codes_cm, jnp.where(do_split, best.feature, 0))     # (K,nn,n)
        node_ids = part(
            node_ids, codes_lvl.transpose(0, 2, 1),
            jnp.where(do_split,
                      jnp.broadcast_to(jnp.arange(nn, dtype=jnp.int32),
                                       (K, nn)), -1),
            best.threshold, best.is_cat, best.default_left)

    return state, node_ids


def _fit_forest_impl(codes, codes_cm, g, h, *, lambda_, **kw) -> TreeArrays:
    """The level loop, then the bottom-leaf settle as its own jitted step —
    the very executable the chunked grower runs, so the two growers'
    leaves round alike."""
    state, node_ids = _fit_forest_jit(codes, codes_cm, g, h,
                                      lambda_=lambda_, **kw)
    feature, threshold, is_cat, default_left = state
    return TreeArrays(feature=feature, threshold=threshold, is_cat=is_cat,
                      default_left=default_left,
                      leaf_value=_settle_jit(g, h, node_ids, feature,
                                             lambda_))


def _decide_level(hist, level, state, is_cat_field, field_mask,
                  lambda_, gamma, min_child_weight, find):
    """Step ② for one level: pick splits from the (K, nn, F, NB, 2) level
    histogram and fold them into the tree-table ``state``.  Pure jnp on
    node-sized arrays — shared verbatim by the in-memory (jitted) and
    chunked (host-driven) growers, so both emit identical trees for
    identical histograms."""
    feature, threshold, is_cat, default_left = state
    K, nn, F, n_bins, _ = hist.shape
    off = nn - 1

    # find_best_splits is vectorized over nodes: fold the class axis into
    # the node axis (works for the host offload too)
    flat = find(hist.reshape(K * nn, F, n_bins, 2), is_cat_field,
                field_mask, lambda_, gamma, min_child_weight)
    best = splits_mod.SplitDecision(*[a.reshape(K, nn) for a in flat])

    # a node whose parent did not split is resolved: it holds the
    # parent's records on the pass-through spine and never splits
    parent = feature[:, jnp.maximum(nn // 2 - 1, 0) + jnp.arange(nn) // 2]
    resolved = (parent < 0) if level > 0 else jnp.zeros((K, nn), bool)
    do_split = (best.gain > 0.0) & (~resolved)

    feature = jax.lax.dynamic_update_slice(
        feature, jnp.where(do_split, best.feature, -1), (0, off))
    threshold = jax.lax.dynamic_update_slice(threshold, best.threshold,
                                             (0, off))
    is_cat = jax.lax.dynamic_update_slice(is_cat, best.is_cat, (0, off))
    default_left = jax.lax.dynamic_update_slice(
        default_left, best.default_left, (0, off))
    state = (feature, threshold, is_cat, default_left)
    return state, best, do_split


_LEAF_LANES = 8192     # records summed side by side per leaf-sum step


def leaf_sums(stat, node_ids, n_leaf: int):
    """(K, n) per-record statistic -> (K, n_leaf) per-leaf sums.

    Summed in one explicit order, with selects and adds only: a scan over
    record blocks accumulates ``_LEAF_LANES`` running sums per leaf, and
    a halving tree folds them.  A scatter, reduce or contraction would
    leave the order to XLA, which picks it by how it fuses the program
    around the op — the in-memory, chunked, fused and sharded growers
    would then round the same records differently."""
    K, n = stat.shape
    lanes = min(_LEAF_LANES, 1 << max(0, (n - 1).bit_length()))
    pad = -n % lanes
    stat = jnp.pad(stat.astype(jnp.float32), ((0, 0), (0, pad)))
    nid = jnp.pad(node_ids, ((0, 0), (0, pad)), constant_values=-1)
    leaf = jnp.arange(n_leaf, dtype=nid.dtype)[None, :, None]

    def step(acc, xs):
        s, i = xs                                              # (K, lanes)
        return acc + jnp.where(i[:, None, :] == leaf, s[:, None, :],
                               0.0), None

    xs = (stat.reshape(K, -1, lanes).transpose(1, 0, 2),
          nid.reshape(K, -1, lanes).transpose(1, 0, 2))
    acc, _ = jax.lax.scan(step, jnp.zeros((K, n_leaf, lanes), jnp.float32),
                          xs)
    while acc.shape[-1] > 1:
        half = acc.shape[-1] // 2
        acc = acc[..., :half] + acc[..., half:]
    return acc[..., 0]


def settle_leaves(Gb, Hb, feature, lambda_):
    """(K, n_leaf) bottom-slot G/H sums -> (K, n_leaf) leaf weights.

    A node that stops splitting sends its records left at every later
    level, so they all land in the leftmost bottom slot of its subtree;
    every slot of that subtree takes the weight of that anchor slot.
    Every leaf, early or not, is thus weighed from per-record sums in
    the one order ``leaf_sums`` fixes — never from a histogram, whose
    cells round differently per shard count and per strategy."""
    K, n_leaf = Gb.shape
    depth = n_leaf.bit_length() - 1
    j = jnp.arange(n_leaf, dtype=jnp.int32)
    anchor = jnp.broadcast_to(j, (K, n_leaf))
    for level in reversed(range(depth)):      # the topmost stopped node wins
        shift = depth - level
        stopped = feature[:, (2 ** level - 1) + (j >> shift)] < 0
        anchor = jnp.where(stopped, (j >> shift) << shift, anchor)
    wb = splits_mod.leaf_weight(Gb, Hb, lambda_)
    return jnp.take_along_axis(wb, anchor, axis=1)


@jax.jit
def _settle_jit(g, h, node_ids, feature, lambda_):
    """Leaf weights of every bottom slot from the final node ids."""
    n_leaf = feature.shape[1] + 1
    return settle_leaves(leaf_sums(g, node_ids, n_leaf),
                         leaf_sums(h, node_ids, n_leaf), feature, lambda_)


# --------------------------------------------------------------------------
# histogram subtraction (paper §II-A) for the level-wise growers
# --------------------------------------------------------------------------
def _child_is_smaller(smaller_is_left):
    """(K, NN/2) per-parent 'left child is smaller' -> (K, NN) per-child
    'this node is the smaller sibling' (children of parent p sit at slots
    2p / 2p+1)."""
    sil2 = jnp.repeat(smaller_is_left, 2, axis=1)             # (K, NN)
    left_slot = (jnp.arange(sil2.shape[1]) % 2) == 0
    return jnp.where(left_slot[None, :], sil2, ~sil2)


def _combine_sibling_hist(parent_hist, small, is_small):
    """Derive the level histogram from the smaller-child partial histogram:
    ``hist[c] = small[c]`` where c is the smaller sibling, else
    ``parent[c // 2] − small[sibling(c)]`` — the paper's "without any
    explicit binning at the other child".  Exact in real arithmetic; in
    float32 the derived sibling reassociates the parent sum (documented
    tolerance, see docs/api.md)."""
    K, nn, F, NB, S = small.shape
    sib = small.reshape(K, nn // 2, 2, F, NB, S)[:, :, ::-1]
    derived = jnp.repeat(parent_hist, 2, axis=1) - sib.reshape(small.shape)
    return jnp.where(is_small[:, :, None, None, None], small, derived)


def _compact_selected(codes, g, h, nid, sel, n_half: int):
    """Pack the ``sel``-marked records into a fixed (n_half, ...) buffer.

    ``n_half = n // 2`` always fits: summed over parents,
    ``min(left, right) <= (left + right) / 2``, so the smaller children
    hold at most ``n // 2`` records (selection is by RECORD COUNT, which
    is what guarantees the bound — hessian mass does not, e.g. under
    GOSS zero-weighting).  Slots past the selected count are padding with
    zero gradient statistics (contributing exactly +0.0) and node 0.
    """
    n = codes.shape[0]
    pos = jnp.where(sel, jnp.cumsum(sel) - 1, n_half)         # dump slot
    idx = jnp.full((n_half + 1,), n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")[:n_half]
    valid = idx < n
    take = jnp.where(valid, idx, 0)
    return (codes[take],
            jnp.where(valid, g[take], 0.0),
            jnp.where(valid, h[take], 0.0),
            jnp.where(valid, nid[take], 0))


def _subtract_level_hist(codes, g, h, node_ids, parent_hist, *,
                         n_nodes: int, n_bins: int, plan: ExecutionPlan):
    """Step ① for one level (> 0) via smaller-child subtraction.

    Bins ONLY the records that landed in the smaller child of each split
    parent — compacted to an ``n // 2`` buffer so the histogram kernel
    reads half the record stream — and derives every sibling as
    ``parent − smaller``.  Per-node record counts come from an O(n)
    on-device segment-sum of the freshly partitioned node ids (no
    device→host trip in the level loop).

    Class handling: the jnp strategies run one full pass *per class*
    anyway, so per-class compaction halves their work at any K.  The
    class-batched Pallas kernel reads the code stream ONCE for all K —
    per-class compaction would read K·n/2 codes instead of n, a net
    loss for K > 2 — so there the bigger-child records are masked to
    zero statistics instead (single batched launch, work unchanged,
    siblings still derived).
    """
    K, n = g.shape
    ones = jnp.ones((n,), jnp.int32)
    counts = jax.vmap(
        lambda nid: jax.ops.segment_sum(ones, nid, n_nodes))(node_ids)
    smaller_is_left = counts[:, 0::2] <= counts[:, 1::2]      # (K, NN/2)
    is_small = _child_is_smaller(smaller_is_left)             # (K, NN)
    sel = jax.vmap(lambda m, nid: m[nid])(is_small, node_ids)  # (K, n)
    if K > 1 and plan.hist_strategy.startswith("pallas"):
        w = sel.astype(jnp.float32)
        small = ops.build_histogram(codes, g * w, h * w, node_ids,
                                    n_nodes=n_nodes, n_bins=n_bins,
                                    plan=plan)
        return _combine_sibling_hist(parent_hist, small, is_small)
    n_half = max(1, n // 2)
    smalls = []
    for k in range(K):
        ck, gk, hk, nk = _compact_selected(codes, g[k], h[k], node_ids[k],
                                           sel[k], n_half)
        smalls.append(ops.build_histogram(ck, gk, hk, nk, n_nodes=n_nodes,
                                          n_bins=n_bins, plan=plan))
    return _combine_sibling_hist(parent_hist, jnp.stack(smalls), is_small)


# --------------------------------------------------------------------------
# out-of-core grower: chunk-accumulated histograms + chunk-local node ids
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("missing_bin", "plan"))
def _partition_chunk(codes, node_ids, feature, threshold, is_cat,
                     default_left, do_split, *, missing_bin: int,
                     plan: ExecutionPlan):
    """Step ③ for one chunk: route the chunk's per-class node ids through
    one level's split decisions.  The column-major copy is chunk-local
    (``codes.T``) — the paper's redundant representation kept to one
    chunk's footprint.  Packed chunks unpack here, inside the jit, so the
    chunk crosses host→device at half the bytes."""
    K, nn = feature.shape
    if isinstance(codes, PackedCodes):
        codes = codes.unpack()
    codes_cm = codes.T                                        # (F, rows)
    codes_lvl = codes_cm[jnp.where(do_split, feature, 0)]     # (K, nn, rows)
    part = jax.vmap(functools.partial(ops.partition_level,
                                      missing_bin=missing_bin, plan=plan))
    return part(node_ids, codes_lvl.transpose(0, 2, 1),
                jnp.where(do_split,
                          jnp.broadcast_to(jnp.arange(nn, dtype=jnp.int32),
                                           (K, nn)), -1),
                threshold, is_cat, default_left)


def fit_forest_chunked(chunks, g, h, *, depth: int, n_bins: int,
                       missing_bin: int, is_cat_field, field_mask,
                       lambda_: float, gamma: float, min_child_weight: float,
                       plan: Optional[ExecutionPlan] = None):
    """Out-of-core twin of :func:`fit_forest`: same math, chunked scans.

    ``chunks`` is a zero-argument callable returning a fresh iterator of
    ``(lo, hi, codes)`` tuples — ``codes`` a (rows, F) uint8 chunk (or a
    :class:`PackedCodes` carrying the same logical rows 4-bit packed, in
    which case every host→device chunk copy moves half the bytes) whose
    first ``hi - lo`` rows are records ``lo:hi`` (extra rows are padding
    and are neutralized with zero gradient statistics).  One iteration
    happens per level (histogram accumulation, with the previous level's
    partition applied lazily in the same pass) plus one final partition
    pass — ``depth + 1`` data passes per tree, device memory bounded by
    one chunk.

    g, h: (K, n) numpy float32 per-class gradient statistics (host
    resident).  Returns ``(TreeArrays with (K, ...) axes, node_ids)``
    where ``node_ids`` is the host (K, n) int32 array of final leaf slots
    — the streaming trainer updates margins from it directly, so step ⑤
    needs no extra traversal pass over the stream.
    """
    plan = resolve_plan(plan).without_chunking()
    g = np.asarray(g, np.float32)
    h = np.asarray(h, np.float32)
    K, n = g.shape
    F = int(is_cat_field.shape[0])
    n_int = 2 ** depth - 1

    state = (jnp.full((K, n_int), -1, jnp.int32),      # feature
             jnp.zeros((K, n_int), jnp.int32),         # threshold
             jnp.zeros((K, n_int), jnp.int32),         # is_cat
             jnp.zeros((K, n_int), jnp.int32))         # default_left
    node_ids = np.zeros((K, n), np.int32)
    find = (splits_mod.find_best_splits_host if plan.host_offload_split
            else splits_mod.find_best_splits)
    pending = None                    # previous level's partition arguments

    def stat_chunk(a, lo, hi, rows):
        """(K, rows) slice of a host array, zero-padded to the chunk (pad
        rows carry zero stats / node 0, contributing exactly +0.0)."""
        s = a[:, lo:hi]
        if rows > hi - lo:
            s = np.pad(s, ((0, 0), (0, rows - (hi - lo))))
        return jnp.asarray(s)

    def apply_pending(codes, lo, hi, rows):
        nid = stat_chunk(node_ids, lo, hi, rows)
        if pending is None:
            return nid
        nid = _partition_chunk(codes, nid, *pending,
                               missing_bin=missing_bin, plan=plan)
        node_ids[:, lo:hi] = np.asarray(nid[:, :hi - lo])
        return nid

    use_sub = bool(plan.hist_subtraction)
    prev_hist = None
    smaller_is_left = None            # (K, nn) hessian-based, per level
    for level in range(depth):
        nn = 2 ** level
        sub_level = use_sub and level > 0
        # chunked subtraction: every chunk must be streamed anyway (the
        # previous level's partition is applied lazily in this pass), so
        # instead of compacting, the bigger-child records are masked to
        # zero stats — the accumulator stays class-batched — and siblings
        # are derived once per level from the previous level's histogram.
        # Smaller-child selection comes from the decision's left_h channel
        # (hessian mass), available BEFORE the pass; masking keeps any
        # selection exact, so hessian-vs-count ties are harmless here.
        is_small = _child_is_smaller(smaller_is_left) if sub_level else None
        hist = jnp.zeros((K, nn, F, n_bins, 2), jnp.float32)
        for lo, hi, codes in chunks():
            if not isinstance(codes, PackedCodes):
                codes = jnp.asarray(codes)
            rows = codes.shape[0]
            nid = apply_pending(codes, lo, hi, rows)
            gc = stat_chunk(g, lo, hi, rows)
            hc = stat_chunk(h, lo, hi, rows)
            if sub_level:
                w = jax.vmap(lambda m, i: m[i])(is_small, nid)
                w = w.astype(jnp.float32)
                gc, hc = gc * w, hc * w
            hist = ops.accumulate_histogram(
                hist, codes, gc, hc, nid, n_nodes=nn,
                n_bins=n_bins, plan=plan)
        if sub_level:
            hist = _combine_sibling_hist(prev_hist, hist, is_small)
        prev_hist = hist
        state, best, do_split = _decide_level(
            hist, level, state, is_cat_field, field_mask, lambda_,
            gamma, min_child_weight, find)
        smaller_is_left = jnp.where(do_split,
                                    2.0 * best.left_h <= best.node_h, False)
        pending = (best.feature, best.threshold, best.is_cat,
                   best.default_left, do_split)

    for lo, hi, codes in chunks():    # final pass: last level's partition
        if not isinstance(codes, PackedCodes):
            codes = jnp.asarray(codes)
        apply_pending(codes, lo, hi, codes.shape[0])

    feature, threshold, is_cat, default_left = state
    leaf_value = _settle_jit(jnp.asarray(g), jnp.asarray(h),
                             jnp.asarray(node_ids), feature, lambda_)
    tree = TreeArrays(feature=feature, threshold=threshold, is_cat=is_cat,
                      default_left=default_left, leaf_value=leaf_value)
    return tree, node_ids


# --------------------------------------------------------------------------
# vertex-by-vertex (leaf-wise) grower with the smaller-child subtraction trick
# --------------------------------------------------------------------------
def fit_tree_lossguide(codes, codes_cm, g, h, *, depth: int, n_bins: int,
                       missing_bin: int, is_cat_field, field_mask,
                       lambda_: float, gamma: float, min_child_weight: float,
                       max_leaves: Optional[int] = None,
                       plan: Optional[ExecutionPlan] = None,
                       hist_strategy: Optional[str] = None) -> TreeArrays:
    """Best-first growth; bins only the smaller child per split (§II-A).

    Control flow (the gain heap) runs on host — the paper itself argues this
    coordination is cheap relative to the record scans; the scans themselves
    (histogram of the smaller child, predicate masks) run on device.
    """
    plan = _lift_loose_kwargs(plan, hist_strategy=hist_strategy)
    n, F = codes.shape
    n_int = 2 ** depth - 1
    n_leaf_slots = 2 ** depth
    max_leaves = max_leaves or n_leaf_slots
    g = jnp.asarray(g, jnp.float32)
    h = jnp.asarray(h, jnp.float32)

    feature = np.full((n_int,), -1, np.int32)
    threshold = np.zeros((n_int,), np.int32)
    is_cat_a = np.zeros((n_int,), np.int32)
    default_left = np.zeros((n_int,), np.int32)
    value_bottom = np.zeros((n_leaf_slots,), np.float32)

    def hist_of(mask):
        return ops.build_histogram(
            codes, g * mask, h * mask, jnp.zeros((n,), jnp.int32),
            n_nodes=1, n_bins=n_bins, plan=plan)[0]               # (F, NB, 2)

    def best_of(hist):
        d = splits_mod.find_best_splits(hist[None], is_cat_field, field_mask,
                                        lambda_, gamma, min_child_weight)
        return jax.device_get(
            (d.gain[0], d.feature[0], d.threshold[0], d.is_cat[0],
             d.default_left[0], d.node_g[0], d.node_h[0], d.left_h[0]))

    root_mask = jnp.ones((n,), jnp.float32)
    root_hist = hist_of(root_mask)
    heap = []
    counter = 0  # tie-break: deterministic heap order

    def push(pos, level, hist, mask):
        nonlocal counter
        gain, f, t, c, dl, G, H, HL = best_of(hist)
        heapq.heappush(heap, (-float(gain), counter,
                              dict(pos=pos, level=level, hist=hist, mask=mask,
                                   f=int(f), t=int(t), c=int(c), dl=int(dl),
                                   G=float(G), H=float(H), HL=float(HL),
                                   gain=float(gain))))
        counter += 1

    def settle_leaf(e):
        reps = 2 ** (depth - e["level"])
        base = e["pos"] - (2 ** e["level"] - 1)
        w = -e["G"] / (e["H"] + lambda_)
        value_bottom[base * reps:(base + 1) * reps] = w

    push(0, 0, root_hist, root_mask)
    n_leaves = 1
    while heap and n_leaves < max_leaves:
        _, _, e = heapq.heappop(heap)
        if e["gain"] <= 0.0 or e["level"] >= depth:
            settle_leaf(e)
            continue
        pos, lvl = e["pos"], e["level"]
        feature[pos], threshold[pos] = e["f"], e["t"]
        is_cat_a[pos], default_left[pos] = e["c"], e["dl"]

        # step ③ — one predicate, one column from the column-major copy
        col = _gather_fields(codes_cm, e["f"]).astype(jnp.int32)
        miss = col == missing_bin
        left = jnp.where(jnp.asarray(e["c"] == 1), col == e["t"],
                         col <= e["t"])
        left = jnp.where(miss, e["dl"] == 1, left)
        mask_l = e["mask"] * left.astype(jnp.float32)
        mask_r = e["mask"] - mask_l

        # the paper's step-① optimization: bin ONLY the smaller child, the
        # sibling histogram is parent − child (no explicit binning).  The
        # decision's left_h counts channel already crossed to the host with
        # the split, so picking the smaller side costs no extra syncs.
        hl = e["HL"]
        hr = e["H"] - e["HL"]
        if hl <= hr:
            hist_small = hist_of(mask_l)
            hist_l, hist_r = hist_small, e["hist"] - hist_small
        else:
            hist_small = hist_of(mask_r)
            hist_l, hist_r = e["hist"] - hist_small, hist_small

        push(2 * pos + 1, lvl + 1, hist_l, mask_l)
        push(2 * pos + 2, lvl + 1, hist_r, mask_r)
        n_leaves += 1

    while heap:  # settle everything left on the heap as leaves
        _, _, e = heapq.heappop(heap)
        settle_leaf(e)

    return TreeArrays(feature=jnp.asarray(feature),
                      threshold=jnp.asarray(threshold),
                      is_cat=jnp.asarray(is_cat_a),
                      default_left=jnp.asarray(default_left),
                      leaf_value=jnp.asarray(value_bottom))
