"""jit'd dispatch wrappers over the Pallas kernels and their alternatives.

Every accelerated GBDT step dispatches through an
:class:`repro.api.ExecutionPlan` so the benchmark harness can reproduce the
paper's machine comparison *as algorithm strategies at equal memory
traffic*:

  histogram (step ①):
    * ``scatter``          — single shared scatter-RMW (multicore analog;
                             also the fastest path on this CPU container)
    * ``scatter_private``  — W privatized replicas + reduce (the GPU
                             shared-memory privatization of §II-D)
    * ``sort``             — sort-by-key + segment-sum (GPU-alternative)
    * ``onehot``           — blocked one-hot einsum in pure jnp (XLA)
    * ``pallas_grouped``   — the Booster kernel (group-by-field, MXU)
    * ``pallas_packed``    — the naive-packing ablation kernel

  traversal / inference (step ⑤, §III-D) and partition (step ③):
    * ``reference`` (gather walk)  vs  ``pallas`` (compare walk)

The resolved plan decides interpret mode: off on a TPU, where the kernels
compile through Mosaic, and on elsewhere (Python execution of the kernel
body — numerically identical, used for validation).  A kernel that fails
raises: a dispatch runs the strategy its plan names, never another.

Calling convention: ``build_histogram(..., plan=plan)`` with a resolved
plan.  The PR-1 loose ``strategy=`` / ``interpret=`` kwargs (and their
``default_hist_strategy`` shim) are gone from these entry points;
config-level strategy strings are lifted into a plan once, at the boundary
(``ExecutionPlan.from_config`` / the deprecated grower kwargs), not per
call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.plan import ExecutionPlan, HIST_STRATEGIES, resolve_plan
# safe either import order: binning only depends on jax/numpy, and the
# core package binds this module lazily (runtime attribute access only)
from repro.core.binning import PackedCodes
from repro.kernels import histogram as _hist_k
from repro.kernels import partition as _part_k
from repro.kernels import traversal as _trav_k
from repro.kernels import ref as _ref
from repro.kernels.ref import TreeArrays

# float32 operands of a jnp contraction would take one bfloat16 MXU pass
# on a TPU at default precision; the one-hot contractions here carry exact
# (g, h) and leaf values, so they ask for full float32
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["HIST_STRATEGIES", "onehot_matmul", "pack_codes", "unpack_codes",
           "build_histogram", "accumulate_histogram", "partition_level",
           "traverse_tree", "predict_ensemble"]


# --------------------------------------------------------------------------
# device-side pack/unpack primitives (paper §III-B compressed codes)
# --------------------------------------------------------------------------
@jax.jit
def pack_codes(codes) -> PackedCodes:
    """4-bit pack on device: (..., n) integer codes -> :class:`PackedCodes`
    (two codes per byte along the last axis).  Codes must be <= 15 —
    i.e. ``n_bins <= 16`` — or information is lost; callers gate on the
    bin count."""
    return PackedCodes.pack(codes)


@jax.jit
def unpack_codes(packed) -> jax.Array:
    """Inverse of :func:`pack_codes` on device: -> (..., n) uint8.
    Plain arrays pass through unchanged, so dispatch layers can call this
    unconditionally."""
    if isinstance(packed, PackedCodes):
        return packed.unpack()
    return jnp.asarray(packed)


# --------------------------------------------------------------------------
# generic primitive: one-hot contraction (shared with the MoE dispatch layer)
# --------------------------------------------------------------------------
def onehot_matmul(idx: jax.Array, values: jax.Array, width: int) -> jax.Array:
    """out[j] = sum_{i : idx[i] == j} values[i]  via a dense MXU contraction.

    idx: (n,) int; values: (n, ...) — returns (width, ...).  This is the
    paper's core primitive (irregular scatter -> dense one-hot matmul) in
    reusable form; the MoE layers use it for token->expert dispatch.
    """
    oh = jax.nn.one_hot(idx, width, dtype=values.dtype)        # (n, width)
    flat = values.reshape(values.shape[0], -1)
    out = jax.lax.dot_general(oh, flat, (((0,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
    return out.reshape((width,) + values.shape[1:])


# --------------------------------------------------------------------------
# step ① — histogram strategies
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def _hist_scatter(codes, g, h, node_ids, n_nodes, n_bins):
    return _ref.histogram_ref(codes, g, h, node_ids, n_nodes, n_bins)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "n_private"))
def _hist_scatter_private(codes, g, h, node_ids, n_nodes, n_bins,
                          n_private=32):
    """GPU-style privatization: W replica histograms, then reduce (§II-D)."""
    n, F = codes.shape
    pad = -n % n_private
    codes = jnp.pad(codes, ((0, pad), (0, 0)))
    g = jnp.pad(g, (0, pad))
    h = jnp.pad(h, (0, pad))
    node_ids = jnp.pad(node_ids, (0, pad))
    cw = codes.reshape(n_private, -1, F)
    gw = g.reshape(n_private, -1)
    hw = h.reshape(n_private, -1)
    nw = node_ids.reshape(n_private, -1)
    per = jax.vmap(lambda c, gg, hh, nn: _ref.histogram_ref(
        c, gg, hh, nn, n_nodes, n_bins))(cw, gw, hw, nw)
    return per.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def _hist_sort(codes, g, h, node_ids, n_nodes, n_bins):
    """Sort-by-key + segment-sum per field (regularized-GPU alternative)."""
    n, F = codes.shape
    stats = jnp.stack([g, h], -1).astype(jnp.float32)

    def per_field(col):
        comb = node_ids.astype(jnp.int32) * n_bins + col.astype(jnp.int32)
        order = jnp.argsort(comb)
        return jax.ops.segment_sum(stats[order], comb[order],
                                   num_segments=n_nodes * n_bins)

    hist = jax.vmap(per_field, in_axes=1)(codes)               # (F, NN*NB, 2)
    return hist.reshape(F, n_nodes, n_bins, 2).transpose(1, 0, 2, 3)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "n_bins", "chunk", "fblk"))
def _hist_onehot(codes, g, h, node_ids, n_nodes, n_bins, chunk=2048, fblk=8):
    """Blocked pure-jnp one-hot contraction (the kernel's XLA twin)."""
    n, F = codes.shape
    pad = -n % chunk
    codes = jnp.pad(codes, ((0, pad), (0, -F % fblk)))
    g = jnp.pad(g, (0, pad))
    h = jnp.pad(h, (0, pad))
    node_ids = jnp.pad(node_ids, (0, pad))
    np_, Fp = codes.shape
    stats = jnp.stack([g, h], -1).astype(jnp.float32)

    def body(acc, xs):
        c, s, nid = xs                                         # (chunk, Fp) ...
        oh_node = jax.nn.one_hot(nid, n_nodes, dtype=jnp.float32)
        sn = (oh_node[:, :, None] * s[:, None, :]).reshape(chunk, n_nodes * 2)
        oh_bin = jax.nn.one_hot(c.astype(jnp.int32), n_bins,
                                dtype=jnp.float32)             # (chunk, Fp, NB)
        contrib = jnp.einsum("nfb,ns->fbs", oh_bin, sn, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
        return acc + contrib, None

    init = jnp.zeros((Fp, n_bins, n_nodes * 2), jnp.float32)
    xs = (codes.reshape(-1, chunk, Fp), stats.reshape(-1, chunk, 2),
          node_ids.reshape(-1, chunk))
    hist, _ = jax.lax.scan(body, init, xs)
    hist = hist[:F].reshape(F, n_bins, n_nodes, 2)
    return hist.transpose(2, 0, 1, 3)


def build_histogram(codes, g, h, node_ids, *, n_nodes: int, n_bins: int,
                    plan: Optional[ExecutionPlan] = None):
    """Dispatch: (n, F) codes -> (n_nodes, F, n_bins, 2) float32 histogram.

    Class-batched form (multi-class boosting): ``g``, ``h``, ``node_ids``
    may carry a leading class axis (K, n) — every class has its own node
    partition but shares the code stream — and the result gains the same
    leading axis: (K, n_nodes, F, n_bins, 2).  The jnp strategies vmap
    over the class axis; the Pallas kernel widens its stats operand so a
    single launch reads the codes once for all K classes.
    """
    plan = resolve_plan(plan)
    strategy = plan.hist_strategy
    if isinstance(codes, PackedCodes) and strategy != "pallas_grouped":
        # the grouped Pallas kernel consumes packed blocks natively (half
        # the HBM code traffic); every other strategy gets the bit-equal
        # unpacked view, fused into its own jit
        codes = codes.unpack()
    batched = g.ndim == 2

    def per_class(fn):
        if not batched:
            return fn
        return jax.vmap(fn, in_axes=(None, 0, 0, 0))

    if strategy == "scatter":
        fn = lambda c, gg, hh, nn: _hist_scatter(c, gg, hh, nn, n_nodes,
                                                 n_bins)
        return per_class(fn)(codes, g, h, node_ids)
    if strategy == "scatter_private":
        fn = lambda c, gg, hh, nn: _hist_scatter_private(c, gg, hh, nn,
                                                         n_nodes, n_bins)
        return per_class(fn)(codes, g, h, node_ids)
    if strategy == "sort":
        fn = lambda c, gg, hh, nn: _hist_sort(c, gg, hh, nn, n_nodes, n_bins)
        return per_class(fn)(codes, g, h, node_ids)
    if strategy == "onehot":
        fn = lambda c, gg, hh, nn: _hist_onehot(c, gg, hh, nn, n_nodes,
                                                n_bins)
        return per_class(fn)(codes, g, h, node_ids)
    if strategy in ("pallas_grouped", "pallas_packed"):
        return _hist_k.histogram_pallas(
            codes, g, h, node_ids, n_nodes=n_nodes, n_bins=n_bins,
            records_per_block=plan.records_per_block,
            fields_per_block=plan.fields_per_block,
            packed=(strategy == "pallas_packed"),
            interpret=plan.interpret)
    raise ValueError(f"unknown histogram strategy {strategy!r}; "
                     f"choose from {HIST_STRATEGIES}")


@functools.lru_cache(maxsize=None)
def _accumulate_jit(n_nodes: int, n_bins: int, plan: ExecutionPlan):
    """Jitted ``hist += chunk_hist`` with the accumulator donated.

    Donation lets XLA update the (K, NN, F, NB, 2) accumulator in place
    instead of allocating a fresh buffer per chunk — the out-of-core
    trainer calls this once per chunk per level, so without donation the
    allocator churns one accumulator-sized buffer per chunk.  Donation is
    only requested on backends that implement it (TPU/GPU); the CPU
    backend would warn and copy anyway.
    """
    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()

    def impl(hist, codes, g, h, node_ids):
        if plan.hist_strategy == "scatter":
            # scatter into the running accumulator: the chunked sum then
            # rounds exactly like one scatter over every chunk's records
            if isinstance(codes, PackedCodes):
                codes = codes.unpack()
            acc = lambda a, gg, hh, nn: _ref.histogram_ref(
                codes, gg, hh, nn, n_nodes, n_bins, init=a)
            if g.ndim == 2:
                return jax.vmap(acc)(hist, g, h, node_ids)
            return acc(hist, g, h, node_ids)
        return hist + build_histogram(codes, g, h, node_ids, n_nodes=n_nodes,
                                      n_bins=n_bins, plan=plan)

    return jax.jit(impl, donate_argnums=donate)


def accumulate_histogram(hist, codes, g, h, node_ids, *, n_nodes: int,
                         n_bins: int,
                         plan: Optional[ExecutionPlan] = None):
    """Chunked step ①: ``hist + build_histogram(chunk)`` in one dispatch.

    The out-of-core trainer accumulates the per-level histogram across
    device-sized chunks — every chunk reuses the per-chunk strategy
    unchanged (Pallas or jnp), and only the (n_nodes, F, n_bins, 2)
    accumulator stays resident between chunks (donated into the jit, so
    no fresh accumulator-sized allocation per chunk).  Adding a zero-stat
    padded record contributes exactly +0.0, so padded chunks keep
    bit-equality with the monolithic histogram.
    """
    # chunk budgets don't change the kernel — drop them from the jit key
    return _accumulate_jit(n_nodes, n_bins,
                           resolve_plan(plan).without_chunking())(
        hist, codes, g, h, node_ids)


# --------------------------------------------------------------------------
# step ③ — partition
# --------------------------------------------------------------------------
def partition_level(node_ids, codes_lvl, split_feature, split_threshold,
                    split_is_cat, split_default_left, *, missing_bin: int,
                    plan: Optional[ExecutionPlan] = None):
    plan = resolve_plan(plan)
    if isinstance(codes_lvl, PackedCodes):
        codes_lvl = codes_lvl.unpack()
    if plan.partition_strategy == "reference":
        return _ref.partition_ref(node_ids, codes_lvl, split_feature,
                                  split_threshold, split_is_cat,
                                  split_default_left, missing_bin)
    return _part_k.partition_pallas(
        node_ids, codes_lvl, split_feature, split_threshold,
        split_is_cat, split_default_left, missing_bin=missing_bin,
        interpret=plan.interpret)


# --------------------------------------------------------------------------
# step ⑤ — traversal / batch inference
# --------------------------------------------------------------------------
def traverse_tree(tree: TreeArrays, codes, *, missing_bin: int,
                  plan: Optional[ExecutionPlan] = None):
    plan = resolve_plan(plan)
    if isinstance(codes, PackedCodes):
        codes = codes.unpack()
    # "scan" only changes multi-tree inference; a single walk is a walk
    if plan.traversal_strategy in ("reference", "scan"):
        return _ref.traverse_ref(tree, codes, missing_bin)
    return _trav_k.traverse_pallas(tree, codes, missing_bin=missing_bin,
                                   interpret=plan.interpret)


_PREDICT_ROWS_PER_CHUNK = 1024   # (chunk, T) walk state stays cache-sized


@functools.partial(jax.jit, static_argnames=("missing_bin", "n_classes"))
def _predict_batched_jit(trees, codes, missing_bin, n_classes):
    """Optimized tree-batched level walk (same math as the
    :func:`repro.kernels.ref.predict_ensemble_batched` oracle — node
    decisions are integer-exact, so the two agree bit-for-bit on the
    walks and to float tolerance on the fold):

    * the four per-node parameters are packed into ONE int32 table
      ``((feat+1) << 16) | (thr << 8) | (cat << 1) | dl`` so each level
      costs a single table gather + a single code gather instead of
      five (bin codes are uint8 and field counts < 2**15 — the repo's
      binning invariants — so the pack is lossless);
    * records walk in ``lax.map`` chunks so the (chunk, T) node matrix
      and its gather intermediates stay cache-resident instead of
      materializing (n, T) arrays per level.
    """
    n = codes.shape[0]
    T = trees.feature.shape[0]
    depth = int(trees.leaf_value.shape[-1]).bit_length() - 1
    if codes.shape[1] >= 1 << 15:
        # field ids this wide overflow the int32 pack — take the unpacked
        # (slower, still one-pass) walk instead of silently corrupting
        return _ref.predict_ensemble_batched(trees, codes, missing_bin,
                                             n_classes=n_classes)
    packed_t = (((trees.feature + 1) << 16) | (trees.threshold << 8)
                | (trees.is_cat << 1) | trees.default_left).T  # (N_int, T)
    leaf_t = trees.leaf_value.T                                # (N_leaf, T)
    cls_oh = (None if n_classes == 1 else
              jax.nn.one_hot(jnp.arange(T) % n_classes, n_classes,
                             dtype=jnp.float32))               # (T, K)

    def walk(cb):
        node = jnp.zeros((cb.shape[0], T), jnp.int32)
        for _ in range(depth):
            p = jnp.take_along_axis(packed_t, node, axis=0)
            f = (p >> 16) - 1
            code = jnp.take_along_axis(cb, jnp.maximum(f, 0), axis=1)
            thr = (p >> 8) & 255
            go_left = jnp.where((p >> 1) & 1, code == thr, code <= thr)
            go_left = jnp.where(code == missing_bin, (p & 1) == 1,
                                go_left)
            go_left = jnp.where(f < 0, True, go_left)
            node = 2 * node + 2 - go_left.astype(jnp.int32)
        vals = jnp.take_along_axis(leaf_t, node - packed_t.shape[0],
                                   axis=0)                     # (chunk, T)
        if cls_oh is None:
            return jnp.sum(vals, axis=1)
        return jax.lax.dot_general(vals, cls_oh, (((1,), (0,)), ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)

    chunk = min(_PREDICT_ROWS_PER_CHUNK, max(1, n))
    cp = jnp.pad(codes.astype(jnp.int32), ((0, -n % chunk), (0, 0)))
    out = jax.lax.map(walk, cp.reshape(-1, chunk, cp.shape[1]))
    return out.reshape((-1,) + out.shape[2:])[:n]


def predict_ensemble(trees: TreeArrays, codes, *, missing_bin: int,
                     depth: int, plan: Optional[ExecutionPlan] = None,
                     n_classes: int = 1):
    """Ensemble margins: (n,) for scalar objectives, (n, K) when
    ``n_classes > 1`` (trees round-major, tree t feeds class t % K).

    ``plan.traversal_strategy`` picks the engine: ``"reference"`` is the
    tree-batched level walk (one pass over the codes for the whole
    ensemble, jitted), ``"scan"`` the legacy per-tree lax.scan baseline,
    ``"pallas"`` the tree-blocked kernel (``plan.trees_per_block`` tree
    tables resident per grid step).
    """
    plan = resolve_plan(plan)
    if isinstance(codes, PackedCodes):
        codes = codes.unpack()
    if plan.traversal_strategy == "scan":
        return _ref.predict_ensemble_ref(trees, codes, missing_bin,
                                         n_classes=n_classes)
    if plan.traversal_strategy == "reference":
        return _predict_batched_jit(trees, codes, missing_bin, n_classes)
    return _trav_k.predict_ensemble_pallas(
        trees, codes, missing_bin=missing_bin, depth=depth,
        interpret=plan.interpret, n_classes=n_classes,
        trees_per_block=plan.trees_per_block)
