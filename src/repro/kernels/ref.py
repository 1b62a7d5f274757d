"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth used by the per-kernel allclose tests and by the
benchmark harness as the "software baseline" implementations.  They mirror
the three dominant GBDT training steps the paper accelerates:

  * ``histogram_ref``    — step ① histogram binning of gradient statistics
  * ``partition_ref``    — step ③ single-predicate evaluation / partition
  * ``traverse_ref``     — step ⑤ one-tree traversal (+ batch inference)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class TreeArrays(NamedTuple):
    """Fixed-shape complete-binary-tree table (depth ``D`` static).

    ``feature`` is -1 for pass-through nodes (a leaf decided above them);
    internal layout matches the paper's step-⑤ "map the tree to a table"
    (feature id, split point, child pointers are implicit: 2i+1 / 2i+2).
    """

    feature: Array       # (2**D - 1,) int32; -1 == pass-through
    threshold: Array     # (2**D - 1,) int32 bin code
    is_cat: Array        # (2**D - 1,) int32 {0,1}; ==1: go left iff code == thr
    default_left: Array  # (2**D - 1,) int32 {0,1}; missing-value direction
    leaf_value: Array    # (2**D,) float32 values at the bottom level

    @property
    def depth(self) -> int:
        return int(self.leaf_value.shape[-1]).bit_length() - 1


# --------------------------------------------------------------------------
# step ① — histogram binning
# --------------------------------------------------------------------------
def histogram_ref(codes: Array, g: Array, h: Array, node_ids: Array,
                  n_nodes: int, n_bins: int, init: Array = None) -> Array:
    """Scatter-add oracle: hist[node, f, bin] += (g, h).

    codes: (n, F) uint; g, h: (n,); node_ids: (n,) int32 in [0, n_nodes).
    Returns (n_nodes, F, n_bins, 2) float32.  ``init``, an (n_nodes, F,
    n_bins, 2) running histogram, is scattered INTO: the adds continue
    its accumulation record by record, so accumulating chunks in order
    rounds exactly like one scatter over all of them.

    One field at a time, g and h apart: every scatter then takes an (n,)
    update vector, where one scatter of (n, F, 2) updates would pad its
    minor dim of 2 to the chip's 128 lanes.
    """
    n, F = codes.shape
    g = g.astype(jnp.float32)
    h = h.astype(jnp.float32)
    base = node_ids.astype(jnp.int32) * n_bins
    if init is None:
        init = jnp.zeros((n_nodes, F, n_bins, 2), jnp.float32)
    acc = init.transpose(1, 3, 0, 2).reshape(F, 2, n_nodes * n_bins)

    def field(args):
        col, a = args
        comb = base + col.astype(jnp.int32)
        return jnp.stack([a[0].at[comb].add(g), a[1].at[comb].add(h)])

    hist = jax.lax.map(field, (codes.T, acc))             # (F, 2, NN*NB)
    return hist.reshape(F, 2, n_nodes, n_bins).transpose(2, 0, 3, 1)


def _decide_go_left(code: Array, feature: Array, threshold: Array,
                    is_cat: Array, default_left: Array, missing_bin: int
                    ) -> Array:
    """Shared predicate semantics (paper Fig 2/3 + missing-bin handling)."""
    is_missing = code == missing_bin
    left_num = code <= threshold
    left_cat = code == threshold
    go_left = jnp.where(is_cat == 1, left_cat, left_num)
    go_left = jnp.where(is_missing, default_left == 1, go_left)
    return jnp.where(feature < 0, True, go_left)


# --------------------------------------------------------------------------
# step ③ — single-predicate evaluation (one level of partitioning)
# --------------------------------------------------------------------------
def partition_ref(node_ids: Array, codes_lvl: Array, split_feature: Array,
                  split_threshold: Array, split_is_cat: Array,
                  split_default_left: Array, missing_bin: int) -> Array:
    """Route each record to its child given the level's chosen splits.

    node_ids: (n,) level-local node index in [0, NN).
    codes_lvl: (n, C) compact per-level field columns; split_feature indexes
        into [0, C) (the paper's field *renumbering*), or -1 for non-splitting
        nodes (records go left, i.e. follow the pass-through spine).
    Returns new (n,) node ids in [0, 2*NN).
    """
    f = split_feature[node_ids]                                     # (n,)
    thr = split_threshold[node_ids]
    cat = split_is_cat[node_ids]
    dl = split_default_left[node_ids]
    code = jnp.take_along_axis(
        codes_lvl, jnp.maximum(f, 0).astype(jnp.int32)[:, None], axis=1)[:, 0]
    go_left = _decide_go_left(code.astype(jnp.int32), f, thr, cat, dl,
                              missing_bin)
    return 2 * node_ids + (1 - go_left.astype(jnp.int32))


# --------------------------------------------------------------------------
# step ⑤ — one-tree traversal (and the batch-inference building block)
# --------------------------------------------------------------------------
def traverse_ref(tree: TreeArrays, codes: Array, missing_bin: int) -> Array:
    """Walk every record through one tree; returns (n,) leaf values.

    codes: (n, C) — columns indexed by ``tree.feature`` (full field set or
    the compacted/renumbered subset, caller's choice).
    """
    n = codes.shape[0]
    depth = tree.depth
    codes = codes.astype(jnp.int32)
    node = jnp.zeros((n,), jnp.int32)
    for _ in range(depth):
        f = tree.feature[node]
        code = jnp.take_along_axis(
            codes, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        go_left = _decide_go_left(code, f, tree.threshold[node],
                                  tree.is_cat[node], tree.default_left[node],
                                  missing_bin)
        node = 2 * node + 2 - go_left.astype(jnp.int32)
    leaf = node - (2 ** depth - 1)
    return tree.leaf_value[leaf]


def predict_ensemble_ref(trees: TreeArrays, codes: Array, missing_bin: int,
                         n_classes: int = 1) -> Array:
    """Legacy batch inference: one tree at a time (paper §II-B baseline).

    ``trees`` holds stacked arrays with a leading tree dimension (T, ...).
    Multi-class ensembles store trees round-major (round r, class k at
    index ``r * K + k``); tree t accumulates into margin column ``t % K``
    and the output gains a class axis: (n, K).  ``n_classes == 1`` keeps
    the scalar (n,) output.

    A depth-T ensemble re-reads every code T times here — this is the
    ``"scan"`` traversal strategy the benchmarks keep as the software
    baseline; the production path is :func:`predict_ensemble_batched`.
    """
    T = trees.feature.shape[0]
    cls_oh = jax.nn.one_hot(jnp.arange(T) % n_classes, n_classes,
                            dtype=jnp.float32)               # (T, K)

    def body(carry, xs):
        t, oh = xs
        tree = TreeArrays(*t)
        out = traverse_ref(tree, codes, missing_bin)         # (n,)
        return carry + out[:, None] * oh[None, :], None

    init = jnp.zeros((codes.shape[0], n_classes), jnp.float32)
    out, _ = jax.lax.scan(body, init, (tuple(trees), cls_oh))
    return out[:, 0] if n_classes == 1 else out


def predict_ensemble_batched(trees: TreeArrays, codes: Array,
                             missing_bin: int, n_classes: int = 1) -> Array:
    """Tree-batched batch inference: all trees advance one level per pass.

    The paper's §III-D scheme pins one tree per BU and streams a *shared*
    record stream past all of them — the software analog keeps an (n, T)
    node-index matrix and advances every tree simultaneously per depth
    level with batched ``take_along_axis`` over the stacked (T, N_int)
    node tables, so the whole ensemble makes ONE pass over the codes
    instead of T.  A final (T, K) one-hot contraction folds the
    round-major per-tree leaf values into class margins (a plain tree-sum
    for ``n_classes == 1``).

    Decision semantics are identical to the per-tree scan — node paths
    and leaf choices are bit-equal; only the floating accumulation order
    of the final fold differs.
    """
    T = trees.feature.shape[0]
    depth = int(trees.leaf_value.shape[-1]).bit_length() - 1
    codes = codes.astype(jnp.int32)
    # (N_int, T) transposed tables: take_along_axis(tab, node, axis=0)
    # fetches tab[node[i, t], t] — every tree's parameter in one gather
    feat_t = trees.feature.T
    thr_t = trees.threshold.T
    cat_t = trees.is_cat.T
    dl_t = trees.default_left.T
    node = jnp.zeros((codes.shape[0], T), jnp.int32)         # (n, T)
    for _ in range(depth):
        f = jnp.take_along_axis(feat_t, node, axis=0)        # (n, T)
        code = jnp.take_along_axis(codes, jnp.maximum(f, 0), axis=1)
        go_left = _decide_go_left(code, f,
                                  jnp.take_along_axis(thr_t, node, axis=0),
                                  jnp.take_along_axis(cat_t, node, axis=0),
                                  jnp.take_along_axis(dl_t, node, axis=0),
                                  missing_bin)
        node = 2 * node + 2 - go_left.astype(jnp.int32)
    leaf = node - (2 ** depth - 1)
    vals = jnp.take_along_axis(trees.leaf_value.T, leaf, axis=0)  # (n, T)
    if n_classes == 1:
        return jnp.sum(vals, axis=1)
    cls_oh = jax.nn.one_hot(jnp.arange(T) % n_classes, n_classes,
                            dtype=jnp.float32)               # (T, K)
    return jax.lax.dot_general(vals, cls_oh, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
