"""Pallas TPU kernels for step ⑤ (one-tree traversal) and batch inference.

Paper §III-B maps the grown tree to a table replicated in every BU's SRAM;
each record walks the table with data-dependent reads.  The walk here is
expressed over a *packed* node table:

  * the four per-node parameters are packed into ONE int32 word
    ``((feat+1) << 16) | (thr << 8) | (cat << 1) | dl`` (bin codes are
    uint8 and field counts < 2**15 — the repo's binning invariants — so
    the pack is lossless), and the whole packed table (≤ a few hundred
    bytes — the paper's own SRAM-residency argument) lives in VMEM,
    *replicated across grid steps* via a constant index_map, exactly like
    the paper replicates the tree per BU;
  * a block of ``trees_per_block`` trees walks together as one
    (TBLK, RBLK) node matrix — trees on sublanes, records on lanes.  The
    chip has no vector gather, so each hop is a compare walk: the node
    word is selected among the level's 2**l candidate nodes, and the
    field value among the F code rows (each a sublane broadcast).  All
    decisions are integer compares, bit-exact against the reference;
  * child pointers are implicit (node <- 2*node + 2 - go_left), so a D-hop
    walk is D dense vector steps, zero irregular HBM accesses.

Batch inference (§III-D) adds a tree grid dimension: record blocks stream
while each grid step holds a *block* of ``trees_per_block`` packed tables
resident, and accumulates the ensemble sum in the revisited output block —
the analog of Booster pinning one tree per BU and averaging load across
records.  Tree-blocking amortizes each record block fetched into VMEM
across ``trees_per_block`` walks, cutting the code-stream traffic from T
reads per record to ``T / trees_per_block``.  One-tree traversal is the
T = 1 case of the same kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, SUBLANES, round_up
from repro.kernels.ref import TreeArrays


def pack_node_table(tree: TreeArrays) -> jax.Array:
    """(..., N_int) int32 packed node words.

    ``((feature+1) << 16) | (threshold << 8) | (is_cat << 1) |
    default_left`` — one word per internal node, so each walk hop selects
    a single table entry instead of four.
    """
    return (((tree.feature.astype(jnp.int32) + 1) << 16)
            | (tree.threshold.astype(jnp.int32) << 8)
            | (tree.is_cat.astype(jnp.int32) << 1)
            | tree.default_left.astype(jnp.int32))


def _select(idx, table, lo: int, hi: int, init):
    """``table[t, idx[t, r]]`` for idx in [lo, hi): a compare walk over the
    candidate columns (each a lane broadcast of one table column)."""
    out = init
    for j in range(lo, hi):
        out = jnp.where(idx == j, table[:, j:j + 1], out)
    return out


def _ensemble_kernel(codes_ref, table_ref, leaf_ref, out_ref, *,
                     depth: int, missing_bin: int, n_classes: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...].astype(jnp.int32)                  # (F, RBLK)
    table = table_ref[...]                                    # (TBLK, N_int)
    tblk, n_int = table.shape
    n_fields, rblk = codes.shape
    # the codes block is fetched ONCE and walked by the whole resident
    # tree block at once (paper: one record stream shared by all BUs)
    node = jnp.zeros((tblk, rblk), jnp.int32)
    for level in range(depth):      # static: fixed-depth walk, §III-B
        p = _select(node, table, 2 ** level - 1, 2 ** (level + 1) - 1,
                    jnp.zeros_like(node))
        f = (p >> 16) - 1
        code = jnp.zeros_like(node)
        for c in range(n_fields):
            code = jnp.where(f == c, codes[c:c + 1, :], code)
        thr = (p >> 8) & 255
        left = jnp.where((p & 2) != 0, (code == thr).astype(jnp.int32),
                         (code <= thr).astype(jnp.int32))
        left = jnp.where(code == missing_bin, p & 1, left)
        left = jnp.where(f < 0, 1, left)                      # pass-through
        node = 2 * node + 2 - left
    vals = _select(node - n_int, leaf_ref[...], 0, leaf_ref.shape[1],
                   jnp.zeros((tblk, rblk), jnp.float32))      # (TBLK, RBLK)
    # multi-class: round-major tree order, tree t owns margin row t % K;
    # the tree block is a multiple of K, so row t of every block feeds
    # class t % K.  Zero-leaf padding trees contribute exactly 0.
    if n_classes == 1:
        out_ref[...] += jnp.sum(vals, axis=0, keepdims=True)
    else:
        for k in range(n_classes):
            acc = vals[k:k + 1, :]
            for t in range(k + n_classes, tblk, n_classes):
                acc = acc + vals[t:t + 1, :]
            out_ref[k:k + 1, :] += acc


def _tree_block(T: int, trees_per_block: int, n_classes: int) -> int:
    """Trees per grid step: the whole ensemble when it fits one block,
    else a multiple of the sublane count and of the class count."""
    quantum = math.lcm(SUBLANES, n_classes)
    tblk = round_up(max(trees_per_block, 1), quantum)
    return T if tblk >= T else tblk


@functools.partial(jax.jit, static_argnames=("missing_bin", "depth",
                                             "records_per_block", "interpret",
                                             "n_classes", "trees_per_block"))
def predict_ensemble_pallas(trees: TreeArrays, codes, *, missing_bin: int,
                            depth: int, interpret: bool,
                            records_per_block: int = 1024, n_classes: int = 1,
                            trees_per_block: int = 8):
    """Batch inference: trees hold stacked (T, ...) arrays; codes (n, F).

    Grid = (record blocks, T / TBLK): each step holds a block of packed
    int32 node tables resident in VMEM (paper: one tree per BU, here a BU
    block per grid step) and accumulates into the revisited output block
    — each record block read is amortized across the whole tree block.
    The ensemble is zero-padded (pass-through trees with all-zero leaves)
    up to a multiple of the block; padding contributes exactly +0.0.
    Requires fewer than 2**15 code columns (the int32 table pack — the
    repo's binning invariant; ``gbdt`` renumbers wider matrices before
    dispatching here).  Returns (n,) float32 ensemble sums — or (n, K)
    per-class margins when ``n_classes > 1`` (trees round-major; tree t
    feeds class t % K, so the walk itself is unchanged).
    """
    n, n_cols = codes.shape
    T = trees.feature.shape[0]
    tblk = _tree_block(T, trees_per_block, n_classes)
    t_pad = round_up(T, tblk) - T
    if t_pad:
        trees = TreeArrays(
            feature=jnp.pad(trees.feature, ((0, t_pad), (0, 0)),
                            constant_values=-1),
            threshold=jnp.pad(trees.threshold, ((0, t_pad), (0, 0))),
            is_cat=jnp.pad(trees.is_cat, ((0, t_pad), (0, 0))),
            default_left=jnp.pad(trees.default_left, ((0, t_pad), (0, 0))),
            leaf_value=jnp.pad(trees.leaf_value, ((0, t_pad), (0, 0))))
    rblk = min(round_up(records_per_block, LANES), round_up(n, LANES))
    np_ = round_up(n, rblk)
    codes_t = jnp.pad(codes.T, ((0, 0), (0, np_ - n)))        # (F, np)
    n_int = trees.feature.shape[1]
    n_leaf = trees.leaf_value.shape[1]
    out = pl.pallas_call(
        functools.partial(_ensemble_kernel, depth=depth,
                          missing_bin=missing_bin, n_classes=n_classes),
        grid=(np_ // rblk, (T + t_pad) // tblk),
        in_specs=[
            pl.BlockSpec((n_cols, rblk), lambda ri, ti: (0, ri)),
            pl.BlockSpec((tblk, n_int), lambda ri, ti: (ti, 0)),
            pl.BlockSpec((tblk, n_leaf), lambda ri, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((n_classes, rblk), lambda ri, ti: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((n_classes, np_), jnp.float32),
        interpret=interpret,
        name="predict_ensemble_pallas",
    )(codes_t, pack_node_table(trees),
      trees.leaf_value.astype(jnp.float32))
    return out[0, :n] if n_classes == 1 else out[:, :n].T


def traverse_pallas(tree: TreeArrays, codes, *, missing_bin: int,
                    interpret: bool, records_per_block: int = 1024):
    """One-tree traversal; codes (n, C) with C matching tree.feature ids.

    Returns (n,) float32 leaf values — the T = 1 ensemble.
    """
    return predict_ensemble_pallas(
        TreeArrays(*[a[None] for a in tree]), codes,
        missing_bin=missing_bin, depth=tree.depth, interpret=interpret,
        records_per_block=records_per_block, trees_per_block=1)
