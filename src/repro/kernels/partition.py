"""Pallas TPU kernel for step ③ — single-predicate evaluation / partition.

Paper §III-B: the freshly chosen predicate is broadcast (replicated) to all
BUs; each BU evaluates it against a streamed single-field column (fetched
from the redundant per-field column-major copy) and routes the record
pointer to the predicate-true or predicate-false stream.

Our level-wise grower evaluates *all* of a level's predicates in one pass:
each record carries its level-local node id, and the level's split table
(one predicate per node, ≤ 2**level entries — tiny, held in SMEM like the
paper's broadcast) decides left/right.  The routed result is the record's
child node id; the fixed-shape design replaces the paper's pointer streams
with an in-place id update (stream compaction is only needed by the
leaf-wise grower and is done with a sort there).

The field columns consumed here are gathered from the column-major copy —
only the ≤ NN fields named by the level's predicates travel HBM→VMEM, which
is the redundant-representation bandwidth saving of §III (steps ③/⑤).
Records ride the lane axis: the kernel walks the level's nodes, reads each
node's predicate as SMEM scalars and its field as one code row, and routes
the records sitting in that node — integer compares only, so the result is
bit-exact on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, round_up


def _partition_kernel(table_ref, node_ref, codes_ref, out_ref, *,
                      missing_bin: int, n_nodes: int):
    node = node_ref[...]                                      # (1, RBLK)

    def route(j, child):
        # predicate words are SMEM scalars; decisions stay int32 0/1 (the
        # chip has no vector form of a scalar-predicated boolean select)
        f = table_ref[0, j]
        thr = table_ref[1, j]
        is_cat = table_ref[2, j]
        code = codes_ref[pl.ds(jnp.maximum(f, 0), 1), :]      # (1, RBLK)
        left = (is_cat * (code == thr).astype(jnp.int32)
                + (1 - is_cat) * (code <= thr).astype(jnp.int32))
        left = jnp.where(code == missing_bin, table_ref[3, j], left)
        left = jnp.maximum(left, (f < 0).astype(jnp.int32))   # pass-through
        return jnp.where(node == j, 2 * j + 1 - left, child)

    out_ref[...] = lax.fori_loop(0, n_nodes, route, 2 * node)


@functools.partial(jax.jit, static_argnames=("missing_bin",
                                             "records_per_block", "interpret"))
def partition_pallas(node_ids, codes_lvl, split_feature, split_threshold,
                     split_is_cat, split_default_left, *, missing_bin: int,
                     records_per_block: int = 1024, interpret: bool):
    """Route records to children.  Level-local ids: out in [0, 2*NN).

    node_ids (n,) int32; codes_lvl (n, C) uint8 compact per-level columns;
    split_* (NN,) with split_feature indexing [0, C) or -1 (pass-through).
    """
    n, n_cols = codes_lvl.shape
    rblk = min(round_up(records_per_block, LANES), round_up(n, LANES))
    np_ = round_up(n, rblk)
    # column-major int32 rows: the kernel reads one code row per node
    codes_t = jnp.pad(codes_lvl.T.astype(jnp.int32), ((0, 0), (0, np_ - n)))
    node_p = jnp.pad(node_ids.astype(jnp.int32), (0, np_ - n))[None]
    n_nodes = split_feature.shape[0]
    table = jnp.stack([split_feature, split_threshold, split_is_cat,
                       split_default_left]).astype(jnp.int32)   # (4, NN)
    out = pl.pallas_call(
        functools.partial(_partition_kernel, missing_bin=missing_bin,
                          n_nodes=n_nodes),
        grid=(np_ // rblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # replicated
            pl.BlockSpec((1, rblk), lambda ri: (0, ri)),
            pl.BlockSpec((n_cols, rblk), lambda ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec((1, rblk), lambda ri: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        interpret=interpret,
    )(table, node_p, codes_t)
    return out[0, :n]
