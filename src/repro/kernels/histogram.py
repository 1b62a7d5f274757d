"""Pallas TPU kernel for step ① — gradient-statistics histogram binning.

This is the TPU-native re-expression of Booster's sea-of-small-SRAMs +
group-by-field mapping (paper §III-A/B):

  * The paper gives every *field* its own 2-KB SRAM so that each streamed
    record performs exactly one read-modify-write per SRAM.  A TPU has no
    independently addressable small memories, but it has an MXU that performs
    a 128x128 systolic contraction per cycle.  We therefore turn the
    irregular ``hist[node, bin] += (g, h)`` scatter into a *dense* one-hot
    contraction per field:

        hist_f (NB, NN*2)  +=  one_hot(codes[:, f], NB)^T  @  stats_node

    where ``stats_node[r] = one_hot(node[r], NN) ⊗ (g[r], h[r])`` carries the
    per-record (g,h) pre-spread over the record's tree-node slot.  The MXU
    plays the role of the 3200 parallel FP adders.

  * Group-by-field becomes a *BlockSpec* statement: the grid tiles the field
    dimension so one grid cell owns ``FBLK`` whole fields, and the VMEM
    accumulator tile ``(FBLK, NN*2, NB)`` keeps *all bins of a field
    together* — one small matmul per field per record-block, never a bin tile
    shared between fields.

  * Records ride the lane axis.  The codes are streamed field-major
    (``(F, n)``, the paper's column-major copy), so a field's one-hot is a
    sublane broadcast of one code row against a bin iota, and the per-field
    contraction is ``stats (S, R) · one_hot (NB, R)ᵀ`` — both operands
    lane-dense, no reshapes, no gathers.

  * Exact arithmetic on the MXU.  The one-hot is exact in bfloat16; the
    float32 stats are split into three bfloat16 parts (hi + mid + lo
    reproduce every normal float32 exactly), stacked on the sublane axis
    and contracted in ONE matmul with float32 accumulation.  The result
    then differs from a float32 scatter only by summation order — a single
    bfloat16 pass would round every (g, h) to 8 mantissa bits.

  * The record stream is the grid's fast axis; Pallas double-buffers the
    HBM→VMEM block DMA exactly like the paper's double-buffered record fetch
    (§III-B), so compute hides under the memory stream.

A ``packed`` variant reproduces the paper's *naive packing* baseline
(Fig 9 ablation): bins of all ``FBLK`` fields are packed into a single
``FBLK*NB``-wide one-hot tile.  MAC count is identical but the transient
one-hot tile is ``FBLK``× larger, which on real hardware forces smaller
record blocks / fewer resident fields — the VMEM-pressure analog of the
paper's serialized SRAM accesses.

When the codes arrive 4-bit packed (:class:`repro.core.binning.PackedCodes`
— paper §III-B's compressed representation), the grouped kernel streams
the packed *bytes* through the BlockSpec pipeline and unpacks the nibbles
in VMEM per block: the HBM→VMEM code traffic halves while the contraction
math — and therefore the histogram, bit for bit — is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.binning import PackedCodes
from repro.kernels.tiling import LANES, STAT_ROWS, SUBLANES, round_up


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def split_bf16x3(x):
    """float32 -> (hi, mid, lo) bfloat16 parts with hi + mid + lo == x for
    every normal float32 (each part takes the next 8 mantissa bits of the
    remainder, and each remainder is exact in float32)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _stats_t(node_ref, g_ref, h_ref, n_nodes: int, n_rows: int):
    """(S, RBLK) float32 spread of (g, h) over the node slots, S >= K*NN*2.

    Row ``s = (k*NN + nn)*2 + c`` holds class k's statistic c (0 = g,
    1 = h) for the records sitting in node nn, zero elsewhere.  The class
    axis K (multi-class boosting: one tree per class per round, each with
    its own node partition) widens the stats operand — the code stream is
    read ONCE and a single matmul accumulates every class's (g, h).
    Records whose node id lies outside [0, NN) contribute nothing."""
    K, rblk = node_ref.shape
    s = _iota((n_rows, rblk), 0)
    slot, is_h = s >> 1, (s & 1) == 1
    out = jnp.zeros((n_rows, rblk), jnp.float32)
    for k in range(K):                                     # static, K small
        node = node_ref[k:k + 1, :]                        # (1, RBLK)
        valid = (node >= 0) & (node < n_nodes)
        stat = jnp.where(is_h, h_ref[k:k + 1, :], g_ref[k:k + 1, :])
        hit = (slot == node + k * n_nodes) & valid
        out = jnp.where(hit, stat, out)
    return out


def _hist_kernel(codes_ref, node_ref, g_ref, h_ref, hist_ref, *,
                 n_bins: int, n_nodes: int, nibble_packed: bool,
                 packed: bool):
    """One (field block, record block) grid cell.

    ``codes_ref``: (FBLK, RBLK) uint8 codes, field-major — or (FBLK/2,
    RBLK) nibble-packed bytes, unpacked here in VMEM (the block DMA from
    HBM moves half the bytes).  ``hist_ref``: the (FBLK, S, NB)
    accumulator, revisited along the record axis.

    Group-by-field (``packed=False``) contracts each field's own
    (NB, RBLK) one-hot.  The naive-packing ablation (``packed=True``)
    concatenates the block's one-hots into one FBLK*NB-wide tile and
    issues a single matmul — identical MACs, an FBLK× larger transient."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    fblk, n_rows, _ = hist_ref.shape
    rblk = node_ref.shape[1]
    lhs = jnp.concatenate(
        split_bf16x3(_stats_t(node_ref, g_ref, h_ref, n_nodes, n_rows)),
        axis=0)                                            # (3S, RBLK) bf16
    raw = codes_ref[...].astype(jnp.int32)
    bins = _iota((n_bins, rblk), 0)

    def one_hot(f):
        if nibble_packed:
            row = (raw[f // 2:f // 2 + 1, :] >> (4 * (f % 2))) & 0xF
        else:
            row = raw[f:f + 1, :]
        return jnp.where(row == bins, 1.0, 0.0).astype(jnp.bfloat16)

    def contract(oh):                                      # -> (S, width)
        acc = lax.dot_general(lhs, oh, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        return (acc[:n_rows] + acc[n_rows:2 * n_rows]) + acc[2 * n_rows:]

    if packed:
        flat = contract(jnp.concatenate([one_hot(f) for f in range(fblk)],
                                        axis=0))
        for f in range(fblk):
            hist_ref[f] += flat[:, f * n_bins:(f + 1) * n_bins]
    else:
        for f in range(fblk):
            hist_ref[f] += contract(one_hot(f))


def _field_block(F: int, fields_per_block: int, nibble: bool) -> int:
    """Fields per grid cell: all of them when they fit one block, else a
    multiple of the sublane count (of packed bytes, for nibble codes)."""
    quantum = 2 * SUBLANES if nibble else SUBLANES
    fblk = round_up(max(fields_per_block, 1), quantum)
    if fblk >= F:
        return F + (F % 2 if nibble else 0)
    return fblk


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "records_per_block",
                     "fields_per_block", "packed", "interpret"))
def histogram_pallas(codes, g, h, node_ids, *, n_nodes: int, n_bins: int,
                     records_per_block: int, fields_per_block: int,
                     packed: bool, interpret: bool):
    """Histogram binning via the one-hot MXU kernel.

    codes: (n, F) uint8, or a :class:`PackedCodes` carrying the same
    logical (n, F) as 4-bit nibbles (grouped kernel only — the packed
    bytes are streamed through the BlockSpec pipeline and unpacked in
    VMEM, halving the HBM code traffic); g, h: (n,) float; node_ids:
    (n,) int32.  Returns (n_nodes, F, n_bins, 2) float32.  Inputs are
    padded to block multiples here (padded records carry g = h = 0 → no
    contribution).

    Class-batched form: g, h, node_ids may carry a leading class axis
    (K, n) — one launch then reads codes once and accumulates all K
    classes' statistics through a K*NN*2-row stats operand, returning
    (K, n_nodes, F, n_bins, 2).
    """
    nibble = isinstance(codes, PackedCodes)
    if nibble and packed:
        # the Fig-9 naive-packing ablation keeps its historical uint8 feed
        codes, nibble = codes.unpack(), False

    batched = g.ndim == 2
    g2 = (g if batched else g[None]).astype(jnp.float32)       # (K, n)
    h2 = (h if batched else h[None]).astype(jnp.float32)
    node2 = (node_ids if batched else node_ids[None]).astype(jnp.int32)
    K = g2.shape[0]

    n, F = codes.shape
    rblk = min(round_up(records_per_block, LANES), round_up(n, LANES))
    fblk = _field_block(F, fields_per_block, nibble)
    np_ = round_up(n, rblk)
    Fp = round_up(F, fblk)
    n_rows = round_up(K * n_nodes * 2, STAT_ROWS)
    pad_r = ((0, 0), (0, np_ - n))
    g2, h2, node2 = (jnp.pad(a, pad_r) for a in (g2, h2, node2))

    # field-major code stream; pad fields only feed the sliced-off hist
    # rows >= F, pad records carry zero stats
    if nibble:
        data = codes.data.T                                    # (ceil(F/2), n)
        code_op = jnp.pad(data, ((0, Fp // 2 - data.shape[0]),
                                 (0, np_ - n)))
        code_spec = pl.BlockSpec((fblk // 2, rblk), lambda fi, ri: (fi, ri))
    else:
        code_op = jnp.pad(codes.T, ((0, Fp - F), (0, np_ - n)))
        code_spec = pl.BlockSpec((fblk, rblk), lambda fi, ri: (fi, ri))

    stat_spec = pl.BlockSpec((K, rblk), lambda fi, ri: (0, ri))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins, n_nodes=n_nodes,
                          nibble_packed=nibble, packed=packed),
        grid=(Fp // fblk, np_ // rblk),   # fields outer, record stream inner
        in_specs=[code_spec, stat_spec, stat_spec, stat_spec],
        out_specs=pl.BlockSpec((fblk, n_rows, n_bins),
                               lambda fi, ri: (fi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, n_rows, n_bins), jnp.float32),
        interpret=interpret,
    )(code_op, node2, g2, h2)

    hist = out[:F, :K * n_nodes * 2].reshape(F, K, n_nodes, 2, n_bins)
    hist = hist.transpose(1, 2, 0, 4, 3)            # (K, NN, F, NB, 2)
    return hist if batched else hist[0]
