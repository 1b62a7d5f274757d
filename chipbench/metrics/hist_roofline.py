"""hist_roofline: the step-① histogram kernel's share of its roofline.

The required work is counted from shapes, whatever strategy implements
it.  Per level of NN nodes over n records and F fields of NB bins: read
n·F one-byte codes, 8n bytes of g and h, 4n bytes of node ids, write
NN·F·NB·8 bytes of float32 (g, h) sums, and make one add per code for each
of the two statistics.  Its least time at the chip's peaks, over the
kernel's device time per round."""
from __future__ import annotations

from chipbench.metrics import hist_kernel_ms
from chipbench.metrics._shared import least_seconds, per_round


def level_work(n: int, F: int, NB: int, NN: int):
    """(ops, bytes) one histogram level requires."""
    ops = 2 * n * F
    nbytes = n * F + 8 * n + 4 * n + NN * F * NB * 8
    return ops, nbytes


def round_work(n: int, F: int, NB: int, depth: int):
    """(ops, bytes) of the histograms of every level of one tree."""
    ops = nbytes = 0
    for level in range(depth):
        o, b = level_work(n, F, NB, 2 ** level)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def read(records):
    kernel = per_round(records, hist_kernel_ms.seconds(records))
    if not kernel:
        return None
    fit = records.fit
    ops, nbytes = round_work(fit["records"], fit["fields"], fit["bins"],
                             fit["depth"])
    return 100.0 * least_seconds(ops, nbytes, records.peaks) / kernel
