"""fit_mfu: the whole boosting round's share of the chip's peak — the
least time its required work takes at the peaks, over the measured round.

Required work per round over n records, F fields, NB bins, depth D, from
shapes (bytes of float32 g, h, margins, labels and int32 node ids):

* gradient statistics: read margins and labels, write g and h (16n B);
* histograms: every level, as ``hist_roofline.level_work`` counts them;
* partition: every level reads the node ids and one code per record and
  writes the node ids (9n B, n ops);
* leaf sums: read g, h and the final node ids (12n B, 2n ops);
* margin update: read D codes per record, read and write margins
  ((8 + D)n B, Dn ops);
* loss: read margins and labels (8n B, 3n ops)."""
from __future__ import annotations

from chipbench.metrics import hist_roofline
from chipbench.metrics._shared import least_seconds


def round_work(n: int, F: int, NB: int, depth: int):
    ops, nbytes = hist_roofline.round_work(n, F, NB, depth)
    nbytes += 16 * n + depth * 9 * n + 12 * n + (8 + depth) * n + 8 * n
    ops += 6 * n + depth * n + 2 * n + depth * n + 3 * n
    return ops, nbytes


def read(records):
    fit = records.fit
    if fit is None:
        return None
    ops, nbytes = round_work(fit["records"], fit["fields"], fit["bins"],
                             fit["depth"])
    round_s = fit["window_s"] / fit["rounds"]
    return 100.0 * least_seconds(ops, nbytes, records.peaks) / round_s
