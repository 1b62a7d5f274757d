"""grow_s: seconds per round of the level-wise grower, steps ①–④
(``core/tree.py``): the timed fit's ``step_times["binning_split"]``, the
program's host clock around tree growth, over its rounds."""
from __future__ import annotations


def read(records):
    fit = records.fit
    if fit is None or "binning_split" not in fit["step_times"]:
        return None
    return fit["step_times"]["binning_split"] / fit["rounds"]
