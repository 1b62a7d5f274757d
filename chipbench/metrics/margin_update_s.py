"""margin_update_s: seconds per round of the step-⑤ margin update in the
fit (``kernels/traversal.py``): the timed fit's
``step_times["traversal"]``, the program's host clock, over its rounds."""
from __future__ import annotations


def read(records):
    fit = records.fit
    if fit is None or "traversal" not in fit["step_times"]:
        return None
    return fit["step_times"]["traversal"] / fit["rounds"]
