"""serve_traversal_ms: device milliseconds per flush of the step-⑤
ensemble traversal kernel (``kernels/traversal.py``) in serving."""
from __future__ import annotations

KERNEL = "predict_ensemble_pallas"


def read(records):
    serve = records.serve
    if records.trace is None or serve is None or not serve["flushes"]:
        return None
    s = records.trace.device_seconds(KERNEL)
    return None if s is None else s * 1e3 / serve["flushes"]
