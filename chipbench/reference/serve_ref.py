"""Plain reference for served predictions: quantile binning
(``binning_ref``) and a walk of every tree, in numpy at float32.  It
imports nothing of the program.

A tree is the complete binary table of ``TreeArrays``: node i's children
are 2i+1 and 2i+2, a node goes left when its code is <= the threshold
(numeric) or equal to it (categorical), a missing code goes the node's
default way.  The margin is the base margin plus every tree's leaf, and
the answer its sigmoid.

``precision="bfloat16"`` stores the features and the leaf values in
bfloat16 before anything else happens: that is the control.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.reference.binning_ref import bin_rows, to_bf16

ROWS_PER_STEP = 4096


def predict(X: np.ndarray, trees: Dict[str, np.ndarray], base_margin: float,
            tables: Dict, precision: str = "float32") -> np.ndarray:
    """Probabilities for raw rows ``X`` (n, F)."""
    leaf = np.asarray(trees["leaf_value"], np.float32)
    if precision == "bfloat16":
        X, leaf = to_bf16(X), to_bf16(leaf)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    codes = bin_rows(X, tables)
    feature = np.asarray(trees["feature"])
    threshold = np.asarray(trees["threshold"])
    is_cat = np.asarray(trees["is_cat"])
    default_left = np.asarray(trees["default_left"])
    T, n_int = feature.shape
    t_idx = np.arange(T)[None, :]
    out = np.empty((X.shape[0],), np.float32)
    for lo in range(0, X.shape[0], ROWS_PER_STEP):
        c = codes[lo:lo + ROWS_PER_STEP]
        r_idx = np.arange(c.shape[0])[:, None]
        node = np.zeros((c.shape[0], T), np.int64)
        while True:
            inner = node < n_int
            if not inner.any():
                break
            at = np.minimum(node, n_int - 1)
            f = feature[t_idx, at]
            code = c[r_idx, np.maximum(f, 0)]
            thr = threshold[t_idx, at]
            left = np.where(is_cat[t_idx, at] == 1, code == thr, code <= thr)
            left = np.where(code == tables["missing"],
                            default_left[t_idx, at] == 1, left)
            left = np.where(f < 0, True, left)
            node = np.where(inner, 2 * node + 1 + (~left), node)
        vals = leaf[t_idx, node - n_int]
        margin = np.float32(base_margin) + vals.sum(axis=1, dtype=np.float32)
        out[lo:lo + c.shape[0]] = 1.0 / (1.0 + np.exp(-margin))
    return out
