"""Plain reference for quantile binning, in numpy at float32.  It imports
nothing of the program.

It follows the documented semantics (``core/binning.py`` module text): a
numeric field's edges are the distinct ``k/(B-1)``-quantiles, k = 1 .. B-2,
of its non-missing sample values (B = ``max_bins``), a value takes the
number of edges at or below it, compared in float32; a categorical value is
its integer id, clipped to the categories the sample holds; a missing
value takes code B-1.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def fit_edges(sample: np.ndarray, categorical, max_bins: int) -> Dict:
    """Edges (F, B-2) float64 padded with inf, and the value bins per
    field, from the rows the program's binner was fitted on."""
    n_value = max_bins - 1
    F = sample.shape[1]
    edges = np.full((F, n_value - 1), np.inf)
    value_bins = np.zeros((F,), np.int64)
    qs = np.linspace(0.0, 1.0, n_value + 1)[1:-1]
    for f in range(F):
        col = np.asarray(sample[:, f], np.float64)
        valid = col[~np.isnan(col)]
        if f in categorical:
            value_bins[f] = int(valid.max()) + 1 if valid.size else 1
            continue
        if valid.size == 0:
            value_bins[f] = 1
            continue
        e = np.unique(np.quantile(valid, qs))
        edges[f, :e.size] = e
        value_bins[f] = e.size + 1
    is_cat = np.array([f in categorical for f in range(F)])
    return {"edges": edges, "value_bins": value_bins, "is_cat": is_cat,
            "missing": max_bins - 1}


def to_bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def bin_rows(X: np.ndarray, tables: Dict) -> np.ndarray:
    X = np.asarray(X, np.float32)
    codes = np.empty(X.shape, np.int64)
    edges32 = tables["edges"].astype(np.float32)
    for f in range(X.shape[1]):
        col = X[:, f]
        nan = np.isnan(col)
        val = np.where(nan, 0.0, col).astype(np.float32)
        if tables["is_cat"][f]:
            c = np.clip(val.astype(np.int64), 0, tables["value_bins"][f] - 1)
        else:
            c = np.searchsorted(edges32[f], val, side="right")
        codes[:, f] = np.where(nan, tables["missing"], c)
    return codes
