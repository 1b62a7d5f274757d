"""Multi-class boosting — class-batched step ① vs per-class passes.

The class-batched histogram build (one launch, K-wide stats operand)
reads the record/code stream ONCE per level regardless of K; the naive
alternative runs K independent scalar passes (K× the code traffic).
This bench measures both at growing K on one paper-shaped dataset, plus
the end-to-end per-round cost of ``multi:softmax`` training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, hist_plan, time_call
from repro import tracing
from repro.core import GBDTConfig, bin_dataset, train
from repro.data import make_tabular
from repro.kernels import ops


def run(scale: float = 1.0, max_bins: int = 64, strategy: str = "onehot"):
    rows = []
    n = max(2000, int(8000 * scale))
    X, y, _ = make_tabular(n, 24, 0, task="multiclass", n_classes=8, seed=0)
    data = bin_dataset(X, max_bins=max_bins)
    plan = hist_plan(strategy)
    rng = np.random.default_rng(0)
    nid1 = jnp.asarray(rng.integers(0, 8, n), jnp.int32)

    for K in (2, 4, 8):
        g = jnp.asarray(rng.normal(size=(K, n)), jnp.float32)
        h = jnp.asarray(rng.uniform(0.1, 1.0, (K, n)), jnp.float32)
        nid = jnp.broadcast_to(nid1, (K, n))

        t_batched = time_call(lambda: ops.build_histogram(
            data.codes, g, h, nid, n_nodes=8, n_bins=data.n_bins,
            plan=plan))
        t_perclass = time_call(lambda: jax.block_until_ready([
            ops.build_histogram(data.codes, g[k], h[k], nid[k],
                                n_nodes=8, n_bins=data.n_bins, plan=plan)
            for k in range(K)]))
        rows.append(csv_row(
            f"hist_class_batched_K{K}", t_batched * 1e6,
            f"per_class_x={t_perclass / t_batched:.2f};"
            f"strategy={strategy};records={n}"))

    res = train(GBDTConfig(n_trees=3, max_depth=5, objective="multi:softmax",
                           n_classes=8, hist_strategy=strategy),
                data, y)
    per_round = res.step_times[tracing.FUSED_ROUNDS] / 3
    rows.append(csv_row("multiclass_train_round", per_round * 1e6,
                        f"K=8;depth=5;records={n};"
                        f"final_loss={res.history['train_loss'][-1]:.4f}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
