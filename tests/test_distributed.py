"""Distributed-path tests.  Multi-device cases run in a subprocess with 8
forced host devices (the main pytest process must keep the default single
device for everything else)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=_ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.slow
def test_distributed_histogram_and_tree_match_single_device():
    out = _run_with_devices(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import fit_tree
from repro.core.splits import find_best_splits
from repro.distributed.sharding import (distributed_histogram,
                                        distributed_split_combine,
                                        pjit_fit_tree)
from repro.launch.mesh import make_mesh
from repro.kernels import ops

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
n, F, NB, NN = 4096, 8, 16, 4
codes = jnp.asarray(rng.integers(0, NB, (n, F)), jnp.uint8)
g = jnp.asarray(rng.normal(size=n), jnp.float32)
h = jnp.asarray(rng.uniform(.1, 1, n), jnp.float32)
nid = jnp.asarray(rng.integers(0, NN, n), jnp.int32)
from repro.api import ExecutionPlan
ref = ops.build_histogram(codes, g, h, nid, n_nodes=NN, n_bins=NB,
                          plan=ExecutionPlan.auto(hist_strategy="scatter"))
dist = distributed_histogram(mesh, codes, g, h, nid, n_nodes=NN,
                             n_bins=NB, strategy="scatter")
np.testing.assert_allclose(np.asarray(dist), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
iscat = jnp.zeros((F,), bool); fmask = jnp.ones((F,), bool)
ds = distributed_split_combine(mesh, dist, iscat, fmask, 1.0, 0.0, 1.0, F)
ss = find_best_splits(ref, iscat, fmask, 1.0, 0.0, 1.0)
np.testing.assert_allclose(np.asarray(ds.gain), np.asarray(ss.gain),
                           rtol=1e-5)
np.testing.assert_array_equal(np.asarray(ds.feature),
                              np.asarray(ss.feature))
codes_cm = jnp.asarray(np.asarray(codes).T.copy())
fj = pjit_fit_tree(mesh, depth=4, n_bins=NB, missing_bin=NB-1,
                   lambda_=1.0, gamma=0.0, min_child_weight=1.0)
t_dist = fj(codes, codes_cm, g, h, iscat, fmask)
t_ref = fit_tree(codes, codes_cm, g, h, depth=4, n_bins=NB,
                 missing_bin=NB-1, is_cat_field=iscat, field_mask=fmask,
                 lambda_=1.0, gamma=0.0, min_child_weight=1.0,
                 hist_strategy="scatter", partition_strategy="reference")
for a, b, nm in zip(t_dist, t_ref, t_ref._fields):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5, err_msg=nm)
print("DIST_OK")
""")
    assert "DIST_OK" in out


@pytest.mark.slow
def test_elastic_shrink_restore_preserves_predictions():
    out = _run_with_devices(r"""
import numpy as np, jax, jax.numpy as jnp, tempfile
from repro.core import GBDTConfig, GBDTModel, bin_dataset, train
from repro.data import make_tabular
from repro.distributed import checkpoint as ckpt
from repro.distributed.elastic import ElasticContext
from repro.distributed.sharding import shard_dataset

X, y, cats = make_tabular(2000, 6, 0, task="regression", seed=1)
data = bin_dataset(X, max_bins=32)
res = train(GBDTConfig(n_trees=3, max_depth=3, hist_strategy="scatter"),
            data, y)
pred0 = np.asarray(res.model.predict(data))
ctx = ElasticContext(model_parallel=2)
assert ctx.mesh.shape == {"data": 4, "model": 2}
with tempfile.TemporaryDirectory() as d:
    ckpt.save(d, res.model.to_state(), step=3)
    # shrink: lose 2 devices -> (3, 2) mesh; restore onto survivor mesh
    mesh2 = ctx.resize(jax.devices()[:6])
    assert mesh2.shape == {"data": 3, "model": 2}
    sharded = shard_dataset(data, mesh2)   # pads 2000 -> 2001 (3 shards)
    state, step, _ = ckpt.restore(d, like=res.model.to_state())
    model2 = GBDTModel.from_state(state)
    pred1 = np.asarray(model2.predict(sharded))[:2000]
np.testing.assert_allclose(pred1, pred0, rtol=1e-5, atol=1e-6)
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_train_distributed_matches_single_device_regression():
    """K=1 parity across shard counts {1, 2, 8}: dyadic targets (multiples
    of 0.25, n a power of two, squared-error h=1) make every round-0
    histogram cell exactly representable, so the first tree must be
    BIT-equal for every shard count; D=1 must be bit-equal to the fused
    single-device trainer for the WHOLE trajectory (trees and losses);
    every D must match the per-op trainer within the documented
    float-tolerance contract (identical structure, leaf values ~1e-6)."""
    out = _run_with_devices(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import GBDTConfig, bin_dataset, train
from repro.distributed.trainer import train_distributed, data_parallel_mesh

rng = np.random.default_rng(0)
n, F = 4096, 6
X = rng.normal(size=(n, F))
y = (rng.integers(-8, 9, n) * 0.25).astype(np.float32)   # dyadic targets
data = bin_dataset(X, max_bins=32)
cfg = GBDTConfig(n_trees=4, max_depth=4, hist_strategy="scatter")
ref = train(cfg, data, y)
fused = train(GBDTConfig(n_trees=4, max_depth=4, hist_strategy="scatter",
                         fused_rounds=True), data, y)
pref = np.asarray(ref.model.predict(data))
cfg1 = GBDTConfig(n_trees=1, max_depth=4, hist_strategy="scatter")
tree0 = train(cfg1, data, y).model.trees
for D in (1, 2, 8):
    mesh = data_parallel_mesh(jax.devices()[:D])
    res = train_distributed(cfg, data, y, mesh=mesh)
    assert res.stats["n_shards"] == D
    # round 0: bit-equal to the single-device tree for EVERY shard count
    t0 = train_distributed(cfg1, data, y, mesh=mesh).model.trees
    for a, b, nm in zip(t0, tree0, tree0._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"round0 D={D} {nm}")
    if D == 1:
        # one shard reassociates nothing: the full trajectory is
        # bit-equal to the fused trainer (same one-jit round program)
        for a, b, nm in zip(res.model.trees, fused.model.trees,
                            tree0._fields):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"D=1 fused {nm}")
        assert res.history["train_loss"] == fused.history["train_loss"]
    # full trajectory vs the per-op trainer: same structure, leaf values
    # within the float contract (FMA/psum reassociation)
    for nm in ("feature", "threshold", "is_cat", "default_left"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res.model.trees, nm)),
            np.asarray(getattr(ref.model.trees, nm)),
            err_msg=f"D={D} {nm}")
    p = np.asarray(res.model.predict(data))
    np.testing.assert_allclose(p, pref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(res.history["train_loss"],
                               ref.history["train_loss"],
                               rtol=1e-5, atol=1e-6)
print("PARITY_K1_OK")
""")
    assert "PARITY_K1_OK" in out


@pytest.mark.slow
def test_train_distributed_matches_single_device_multiclass():
    """K=3 softmax parity across shard counts {1, 2, 8}: softmax gradients
    are not dyadic, so D>1 psum reassociation forbids bit-equality — the
    contract is identical tree STRUCTURE (integer fields) plus allclose
    leaf values/losses for the whole fit, and D=1 stays bit-equal."""
    out = _run_with_devices(r"""
import numpy as np, jax
from repro.core import GBDTConfig, bin_dataset, train
from repro.distributed.trainer import train_distributed, data_parallel_mesh

rng = np.random.default_rng(1)
n, F = 4096, 6
X = rng.normal(size=(n, F))
y = rng.integers(0, 3, n)
data = bin_dataset(X, max_bins=32)
cfg = GBDTConfig(n_trees=3, max_depth=3, objective="multi:softmax",
                 n_classes=3, hist_strategy="scatter")
ref = train(cfg, data, y, eval_set=(data, y))
fused = train(GBDTConfig(n_trees=3, max_depth=3, objective="multi:softmax",
                         n_classes=3, hist_strategy="scatter",
                         fused_rounds=True), data, y, eval_set=(data, y))
pfused = np.asarray(fused.model.predict_margin(data.codes))
pref = np.asarray(ref.model.predict_margin(data.codes))
for D in (1, 2, 8):
    mesh = data_parallel_mesh(jax.devices()[:D])
    res = train_distributed(cfg, data, y, mesh=mesh, eval_set=(data, y))
    for nm in ("feature", "threshold", "is_cat", "default_left"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res.model.trees, nm)),
            np.asarray(getattr(ref.model.trees, nm)),
            err_msg=f"D={D} {nm}")
    p = np.asarray(res.model.predict_margin(data.codes))
    if D == 1:   # one shard: bit-equal to the fused one-jit round program
        for a, b in zip(res.model.trees, fused.model.trees):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(p, pfused)
    np.testing.assert_allclose(p, pref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(res.history["eval_loss"],
                               ref.history["eval_loss"],
                               rtol=1e-5, atol=1e-6)
print("PARITY_K3_OK")
""")
    assert "PARITY_K3_OK" in out


@pytest.mark.slow
def test_train_distributed_hist_subtraction_and_estimator_mesh():
    """The §II-A smaller-child masking path keeps shard parity (psum'd
    integer counts pick the same child everywhere), and the estimator's
    ``fit(mesh=...)`` surface routes through the distributed engine."""
    out = _run_with_devices(r"""
import numpy as np, jax
from repro.api import BoosterRegressor, ExecutionPlan
from repro.core import GBDTConfig, bin_dataset, train
from repro.data import make_tabular
from repro.distributed.trainer import train_distributed, data_parallel_mesh

X, y, _ = make_tabular(2048, 6, 0, task="regression", seed=3)
data = bin_dataset(X, max_bins=32)
plan = ExecutionPlan.auto(hist_subtraction=True)
cfg = GBDTConfig(n_trees=3, max_depth=4, hist_strategy="scatter")
ref = train(cfg, data, y, plan=plan)
res = train_distributed(cfg, data, y, plan=plan,
                        mesh=data_parallel_mesh(jax.devices()))
for nm in ("feature", "threshold", "is_cat", "default_left"):
    np.testing.assert_array_equal(np.asarray(getattr(res.model.trees, nm)),
                                  np.asarray(getattr(ref.model.trees, nm)),
                                  err_msg=nm)
np.testing.assert_allclose(np.asarray(res.model.predict(data)),
                           np.asarray(ref.model.predict(data)),
                           rtol=1e-5, atol=1e-6)

est = BoosterRegressor(n_trees=3, max_depth=4, max_bins=32)
est.fit(X, y, mesh=data_parallel_mesh(jax.devices()))
assert est.stats_["distributed"] and est.stats_["n_shards"] == 8
np.testing.assert_allclose(np.asarray(est.predict(X)),
                           np.asarray(ref.model.predict(data)),
                           rtol=1e-5, atol=1e-5)
print("SUBTRACT_ESTIMATOR_OK")
""")
    assert "SUBTRACT_ESTIMATOR_OK" in out


@pytest.mark.slow
def test_train_distributed_pallas_partition_matches_reference():
    """The Pallas partition kernel runs inside the sharded round step (no
    strategy is swapped there): routing is integer-exact, so a 4-shard fit
    with it grows bit-identical trees to one with the reference walk."""
    out = _run_with_devices(r"""
import numpy as np, jax
from repro.api import ExecutionPlan
from repro.core import GBDTConfig, bin_dataset
from repro.data import make_tabular
from repro.distributed.trainer import train_distributed, data_parallel_mesh

X, y, _ = make_tabular(1024, 4, 0, task="regression", seed=5)
data = bin_dataset(X, max_bins=16)
cfg = GBDTConfig(n_trees=2, max_depth=3)
mesh = data_parallel_mesh(jax.devices())
fits = [train_distributed(cfg, data, y, mesh=mesh,
                          plan=ExecutionPlan.auto(hist_strategy="scatter",
                                                  partition_strategy=p))
        for p in ("pallas", "reference")]
for a, b in zip(fits[0].model.trees, fits[1].model.trees):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("PALLAS_PARTITION_OK")
""", n_devices=4)
    assert "PALLAS_PARTITION_OK" in out


@pytest.mark.slow
def test_smoke_arch_lowers_on_tiny_production_mesh():
    """A reduced config lowers+compiles with the full sharding rules on an
    8-device (4 data x 2 model) mesh — the dry-run path end to end."""
    out = _run_with_devices(r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh
from repro.models import lm, optim

mesh = make_mesh((4, 2), ("data", "model"))
for aid in ("qwen3-14b", "mixtral-8x22b", "jamba-v0.1-52b"):
    cfg = get_smoke(aid)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    pshard = lm.param_shardings(cfg, mesh)
    params = jax.tree.map(jax.device_put, params, pshard)
    opt = optim.adamw_init(params)
    B, S = 8, 16
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    bshard = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    batch = jax.tree.map(jax.device_put, batch, bshard)
    step = jax.jit(lm.make_train_step(cfg))
    with mesh:
        p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"])), aid
    print("LOWER_OK", aid, float(m["loss"]))
""")
    assert out.count("LOWER_OK") == 3
