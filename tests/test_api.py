"""The `repro.api` facade: estimators, ExecutionPlan, unified bundles."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.api import (BoosterClassifier, BoosterRegressor, ExecutionPlan,
                       load, load_checkpoint, save)
from repro.api.estimator import NotFittedError
from repro.core import GBDTConfig, train
from repro.core.binning import Binner
from repro.core.gbdt import GBDTModel
from repro.core.inference import GBDTPipeline, feature_importance
from repro.data import make_tabular
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def data():
    X, y, cats = make_tabular(1500, 5, 2, n_cats=6, task="regression",
                              missing_rate=0.03, seed=11)
    return X, y, cats


@pytest.fixture(scope="module")
def fitted(data):
    X, y, cats = data
    est = BoosterRegressor(n_trees=6, max_depth=4, learning_rate=0.3,
                           max_bins=32, categorical_fields=cats, seed=3)
    est.fit(X, y)
    return est


# --------------------------------------------------------------------------
# ExecutionPlan
# --------------------------------------------------------------------------
def test_plan_auto_resolves_for_backend():
    plan = ExecutionPlan.auto()
    # tests pin JAX_PLATFORMS=cpu (conftest), so the software paths win
    assert plan.hist_strategy == "scatter"
    assert plan.partition_strategy == "reference"
    assert plan.traversal_strategy == "reference"
    assert plan.interpret is True
    # idempotent and already-concrete
    assert plan.resolved() == plan


def test_plan_from_config_lifts_legacy_strings():
    cfg = GBDTConfig(hist_strategy="sort", partition_strategy="pallas",
                     traversal_strategy="reference",
                     host_offload_split=True)
    plan = ExecutionPlan.from_config(cfg)
    assert plan.hist_strategy == "sort"
    assert plan.partition_strategy == "pallas"
    assert plan.traversal_strategy == "reference"
    assert plan.host_offload_split is True


def test_plan_rejects_unknown_strategy():
    with pytest.raises(ValueError):
        ExecutionPlan(hist_strategy="warp_speed")


def test_plan_is_hashable_static_arg():
    a = ExecutionPlan.auto()
    b = ExecutionPlan.auto()
    assert hash(a) == hash(b) and a == b


# --------------------------------------------------------------------------
# the PR-1 loose-kwarg shim is gone: ops entry points are plan-only
# --------------------------------------------------------------------------
def test_ops_reject_loose_strategy_kwarg():
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 8, (300, 4)), jnp.uint8)
    g = jnp.asarray(rng.normal(size=300), jnp.float32)
    h = jnp.asarray(rng.uniform(0, 1, 300), jnp.float32)
    nid = jnp.asarray(rng.integers(0, 2, 300), jnp.int32)
    with pytest.raises(TypeError):
        ops.build_histogram(codes, g, h, nid, n_nodes=2, n_bins=8,
                            strategy="sort")
    with pytest.raises(TypeError):
        ops.build_histogram(codes, g, h, nid, n_nodes=2, n_bins=8,
                            interpret=False)


def test_ops_plan_dispatch_matches_reference():
    rng = np.random.default_rng(1)
    codes = jnp.asarray(rng.integers(0, 8, (200, 3)), jnp.uint8)
    g = jnp.asarray(rng.normal(size=200), jnp.float32)
    h = jnp.asarray(rng.uniform(0, 1, 200), jnp.float32)
    nid = jnp.asarray(rng.integers(0, 2, 200), jnp.int32)
    want = ref.histogram_ref(codes, g, h, nid, 2, 8)
    for s in ("scatter", "sort", "onehot"):
        got = ops.build_histogram(codes, g, h, nid, n_nodes=2, n_bins=8,
                                  plan=ExecutionPlan.auto(hist_strategy=s))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


# --------------------------------------------------------------------------
# estimator <-> functional-path parity
# --------------------------------------------------------------------------
def test_estimator_matches_functional_train(data, fitted):
    X, y, cats = data
    binned = Binner(max_bins=32, categorical_fields=cats).fit_transform(X)
    res = train(GBDTConfig(n_trees=6, max_depth=4, learning_rate=0.3,
                           seed=3), binned, y)
    np.testing.assert_allclose(np.asarray(fitted.predict(X)),
                               np.asarray(res.model.predict(binned)),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(fitted.history_["train_loss"],
                               res.history["train_loss"], rtol=1e-6)


def test_get_set_params_roundtrip(data):
    est = BoosterRegressor(n_trees=9, learning_rate=0.05)
    params = est.get_params()
    assert params["n_trees"] == 9 and params["learning_rate"] == 0.05
    est.set_params(n_trees=4, max_depth=3)
    assert est.n_trees == 4 and est.max_depth == 3
    with pytest.raises(ValueError):
        est.set_params(bogus_param=1)
    with pytest.raises(TypeError):
        BoosterRegressor(bogus_param=1)


def test_unfitted_raises(data):
    X, _, _ = data
    with pytest.raises(NotFittedError):
        BoosterRegressor().predict(X)


def test_classifier_labels_and_proba():
    X, y, cats = make_tabular(1200, 6, 2, task="binary", seed=5)
    est = BoosterClassifier(n_trees=8, max_depth=4, learning_rate=0.3,
                            max_bins=32, categorical_fields=cats)
    est.fit(X, y)
    labels = est.predict(X)
    proba = est.predict_proba(X)
    assert set(np.unique(labels)) <= {0, 1}
    assert proba.shape == (1200, 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-6)
    assert (labels == y).mean() > 0.75


def test_warm_start_xgb_model(data, fitted):
    X, y, cats = data
    cont = BoosterRegressor(n_trees=3, max_depth=4, learning_rate=0.3,
                            max_bins=32, categorical_fields=cats, seed=3)
    cont.fit(X, y, xgb_model=fitted)
    assert cont.n_trees_ == fitted.n_trees_ + 3


def test_warm_start_mismatch_raises_early(data, fitted):
    X, y, cats = data
    bad = BoosterRegressor(n_trees=2, max_depth=5, max_bins=32,
                           categorical_fields=cats)
    with pytest.raises(ValueError, match="max_depth"):
        bad.fit(X, y, xgb_model=fitted)


def test_repr_with_array_params():
    est = BoosterRegressor(categorical_fields=np.array([3, 4]), n_trees=2)
    assert "categorical_fields=(3, 4)" in repr(est)
    assert est.get_params()["categorical_fields"] == (3, 4)


def test_xgb_model_wins_over_checkpoints(data, fitted, tmp_path):
    X, y, cats = data
    d = str(tmp_path / "ckpt_conflict")
    first = BoosterRegressor(n_trees=2, max_depth=4, max_bins=32,
                             categorical_fields=cats, seed=3)
    first.fit(X, y, checkpoint_dir=d)
    cont = BoosterRegressor(n_trees=2, max_depth=4, learning_rate=0.3,
                            max_bins=32, categorical_fields=cats, seed=3)
    with pytest.warns(UserWarning, match="xgb_model wins"):
        cont.fit(X, y, xgb_model=fitted, checkpoint_dir=d)
    assert cont.n_trees_ == fitted.n_trees_ + 2


# --------------------------------------------------------------------------
# staged_predict == the training-history prefix ensembles
# --------------------------------------------------------------------------
def test_staged_predict_consistent_with_history(data, fitted):
    X, y, _ = data
    stages = list(fitted.staged_predict(X))
    assert len(stages) == fitted.n_trees_
    np.testing.assert_allclose(np.asarray(stages[-1]),
                               np.asarray(fitted.predict(X)),
                               rtol=1e-5, atol=1e-6)
    # k-th stage's squared-error loss reproduces history["train_loss"][k]
    for k in (0, fitted.n_trees_ - 1):
        loss_k = float(np.mean(0.5 * (np.asarray(stages[k]) - y) ** 2))
        np.testing.assert_allclose(loss_k,
                                   fitted.history_["train_loss"][k],
                                   rtol=1e-5)


# --------------------------------------------------------------------------
# one serialization story
# --------------------------------------------------------------------------
def test_estimator_bundle_roundtrip(data, fitted, tmp_path):
    X, _, _ = data
    path = str(tmp_path / "bundle")
    fitted.save(path)
    est2 = load(path)
    assert isinstance(est2, BoosterRegressor)
    assert est2.get_params()["n_trees"] == fitted.get_params()["n_trees"]
    np.testing.assert_array_equal(np.asarray(est2.predict(X)),
                                  np.asarray(fitted.predict(X)))


def test_bundle_naming_the_removed_fused_rounds_param_loads(fitted):
    """Bundles saved while a round had an opt-in ``fused_rounds`` knob
    name it among their params; they still load."""
    from repro.api import serialize
    arrays, meta = serialize.pack(fitted)
    meta["estimator"]["params"]["fused_rounds"] = False
    est = serialize.unpack(arrays, meta)
    assert isinstance(est, BoosterRegressor)
    assert est.get_params() == fitted.get_params()


def test_pipeline_and_model_share_bundle_format(data, fitted, tmp_path):
    X, _, _ = data
    pipe = fitted.to_pipeline()
    p_path, m_path = str(tmp_path / "pipe"), str(tmp_path / "model")
    save(p_path, pipe)
    save(m_path, fitted.model_)
    pipe2 = load(p_path)
    assert isinstance(pipe2, GBDTPipeline)
    np.testing.assert_array_equal(np.asarray(pipe2.predict(X)),
                                  np.asarray(fitted.predict(X)))
    model2 = load(m_path)
    assert isinstance(model2, GBDTModel)
    codes = fitted.binner_.transform(X)
    # like-for-like path: the estimator serves through the fused engine
    # (1-ulp reassociation vs a direct codes predict), so round-trip
    # exactness is asserted against the same direct call
    np.testing.assert_array_equal(np.asarray(model2.predict(codes)),
                                  np.asarray(fitted.model_.predict(codes)))
    # estimator loader promotes a pipeline bundle (same payload family)
    est_from_pipe = BoosterRegressor.load(p_path)
    np.testing.assert_array_equal(np.asarray(est_from_pipe.predict(X)),
                                  np.asarray(fitted.predict(X)))


def test_checkpoint_flow_and_resume(data, tmp_path):
    X, y, cats = data
    ckpt_dir = str(tmp_path / "ckpt")
    est = BoosterRegressor(n_trees=4, max_depth=3, learning_rate=0.3,
                           max_bins=32, categorical_fields=cats, seed=3)
    est.fit(X, y, checkpoint_dir=ckpt_dir, checkpoint_every=2)
    obj, step = load_checkpoint(ckpt_dir)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(obj.predict(X)),
                                  np.asarray(est.predict(X)))
    # a fresh estimator resumes instead of retraining (0 additional trees)
    est2 = BoosterRegressor(n_trees=4, max_depth=3, learning_rate=0.3,
                            max_bins=32, categorical_fields=cats, seed=3)
    est2.fit(X, y, checkpoint_dir=ckpt_dir, checkpoint_every=2)
    assert est2.n_trees_ == 4
    np.testing.assert_array_equal(np.asarray(est2.predict(X)),
                                  np.asarray(est.predict(X)))


def test_legacy_checkpoint_dir_trains_fresh(data, tmp_path):
    """A checkpoint dir holding only legacy (positional-leaf) payloads
    must not abort fit — it falls back to training from scratch."""
    from repro.distributed import checkpoint as ckpt
    X, y, cats = data
    d = str(tmp_path / "legacy")
    ckpt.save(d, {"a": np.zeros(3)}, step=5)
    est = BoosterRegressor(n_trees=2, max_depth=3, max_bins=16,
                           categorical_fields=cats)
    est.fit(X, y, checkpoint_dir=d)
    assert est.n_trees_ == 2


def test_corrupt_bundle_rejected(fitted, tmp_path):
    import os
    path = str(tmp_path / "bundle")
    fitted.save(path)
    with open(os.path.join(path, "arrays.npz"), "r+b") as f:
        f.seek(30)
        f.write(b"\xde\xad")   # bit-rot: sha256 verification must catch it
    with pytest.raises(FileNotFoundError):
        load(path)


# --------------------------------------------------------------------------
# vectorized feature_importance == the reference double loop
# --------------------------------------------------------------------------
def _importance_reference(model, kind):
    feats = np.asarray(model.trees.feature)
    leaves = np.asarray(model.trees.leaf_value, np.float64)
    imp = np.zeros((model.n_fields,), np.float64)
    T, n_int = feats.shape
    depth = model.max_depth
    for t in range(T):
        for pos in range(n_int):
            f = feats[t, pos]
            if f < 0:
                continue
            if kind == "split":
                imp[f] += 1.0
            else:
                level = (pos + 1).bit_length() - 1
                reps = 2 ** (depth - level)
                base = (pos - (2 ** level - 1)) * reps
                w = reps if kind == "cover" else 1.0
                imp[f] += w * float(np.var(leaves[t, base:base + reps]))
    s = imp.sum()
    return imp / s if s > 0 else imp


@pytest.mark.parametrize("kind", ["split", "gain", "cover"])
def test_feature_importance_vectorized_matches_loop(fitted, kind):
    got = feature_importance(fitted.model_, kind)
    want = _importance_reference(fitted.model_, kind)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(fitted.feature_importances_,
                               feature_importance(fitted.model_, "gain"))
