"""End-to-end GBDT training: accuracy, invariances, paper-claimed
numerical neutrality of the software optimizations."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import GBDTConfig, bin_dataset, train
from repro.core.binning import BinnedDataset
from repro.data import make_tabular


def _split(data: BinnedDataset, y, n_tr):
    def sub(sl):
        return BinnedDataset(
            data.codes[sl],
            jnp.asarray(np.asarray(data.codes[sl]).T.copy()),
            data.is_categorical, data.n_bins, data.bin_edges,
            data.n_value_bins)
    return sub(slice(0, n_tr)), y[:n_tr], sub(slice(n_tr, None)), y[n_tr:]


@pytest.fixture(scope="module")
def reg_data():
    X, y, cats = make_tabular(2000, 8, 4, n_cats=10, task="regression",
                              missing_rate=0.05, seed=3)
    data = bin_dataset(X, max_bins=64, categorical_fields=cats)
    return _split(data, y, 1600)


@pytest.fixture(scope="module")
def cls_data():
    X, y, cats = make_tabular(1500, 10, 2, task="binary", seed=7)
    data = bin_dataset(X, max_bins=32, categorical_fields=cats)
    return _split(data, y, 1200)


def test_regression_learns(reg_data):
    tr, ytr, te, yte = reg_data
    res = train(GBDTConfig(n_trees=30, max_depth=5, learning_rate=0.3,
                           hist_strategy="scatter"), tr, ytr,
                eval_set=(te, jnp.asarray(yte)))
    pred = np.asarray(res.model.predict(te))
    r2 = 1 - np.mean((pred - yte) ** 2) / np.var(yte)
    assert r2 > 0.7, r2
    assert res.history["train_loss"][-1] < res.history["train_loss"][0] / 5


def test_classification_learns(cls_data):
    tr, ytr, te, yte = cls_data
    res = train(GBDTConfig(n_trees=20, max_depth=4, learning_rate=0.3,
                           objective="binary:logistic",
                           hist_strategy="scatter"), tr, ytr)
    acc = float(((np.asarray(res.model.predict(te)) > .5) == yte).mean())
    assert acc > 0.75, acc


def test_lossguide_learns(reg_data):
    tr, ytr, te, yte = reg_data
    res = train(GBDTConfig(n_trees=10, max_depth=5, learning_rate=0.3,
                           grow_policy="lossguide", max_leaves=16,
                           hist_strategy="scatter"), tr, ytr)
    pred = np.asarray(res.model.predict(te))
    r2 = 1 - np.mean((pred - yte) ** 2) / np.var(yte)
    assert r2 > 0.5, r2


@pytest.mark.slow
def test_strategies_grow_identical_trees(reg_data):
    """Paper §IV: 'software changes ... do not affect the numerical
    results'.  scatter / sort / one-hot MXU / packed produce the same
    ensemble (same splits; leaf values to fp tolerance)."""
    tr, ytr, _, _ = reg_data
    cfgs = [GBDTConfig(n_trees=5, max_depth=4, hist_strategy=s)
            for s in ("scatter", "sort", "onehot", "pallas_grouped")]
    results = [train(c, tr, ytr) for c in cfgs]
    t0 = results[0].model.trees
    for r in results[1:]:
        np.testing.assert_array_equal(np.asarray(r.model.trees.feature),
                                      np.asarray(t0.feature))
        np.testing.assert_array_equal(np.asarray(r.model.trees.threshold),
                                      np.asarray(t0.threshold))
        np.testing.assert_allclose(np.asarray(r.model.trees.leaf_value),
                                   np.asarray(t0.leaf_value),
                                   rtol=1e-4, atol=1e-5)


def test_pallas_partition_and_traversal_match_reference(reg_data):
    tr, ytr, _, _ = reg_data
    a = train(GBDTConfig(n_trees=4, max_depth=4,
                         hist_strategy="scatter",
                         partition_strategy="reference",
                         traversal_strategy="reference"), tr, ytr)
    b = train(GBDTConfig(n_trees=4, max_depth=4,
                         hist_strategy="scatter",
                         partition_strategy="pallas",
                         traversal_strategy="pallas"), tr, ytr)
    np.testing.assert_allclose(a.history["train_loss"],
                               b.history["train_loss"], rtol=1e-5)


def test_subsample_colsample_run(reg_data):
    tr, ytr, _, _ = reg_data
    res = train(GBDTConfig(n_trees=6, max_depth=4, subsample=0.7,
                           colsample_bytree=0.7, hist_strategy="scatter"),
                tr, ytr)
    assert res.history["train_loss"][-1] < res.history["train_loss"][0]


def test_early_stopping(reg_data):
    tr, ytr, te, yte = reg_data
    res = train(GBDTConfig(n_trees=60, max_depth=6, learning_rate=0.8,
                           early_stopping_rounds=3,
                           hist_strategy="scatter"),
                tr, ytr, eval_set=(te, jnp.asarray(yte)))
    assert res.model.n_trees < 60  # aggressive LR overfits -> stops early


def test_deterministic_replay(reg_data):
    """Same seed -> bit-identical ensembles (fault-tolerant replay)."""
    tr, ytr, _, _ = reg_data
    cfg = GBDTConfig(n_trees=5, max_depth=4, subsample=0.8, seed=13,
                     hist_strategy="scatter")
    a, b = train(cfg, tr, ytr), train(cfg, tr, ytr)
    for fa, fb in zip(a.model.trees, b.model.trees):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_warm_start_continues(reg_data):
    tr, ytr, _, _ = reg_data
    cfg = GBDTConfig(n_trees=4, max_depth=4, hist_strategy="scatter")
    first = train(cfg, tr, ytr)
    cont = train(cfg, tr, ytr, init_model=first.model)
    assert cont.model.n_trees == 8
    assert cont.history["train_loss"][-1] <= first.history["train_loss"][-1]


@pytest.mark.parametrize("feature,want", [
    # root splits; node 1 stops, so its records all sit in slot 0
    ([3, -1, 5], [-2.0 / 4.0, -2.0 / 4.0, 1.0 / 2.0, -4.0 / 2.0]),
    # the root stops: every record sits in slot 0
    ([-1, -1, -1], [-2.0 / 4.0] * 4),
])
def test_early_leaf_weighed_from_record_sums(feature, want):
    """A node that stops splitting is weighed from the per-record G/H sums
    of the leftmost bottom slot of its subtree, where its records land,
    and that weight fills every slot of the subtree."""
    from repro.core.tree import settle_leaves
    Gb = jnp.asarray([[2.0, 0.0, -1.0, 4.0]])
    Hb = jnp.asarray([[3.0, 0.0, 1.0, 1.0]])
    if feature[0] < 0:                    # no record left slot 0
        Gb, Hb = Gb.at[0, 1:].set(0.0), Hb.at[0, 1:].set(0.0)
    got = settle_leaves(Gb, Hb, jnp.asarray([feature], jnp.int32), 1.0)
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  np.asarray(want, np.float32))
