"""Histogram-subtraction level growers + fused boosting rounds.

Parity contract (documented float tolerance): the derived sibling
``parent − smaller`` reassociates the parent's float32 sum, so subtraction
histograms match the direct pass to ~ulp(parent) per bucket — NOT bitwise.
Split decisions argmax over well-separated gains, so trees come out
*structurally identical* on generic data; leaf values (segment sums over
the same final partition) match to float tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.api.plan import HIST_STRATEGIES, ExecutionPlan
from repro.core import GBDTConfig, bin_dataset, train
from repro.core import gbdt as gbdt_mod
from repro.core import losses as losses_mod
from repro.core import splits as splits_mod
from repro.core import tree as tree_mod
from repro.core.binning import BinnedDataset
from repro.data import make_tabular
from repro.kernels import ops


def _dataset(n=900, seed=5, max_bins=32):
    X, y, cats = make_tabular(n, 6, 2, n_cats=8, task="regression",
                              missing_rate=0.05, seed=seed)
    return bin_dataset(X, max_bins=max_bins, categorical_fields=cats), y


def _stats(n, K, seed=0):
    rng = np.random.default_rng(seed)
    g = np.asarray(rng.normal(size=(K, n)), np.float32)
    h = np.abs(np.asarray(rng.normal(size=(K, n)), np.float32)) + 0.1
    return g, h


def _grow_kwargs(data, depth=4):
    F = data.codes.shape[1]
    return dict(depth=depth, n_bins=data.n_bins,
                missing_bin=data.missing_bin,
                is_cat_field=data.is_categorical,
                field_mask=jnp.ones((F,), bool), lambda_=1.0, gamma=0.0,
                min_child_weight=1.0)


def _chunks(codes_np, rows):
    n = codes_np.shape[0]

    def it():
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            c = codes_np[lo:hi]
            if c.shape[0] < rows:
                c = np.pad(c, ((0, rows - c.shape[0]), (0, 0)))
            yield lo, hi, c
    return it


def _assert_tree_parity(a, b, rtol=1e-4, atol=1e-5):
    np.testing.assert_array_equal(np.asarray(a.feature), np.asarray(b.feature))
    np.testing.assert_array_equal(np.asarray(a.threshold),
                                  np.asarray(b.threshold))
    np.testing.assert_array_equal(np.asarray(a.is_cat), np.asarray(b.is_cat))
    # default_left is NOT asserted bitwise: when a node sees no missing
    # records in its chosen feature, both missing directions have exactly
    # equal gain and the ~ulp residual in a derived sibling histogram
    # breaks the tie arbitrarily — a don't-care bit (no record routes
    # through it during training).  Routing of records that DO exist is
    # covered by the leaf-value check (same final partition).
    np.testing.assert_allclose(np.asarray(a.leaf_value),
                               np.asarray(b.leaf_value), rtol=rtol, atol=atol)


# --------------------------------------------------------------------------
# histogram-level parity: derived siblings match the direct pass
# --------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", HIST_STRATEGIES)
@pytest.mark.parametrize("K", [1, 3])
def test_subtraction_level_hist_matches_direct(strategy, K):
    data, _ = _dataset()
    n, F = data.codes.shape
    g, h = _stats(n, K)
    gd, hd = jnp.asarray(g), jnp.asarray(h)
    plan = ExecutionPlan(hist_strategy=strategy).resolved()
    rng = np.random.default_rng(3)
    # a realistic level-1 partition: children 2p/2p+1 of 2 parents
    node_ids = jnp.asarray(rng.integers(0, 4, size=(K, n)), jnp.int32)
    parent = ops.build_histogram(data.codes, gd, hd, node_ids // 2,
                                 n_nodes=2, n_bins=data.n_bins, plan=plan)
    direct = ops.build_histogram(data.codes, gd, hd, node_ids,
                                 n_nodes=4, n_bins=data.n_bins, plan=plan)
    sub = tree_mod._subtract_level_hist(data.codes, gd, hd, node_ids,
                                        parent, n_nodes=4,
                                        n_bins=data.n_bins, plan=plan)
    scale = float(jnp.max(jnp.abs(parent)))
    np.testing.assert_allclose(np.asarray(sub), np.asarray(direct),
                               rtol=1e-4, atol=1e-5 * max(scale, 1.0))


# --------------------------------------------------------------------------
# grower-level parity: subtraction-vs-direct, monolithic and chunked,
# all 6 strategies x K in {1, 3}
# --------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", HIST_STRATEGIES)
@pytest.mark.parametrize("K", [1, 3])
def test_monolithic_grower_parity(strategy, K):
    data, _ = _dataset()
    n, F = data.codes.shape
    g, h = _stats(n, K)
    kw = _grow_kwargs(data)
    direct = tree_mod.fit_forest(
        data.codes, data.codes_cm, jnp.asarray(g), jnp.asarray(h),
        plan=ExecutionPlan(hist_strategy=strategy).resolved(), **kw)
    sub = tree_mod.fit_forest(
        data.codes, data.codes_cm, jnp.asarray(g), jnp.asarray(h),
        plan=ExecutionPlan(hist_strategy=strategy,
                           hist_subtraction=True).resolved(), **kw)
    _assert_tree_parity(direct, sub)


@pytest.mark.parametrize("strategy", HIST_STRATEGIES)
@pytest.mark.parametrize("K", [1, 3])
def test_chunked_grower_parity(strategy, K):
    data, _ = _dataset()
    n, F = data.codes.shape
    g, h = _stats(n, K)
    codes_np = np.asarray(data.codes)
    kw = _grow_kwargs(data)
    direct, nid_d = tree_mod.fit_forest_chunked(
        _chunks(codes_np, 256), g, h,
        plan=ExecutionPlan(hist_strategy=strategy).resolved(), **kw)
    sub, nid_s = tree_mod.fit_forest_chunked(
        _chunks(codes_np, 256), g, h,
        plan=ExecutionPlan(hist_strategy=strategy,
                           hist_subtraction=True).resolved(), **kw)
    _assert_tree_parity(direct, sub)
    np.testing.assert_array_equal(nid_d, nid_s)


def test_chunked_matches_monolithic_under_subtraction():
    """Same trees from the in-memory and out-of-core subtraction growers
    (their smaller-child selections may differ — count- vs hessian-based —
    but the derived histograms agree to tolerance, so the argmaxes do)."""
    data, _ = _dataset()
    n, F = data.codes.shape
    g, h = _stats(n, 1)
    kw = _grow_kwargs(data)
    plan = ExecutionPlan(hist_strategy="scatter",
                         hist_subtraction=True).resolved()
    mono = tree_mod.fit_forest(data.codes, data.codes_cm, jnp.asarray(g),
                               jnp.asarray(h), plan=plan, **kw)
    chunked, _ = tree_mod.fit_forest_chunked(
        _chunks(np.asarray(data.codes), 200), g, h, plan=plan, **kw)
    _assert_tree_parity(mono, chunked)


# --------------------------------------------------------------------------
# counts channel: SplitDecision.left_h equals the left child's hessian mass
# --------------------------------------------------------------------------
def test_split_decision_left_h_matches_partition():
    data, _ = _dataset()
    n, F = data.codes.shape
    g, h = _stats(n, 1)
    gd, hd = jnp.asarray(g[0]), jnp.asarray(h[0])
    nid = jnp.zeros((n,), jnp.int32)
    hist = ops.build_histogram(data.codes, gd, hd, nid, n_nodes=1,
                               n_bins=data.n_bins,
                               plan=ExecutionPlan().resolved())
    best = splits_mod.find_best_splits(hist, data.is_categorical,
                                       jnp.ones((F,), bool), 1.0, 0.0, 1.0)
    assert float(best.gain[0]) > 0
    # route the records with the chosen split and sum hessians on the left
    child = ops.partition_level(
        nid, data.codes_cm[best.feature].T, jnp.zeros((1,), jnp.int32),
        best.threshold, best.is_cat, best.default_left,
        missing_bin=data.missing_bin, plan=ExecutionPlan().resolved())
    hl = float(jnp.sum(jnp.where(child == 0, hd, 0.0)))
    np.testing.assert_allclose(float(best.left_h[0]), hl, rtol=1e-5)
    # host-offloaded twin carries the same channel
    best_host = splits_mod.find_best_splits_host(
        hist, data.is_categorical, jnp.ones((F,), bool), 1.0, 0.0, 1.0)
    np.testing.assert_allclose(float(best_host.left_h[0]),
                               float(best.left_h[0]), rtol=1e-6)


# --------------------------------------------------------------------------
# donated chunked accumulator stays correct when rebound in a loop
# --------------------------------------------------------------------------
def test_accumulate_histogram_rebinding():
    """The jitted (accumulator-donating) accumulate stays bit-equal to the
    monolithic pass when rebound chunk-by-chunk in a loop.  Integer-valued
    stats keep float accumulation order-independent (the same trick as
    test_streaming's bit-equality matrix), so the assert is bit-strict."""
    data, _ = _dataset(n=400)
    n, F = data.codes.shape
    rng = np.random.default_rng(7)
    gd = jnp.asarray(rng.integers(-8, 9, (1, n)), jnp.float32)
    hd = jnp.asarray(rng.integers(0, 5, (1, n)), jnp.float32)
    nid = jnp.zeros((1, n), jnp.int32)
    plan = ExecutionPlan().resolved()
    full = ops.build_histogram(data.codes, gd, hd, nid, n_nodes=1,
                               n_bins=data.n_bins, plan=plan)
    acc = jnp.zeros_like(full)
    for lo in range(0, n, 128):
        hi = min(lo + 128, n)
        acc = ops.accumulate_histogram(
            acc, data.codes[lo:hi], gd[:, lo:hi], hd[:, lo:hi],
            nid[:, lo:hi], n_nodes=1, n_bins=data.n_bins, plan=plan)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(acc))


# --------------------------------------------------------------------------
# fused boosting rounds: trajectory parity vs a host-driven round loop
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def boost_data():
    X, y, cats = make_tabular(1800, 8, 4, n_cats=10, task="regression",
                              missing_rate=0.05, seed=3)
    data = bin_dataset(X, max_bins=64, categorical_fields=cats)

    def sub(sl):
        return BinnedDataset(
            data.codes[sl],
            jnp.asarray(np.asarray(data.codes[sl]).T.copy()),
            data.is_categorical, data.n_bins, data.bin_edges,
            data.n_value_bins)
    return sub(slice(0, 1400)), y[:1400], sub(slice(1400, None)), y[1400:]


def _host_rounds(config, data, y, eval_set=None, plan=None):
    """The oracle: one round at a time from the host, each step its own
    dispatch, over the public grower, ``shrink`` and the step-⑤
    traversal.  Returns (model, history) as ``train`` would."""
    plan = (plan or ExecutionPlan.from_config(config)).resolved()
    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs
    y = jnp.asarray(y, jnp.float32)
    n, F = data.codes.shape
    if K is not None:
        base = np.asarray(loss.base_margin(y), np.float32)
        start = lambda rows: jnp.broadcast_to(jnp.asarray(base), (rows, K))
        grow, predict = tree_mod.fit_forest, gbdt_mod._predict_forest
    else:
        base = float(loss.base_margin(y))
        start = lambda rows: jnp.full((rows,), base, jnp.float32)
        grow, predict = tree_mod.fit_tree, gbdt_mod._predict_one_tree
    margins = start(n)
    history = {"train_loss": []}
    if eval_set is not None:
        ev_data, ev_y = eval_set[0], jnp.asarray(eval_set[1], jnp.float32)
        ev_margins = start(ev_y.shape[0])
        history["eval_loss"] = []
    key = jax.random.PRNGKey(config.seed)
    trees, best, best_round = [], np.inf, -1
    for t in range(config.n_trees):
        g, h, field_mask = gbdt_mod._gradients(
            config, loss, margins, y, jax.random.fold_in(key, t), n, F, K)
        if K is not None:
            g, h = g.T, h.T
        tree = gbdt_mod.shrink(
            grow(data.codes, data.codes_cm, g, h, field_mask=field_mask,
                 depth=config.max_depth, n_bins=data.n_bins,
                 missing_bin=data.missing_bin,
                 is_cat_field=data.is_categorical, lambda_=config.lambda_,
                 gamma=config.gamma,
                 min_child_weight=config.min_child_weight, plan=plan),
            config.learning_rate)
        trees.append(tree)
        margins = margins + predict(tree, data, plan)
        history["train_loss"].append(float(jnp.mean(loss.value(margins, y))))
        if eval_set is not None:
            ev_margins = ev_margins + predict(tree, ev_data, plan)
            ev = float(jnp.mean(loss.value(ev_margins, ev_y)))
            history["eval_loss"].append(ev)
            if ev < best - 1e-12:
                best, best_round = ev, t
            if (config.early_stopping_rounds is not None
                    and t - best_round >= config.early_stopping_rounds):
                break
    return (gbdt_mod._as_model(trees, base, config, data.missing_bin, F),
            history)


@pytest.mark.parametrize("kw", [
    dict(),
    dict(subsample=0.7, colsample_bytree=0.7),
    dict(objective="binary:logistic"),
])
def test_fused_rounds_trajectory_parity(boost_data, kw):
    """Fusing a round into one XLA program lets the compiler reassociate
    float chains (e.g. ``-G/(H+λ) * lr``), so margins drift by ulps and a
    near-tied split in a later round may flip — round 0 is bit-identical
    (identical inputs), and the loss trajectory and predictions agree to
    float tolerance throughout."""
    tr, ytr, te, _ = boost_data
    if kw.get("objective") == "binary:logistic":
        ytr = (np.asarray(ytr) > np.median(ytr)).astype(np.float32)
    cfg = GBDTConfig(n_trees=6, max_depth=4, hist_strategy="scatter", **kw)
    a_model, a_hist = _host_rounds(cfg, tr, ytr)
    b = train(cfg, tr, ytr)
    for fa, fb in zip(a_model.trees[:4], b.model.trees[:4]):
        np.testing.assert_array_equal(np.asarray(fa)[0], np.asarray(fb)[0])
    np.testing.assert_allclose(a_hist["train_loss"],
                               b.history["train_loss"], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(a_model.predict(te)),
                               np.asarray(b.model.predict(te)),
                               rtol=1e-3, atol=1e-4)


def test_fused_rounds_goss_losses_match(boost_data):
    """GOSS ranks records by |g|; ulp-level margin differences between the
    fused and host loops can flip near-ties in that ranking, so structural
    equality is not guaranteed — the loss trajectories still agree."""
    tr, ytr, _, _ = boost_data
    cfg = GBDTConfig(n_trees=6, max_depth=4, hist_strategy="scatter",
                     goss_top_rate=0.2, goss_other_rate=0.2)
    _, a_hist = _host_rounds(cfg, tr, ytr)
    b = train(cfg, tr, ytr)
    np.testing.assert_allclose(a_hist["train_loss"],
                               b.history["train_loss"], rtol=1e-4)


def test_fused_rounds_multiclass_parity(boost_data):
    tr, ytr, _, _ = boost_data
    y3 = np.digitize(np.asarray(ytr),
                     np.quantile(np.asarray(ytr), [0.33, 0.66]))
    cfg = GBDTConfig(n_trees=4, max_depth=3, objective="multi:softmax",
                     n_classes=3, hist_strategy="scatter")
    a_model, a_hist = _host_rounds(cfg, tr, y3.astype(np.float32))
    b = train(cfg, tr, y3.astype(np.float32))
    for fa, fb in zip(a_model.trees[:4], b.model.trees[:4]):
        # round 0 (the first K class trees) sees bit-identical inputs
        np.testing.assert_array_equal(np.asarray(fa)[:3], np.asarray(fb)[:3])
    np.testing.assert_allclose(a_hist["train_loss"],
                               b.history["train_loss"], rtol=1e-4)


def test_fused_rounds_early_stopping_matches(boost_data):
    tr, ytr, te, yte = boost_data
    cfg = GBDTConfig(n_trees=40, max_depth=5, learning_rate=0.5,
                     early_stopping_rounds=3, hist_strategy="scatter")
    a_model, a_hist = _host_rounds(cfg, tr, ytr, eval_set=(te, yte))
    b = train(cfg, tr, ytr, eval_set=(te, jnp.asarray(yte)))
    assert a_model.n_trees == b.model.n_trees
    assert len(b.history["eval_loss"]) == b.model.n_trees
    np.testing.assert_allclose(a_hist["eval_loss"], b.history["eval_loss"],
                               rtol=1e-4)
    assert "fused_rounds" in b.step_times


def test_fused_rounds_init_model_parity(boost_data):
    """A fit continued from ``init_model`` keeps the round index (and so
    the RNG stream) and lands on the uninterrupted trajectory."""
    tr, ytr, te, _ = boost_data
    cfg = GBDTConfig(n_trees=6, max_depth=4, hist_strategy="scatter",
                     subsample=0.8)
    a_model, a_hist = _host_rounds(cfg, tr, ytr)
    half = dataclasses.replace(cfg, n_trees=3)
    first = train(half, tr, ytr)
    b = train(half, tr, ytr, init_model=first.model)
    assert b.model.n_trees == 6
    np.testing.assert_allclose(a_hist["train_loss"][3:],
                               b.history["train_loss"], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(a_model.predict(te)),
                               np.asarray(b.model.predict(te)),
                               rtol=1e-3, atol=1e-4)


def test_fused_plus_subtraction_end_to_end(boost_data):
    """The acceptance path: fused rounds + hist_subtraction together
    reproduce the host loop's direct-histogram trajectory (same
    float-tolerance contract as each optimization alone)."""
    tr, ytr, te, _ = boost_data
    plan = ExecutionPlan(hist_strategy="scatter",
                         hist_subtraction=True).resolved()
    a_model, a_hist = _host_rounds(
        GBDTConfig(n_trees=6, max_depth=5, hist_strategy="scatter"), tr, ytr)
    b = train(GBDTConfig(n_trees=6, max_depth=5), tr, ytr, plan=plan)
    np.testing.assert_allclose(a_hist["train_loss"],
                               b.history["train_loss"], rtol=1e-4)
    np.testing.assert_allclose(np.asarray(a_model.predict(te)),
                               np.asarray(b.model.predict(te)),
                               rtol=1e-3, atol=1e-4)


def test_grow_policy_picks_the_round_loop(boost_data):
    """Depthwise fits run one program a round; lossguide alone keeps the
    host loop, and each reports its own ``step_times`` keys."""
    tr, ytr, _, _ = boost_data
    kw = dict(n_trees=2, max_depth=3)
    depthwise = train(GBDTConfig(**kw), tr, ytr).step_times
    lossguide = train(GBDTConfig(grow_policy="lossguide", **kw),
                      tr, ytr).step_times
    assert set(depthwise) == {tracing.FUSED_ROUNDS, tracing.SYNC_WAIT}
    assert set(lossguide) == set(tracing.HOST_LOOP_KEYS) | {
        tracing.SYNC_WAIT}


def test_streaming_subtraction_trajectory_parity():
    from repro.api import BoosterRegressor
    from repro.data.synthetic import SyntheticSource

    src = SyntheticSource(3000, 10, seed=0)
    kw = dict(n_trees=4, max_depth=4, learning_rate=0.3, max_bins=32)
    base = BoosterRegressor(**kw)
    base.fit(data=src, plan=ExecutionPlan(chunk_bytes=40_000))
    sub = BoosterRegressor(**kw)
    sub.fit(data=src, plan=ExecutionPlan(chunk_bytes=40_000,
                                         hist_subtraction=True))
    for fa, fb in zip(base.model_.trees[:4], sub.model_.trees[:4]):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    np.testing.assert_allclose(base.history_["train_loss"],
                               sub.history_["train_loss"], rtol=1e-5)
