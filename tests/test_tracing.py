"""The boosting round's host spans, device scopes and step_times keys
(``repro.tracing``): what a profiler trace of a fit holds, what the
compiled round programs carry in their op metadata, and that tracing
changes no result."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.api import ExecutionPlan
from repro.core import GBDTConfig, bin_dataset, train
from repro.core import gbdt as gbdt_mod
from repro.core import tree as tree_mod
from repro.data import make_tabular
from repro.resilience import GracefulShutdown, RecoveryPolicy

PHASES = (tracing.GRADIENTS, tracing.GROW, tracing.MARGIN_UPDATE,
          tracing.LOSS, tracing.COMMIT)


@pytest.fixture(scope="module")
def data():
    X, y, _ = make_tabular(400, 5, 0, task="binary", seed=11)
    return bin_dataset(X, max_bins=16), y


def _config(**kw):
    return GBDTConfig(max_depth=3, objective="binary:logistic", **kw)


def _traced_fit(tmp_path, config, data, **kw):
    """(result, repro.* host spans as (name, what, start, end)) of a fit
    under the profiler."""
    with jax.profiler.trace(str(tmp_path)):
        result = train(config, *data, **kw)
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    stats = dict(e.stats)
                    start = int(e.start_ns)
                    spans.append((e.name[len(tracing.PREFIX):],
                                  stats.get("what"), start,
                                  start + int(e.duration_ns)))
    return result, spans


def _inside(spans, outer):
    """The spans that lie inside the span ``outer``."""
    _, _, s, e = outer
    return [x for x in spans if x is not outer and s <= x[2] and x[3] <= e]


def test_host_loop_rounds_hold_phases_and_syncs(tmp_path, data):
    result, spans = _traced_fit(
        tmp_path, _config(n_trees=2, grow_policy="lossguide"), data)
    rounds = [x for x in spans if x[0] == tracing.ROUND]
    assert len(rounds) == 2
    for r in rounds:
        inner = _inside(spans, r)
        assert sorted(x[0] for x in inner if x[0] != tracing.SYNC) == sorted(
            PHASES)
        syncs = [x[1] for x in inner if x[0] == tracing.SYNC]
        assert syncs == ["tree", "margins", "loss"]
    assert len(result.history["train_loss"]) == 2


def _rounds(spans):
    """The round spans in time order, each with the spans inside it."""
    rounds = sorted((x for x in spans if x[0] == tracing.ROUND),
                    key=lambda x: x[2])
    return [(r, _inside(spans, r)) for r in rounds]


def test_fused_loop_syncs_only_in_sentinel_rounds(tmp_path, data):
    """Every round reads its loss back once; a recovery policy adds the
    sentinel's margins check and snapshot every ``log_every`` rounds."""
    _, spans = _traced_fit(tmp_path, _config(n_trees=12, log_every=10),
                           data, recovery=RecoveryPolicy())
    rounds = _rounds(spans)
    assert len(rounds) == 12
    synced = []
    for i, (_, inner) in enumerate(rounds):
        assert {x[0] for x in inner} >= {tracing.DISPATCH, tracing.COMMIT}
        syncs = [x[1] for x in inner if x[0] == tracing.SYNC]
        if syncs != ["loss"]:
            synced.append(i)
            assert syncs == ["loss", "sentinel", "snapshot"]
    assert synced == [0, 10, 11]


class _Asked(GracefulShutdown):
    """A shutdown that is never requested, whose question is a span."""

    def __init__(self):
        super().__init__(signals=())

    @property
    def requested(self):
        with jax.profiler.TraceAnnotation(tracing.PREFIX + "asked"):
            return super().requested


@pytest.mark.parametrize("recovery", [None, RecoveryPolicy()],
                         ids=["no_policy", "policy"])
def test_rounds_commit_after_their_loss_is_read(tmp_path, data, recovery):
    """Each depthwise round is one dispatch; its loss is read back before
    its commit (where the callback and the shutdown question run), and
    the next round is dispatched only after that commit.  Only a recovery
    policy takes margins snapshots."""
    def callback(t_idx, model):
        with jax.profiler.TraceAnnotation(tracing.PREFIX + "callback"):
            pass

    _, spans = _traced_fit(tmp_path, _config(n_trees=12), data,
                           callback=callback, shutdown=_Asked(),
                           recovery=recovery)
    rounds = _rounds(spans)
    assert len(rounds) == 12
    last_commit_end = None
    for _, inner in rounds:
        assert [x[0] for x in inner].count(tracing.DISPATCH) == 1
        dispatch, = (x for x in inner if x[0] == tracing.DISPATCH)
        commit, = (x for x in inner if x[0] == tracing.COMMIT)
        loss, = (x for x in inner
                 if x[0] == tracing.SYNC and x[1] == "loss")
        assert dispatch[3] <= loss[2] and loss[3] <= commit[2]
        for name in ("callback", "asked"):
            hook, = (x for x in inner if x[0] == name)
            assert commit[2] <= hook[2] and hook[3] <= commit[3]
        if last_commit_end is not None:
            assert last_commit_end <= dispatch[2]
        last_commit_end = commit[3]
    snapshots = [x for x in spans
                 if x[0] == tracing.SYNC and x[1] == "snapshot"]
    assert bool(snapshots) == (recovery is not None)


def test_diverging_fit_without_recovery_keeps_nan_model(tmp_path, data):
    """No recovery policy: no check and no snapshot, and the fit returns
    its NaN-loss model, as the host loop does."""
    cfg = GBDTConfig(n_trees=3, max_depth=2, learning_rate=1e20,
                     log_every=1)
    result, spans = _traced_fit(tmp_path, cfg, data)
    assert result.model.n_trees == 3
    assert not np.isfinite(result.history["train_loss"][-1])
    assert {x[1] for x in spans if x[0] == tracing.SYNC} == {"loss"}


def _scopes_in(text):
    return {s for s in (tracing.GRADIENTS, tracing.STEP2, tracing.STEP4,
                        tracing.STEP5, tracing.LOSS)
            if f"/{s}/" in text} | {
        f"{s}/level{lv}" for s in (tracing.STEP1, tracing.STEP2,
                                   tracing.STEP3)
        for lv in range(3) if f"/{s}/level{lv}/" in text}


def _levels(depth):
    return {f"{s}/level{lv}" for s in (tracing.STEP1, tracing.STEP2,
                                       tracing.STEP3)
            for lv in range(depth)}


def test_compiled_rounds_carry_the_step_scopes(data):
    codes, y = data[0], jnp.asarray(data[1], jnp.float32)
    n, F = codes.codes.shape
    plan = ExecutionPlan.auto().resolved()
    g = h = jnp.ones((1, n), jnp.float32)
    common = dict(is_cat_field=codes.is_categorical,
                  field_mask=jnp.ones((F,), bool), lambda_=1.0, gamma=0.0,
                  min_child_weight=1.0)
    grow = tree_mod._fit_forest_jit.lower(
        codes.codes, codes.codes_cm, g, h, depth=3, n_bins=codes.n_bins,
        missing_bin=codes.missing_bin, plan=plan, **common)
    text = grow.compile().as_text()
    assert _scopes_in(text) == _levels(3) | {tracing.STEP2}

    _, node_ids = tree_mod._fit_forest_jit(
        codes.codes, codes.codes_cm, g, h, depth=3, n_bins=codes.n_bins,
        missing_bin=codes.missing_bin, plan=plan, **common)
    feature = jnp.full((1, 7), -1, jnp.int32)
    settle = tree_mod._settle_jit.lower(g, h, node_ids, feature, 1.0)
    assert tracing.STEP4 in _scopes_in(settle.compile().as_text())

    cfg = _config(n_trees=1)
    step = gbdt_mod._fused_round_step(gbdt_mod._fused_step_key(cfg), plan,
                                      n, F, codes.n_bins, None)
    fused = step.lower(jnp.zeros((n,), jnp.float32), y,
                       jax.random.PRNGKey(0), 0, codes.codes,
                       codes.codes_cm, codes.is_categorical)
    assert _scopes_in(fused.compile().as_text()) == _levels(3) | {
        tracing.GRADIENTS, tracing.STEP2, tracing.STEP4, tracing.STEP5,
        tracing.LOSS}


def _policy(fused):
    """The grow policy of each round loop: depthwise rounds are fused."""
    return "depthwise" if fused else "lossguide"


@pytest.mark.parametrize("fused", [False, True])
def test_step_times_keys(data, fused):
    times = train(_config(n_trees=3, grow_policy=_policy(fused)),
                  *data).step_times
    phases = ((tracing.FUSED_ROUNDS,) if fused
              else tracing.HOST_LOOP_KEYS)
    assert set(times) == set(phases) | {tracing.SYNC_WAIT}
    assert 0.0 < times[tracing.SYNC_WAIT] <= sum(times[k] for k in phases)


@pytest.mark.parametrize("fused", [False, True])
def test_tracing_changes_no_result(tmp_path, data, fused):
    cfg = _config(n_trees=3, grow_policy=_policy(fused))
    plain = train(cfg, *data)
    traced, _ = _traced_fit(tmp_path, cfg, data)
    for a, b in zip(plain.model.trees, traced.model.trees):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain.history["train_loss"] == traced.history["train_loss"]
