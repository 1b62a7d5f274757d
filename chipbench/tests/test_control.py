"""What decides ``correct`` has to fail: the control (the reference in the
next lower precision put in the program's place) and each fault a cell
can have, planted under the timed path, with the rest of a run driven as
the benchmark drives it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells import FIXTURES, run_cell

from chipbench.drivers import fit
from chipbench.harness import Check, Clock, load_json
from chipbench.reference import fit_ref


def _fit_inputs():
    from cells import SEED
    config = load_json(FIXTURES / "configs" / "tiny-higgs.json")
    traffic = load_json(FIXTURES / "traffic" / "tiny-fit.json")
    _, codes, y = fit.prepare(config, traffic, SEED, Clock(), {})
    return config, codes, y


def test_fit_control_fails_the_cell_limits():
    config, codes, y = _fit_inputs()
    limits = load_json(FIXTURES / "limits" / "tiny-higgs-fit.json")
    ref = fit.reference_rounds(config, codes, y, 3)
    low = fit.reference_rounds(config, codes, y, 3, stats="bfloat16")
    got = fit_ref.compare(low["trees"], low["losses"], ref)
    assert not all(Check(k, got[k], v).ok for k, v in limits.items()
               if k in got), got


def test_fit_binning_control_fails_the_cell_limit():
    from cells import SEED
    config = load_json(FIXTURES / "configs" / "tiny-higgs.json")
    traffic = load_json(FIXTURES / "traffic" / "tiny-fit.json")
    limits = load_json(FIXTURES / "limits" / "tiny-higgs-fit.json")
    got = fit.code_mismatch(config, traffic, SEED, None, precision="bfloat16")
    assert not Check("code_mismatch", got, limits["code_mismatch"]).ok, got


def test_split_with_an_empty_side_fails_the_cell_limits():
    config, codes, y = _fit_inputs()
    limits = load_json(FIXTURES / "limits" / "tiny-higgs-fit.json")
    ref = fit.reference_rounds(config, codes, y, 3)
    first = ref["trees"][0]
    numeric = int(np.flatnonzero(~ref["params"]["is_cat_field"])[0])
    feature, threshold = first.feature.copy(), first.threshold.copy()
    is_cat, default_left = first.is_cat.copy(), first.default_left.copy()
    # every value bin and the missing values go left: the right is empty
    feature[0], threshold[0] = numeric, config["model"]["max_bins"] - 2
    is_cat[0], default_left[0] = 0, 1
    empty = first._replace(feature=feature, threshold=threshold,
                           is_cat=is_cat, default_left=default_left)
    got = fit_ref.compare([empty] + ref["trees"][1:], ref["losses"], ref)
    assert got["light_splits"] == 1
    assert not Check("light_splits", got["light_splits"],
                     limits["light_splits"]).ok


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "altered_leaf"])
def test_fit_faults_in_the_reference_fail(fault):
    config, codes, y = _fit_inputs()
    limits = load_json(FIXTURES / "limits" / "tiny-higgs-fit.json")
    ref = fit.reference_rounds(config, codes, y, 3)
    bad = fit.reference_rounds(config, codes, y, 3, fault=fault)
    got = fit_ref.compare(bad["trees"], bad["losses"], ref)
    assert not all(Check(k, got[k], v).ok for k, v in limits.items()
               if k in got), got


def _unchanged(monkeypatch):
    from repro.core import gbdt
    monkeypatch.setattr(gbdt, "_predict_one_tree",
                        lambda tree, data, plan: jnp.zeros(
                            (data.codes.shape[0],), jnp.float32))


def _half_batch(monkeypatch):
    from repro.core import gbdt
    real = gbdt._round_stats

    def half(config, tkey, g, h, n, F, K):
        g, h, mask = real(config, tkey, g, h, n, F, K)
        keep = 2.0 * (jnp.arange(n) % 2 == 0)
        return g * keep, h * keep, mask
    monkeypatch.setattr(gbdt, "_round_stats", half)


def _altered_leaf(monkeypatch):
    from repro.core import gbdt
    real = gbdt.shrink

    def altered(tree, learning_rate):
        tree = real(tree, learning_rate)
        return tree._replace(leaf_value=tree.leaf_value.at[0].multiply(1.01))
    monkeypatch.setattr(gbdt, "shrink", altered)


def _altered_code(monkeypatch):
    from repro.core.binning import Binner
    real = Binner.transform_codes_device

    def altered(self, X):
        codes = real(self, X)
        return codes.at[::97, 0].add(1)
    monkeypatch.setattr(Binner, "transform_codes_device", altered)


@pytest.mark.parametrize("plant", [_unchanged, _half_batch, _altered_leaf,
                                   _altered_code],
                         ids=["unchanged", "half_batch", "altered_leaf",
                              "altered_code"])
def test_fit_run_with_a_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    rc, line = run_cell("tiny-higgs-fit")
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def _altered_answer(monkeypatch):
    from repro.core.inference import GBDTPipeline
    real = GBDTPipeline.predict

    def altered(self, X, *a, **kw):
        out = real(self, X, *a, **kw)
        return out.at[0].add(1e-2)
    monkeypatch.setattr(GBDTPipeline, "predict", altered)


def _half_flush(monkeypatch):
    from repro.core.inference import GBDTPipeline
    real = GBDTPipeline.predict

    def half(self, X, *a, **kw):
        out = np.asarray(real(self, X, *a, **kw))
        keep = -(-len(out) // 2)
        return np.concatenate([out[:keep], np.full(len(out) - keep, 0.5,
                                                   out.dtype)])
    monkeypatch.setattr(GBDTPipeline, "predict", half)


@pytest.mark.parametrize("plant", [_altered_answer, _half_flush],
                         ids=["altered_answer", "half_flush"])
def test_serve_run_with_a_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    rc, line = run_cell("tiny-flight-serve")
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_serve_control_fails_the_cell_limits():
    from cells import SEED
    from chipbench.drivers import serve
    from chipbench.reference import binning_ref, serve_ref
    config = load_json(FIXTURES / "configs" / "tiny-flight.json")
    traffic = load_json(FIXTURES / "traffic" / "tiny-serve.json")
    limits = load_json(FIXTURES / "limits" / "tiny-flight-serve.json")
    _, pool, sample, trees = serve.prepare(config, traffic, SEED, None,
                                           Clock(), {})
    _, sizes, offsets = serve.schedule(traffic, 2.0, SEED, pool.shape[0])
    tables = binning_ref.fit_edges(sample, {1, 2, 3},
                                 config["model"]["max_bins"])
    host = {f: np.asarray(getattr(trees, f)) for f in trees._fields}
    answers = [None] * len(sizes)
    for k in serve.sampled_requests(sizes, traffic, SEED):
        o = offsets[k]
        answers[k] = serve_ref.predict(pool[o:o + sizes[k]], host,
                                       config["model"]["base_margin"],
                                       tables, precision="bfloat16")
    gap = serve.compare({"sizes": sizes, "offsets": offsets,
                         "answers": answers}, pool, sample, trees, config,
                        traffic, SEED)
    assert not Check("pred_gap", gap, limits["pred_gap"]).ok, gap


def test_seed_keys_use_every_bit():
    from chipbench.data import seed_key
    a, b = seed_key(5), seed_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
