"""Model text-format interop tests against reference-produced goldens.

Mirrors the reference test strategy (SURVEY.md §4): golden files under
.golden/ were produced by the reference CLI built from /root/reference.
"""
import os

import numpy as np
import pytest

from lightgbm_tpu.models.gbdt_model import GBDTModel
from tests.conftest import GOLDEN_DIR

GOLDEN_MODEL = os.path.join(GOLDEN_DIR, "binary/golden_model.txt")
GOLDEN_PRED = os.path.join(GOLDEN_DIR, "binary/golden_pred.txt")

needs_golden = pytest.mark.skipif(not os.path.exists(GOLDEN_MODEL),
                                  reason="golden files not generated")


@needs_golden
def test_load_reference_model_and_predict(binary_data):
    """A model trained by the reference CLI loads and predicts identically."""
    _, _, X_test, _ = binary_data
    model = GBDTModel.load_model(GOLDEN_MODEL)
    assert len(model.trees) == 20
    raw = model.predict_raw(X_test)[:, 0]
    pred = 1.0 / (1.0 + np.exp(-raw))
    golden = np.loadtxt(GOLDEN_PRED)
    np.testing.assert_allclose(pred, golden, atol=1e-12)


@needs_golden
def test_save_load_roundtrip(binary_data):
    _, _, X_test, _ = binary_data
    model = GBDTModel.load_model(GOLDEN_MODEL)
    text = model.save_model_to_string()
    model2 = GBDTModel.load_model_from_string(text)
    np.testing.assert_array_equal(model.predict_raw(X_test), model2.predict_raw(X_test))


@needs_golden
def test_predict_leaf_index_shape(binary_data):
    _, _, X_test, _ = binary_data
    model = GBDTModel.load_model(GOLDEN_MODEL)
    leaves = model.predict_leaf_index(X_test)
    assert leaves.shape == (X_test.shape[0], 20)
    assert leaves.max() < 31


@needs_golden
def test_dump_model_json(binary_data):
    model = GBDTModel.load_model(GOLDEN_MODEL)
    dump = model.dump_model()
    assert dump["num_class"] == 1
    assert len(dump["tree_info"]) == 20
    t0 = dump["tree_info"][0]["tree_structure"]
    assert "split_feature" in t0 and "threshold" in t0


@needs_golden
def test_feature_importance(binary_data):
    model = GBDTModel.load_model(GOLDEN_MODEL)
    imp = model.feature_importance()
    assert imp.sum() == sum(t.num_leaves - 1 for t in model.trees)
    gain = model.feature_importance(importance_type="gain")
    assert (gain >= 0).all() and gain.sum() > 0


def test_config_aliases():
    from lightgbm_tpu.config import Config
    c = Config({"num_leaf": 63, "eta": 0.2, "objective": "binary"})
    assert c.num_leaves == 63
    assert c.learning_rate == 0.2
    assert c.metric == ["binary_logloss"]
    c2 = Config({"objective": "mse"})
    assert c2.objective == "regression"
    assert c2.metric == ["l2"]


def test_config_check_fails():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        Config({"num_leaves": 1})


def _two_orders_of_one_tree():
    """The same three splits made in two orders (leaf-wise growth picks
    the frontier leaf by gain; a near-tie in gain swaps the order)."""
    from lightgbm_tpu.models.tree import Tree
    trees = []
    for second, third in ((0, 1), (1, 0)):
        t = Tree(4)
        t.split(0, 0, 0, 0.5, -1.0, 1.0, 60, 40, 9.0, 0, True)
        sides = {0: (1, 0.25, -2.0, -0.5, 30, 30, 4.0),
                 1: (2, 0.75, 0.5, 2.0, 25, 15, 4.0)}
        for leaf in (second, third):
            f, thr, lv, rv, lc, rc, gain = sides[leaf]
            t.split(leaf, f, 0, thr, lv, rv, lc, rc, gain, 0, True)
        trees.append(t)
    return trees


def _model_text(tree):
    m = GBDTModel()
    m.max_feature_idx = 2
    m.objective_str = "regression"
    m.trees.append(tree)
    return m.save_model_to_string()


def test_compare_tree_functions_ignores_split_order_and_names_divergence():
    """What chip_smoke.py compares engines by: two trees that made the same
    splits in another order are the same function (same leaf regions, row
    counts and values), while a moved threshold is reported as a
    divergence with the gains on both sides."""
    from lightgbm_tpu.models.gbdt_model import (assert_models_equivalent,
                                                compare_tree_functions)
    a, b = _two_orders_of_one_tree()
    text_a, text_b = _model_text(a), _model_text(b)
    with pytest.raises(AssertionError):     # the strict, ordered rule
        assert_models_equivalent(text_a, text_b)
    (rep,) = compare_tree_functions(text_a, text_b)
    assert rep["leaves"] == [4, 4] and rep["common_regions"] == 4
    assert rep["counts_equal"] and rep["max_value_diff"] == 0.0
    assert rep["divergence"] is None
    assert rep["split_order"].startswith("differs from split 1")

    b.threshold[0] = 0.6                    # the root now cuts elsewhere
    (rep,) = compare_tree_functions(text_a, _model_text(b))
    assert rep["common_regions"] == 0
    assert rep["divergence"].startswith("depth 0: feature 0 <= 0.5")
