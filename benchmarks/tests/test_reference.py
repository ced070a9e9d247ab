"""The plain reference against the program at a small size, and that it
catches what it exists to catch."""
import copy

import numpy as np
import pytest

from benchmarks.lib import quality, reference, synth


@pytest.fixture(scope="module")
def trained():
    import lightgbm_tpu as lgb
    X, y = synth.binary_task(8000, 28, (4, 0))
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "learning_rate": 0.1, "verbose": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(4):
        bst.update()
    bst.current_iteration()
    return bst, X, y


def test_first_tree_is_what_the_data_says(trained):
    bst, X, y = trained
    check = reference.tree0_check(bst._engine.model.trees[0], X, y, 0.1)
    assert check["counts_ok"] and check["max_value_diff"] < 5e-4
    assert check["leaves"] == 31 and check["rows"] == 8000


def test_a_moved_row_or_a_wrong_sum_is_caught(trained):
    bst, X, y = trained
    tree = copy.deepcopy(bst._engine.model.trees[0])
    tree.threshold[3] += 0.05                   # some rows change sides
    assert not reference.tree0_check(tree, X, y, 0.1)["counts_ok"]
    tree = copy.deepcopy(bst._engine.model.trees[0])
    tree.leaf_value[5] += 0.01
    assert reference.tree0_check(tree, X, y, 0.1)["max_value_diff"] > 5e-3


def test_walk_agrees_with_the_programs_host_predictor(trained):
    bst, X, _ = trained
    raw = reference.predict_raw(bst._engine.model.trees, X[:2000])
    assert np.allclose(raw, bst.predict(X[:2000], raw_score=True),
                       rtol=0, atol=1e-12)


def test_serving_model_scores_agree_on_the_float32_grid():
    from lightgbm_tpu.basic import Booster
    model = synth.serving_model(8, 31, 28, (1, 3))
    pool = synth.feature_rows(3000, 28, (1, 2))
    got = Booster(model_str=model.save_model_to_string()).predict(
        pool, device=True)
    want = reference.sigmoid(reference.predict_raw(
        model.trees, pool.astype(np.float64)))
    assert np.allclose(got, want, rtol=1e-5, atol=1e-6)


def test_counts_must_be_equal_below_two_to_the_24_and_close_above(trained):
    bst, X, y = trained
    tree = bst._engine.model.trees[0]
    rows = np.bincount(reference.leaf_index(tree, X), minlength=31)
    assert not reference.count_slack(tree, rows).any()
    one_off = copy.deepcopy(tree)
    one_off.leaf_count[0] += 1
    assert not reference.tree0_check(one_off, X, y, 0.1)["counts_ok"]
    # the same tree over 5,000 times the rows: 40M, whose half-ulp in
    # float32 is 2.  Every leaf has the root above it (16 rows of slack);
    # one more for each further ancestor of 2^24 rows or more
    slack = reference.count_slack(tree, rows * 5000)
    assert slack.min() >= 16 and slack.max() <= 64
    assert (slack % 16 == 0).all()


def test_auc():
    y = np.array([0, 0, 1, 1])
    assert quality.auc(y, np.array([0.1, 0.4, 0.35, 0.8])) == 0.75
    assert quality.auc(y, np.array([0.1, 0.2, 0.3, 0.4])) == 1.0
