"""A wide dense table through `lgb.train` against the plain float64
grower (tests/plain_grower.py): the Epsilon configuration's parameters
at a size the CPU holds.  Three hundred columns make the split search
scan 300 x 63 candidates a leaf and the partition move 300-column rows:
a search that skipped columns or a partition that moved a row to the
wrong side gives another tree, other counts or another loss."""
import copy

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt_model import compare_tree_functions

import plain_grower

ROWS, COLUMNS, TREES = 3000, 300, 4
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 100, "verbosity": -1}


@pytest.fixture(scope="module")
def wide():
    rng = np.random.default_rng(26)
    X = rng.standard_normal((ROWS, COLUMNS))
    w = rng.standard_normal(COLUMNS) * 0.5 * np.sqrt(28 / COLUMNS)
    logit = X @ w + 0.4 * X[:, 0] * X[:, 1] + 0.3 * np.abs(X[:, 2])
    y = (logit + 0.8 * rng.standard_normal(ROWS) > 0).astype(np.float32)
    train_set = lgb.Dataset(X, label=y, params=PARAMS).construct()
    bst = lgb.train(PARAMS, train_set, num_boost_round=TREES)
    bounds = [np.asarray(m.bin_upper_bound)
              for m in train_set.binned.bin_mappers]
    trees, losses = plain_grower.boost_binary(
        X, y, bounds, TREES, num_leaves=PARAMS["num_leaves"],
        learning_rate=PARAMS["learning_rate"],
        min_data_in_leaf=PARAMS["min_data_in_leaf"],
        min_sum_hessian=PARAMS["min_sum_hessian_in_leaf"])
    return X, y, bst, trees, losses


def _as_model_string(bst, trees):
    """The booster's model with the plain grower's trees in place of its
    own: the header is the data set's, the trees are what is compared."""
    model = copy.deepcopy(bst._engine.model)
    model.trees = trees
    return model.save_model_to_string()


def test_first_tree_is_the_plain_growers(wide):
    """Tree 0 sees the same gradients on both sides (labels and the
    initial score alone): the same function, region for region, the same
    rows in each, leaf values within the CPU's float32 sums."""
    X, y, bst, trees, _ = wide
    report = compare_tree_functions(
        bst.model_to_string(num_iteration=1), _as_model_string(bst, trees[:1]))
    first = report[0]
    assert first["leaves"][0] == first["leaves"][1] >= 5, first
    assert first["common_regions"] == first["leaves"][0], first
    assert first["counts_equal"], first
    assert first["max_value_diff"] <= 1e-5, first
    # every row lands in the leaf the plain partition put it in
    leaf = bst.predict(X, pred_leaf=True, num_iteration=1) \
        .reshape(-1).astype(np.int64)
    counts = np.bincount(leaf, minlength=trees[0].num_leaves)
    assert sorted(counts) == sorted(trees[0].leaf_count[:trees[0].num_leaves])


def test_later_trees_reach_the_plain_growers_loss(wide):
    """From the second tree on the float32 scores differ in the last
    place, so a gain tie may fall either way; the training loss after
    each tree does not move by that."""
    X, y, bst, _, losses = wide
    for k in range(1, TREES + 1):
        raw = bst.predict(X, raw_score=True, num_iteration=k)
        assert abs(plain_grower.log_loss(y, raw) - losses[k - 1]) <= 1e-4, k
    assert losses[-1] < losses[0] < np.log(2.0)
