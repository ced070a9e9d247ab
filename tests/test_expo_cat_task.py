"""The benchmark task `binary_cat` (benchmarks/tasks/binary_cat.py) against
the program on the CPU: its plain walk of a tree with categorical nodes
against `Booster.predict`, its first-tree check on a sound tree and on one
whose category was moved to the other side, and the booster's split
counters the cell's `split.categorical_share` reads."""
import copy
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = {"rows": 40000, "heldout_rows": 8000, "features": 8,
       "categorical_feature": [0, 1, 2, 4, 5, 6], "quality_metric": "auc",
       "params": {"objective": "binary", "num_leaves": 31, "max_bin": 255,
                  "learning_rate": 0.1, "verbose": -1}}


@pytest.fixture(scope="module")
def task():
    from benchmarks.run import load_module
    return load_module(os.path.join(ROOT, "benchmarks", "tasks",
                                    "binary_cat.py"))


@pytest.fixture(scope="module")
def trained(task):
    data = task.make(CFG, 2**31 + 3, 0)
    held = task.make(CFG, 2**31 + 3, 1)
    train = lgb.Dataset(data["X"], label=data["y"], params=CFG["params"],
                        **task.dataset_args(data))
    bst = lgb.Booster(CFG["params"], train)
    for _ in range(3):
        bst.update()
    bst.current_iteration()
    return bst, data, held


def test_walk_is_the_boosters_prediction(task, trained):
    bst, _, held = trained
    trees = bst._engine.model.trees
    assert sum(t.num_cat for t in trees) >= 20
    X = held["X"].copy()
    # values no training row showed: an airport past the table, a
    # negative code, a fraction, NaN in a categorical column
    X[:6, task.ORIGIN] = [900.0, -4.0, 17.5, np.nan, 312.0, 1e9]
    X[:6, task.DEPTIME] = [np.nan, 0.0, 2359.0, 1200.5, -1.0, 1e9]
    raw = task.predict_raw(trees, X)
    np.testing.assert_allclose(raw, bst.predict(X, raw_score=True),
                               rtol=0, atol=1e-12)
    leaf = task.leaf_index(trees[0], X)
    np.testing.assert_array_equal(leaf, trees[0].predict_leaf_index(X))
    auc = task.heldout(trees, held, CFG)
    assert 0.7 < auc < 0.9


def test_first_tree_holds_a_sound_tree_and_refuses_a_moved_category(
        task, trained, capsys):
    bst, data, _ = trained
    tree = bst._engine.model.trees[0]
    sound = task.first_tree(tree, data, CFG)
    assert sound["counts_ok"] and sound["max_count_diff"] == 0
    assert sound["max_value_diff"] < 1e-5 \
        and sound["largest_value_diff"] < 1e-3
    assert sound["max_value_diff"] >= sound["value_diff_quartiles"][2]
    assert abs(sound["positive_share"] - 0.215) < 0.01
    assert sound["root_is_categorical"]
    assert sound["root_gain_rel_diff"][0] < 1e-6
    assert "compared tree0_root_gain_rel_diff" in capsys.readouterr().err

    # one category of one deep node moved to the other side: a few rows
    # change leaves and the counts say so
    sets = task.category_sets(tree)
    node = max(n for n in sets if n > 0)
    moved = copy.deepcopy(tree)
    k = int(moved.threshold[node])
    word = moved.cat_boundaries[k]
    values = data["X"][:, int(tree.split_feature[node])].astype(np.int64)
    present = [v for v in np.unique(values) if v < 32]
    moved.cat_threshold[word] ^= 1 << int(present[0])
    bad = task.first_tree(moved, data, CFG)
    if bad["max_count_diff"] == 0:      # no row of that value reached it
        pytest.skip("the moved category holds no row under that node")
    assert not bad["counts_ok"] and bad["leaves_off"] >= 2

    # one leaf's value altered by 1e-3: the measure reads a 32nd of it
    lone = copy.deepcopy(tree)
    lone.leaf_value[3] += 1e-3
    assert task.first_tree(lone, data, CFG)["max_value_diff"] > 3e-5

    # the root's set moved: rows, and the gain the root no longer has
    root = copy.deepcopy(tree)
    k = int(root.threshold[0])
    lo, hi = root.cat_boundaries[k], root.cat_boundaries[k + 1]
    for w in range(lo, hi):
        root.cat_threshold[w] ^= 0xFFFF
    bad = task.first_tree(root, data, CFG)
    assert not bad["counts_ok"]
    assert bad["root_gain_rel_diff"][0] > bad["root_gain_rel_diff"][1]


def test_leaves_under_a_categorical_split_are_regularised_by_cat_l2(
        task, trained):
    """A leaf's value follows from its rows and from the KIND of the split
    that made it: with cat_l2 left out of the recomputation the
    categorical leaves, and only they, stand apart."""
    bst, data, _ = trained
    tree = bst._engine.model.trees[0]
    by_cat = task._parent_is_categorical(tree)
    assert by_cat.any() and not by_cat.all()
    without = dict(CFG, params=dict(CFG["params"], cat_l2=0.0))
    off = task.first_tree(tree, data, without)["max_value_diff"]
    assert off > 20 * task.first_tree(tree, data, CFG)["max_value_diff"]


def test_booster_counts_its_trees_splits_by_kind(trained):
    bst, _, _ = trained
    eng = bst._engine
    counters = eng._fast.counters
    trees = eng.model.trees
    assert counters["splits"] == [t.num_leaves - 1 for t in trees]
    assert counters["categorical_splits"] == [t.num_cat for t in trees]
    assert sum(counters["categorical_splits"]) * 2 > sum(counters["splits"])
