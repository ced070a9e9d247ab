"""Missing values on the fast path, held to a plain reference kept here.

The data has the structure of the `bosch` cell at a size a CPU trains
(benchmarks/tasks/binary_missing.py is the cell's own generator): parts
follow routes through stations, a station's columns are present or NaN
TOGETHER, most cells are NaN, positives are rare, and both the visit of a
station and the reading of a column move the risk.  Readings take a dozen
distinct values, so the bins enumerate them and the program's search sees
every threshold the plain one sees.

The reference is numpy alone: a walk of the model file's own arrays that
routes a NaN by the node's `default_left`, and a float64 split search
over every column and every distinct value in BOTH directions (the
missing rows right, the missing rows left) under
`min_sum_hessian_in_leaf`.  Nothing of the program's arithmetic is used.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb

ROWS, STATIONS, PER_STATION = 6144, 12, 4
FEATURES = STATIONS * PER_STATION + 1           # and one column never empty
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 20.0, "verbose": -1}
ROUNDS = 4
K_EPSILON = 1e-15
MISSING_ZERO, MISSING_NAN = 1, 2


def make_parts(seed=11):
    """(X [ROWS, FEATURES] float32 with NaN, y [ROWS])."""
    rng = np.random.default_rng(seed)
    routes = rng.random((9, STATIONS)) < 0.25
    routes[:, 0] |= np.arange(9) % 2 == 0           # a well-visited station
    route = rng.integers(0, 9, ROWS)
    seen = routes[route]                            # [ROWS, STATIONS]
    X = np.full((ROWS, FEATURES), np.nan, np.float32)
    z = rng.integers(0, 12, (ROWS, FEATURES - 1)).astype(np.float32)
    here = np.repeat(seen, PER_STATION, axis=1)
    X[:, :-1][here] = z[here]
    X[:, -1] = rng.integers(0, 5, ROWS)             # the part's type
    latent = 1.6 * seen[:, 0] - 1.2 * seen[:, 3] + 0.9 * seen[:, 5] \
        + 0.25 * np.where(here[:, 2], z[:, 2] - 5.5, 0.0) \
        - 0.25 * np.where(here[:, 21], z[:, 21] - 5.5, 0.0) \
        + 0.3 * (X[:, -1] - 2.0) + 0.7 * rng.standard_normal(ROWS)
    y = (latent > np.quantile(latent, 0.93)).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def trained():
    X, y = make_parts()
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
    eng = bst._engine
    assert eng._fast_active
    assert eng.train_set.num_data_padded == ROWS
    return X, y, bst


# -- the plain reference ------------------------------------------------------

def node_goes_left(tree, node, X):
    """[rows] bool: where internal node `node` sends each row of X."""
    dt = int(tree.decision_type[node])
    assert not dt & 1, "a categorical node"
    x = X[:, int(tree.split_feature[node])].astype(np.float64)
    nan = np.isnan(x)
    if (dt >> 2) & 3 == MISSING_NAN:
        return np.where(nan, bool(dt & 2), x <= tree.threshold[node])
    x = np.where(nan, 0.0, x)
    if (dt >> 2) & 3 == MISSING_ZERO:
        return np.where(np.abs(x) <= 1e-35, bool(dt & 2),
                        x <= tree.threshold[node])
    return x <= tree.threshold[node]


def walk(tree, X):
    """(leaf of every row, {internal node: rows that reach it})."""
    at = np.zeros(len(X), np.int64)                 # >= 0: internal node
    reach = {}
    for node in range(tree.num_leaves - 1):         # parents come first
        rows = np.flatnonzero(at == node)
        reach[node] = rows
        left = node_goes_left(tree, node, X[rows])
        at[rows] = np.where(left, tree.left_child[node],
                            tree.right_child[node])
    assert (at < 0).all()
    return ~at, reach


def plain_search(X, grad, hess, min_hess):
    """The best split of these rows: (gain, feature, value or None,
    missing rows left, rows sent left), every column, every distinct
    value, both directions; None where no split stands."""
    sum_g, sum_h = grad.sum(), hess.sum() + 2 * K_EPSILON

    def leaf_gain(g, h):
        return g * g / h

    parent = leaf_gain(sum_g, sum_h)
    best = None
    for f in range(X.shape[1]):
        x = X[:, f].astype(np.float64)
        nan = np.isnan(x)
        sides = [(False, v) for v in np.unique(x[~nan])]
        if nan.any():
            sides += [(True, v) for v in [None] + list(np.unique(x[~nan]))]
        for missing_left, v in sides:
            left = np.zeros(len(x), bool) if v is None else (x <= v) & ~nan
            left |= nan & missing_left
            lh = hess[left].sum() + K_EPSILON
            rh = sum_h - lh
            if not 0 < left.sum() < len(x) or lh < min_hess or rh < min_hess:
                continue
            lg = grad[left].sum()
            gain = leaf_gain(lg, lh) + leaf_gain(sum_g - lg, rh) - parent
            if best is None or gain > best[0]:
                best = (gain, f, v, missing_left, left)
    return best


def tree0_gradients(y):
    """Binary log-loss boosted from the average: (g [rows], h [rows],
    the initial score)."""
    p = float(np.mean(y > 0))
    return np.where(y > 0, p - 1.0, p), np.full(len(y), p * (1.0 - p)), \
        float(np.log(p / (1.0 - p)))


# -- the tests ----------------------------------------------------------------

def test_every_column_but_one_has_a_nan_bin(trained):
    X, _, bst = trained
    mappers = bst._engine.train_set.bin_mappers
    assert [m.missing_type for m in mappers] \
        == [MISSING_NAN] * (FEATURES - 1) + [0]
    assert 0.55 < np.isnan(X).mean() < 0.85
    # a NaN is not a zero: nothing is sparse, so nothing is bundled
    assert bst._engine.train_set.bundle_info is None


@pytest.mark.parametrize("categorical", [(), (FEATURES - 1,)])
def test_a_float32_table_is_binned_where_it_lies(trained, categorical):
    """`lgb.Dataset` makes no float64 copy of a float32 table (a million
    rows of 968 columns would be 7.7 GB): find-bin and the native encode
    read it in place, and give the bins of its float64 copy, NaN bins and
    a categorical column's too."""
    X, y, _ = trained
    assert X.dtype == np.float32
    made = [lgb.Dataset(data, label=y, params=PARAMS,
                        categorical_feature=list(categorical)).construct()
            for data in (X, X.astype(np.float64))]
    as32, as64 = (d.binned for d in made)
    assert as32.binning["path"] == as64.binning["path"] == "native"
    np.testing.assert_array_equal(as32.bins, as64.bins)
    for a, b in zip(as32.bin_mappers, as64.bin_mappers):
        assert (a.num_bin, a.missing_type, a.default_bin, a.bin_type) \
            == (b.num_bin, b.missing_type, b.default_bin, b.bin_type)
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)
    # the NaN bin is a column's last, and holds its empty cells
    nan_bin = as32.bin_mappers[0].num_bin - 1
    np.testing.assert_array_equal(as32.bins[0, :ROWS] == nan_bin,
                                  np.isnan(X[:, 0]))


def test_one_input_path_keeps_a_float32_table(trained):
    """`basic._to_2d_float` is the one way in for training and prediction
    alike: a C-contiguous float32 table comes back as it is, anything else
    as float64; and the predictors read the same scores off either."""
    from lightgbm_tpu.basic import _to_2d_float
    X, _, bst = trained
    assert _to_2d_float(X) is X
    for other in (np.asfortranarray(X), X.astype(np.float64), X[:, 0],
                  X[:5].tolist()):
        out = _to_2d_float(other)
        assert out.dtype == np.float64 and out.ndim == 2
    wide = X.astype(np.float64)
    np.testing.assert_array_equal(bst.predict(X), bst.predict(wide))
    np.testing.assert_array_equal(bst.predict(X, pred_leaf=True),
                                  bst.predict(wide, pred_leaf=True))


def test_tree0_row_for_row_and_value_for_value(trained):
    X, y, bst = trained
    tree = bst._engine.model.trees[0]
    nl = tree.num_leaves
    leaf, _ = walk(tree, X)
    grad, hess, init = tree0_gradients(y)
    np.testing.assert_array_equal(np.bincount(leaf, minlength=nl),
                                  tree.leaf_count[:nl])
    sum_g = np.bincount(leaf, weights=grad, minlength=nl)
    sum_h = np.bincount(leaf, weights=hess, minlength=nl)
    np.testing.assert_allclose(
        tree.leaf_value[:nl],
        init - sum_g / sum_h * PARAMS["learning_rate"], rtol=0, atol=2e-5)
    # and the model's own predictor walks as the reference does
    one = np.zeros(len(X))
    for t in bst._engine.model.trees:
        one += np.asarray(t.leaf_value)[walk(t, X)[0]]
    np.testing.assert_allclose(bst.predict(X, raw_score=True), one,
                               rtol=0, atol=1e-9)


@pytest.mark.parametrize("which", ["root", "second", "third", "last"])
def test_a_nodes_split_is_the_plain_searchs(trained, which):
    """The node's feature, threshold and direction give the gain of the
    best split the plain search finds among the rows that reach it, and
    send the same rows left (stations' columns are empty together, so
    several columns can make one and the same split)."""
    X, y, bst = trained
    tree = bst._engine.model.trees[0]
    node = {"root": 0, "second": 1, "third": 2,
            "last": tree.num_leaves - 2}[which]
    grad, hess, _ = tree0_gradients(y)
    rows = walk(tree, X)[1][node]
    assert len(rows) == tree.internal_count[node]
    gain, feature, value, missing_left, left = plain_search(
        X[rows], grad[rows], hess[rows], PARAMS["min_sum_hessian_in_leaf"])
    got_left = node_goes_left(tree, node, X[rows])
    lg, lh = grad[rows][got_left].sum(), hess[rows][got_left].sum() + K_EPSILON
    sum_g, sum_h = grad[rows].sum(), hess[rows].sum() + 2 * K_EPSILON
    got_gain = lg * lg / lh + (sum_g - lg) ** 2 / (sum_h - lh) \
        - sum_g * sum_g / sum_h
    # (the last splits part rows that are all negatives: a gain of 0 in
    # float64, of some 1e-6 of rounding in the program's float32, which is
    # why the tree grows on until the hessian floor ends it)
    assert got_gain == pytest.approx(gain, rel=1e-9, abs=1e-9)
    assert got_gain == pytest.approx(tree.split_gain[node], rel=2e-4,
                                     abs=1e-4)
    if gain <= 1e-4:
        return
    np.testing.assert_array_equal(got_left, left)
    if int(tree.split_feature[node]) == feature:
        x = X[rows, feature]
        if np.isnan(x).any():
            assert bool(int(tree.decision_type[node]) & 2) == missing_left
        below = x[~np.isnan(x) & (x <= tree.threshold[node])]
        assert (below.max() if len(below) else None) == value


def test_both_directions_occur_in_the_first_trees(trained):
    _, _, bst = trained
    directions = set()
    for tree in bst._engine.model.trees:
        dt = np.asarray(tree.decision_type[:tree.num_leaves - 1], np.int64)
        aware = (dt >> 2) & 3 == MISSING_NAN
        directions |= set((dt[aware] & 2) != 0)
    assert directions == {True, False}


def test_trees_stop_under_the_hessian_floor_with_no_dead_round(trained):
    """Every tree ends before `num_leaves`; in tree 0 because no leaf has
    a split left that keeps `min_sum_hessian_in_leaf` on both sides and
    gains anything; and the growth loop made one round a split."""
    X, y, bst = trained
    eng = bst._engine
    trees = eng.model.trees
    assert all(2 < t.num_leaves < PARAMS["num_leaves"] for t in trees)
    assert eng.split_rounds_total == sum(t.num_leaves - 1 for t in trees)
    grad, hess, _ = tree0_gradients(y)
    leaf, _ = walk(trees[0], X)
    floor = PARAMS["min_sum_hessian_in_leaf"]
    assert hess.sum() / floor < PARAMS["num_leaves"]
    for k in range(trees[0].num_leaves):
        rows = leaf == k
        assert hess[rows].sum() >= floor
        found = plain_search(X[rows], grad[rows], hess[rows], floor)
        assert found is None or found[0] <= 1e-9


@pytest.mark.parametrize("learner", ["serial", "frontier", "data", "voting",
                                     "feature"])
def test_counters_against_the_trees_and_the_raw_data(trained, learner):
    """`missing_splits`, `default_left_splits` and `rows_missing`: an
    entry a finished tree, equal to what the finished tree and the raw
    table say; on a mesh the rows are counted once, not once a device;
    and where a round commits several splits (`tpu_frontier_batch`), the
    committed ones' and no others'."""
    X, y, bst = trained
    if learner == "frontier":
        bst = lgb.train({**PARAMS, "tpu_frontier_batch": 4},
                        lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
        assert bst._engine.split_rounds_total \
            < sum(t.num_leaves - 1 for t in bst._engine.model.trees)
    elif learner != "serial":
        bst = lgb.train({**PARAMS, "tree_learner": learner},
                        lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
        assert bst._engine.mesh is not None
    eng = bst._engine
    assert eng._fast_active
    counters = eng._fast.counters
    mappers = eng.train_set.bin_mappers
    trees = eng.model.trees
    assert {len(v) for v in counters.values()} == {ROUNDS}
    seen_plain = False
    for i, tree in enumerate(trees):
        ni = tree.num_leaves - 1
        features = np.asarray(tree.split_feature[:ni], np.int64)
        aware = np.array([mappers[f].missing_type != 0 for f in features])
        dt = np.asarray(tree.decision_type[:ni], np.int64)
        assert counters["splits"][i] == ni
        assert counters["missing_splits"][i] == aware.sum()
        assert counters["default_left_splits"][i] \
            == (aware & ((dt & 2) != 0)).sum()
        reach = walk(tree, X)[1]
        assert counters["rows_missing"][i] == sum(
            int(np.isnan(X[reach[node], features[node]]).sum())
            for node in range(ni))
        assert 0 < counters["rows_missing"][i] \
            < counters["rows_partitioned"][i]
        seen_plain |= bool((~aware).any())
    # the never-empty column splits too, and counts as no missing split
    assert seen_plain


def test_rows_missing_in_a_bundle_with_zero_as_missing():
    """Sparse columns that exclude one another share a storage column
    (EFB), and with `zero_as_missing` a column's zeros are its missing
    rows: they sit in the bundle's default bin, which the histogram holds
    only as the bundle's total less the member's own bins.  The counter
    reads them all the same."""
    rng = np.random.default_rng(3)
    n, groups, per = 4096, 6, 5
    X = np.zeros((n, groups * per), np.float32)
    for g in range(groups):                 # one column of a group a row
        which = rng.integers(0, per + 2, n)
        rows = np.flatnonzero(which < per)
        X[rows, g * per + which[rows]] = rng.integers(1, 9, len(rows))
    latent = (X[:, 0] > 4) * 1.5 + (X[:, 7] > 0) * 1.0 - (X[:, 12] > 3) \
        + 0.8 * rng.standard_normal(n)
    y = (latent > np.quantile(latent, 0.8)).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "min_data_in_leaf": 20, "zero_as_missing": True, "verbose": -1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    eng = bst._engine
    assert eng._fast_active and eng.train_set.bundle_info is not None
    assert {m.missing_type for m in eng.train_set.bin_mappers} \
        == {MISSING_ZERO}
    counters = eng._fast.counters
    for i, tree in enumerate(eng.model.trees):
        ni = tree.num_leaves - 1
        assert ni > 3 and counters["missing_splits"][i] == ni
        reach = walk(tree, X)[1]
        assert counters["rows_missing"][i] == sum(
            int((X[reach[node], tree.split_feature[node]] == 0).sum())
            for node in range(ni))
