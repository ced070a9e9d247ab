"""LambdaRank's bucketed query layout (objective/rank.py) against the
plain float64 reference the benchmark holds it to
(benchmarks/tasks/rank.py: an [n, n] block a query, numpy, no code
shared with the program), at random scores and on query lengths drawn
from the `msltr` cell's generator scaled down; the bucketed layout
against the padded one it replaces; the fast path's trees against the
legacy grower's under `lambdarank`."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.objective import rank
from lightgbm_tpu.objective.rank import LambdarankNDCG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def task():
    from benchmarks.run import load_module
    return load_module(os.path.join(ROOT, "benchmarks", "tasks", "rank.py"))


def make_queries(task, case, seed=11):
    """(sizes, label, score, weight): 160 queries of the cell's
    generator cut to 1..260 rows (6,000 in all), random grades and
    scores, and what the case asks for on top."""
    rng = np.random.default_rng(seed)
    sizes = task.query_sizes(160, 6000, 260, (seed, 0, 1))
    if case == "single_rows":
        # queries of one row, first, last and in the middle: no pair
        sizes = np.concatenate([[1, 1], sizes[:80], [1], sizes[80:], [1]])
    n = int(sizes.sum())
    ends = np.cumsum(sizes)
    label = rng.integers(0, 5, n).astype(np.float64)
    score = rng.normal(size=n).astype(np.float32)
    weight = None
    if case == "one_label":
        # one grade throughout a query: no pair, and an ideal DCG of 0
        # where the grade is 0
        for q, grade in ((3, 0.0), (40, 2.0), (len(sizes) - 1, 4.0)):
            label[ends[q] - sizes[q]:ends[q]] = grade
    elif case == "tied_scores":
        # ties rank in original order; a query tied throughout has no
        # score range and skips the 0.01 + |ds| normalisation
        score[rng.integers(0, n, n // 3)] = 0.5
        score[ends[5] - sizes[5]:ends[5]] = -1.25
        score = np.round(score, 1)
    elif case == "row_weights":
        weight = rng.uniform(0.2, 3.0, n)
    elif case == "far_scores":
        # differences past exp's float32 range: the sigmoid is 0 there
        score *= 40.0
    return sizes, label, score, weight


def program(sizes, label, score, weight, params=None):
    obj = LambdarankNDCG(Config(dict({"objective": "lambdarank"},
                                     **(params or {}))))
    obj.init(label, weight, np.concatenate([[0], np.cumsum(sizes)]))
    g, h = obj.get_gradients(jnp.asarray(score), None, None)
    return obj, np.asarray(g, np.float64), np.asarray(h, np.float64)


def close(got, want):
    """float32 sums of up to 260 pair terms against float64: a few units
    in the last place of the largest gradient."""
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-6 * np.abs(want).max())


CASES = ["random", "single_rows", "one_label", "tied_scores", "row_weights",
         "far_scores"]


@pytest.mark.parametrize("case", CASES)
def test_gradients_match_the_float64_reference(task, case):
    sizes, label, score, weight = make_queries(task, case)
    obj, g, h = program(sizes, label, score, weight)
    assert obj.counters["buckets"] > 1
    g64, h64 = task.lambdarank(score, label, sizes, weight=weight)
    close(g, g64)
    close(h, h64)
    assert np.abs(g64).max() > 0.1 and (h >= 0).all()


@pytest.mark.parametrize("params", [{"sigmoid": 2.0}, {"max_position": 5}])
def test_gradients_match_the_reference_under_other_parameters(task, params):
    sizes, label, score, _ = make_queries(task, "random", seed=12)
    _, g, h = program(sizes, label, score, None, params)
    g64, h64 = task.lambdarank(score, label, sizes,
                               sigma=params.get("sigmoid", 1.0),
                               max_position=params.get("max_position", 20))
    close(g, g64)
    close(h, h64)


def test_a_query_alone_in_the_last_bucket(task, monkeypatch):
    """One query far longer than every other, in a bucket to itself: a
    lane tile of one real query and 127 empty ones.  (The planner would
    fill the tile with the 127 next longest, which costs nothing; the
    plan is set by hand here.)"""
    sizes, label, score, _ = make_queries(task, "random", seed=13)
    sizes = np.concatenate([sizes, [900]])
    rng = np.random.default_rng(1)
    label = np.concatenate([label, rng.integers(0, 5, 900)])
    score = np.concatenate([score, rng.normal(size=900).astype(np.float32)])
    monkeypatch.setattr(rank, "plan_buckets",
                        lambda s: [(0, 96), (96, 264), (264, 904)])
    obj, g, h = program(sizes, label, score, None)
    last = obj.buckets[-1]
    assert last.length == 904 and list(last.queries) == [len(sizes) - 1]
    assert (last.chunks, last.width) == (1, 128)
    g64, h64 = task.lambdarank(score, label, sizes)
    close(g, g64)
    close(h, h64)


@pytest.mark.parametrize("case", ["random", "tied_scores", "row_weights"])
def test_bucketed_layout_equals_the_padded_one(task, case, monkeypatch):
    """The same program with every query padded to the longest (one
    bucket, the layout before PR 31): each pair's value is the same and
    a document's pairs are summed in the same order, so the gradients
    are equal to the last bit but for the empty slots' zeros."""
    sizes, label, score, weight = make_queries(task, case)
    obj, g, h = program(sizes, label, score, weight)
    plan = rank.plan_buckets
    monkeypatch.setattr(rank, "plan_buckets", lambda s: plan(s, 1))
    padded, g1, h1 = program(sizes, label, score, weight)
    assert padded.counters["buckets"] == 1 < obj.counters["buckets"]
    assert padded.counters["pairs"] == obj.counters["pairs"] \
        == int((sizes * sizes).sum())
    assert padded.counters["pair_slots"] > 1.5 * obj.counters["pair_slots"]
    np.testing.assert_array_equal(g, g1)
    np.testing.assert_array_equal(h, h1)


def test_plan_covers_every_size_in_few_buckets(task):
    """At the cell's own query count: at most MAX_BUCKETS ranges, every
    size in exactly one, under 3 slots a pair where one padded bucket
    computes 68."""
    sizes = task.query_sizes(18919, 2270296, 1251, (2**31 + 5, 0, 1))
    plan = rank.plan_buckets(sizes)
    assert 1 < len(plan) <= rank.MAX_BUCKETS
    assert plan[0][0] == 0 and plan[-1][1] == 1256
    assert all(a[1] == b[0] for a, b in zip(plan, plan[1:]))

    def slots(plan):
        return sum(-(-int(((sizes > lo) & (sizes <= n)).sum()) // 128) * 128
                   * n * n for lo, n in plan)

    pairs = int((sizes * sizes).sum())
    assert slots(plan) < 2.2 * pairs
    assert slots(rank.plan_buckets(sizes, 1)) > 60 * pairs


def test_gradients_in_any_row_order(task):
    """The fast path hands scores in partition order with the original
    row of each, guard rows and padding marked by an index past the last
    row: the answer comes back in that order, zero where no row is."""
    sizes, label, score, weight = make_queries(task, "row_weights")
    obj, g, h = program(sizes, label, score, weight)
    n = len(score)
    rng = np.random.default_rng(2)
    row = np.concatenate([rng.permutation(n), np.full(37, n + 5)])
    row = row[rng.permutation(len(row))].astype(np.int32)
    there = row < n
    shuffled = np.where(there, score[np.minimum(row, n - 1)], 7.0)
    gp, hp = obj.gradients_in_order(jnp.asarray(shuffled, jnp.float32),
                                    jnp.asarray(row))
    gp, hp = np.asarray(gp, np.float64), np.asarray(hp, np.float64)
    np.testing.assert_array_equal(gp[there], g[row[there]])
    np.testing.assert_array_equal(hp[there], h[row[there]])
    assert not gp[~there].any() and not hp[~there].any()


def test_inverse_max_dcg_is_the_per_query_loop(task):
    sizes, label, _, _ = make_queries(task, "one_label")
    gains = rank.default_label_gain()
    ends = np.cumsum(sizes)
    for k in (1, 5, 20):
        loop = [rank.max_dcg_at_k(k, label[e - n:e], gains)
                for n, e in zip(sizes, ends)]
        want = [1.0 / d if d > 0 else 0.0 for d in loop]
        np.testing.assert_allclose(
            rank.inverse_max_dcg(k, label, sizes, gains), want, rtol=1e-14)


def ranking_data(task, seed=5):
    cfg = {"rows": 5000, "queries": 90, "features": 12, "longest_query": 300}
    data = task.make(cfg, seed, 0)
    return data["X"], data["y"], data["group"]


def test_fast_path_trees_equal_the_legacy_growers(task):
    """`lambdarank` on the fast path (one fused `gbdt.step`, gradients in
    partition order) grows the trees `boosting/grower.py` grows from
    gradients computed in original row order."""
    from conftest import assert_models_equivalent
    from lightgbm_tpu.boosting.gbdt import GBDT
    X, y, group = ranking_data(task)
    params = {"objective": "lambdarank", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 10, "seed": 3}
    fast = lgb.train(dict(params), lgb.Dataset(X, label=y, group=group),
                     num_boost_round=3)
    assert fast._engine._fast_active, "lambdarank fell off the fast path"
    assert fast._engine.objective.counters["buckets"] >= 1
    eligible = GBDT._fast_eligible
    GBDT._fast_eligible = lambda self: False
    try:
        legacy = lgb.train(dict(params),
                           lgb.Dataset(X, label=y, group=group),
                           num_boost_round=3)
    finally:
        GBDT._fast_eligible = eligible
    assert not legacy._engine._fast_active
    assert_models_equivalent(fast.model_to_string(),
                             legacy.model_to_string())


def test_fast_path_first_tree_is_the_references(task):
    """Tree 0 from `lgb.train` against the benchmark's own check: counts
    exact, values within float32 of the float64 recomputation."""
    X, y, group = ranking_data(task, seed=6)
    params = {"objective": "lambdarank", "num_leaves": 15, "verbose": -1,
              "learning_rate": 0.1, "min_data_in_leaf": 10}
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y, group=group),
                    num_boost_round=1)
    read = task.first_tree(bst._engine.model.trees[0],
                           {"X": X, "y": y, "group": group},
                           {"params": params})
    assert read["counts_ok"] and read["max_count_diff"] == 0
    assert read["max_value_diff"] < 1e-6 < read["max_abs_value"]


def test_first_tree_measure_holds_every_leaf_and_any_one(task):
    """`max_value_diff` of the task is the third quartile of the leaves'
    differences or a 128th of the largest: every leaf moved a little
    reads that much, one leaf moved a lot reads a 128th of it."""
    X, y, group = ranking_data(task, seed=7)
    params = {"objective": "lambdarank", "num_leaves": 31, "verbose": -1,
              "learning_rate": 0.1, "min_data_in_leaf": 10}
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y, group=group),
                    num_boost_round=1)
    tree = bst._engine.model.trees[0]
    data, cfg = {"X": X, "y": y, "group": group}, {"params": params}
    sound = task.first_tree(tree, data, cfg)
    assert sound["max_value_diff"] <= sound["largest_value_diff"] < 1e-6
    nl = int(tree.num_leaves)
    kept = np.array(tree.leaf_value[:nl])
    try:
        tree.leaf_value[:nl] = kept + 1e-5
        every = task.first_tree(tree, data, cfg)
        tree.leaf_value[:nl] = kept
        tree.leaf_value[3] += 1e-3
        one = task.first_tree(tree, data, cfg)
    finally:
        tree.leaf_value[:nl] = kept
    assert every["max_value_diff"] == pytest.approx(1e-5, rel=0.1)
    assert one["max_value_diff"] == pytest.approx(
        1e-3 / task.LONE_LEAF_ROOM, rel=0.01)
    assert one["largest_value_diff"] == pytest.approx(1e-3, rel=0.01)
    assert every["counts_ok"] and one["counts_ok"]
