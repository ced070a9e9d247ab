"""The partitioned grower must make the same trees as the masked grower.

Both implement SerialTreeLearner semantics; grower2 restores the reference's
O(rows-touched) cost model (DataPartition + build-smaller-child).  On the f32
CPU path the histograms are bit-comparable, so the grown trees must agree
split for split."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.boosting.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.boosting.grower2 import (PayloadCols,
                                           make_partitioned_grower)
from lightgbm_tpu.boosting.gbdt import _feature_meta_device
from lightgbm_tpu.ops import segment as seg


def _make_problem(n=3000, f=6, seed=0, with_nan=False, categorical=()):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float64)
    for c in categorical:
        X[:, c] = rng.integers(0, 12, size=n)
    if with_nan:
        X[rng.random((n, f)) < 0.1] = np.nan
    y = (X[:, 0] + 0.5 * np.nan_to_num(X[:, 1]) +
         rng.standard_normal(n) * 0.1 > 0).astype(np.float32)
    return X, y


def _grow_both(X, y, num_leaves=31, categorical=(), min_data=20):
    config = Config({"objective": "binary", "max_bin": 63,
                     "num_leaves": num_leaves,
                     "min_data_in_leaf": min_data})
    ds = BinnedDataset.from_matrix(X, config, categorical_feature=categorical,
                                   row_chunk=1024)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    has_cat = bool(categorical)
    gcfg = GrowerConfig(num_leaves=num_leaves, max_depth=-1, lambda_l1=0.0,
                        lambda_l2=0.1, max_delta_step=0.0,
                        min_data_in_leaf=min_data,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad, with_categorical=has_cat)

    n = len(y)
    grad = np.zeros(n_pad, np.float32)
    hess = np.zeros(n_pad, np.float32)
    grad[:n] = 0.5 - y
    hess[:n] = 0.25
    mask = np.zeros(n_pad, np.float32)
    mask[:n] = 1.0

    # masked grower
    grow1 = make_tree_grower(meta, gcfg, ds.max_num_bin)
    vals = jnp.stack([jnp.asarray(grad * mask), jnp.asarray(hess * mask),
                      jnp.asarray(mask)], axis=1)
    fmask = jnp.ones(ds.num_features, bool)
    out1 = jax.device_get(grow1(jnp.asarray(ds.bins), vals, fmask))

    # partitioned grower
    F = ds.num_features
    cols = PayloadCols(grad=F, hess=F + 1, cnt=F + 2, value=F + 3)
    P = F + 4
    payload = np.zeros((n_pad + seg.GUARD, P), np.float32)
    payload[:n_pad, :F] = ds.bins.T
    payload[:n_pad, cols.grad] = grad * mask
    payload[:n_pad, cols.hess] = hess * mask
    payload[:n_pad, cols.cnt] = mask
    grow2 = make_partitioned_grower(meta, gcfg, ds.max_num_bin, cols, F)
    tree2, payload2, _ = grow2(jnp.asarray(payload),
                               jnp.zeros_like(jnp.asarray(payload)), fmask)
    out2 = jax.device_get(tree2)
    return out1, out2, np.asarray(jax.device_get(payload2)), cols, ds


def _assert_same_tree(out1, out2):
    nl = int(out1["num_leaves"])
    assert int(out2["num_leaves"]) == nl
    ni = nl - 1
    np.testing.assert_array_equal(out1["split_feature"][:ni],
                                  out2["split_feature"][:ni])
    np.testing.assert_array_equal(out1["split_bin"][:ni],
                                  out2["split_bin"][:ni])
    np.testing.assert_array_equal(out1["default_left"][:ni],
                                  out2["default_left"][:ni])
    np.testing.assert_array_equal(out1["left_child"][:ni],
                                  out2["left_child"][:ni])
    np.testing.assert_array_equal(out1["right_child"][:ni],
                                  out2["right_child"][:ni])
    np.testing.assert_array_equal(out1["split_is_cat"][:ni],
                                  out2["split_is_cat"][:ni])
    np.testing.assert_allclose(out1["split_gain"][:ni],
                               out2["split_gain"][:ni], rtol=1e-4)
    np.testing.assert_allclose(out1["leaf_value"][:nl],
                               out2["leaf_value"][:nl], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(out1["leaf_count"][:nl],
                               out2["leaf_count"][:nl], rtol=1e-6)


def test_same_tree_numerical():
    X, y = _make_problem()
    out1, out2, _, _, _ = _grow_both(X, y)
    assert int(out1["num_leaves"]) > 4
    _assert_same_tree(out1, out2)


def test_same_tree_with_nan():
    X, y = _make_problem(with_nan=True, seed=3)
    out1, out2, _, _, _ = _grow_both(X, y)
    _assert_same_tree(out1, out2)


def test_same_tree_categorical():
    X, y = _make_problem(seed=5, categorical=(2, 4))
    out1, out2, _, _, _ = _grow_both(X, y, categorical=(2, 4))
    assert int(out1["num_leaves"]) > 2
    _assert_same_tree(out1, out2)


def test_segments_and_values_consistent():
    """Segments tile the padded rows; the payload value column equals the
    final leaf value of each segment (what the score update adds)."""
    X, y = _make_problem(seed=7)
    out1, out2, payload2, cols, ds = _grow_both(X, y)
    nl = int(out2["num_leaves"])
    starts = out2["seg_start"][:nl]
    cnts = out2["seg_cnt"][:nl]
    order = np.argsort(starts)
    assert starts[order][0] == 0
    assert np.all(starts[order][1:] == (starts + cnts)[order][:-1])
    assert (starts + cnts)[order][-1] == ds.num_data_padded
    for li in range(nl):
        s, c = int(starts[li]), int(cnts[li])
        got = payload2[s:s + c, cols.value]
        np.testing.assert_allclose(
            got, np.full(c, out2["leaf_value"][li], np.float32), rtol=1e-6)


def test_masked_counts_match_bagging():
    """Rows with zeroed count-mask are still routed (partitioned) but carry
    no statistics — mirrors bagging via zeroed vals."""
    X, y = _make_problem(seed=11)
    rng = np.random.default_rng(0)
    keep = rng.random(len(y)) < 0.7

    config = Config({"objective": "binary", "max_bin": 63, "num_leaves": 15,
                     "min_data_in_leaf": 20})
    ds = BinnedDataset.from_matrix(X, config, row_chunk=1024)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    gcfg = GrowerConfig(num_leaves=15, max_depth=-1, lambda_l1=0.0,
                        lambda_l2=0.1, max_delta_step=0.0, min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad)
    n = len(y)
    grad = np.zeros(n_pad, np.float32)
    hess = np.zeros(n_pad, np.float32)
    grad[:n] = (0.5 - y) * keep
    hess[:n] = 0.25 * keep
    mask = np.zeros(n_pad, np.float32)
    mask[:n] = keep

    grow1 = make_tree_grower(meta, gcfg, ds.max_num_bin)
    vals = jnp.stack([jnp.asarray(grad), jnp.asarray(hess),
                      jnp.asarray(mask)], axis=1)
    fmask = jnp.ones(ds.num_features, bool)
    out1 = jax.device_get(grow1(jnp.asarray(ds.bins), vals, fmask))

    F = ds.num_features
    cols = PayloadCols(grad=F, hess=F + 1, cnt=F + 2, value=F + 3)
    payload = np.zeros((n_pad + seg.GUARD, F + 4), np.float32)
    payload[:n_pad, :F] = ds.bins.T
    payload[:n_pad, cols.grad] = grad
    payload[:n_pad, cols.hess] = hess
    payload[:n_pad, cols.cnt] = mask
    grow2 = make_partitioned_grower(meta, gcfg, ds.max_num_bin, cols, F)
    tree2, _, _ = grow2(jnp.asarray(payload),
                        jnp.zeros((n_pad + seg.GUARD, F + 4), jnp.float32),
                        fmask)
    out2 = jax.device_get(tree2)
    _assert_same_tree(out1, out2)


def test_histogram_pool_recompute_matches():
    """The LRU histogram pool (histogram_pool_size) against the
    unbounded grower — DETERMINISTIC contract (ISSUE 13 satellite;
    formerly a borderline numeric flake asserting near-bit equality
    across 8 compounding rounds): an evicted parent is rebuilt from its
    still-contiguous row segment (reference HistogramPool
    recompute-on-miss), and a from-rows rebuild legitimately differs at
    ulp level from the subtraction-derived histogram the unbounded
    grower holds — the reference's recompute has the same property —
    so near-tie splits may flip.  What IS exact, and pinned here:

    * a pool with >= num_leaves slots never evicts, and its model is
      BYTE-identical to the unbounded grower's (the pool bookkeeping —
      slot reuse, LRU priority — inserts no numeric drift of its own);
    * the ~4-slot recompute path trains the same number of trees to the
      same training loss within 1% with finite predictions.
    """
    import lightgbm_tpu as lgb
    X, y = _make_problem(n=4000, f=8, seed=13)
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 31, "max_bin": 63, "min_data_in_leaf": 20,
              "verbose": -1}
    full = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=8)
    # ample pool: slot budget >> 31 leaves -> no eviction, no recompute
    ample = lgb.train({**params, "histogram_pool_size": 64.0},
                      lgb.Dataset(X, label=y), num_boost_round=8)
    assert ample.model_to_string() == full.model_to_string()
    # ~4 slots: 63 bins * 8 features * 3 * 4B per slot -> recompute path
    tiny = lgb.train({**params, "histogram_pool_size": 0.025},
                     lgb.Dataset(X, label=y), num_boost_round=8)
    assert tiny.num_trees() == full.num_trees()
    pf, pt = full.predict(X), tiny.predict(X)
    assert np.isfinite(pt).all()

    def logloss(p):
        p = np.clip(p, 1e-7, 1.0 - 1e-7)
        return float(-np.mean(y * np.log(p) + (1.0 - y) * np.log(1.0 - p)))

    lf, lt = logloss(pf), logloss(pt)
    assert abs(lt - lf) <= 0.01 * max(lf, 1e-6), (lt, lf)


def test_grower_reports_the_engines_it_resolved():
    """The grower names the histogram and partition implementations it
    chose (from platform and shape) as `.engines`, and the booster passes
    them on — on the CPU both are the portable lax engine.  A TPU must read
    "pallas" / "pallas-acc" at the Higgs shape; chip_smoke.py asserts that
    on the chip."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.grower2 import partition_engine

    assert partition_engine("auto", 128, 256) == "lax"
    assert partition_engine("lax", 128, 256) == "lax"

    rng = np.random.default_rng(0)
    X = rng.standard_normal((600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                       "verbose": -1}, lgb.Dataset(X, label=y))
    assert bst._engine.engines is None      # fast path not built yet
    bst.update()
    assert bst._engine.engines == {"histogram": "lax", "partition": "lax"}
    assert bst._engine._fast.grower.engines is bst._engine.engines
