"""Reference binding surface parity: Booster.eval/attr/model_from_string/
shuffle_models/get_leaf_output, Dataset.get_field/set_field etc."""
import copy

import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((400, 6)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "metric": "auc",
                     "num_leaves": 15, "verbose": -1}, ds,
                    num_boost_round=5)
    return bst, ds, X, y


def test_booster_eval_arbitrary_dataset(trained):
    bst, ds, X, y = trained
    rng = np.random.default_rng(1)
    X2 = rng.standard_normal((150, 6)).astype(np.float32)
    y2 = (X2[:, 0] + 0.4 * X2[:, 1] > 0).astype(np.float64)
    d2 = ds.create_valid(X2, label=y2)
    res = bst.eval(d2, "holdout")
    assert res and res[0][0] == "holdout" and res[0][1] == "auc"
    assert 0.5 < res[0][2] <= 1.0


def test_attr_roundtrip(trained):
    bst = trained[0]
    assert bst.attr("note") is None
    bst.set_attr(note="hello")
    assert bst.attr("note") == "hello"
    bst.set_attr(note=None)
    assert bst.attr("note") is None
    with pytest.raises(Exception):
        bst.set_attr(bad=123)


def test_model_from_string_and_leaf_output(trained):
    bst, _, X, _ = trained
    s = bst.model_to_string()
    other = lgb.Booster(model_str=s)
    other.model_from_string(s, verbose=False)
    np.testing.assert_allclose(other.predict(X), bst.predict(X), atol=1e-12)
    lv = bst.get_leaf_output(0, 0)
    assert np.isfinite(lv)


def test_shuffle_models_preserves_predictions(trained):
    bst, _, X, _ = trained
    before = bst.predict(X)
    clone = copy.deepcopy(bst)
    clone.shuffle_models()
    np.testing.assert_allclose(clone.predict(X), before, atol=1e-12)
    assert clone.num_trees() == bst.num_trees()


def test_copy_deepcopy(trained):
    bst, _, X, _ = trained
    c1 = copy.copy(bst)
    c2 = copy.deepcopy(bst)
    for c in (c1, c2):
        np.testing.assert_allclose(c.predict(X), bst.predict(X), atol=1e-12)


def test_dataset_fields(trained):
    _, ds, X, y = trained
    np.testing.assert_array_equal(ds.get_field("label"), y)
    w = np.ones(len(y))
    ds.set_field("weight", w)
    np.testing.assert_array_equal(ds.get_field("weight"), w)
    with pytest.raises(Exception):
        ds.get_field("nope")
    assert ds.get_field("group") is None


def test_set_categorical_after_construct_raises(trained):
    _, ds, _, _ = trained
    with pytest.raises(Exception):
        ds.set_categorical_feature([0])
    ds.set_categorical_feature("auto")  # unchanged value is fine


def test_free_network_and_set_network_noop(trained):
    bst = trained[0]
    assert bst.free_network() is bst
    assert bst.set_network("machines") is bst


def test_model_from_string_invalidates_device_cache(trained):
    bst, _, X, y = trained
    p1 = bst.predict(X, device=True)
    rng = np.random.default_rng(2)
    y2 = (X[:, 2] > 0).astype(np.float64)
    other = lgb.train({"objective": "binary", "num_leaves": 15,
                       "verbose": -1}, lgb.Dataset(X, label=y2),
                      num_boost_round=5)
    clone = copy.deepcopy(bst)
    clone.model_from_string(other.model_to_string(), verbose=False)
    np.testing.assert_allclose(clone.predict(X, device=True),
                               other.predict(X), rtol=1e-5, atol=1e-6)


def test_shuffle_models_invalid_range_raises(trained):
    bst = trained[0]
    clone = copy.deepcopy(bst)
    with pytest.raises(Exception):
        clone.shuffle_models(5, 3)
    with pytest.raises(Exception):
        clone.shuffle_models(-2)


def test_eval_on_path_dataset(trained, tmp_path):
    bst, ds, X, y = trained
    f = tmp_path / "valid.tsv"
    np.savetxt(f, np.column_stack([y[:100], X[:100]]), delimiter="\t",
               fmt="%.7g")
    d2 = lgb.Dataset(str(f), reference=ds)
    res = bst.eval(d2, "file")
    assert res and np.isfinite(res[0][2])


def test_num_feature_and_ref_chain(trained):
    bst, ds, X, y = trained
    assert bst.num_feature() == X.shape[1]
    d2 = ds.create_valid(X[:50], label=y[:50])
    d2.construct(bst.config)
    chain = d2.get_ref_chain()
    assert ds in chain and d2 in chain and len(chain) == 2


def test_reset_parameter_method():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((400, 6)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "learning_rate": 0.1}, lgb.Dataset(X, label=y))
    bst.update()
    bst.reset_parameter({"learning_rate": 0.01})
    assert bst._engine.shrinkage_rate == 0.01
    bst.update()
    assert bst.num_trees() == 2


def test_reset_parameter_rf_keeps_unit_shrinkage():
    """rf.hpp ResetConfig semantics: RF scores are running averages, so a
    learning_rate reset must NOT unpin shrinkage from 1.0."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((500, 6)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    bst = lgb.Booster({"objective": "binary", "boosting": "rf",
                       "bagging_fraction": 0.7, "bagging_freq": 1,
                       "feature_fraction": 0.7, "verbose": -1},
                      lgb.Dataset(X, label=y))
    bst.update()
    bst.reset_parameter({"learning_rate": 0.05})
    assert bst._engine.shrinkage_rate == 1.0
    bst.update()
    assert bst.num_trees() == 2


def test_scipy_sparse_input_train_and_predict():
    """Reference basic.py accepts scipy.sparse for Dataset AND predict;
    the dense-columnar binning densifies at the boundary (EFB recovers
    the storage win — docs/STORAGE.md)."""
    import scipy.sparse as sp
    X = sp.random(600, 30, density=0.1, format="csr", random_state=0,
                  dtype=np.float64)
    y = (np.asarray(X.sum(axis=1)).ravel() > 0.5).astype(np.float32)
    bst = lgb.train({"objective": "binary", "verbose": -1},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    p_sparse = bst.predict(X.tocsc())
    p_dense = bst.predict(X.toarray())
    np.testing.assert_allclose(p_sparse, p_dense, atol=1e-12)
    assert np.isfinite(p_sparse).all()


def test_scipy_sparse_cv_subsets_stay_sparse(monkeypatch):
    """cv folds of a sparse input must row-slice while still sparse —
    toarray may only ever see fold-sized slices, never the full matrix."""
    import scipy.sparse as sp
    X = sp.random(900, 25, density=0.1, format="csr", random_state=2,
                  dtype=np.float64)
    y = (np.asarray(X.sum(axis=1)).ravel() > 0.5).astype(np.float32)
    densified_rows = []
    orig = sp.csr_matrix.toarray

    def spy(self, *a, **k):
        densified_rows.append(self.shape[0])
        return orig(self, *a, **k)

    monkeypatch.setattr(sp.csr_matrix, "toarray", spy)
    res = lgb.cv({"objective": "binary", "verbose": -1},
                 lgb.Dataset(X, label=y), num_boost_round=3, nfold=3)
    assert any(res[k][-1] > 0 for k in res if k.endswith("-mean"))
    assert densified_rows, "sparse path never engaged"
    # the parent Dataset's construction densifies the full matrix ONCE
    # (binning needs the columns); every fold slice must be fold-sized
    full = [n for n in densified_rows if n == 900]
    assert len(full) <= 1, \
        "folds re-densified the full matrix: %r" % densified_rows


def test_scipy_sparse_dok_input():
    """dok_matrix subclasses dict — its .values method must not shadow
    the sparse branch (ordering bug found in review)."""
    import scipy.sparse as sp
    X = sp.dok_matrix((300, 10), dtype=np.float64)
    rng = np.random.default_rng(3)
    for _ in range(400):
        X[rng.integers(0, 300), rng.integers(0, 10)] = rng.random()
    y = (np.asarray(X.tocsr().sum(axis=1)).ravel() > 0.2).astype(np.float32)
    bst = lgb.train({"objective": "binary", "verbose": -1},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert np.isfinite(bst.predict(X)).all()


def test_kernel_smoke_refuses_the_cpu():
    """exp/smoke_tpu_kernels.py judges Mosaic on the chip: on any other
    platform it must exit non-zero naming the platform, not report
    verdicts (its --interpret rehearsal is the explicit CPU mode)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "exp", "smoke_tpu_kernels.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "platform is 'cpu', not tpu" in r.stderr, r.stderr[-500:]
    assert "verdicts" not in r.stdout
