"""Native (C++) ingest vs the Python parsers and per-feature binning.

The reference's loader is native end to end (dataset_loader.cpp +
parser.cpp + ValueToBin); cpp/ingest.cc supplies the same native stages
behind the tolerant Python implementations.  These tests pin byte-exact
agreement between the two paths.
"""
import os
import tempfile

import numpy as np
import pytest

from lightgbm_tpu.io import native
from lightgbm_tpu.io import parser as pmod
from lightgbm_tpu.io.binning import BinMapper
from lightgbm_tpu.io.dataset import BinnedDataset


needs_native = pytest.mark.skipif(native._load() is None,
                                  reason="native library unavailable")


def _write(tmpdir, text, name="data.csv"):
    path = os.path.join(tmpdir, name)
    with open(path, "w") as fh:
        fh.write(text)
    return path


@needs_native
def test_parse_dense_matches_python_csv():
    rng = np.random.default_rng(0)
    n, f = 997, 7
    X = np.round(rng.standard_normal((n, f)) * 100, 4)
    y = rng.integers(0, 2, n)
    with tempfile.TemporaryDirectory() as td:
        lines = []
        for i in range(n):
            lines.append(",".join([str(int(y[i]))] +
                                  [repr(float(v)) for v in X[i]]))
        path = _write(td, "\n".join(lines) + "\n")
        Xn, yn = native.parse_dense(path, ",", 0, False, f + 1)
        Xp, yp = pmod._parse_delimited(
            open(path).readlines(), ",", 0, None)
        np.testing.assert_array_equal(Xn, Xp)
        np.testing.assert_array_equal(yn, yp)


@needs_native
def test_parse_dense_missing_markers_and_header():
    text = ("label\tf0\tf1\tf2\n"
            "1\t0.5\tna\t-3\n"
            "0\t\t2.25e2\tNaN\n"
            "\n"
            "1\tnull\t?\t7\n")
    with tempfile.TemporaryDirectory() as td:
        path = _write(td, text, "data.tsv")
        Xn, yn = native.parse_dense(path, "\t", 0, True, 4)
        assert Xn.shape == (3, 3)
        np.testing.assert_array_equal(yn, [1, 0, 1])
        assert Xn[0, 0] == 0.5 and np.isnan(Xn[0, 1]) and Xn[0, 2] == -3
        assert np.isnan(Xn[1, 0]) and Xn[1, 1] == 225.0 and np.isnan(Xn[1, 2])
        assert np.isnan(Xn[2, 0]) and np.isnan(Xn[2, 1]) and Xn[2, 2] == 7


@needs_native
def test_parse_dense_rejects_ragged_wide_rows():
    """Rows wider than the schema must fall back to the Python parser
    (whose widest-row semantics decide the width)."""
    with tempfile.TemporaryDirectory() as td:
        path = _write(td, "1,2,3\n0,4,5,6\n")
        assert native.parse_dense(path, ",", 0, False, 3) is None


@needs_native
def test_parse_file_native_and_python_agree_end_to_end():
    """parse_file (which now tries native first) against the pure-Python
    parser on the reference's binary example."""
    ref = "/root/reference/examples/binary_classification/binary.train"
    X1, y1 = pmod.parse_file(ref)
    X2, y2 = pmod._parse_delimited(open(ref).readlines(), "\t", 0, None)
    np.testing.assert_array_equal(X1, X2)
    np.testing.assert_array_equal(y1, y2)


@needs_native
def test_encode_bins_matches_python():
    rng = np.random.default_rng(1)
    n, f = 4096, 9
    X = rng.standard_normal((n, f))
    X[rng.random((n, f)) < 0.1] = np.nan   # exercise NaN missing handling
    X[:, 3] = np.round(X[:, 3] * 2)        # few distinct values
    from lightgbm_tpu.config import Config
    ds = BinnedDataset.from_matrix(X, Config({"max_bin": 255}))
    ref_bins = np.zeros_like(np.asarray(ds.bins))
    got = np.asarray(ds.bins)
    mappers = ds.bin_mappers
    # recompute with the pure-Python path; storage layouts must agree
    # (from_matrix used the native encoder when available)
    for j, m in enumerate(mappers):
        if m.is_trivial:
            continue
        ref_bins[j, :n] = m.values_to_bins(X[:, j].astype(np.float64))
    np.testing.assert_array_equal(got[:, :n], ref_bins[:, :n])


@needs_native
def test_encode_bins_declines_a_category_value_past_its_table(monkeypatch):
    """Categorical columns go through the native encoder by a dense table
    from category value to bin; only a value too large for such a table
    (a hashed id) sends the matrix back to the Python path."""
    X = np.abs(np.random.default_rng(2).integers(0, 5, (256, 2))).astype(float)
    from lightgbm_tpu.config import Config
    ds = BinnedDataset.from_matrix(X, Config({"max_bin": 15}),
                                   categorical_feature=[0])
    assert ds.binning["path"] == "native"
    mappers = ds.bin_mappers
    bins_out = np.zeros((2, 256), np.uint8)
    assert native.encode_bins(X, mappers, bins_out) is True
    np.testing.assert_array_equal(bins_out, np.asarray(ds.bins)[:, :256])
    monkeypatch.setattr(native, "CAT_TABLE_MAX", 3)
    assert native.encode_bins(X, mappers, bins_out) is False


def _mixed_matrix(n=6000, seed=4):
    """Four categorical columns (one with NaN, negative, fractional,
    never-sampled and huge values; one whose rare tail folds into the
    last bin; one with category 0 the most frequent; one constant) among
    numerical ones with NaN."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 7))
    X[rng.random((n, 7)) < 0.05] = np.nan
    X[:, 1] = rng.integers(0, 9, n)
    X[rng.random(n) < 0.1, 1] = np.nan
    X[:40, 1] = [-3.0, -0.5, 2.7, 8.2] * 10
    heavy = 1.0 / np.arange(1, 400) ** 2.5
    X[:, 3] = rng.choice(399, n, p=heavy / heavy.sum()) * 3 + 1
    X[:, 4] = np.where(rng.random(n) < 0.7, 0, rng.integers(1, 6, n))
    X[:, 6] = 5.0
    return X, [1, 3, 4, 6]


def _dirtied(X, cats):
    """Rows the mappers never saw: values with no bin, past any table,
    infinite, and NaN in every categorical column."""
    D = X[:64].copy()
    for j in cats:
        D[:9, j] = [1e12, -1e12, np.inf, -np.inf, 400.0, 1e6 + 0.5, -1.0,
                    np.nan, 2 ** 40]
    return D


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("max_bin", [255, 16])
def test_categorical_columns_encode_natively_as_the_python_lookup(
        dtype, max_bin):
    """A matrix of mixed column types goes native whole, and each
    categorical column's bins are the mapper's own Python lookup's: NaN
    by the missing type, negative, unseen, fractional and out-of-table
    values included."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.binning import BIN_TYPE_CATEGORICAL, MISSING_NAN
    from lightgbm_tpu.runtime import tracing
    X, cats = _mixed_matrix()
    X = X.astype(dtype)
    tracing.reset()
    ds = BinnedDataset.from_matrix(X, Config({"max_bin": max_bin}),
                                   categorical_feature=cats)
    if native._load() is not None:
        assert ds.binning["path"] == "native"
    spans = [e for e in tracing.export_chrome()["traceEvents"]
             if e["ph"] == "X" and e["name"] == "dataset/encode_categorical"]
    assert spans and all(e["args"]["columns"] == 3 for e in spans)
    got = np.asarray(ds.bins)
    kinds = set()
    for j, m in enumerate(ds.bin_mappers):
        if m.is_trivial:
            assert j == 6 and not got[j].any()
            continue
        assert (m.bin_type == BIN_TYPE_CATEGORICAL) == (j in cats)
        np.testing.assert_array_equal(
            got[j, :len(X)], m.values_to_bins(X[:, j].astype(np.float64)))
        kinds.add((j in cats, m.missing_type == MISSING_NAN))
    assert {(True, True), (True, False)} <= kinds
    # as a validation set: the training mappers over rows they never saw
    D = _dirtied(X, cats)
    vs = BinnedDataset.from_matrix(D, Config({"max_bin": max_bin}),
                                   bin_mappers=ds.bin_mappers,
                                   categorical_feature=cats)
    assert vs.binning["path"] == ds.binning["path"]
    for j, m in enumerate(ds.bin_mappers):
        if not m.is_trivial:
            np.testing.assert_array_equal(
                np.asarray(vs.bins)[j, :len(D)],
                m.values_to_bins(D[:, j].astype(np.float64)))
    # the rare tail of column 3 shares the last bin
    m3 = ds.bin_mappers[3]
    assert len(np.unique(X[:, 3])) > m3.num_bin
    tracing.reset()


def _values_to_bins_one_by_one(m, values):
    """The per-value dictionary lookup `values_to_bins` was before it was
    vectorised (bin.h ValueToBin:452-487), kept here as its oracle."""
    from lightgbm_tpu.io.binning import MISSING_NAN
    last = max(m.num_bin - 1, 0)
    out = np.full(len(values), last, dtype=np.int32)
    for i, v in enumerate(values):
        if np.isnan(v):
            if m.missing_type != MISSING_NAN:
                out[i] = m.categorical_2_bin.get(0, last)
        elif np.isfinite(v) and int(v) >= 0:
            out[i] = m.categorical_2_bin.get(int(v), last)
    return out


@pytest.mark.parametrize("col", [1, 3, 4])
def test_vectorised_categorical_lookup_matches_the_per_value_one(col):
    from lightgbm_tpu.config import Config
    X, cats = _mixed_matrix(seed=9)
    ds = BinnedDataset.from_matrix(X, Config({"max_bin": 32}),
                                   categorical_feature=cats)
    m = ds.bin_mappers[col]
    probe = np.concatenate([X[:, col], _dirtied(X, cats)[:, col],
                            [-0.999, 0.0, 0.5, 1e300, -1e300, 7.999]])
    np.testing.assert_array_equal(m.values_to_bins(probe),
                                  _values_to_bins_one_by_one(m, probe))


@needs_native
def test_parse_dense_overflow_parity_and_label_guards():
    """1e400 must parse to inf (python float() parity, not NaN); label
    columns outside the schema decline to the Python path; short lines
    that end before the label yield NaN labels."""
    with tempfile.TemporaryDirectory() as td:
        path = _write(td, "1,1e400,2\n0,-1e400,1e-400\n")
        Xn, yn = native.parse_dense(path, ",", 0, False, 3)
        assert np.isposinf(Xn[0, 0]) and np.isneginf(Xn[1, 0])
        assert Xn[1, 1] == 0.0
        assert native.parse_dense(path, ",", 5, False, 3) is None
        assert native.parse_dense(path, ",", -1, False, 3) is None
        path2 = _write(td, "1,2\n0\n3,4\n", "short.csv")
        Xs, ys = native.parse_dense(path2, ",", 1, False, 2)
        np.testing.assert_array_equal(ys[[0, 2]], [2, 4])
        assert np.isnan(ys[1])


@needs_native
def test_parse_dense_declines_text_tokens_and_keeps_sep_only_rows():
    """A real text cell (not a missing marker) declines to the Python
    parser, which raises loudly — silent NaN-corruption is worse than an
    error.  Separator-only lines are data rows of empty fields (the
    pandas-path semantics), not blank lines."""
    with tempfile.TemporaryDirectory() as td:
        p1 = _write(td, "1,red,3\n0,2,4\n")
        assert native.parse_dense(p1, ",", 0, False, 3) is None
        p2 = _write(td, "1\t2\t3\n\t\t\n4\t5\t6\n", "w.tsv")
        X, y = native.parse_dense(p2, "\t", 0, False, 3)
        assert X.shape == (3, 2) and np.isnan(X[1]).all()
        p3 = _write(td, "1," + "1" + "0" * 400 + ",2\n", "o.csv")
        X, _ = native.parse_dense(p3, ",", 0, False, 3)
        assert np.isposinf(X[0, 0])


def test_python_binning_fallback_is_visible_not_silent(monkeypatch):
    """When the native library cannot be built or loaded, binning takes the
    Python loop — minutes at Higgs scale — so the fallback must say so:
    one warning carrying the reason, and the path and its time recorded on
    the dataset (`BinnedDataset.binning`, which chip_smoke.py prints)."""
    from lightgbm_tpu import capi
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import Log

    rng = np.random.default_rng(1)
    X = rng.standard_normal((500, 4))
    config = Config({"max_bin": 31})
    reference = BinnedDataset.from_matrix(X, config)

    def no_compiler():
        raise OSError("make: command not found")

    warnings = []
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_failed", False)
    monkeypatch.setattr(capi, "_lib", None)
    monkeypatch.setattr(capi, "ensure_built", no_compiler)
    monkeypatch.setattr(Log, "warning", staticmethod(
        lambda msg, *a: warnings.append(msg % a)))
    ds = BinnedDataset.from_matrix(X, config)
    again = BinnedDataset.from_matrix(X, config)
    assert ds.binning["path"] == again.binning["path"] == "python"
    assert ds.binning["seconds"] >= 0.0
    assert len(warnings) == 1 and "make: command not found" in warnings[0]
    np.testing.assert_array_equal(ds.bins, reference.bins)


def _find_bin_columns():
    """name -> a column of 5,000 sampled values, one kind of column a
    case."""
    rng = np.random.default_rng(26)
    n = 5000
    cols = {
        "continuous": rng.standard_normal(n),
        "continuous_f32": rng.standard_normal(n).astype(np.float32),
        "few_distinct": rng.integers(-20, 20, n).astype(np.float64),
        "mostly_zeros": np.where(rng.random(n) < 0.93, 0.0,
                                 rng.standard_normal(n)),
        "with_nans": np.where(rng.random(n) < 0.1, np.nan,
                              rng.standard_normal(n)),
        "all_nan": np.full(n, np.nan),
        "constant": np.full(n, 3.25),
        "two_values": (rng.random(n) < 0.3).astype(np.float64),
        "heavy_value": np.where(rng.random(n) < 0.4, 1.5,
                                rng.standard_normal(n)),
        "positive_only": rng.exponential(2.0, n),
        "negative_only": -rng.exponential(2.0, n),
        "tiny_about_zero": rng.standard_normal(n) * 1e-36,
        "with_infs": np.where(rng.random(n) < 0.01, np.inf,
                              rng.standard_normal(n)),
        # numpy's sort decides which zero stands for the run of zeros:
        # the native routine hands such a column back
        "negative_zero": np.where(rng.random(n) < 0.5, -0.0,
                                  rng.integers(0, 3, n).astype(np.float64)),
    }
    return cols


@needs_native
@pytest.mark.parametrize("kwargs", [
    dict(max_bin=63), dict(max_bin=255), dict(max_bin=15, min_data_in_bin=50),
    dict(max_bin=63, zero_as_missing=True),
    dict(max_bin=63, use_missing=False), dict(max_bin=2),
], ids=lambda kw: "-".join("%s=%s" % kv for kv in kw.items()))
@pytest.mark.parametrize("name", sorted(_find_bin_columns()))
def test_native_find_bin_matches_python(name, kwargs):
    """The native find-bin is the Python routine statement for statement:
    byte-equal upper bounds, and every other field of the mapper equal,
    on every kind of column; a column it hands back goes to the Python
    routine (None in its place)."""
    col = _find_bin_columns()[name]
    kw = dict(dict(min_data_in_bin=3, use_missing=True,
                   zero_as_missing=False), **kwargs)
    # a strided view of a wider matrix, rows sampled out of order
    X = np.zeros((len(col) + 100, 3), dtype=col.dtype)
    X[:len(col), 1] = col
    idx = np.random.default_rng(0).permutation(len(col))
    want = BinMapper()
    want.find_bin(col.astype(np.float64), len(col), kw["max_bin"],
                  min_data_in_bin=kw["min_data_in_bin"],
                  use_missing=kw["use_missing"],
                  zero_as_missing=kw["zero_as_missing"])
    got = native.find_bins(X, idx, np.array([1, 0, 1], np.uint8), **kw)
    assert got[0] is None and got[2] is None        # skipped columns
    if name == "negative_zero":
        assert got[1] is None
        return
    got, want = got[1].to_arrays(), want.to_arrays()
    assert got["bin_upper_bound"].tobytes() == want["bin_upper_bound"].tobytes()
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)


@needs_native
def test_find_bins_native_and_python_give_one_dataset(monkeypatch):
    """`from_matrix` through the native find-bin (numerical columns in
    threads, the categorical one and a negative-zero one by the Python
    routine) and, with no library, through the Python routine alone: the
    same mappers and the same bins, and the one span says which path
    ran."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.runtime import tracing
    rng = np.random.default_rng(3)
    X = rng.standard_normal((3000, 12))
    X[:, 4] = rng.integers(0, 9, 3000)
    X[::7, 5] = np.nan
    X[:, 6] = np.where(rng.random(3000) < 0.5, -0.0, 1.0)
    config = Config({"max_bin": 63})

    def construct():
        tracing.reset()
        ds = BinnedDataset.from_matrix(X, config, categorical_feature=[4])
        return ds, [e["args"].get("path")
                    for e in tracing.export_chrome()["traceEvents"]
                    if e["ph"] == "X" and e["name"] == "dataset/find_bins"]

    native_ds, paths = construct()
    assert paths == ["native"]
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_failed", True)
    python_ds, paths = construct()
    assert paths == ["python"]
    np.testing.assert_array_equal(native_ds.bins, python_ds.bins)
    for a, b in zip(native_ds.bin_mappers, python_ds.bin_mappers):
        assert a.to_arrays().keys() == b.to_arrays().keys()
        for key, value in b.to_arrays().items():
            np.testing.assert_array_equal(np.asarray(a.to_arrays()[key]),
                                          np.asarray(value), err_msg=key)
