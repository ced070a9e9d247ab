"""ctypes wrappers for the native ingest fast paths (cpp/ingest.cc).

The reference's loader is native code end to end (dataset_loader.cpp +
parser.cpp + bin.h ValueToBin); these wrappers give the Python loader the
same native parse and bin-encode stages.  Every entry returns None (or
False) on any problem so callers fall back to the tolerant Python
implementations; a library that cannot be built or loaded is reported
once, with the reason, because at Higgs scale the Python bin loop costs
minutes.
"""
from __future__ import annotations

import contextlib
import ctypes
from typing import List, Optional, Tuple

import numpy as np

from ..utils.log import Log

_lib = None
_lib_failed = False


def _load():
    """The ingest symbols live in the same shared library as the
    prediction C API; reuse its build-and-load machinery."""
    global _lib, _lib_failed
    if _lib is None and not _lib_failed:
        try:
            from ..capi import load_lib
            lib = load_lib()
            lib.LGBMT_CountRows.restype = ctypes.c_longlong
            lib.LGBMT_CountRows.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                            ctypes.c_char]
            lib.LGBMT_ParseDense.restype = ctypes.c_int
            lib.LGBMT_ParseDense.argtypes = [
                ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
                ctypes.c_longlong, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_double)]
            lib.LGBMT_EncodeBins.restype = ctypes.c_int
            lib.LGBMT_EncodeBins.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_longlong,
                ctypes.c_int, ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_ubyte), ctypes.c_longlong]
            lib.LGBMT_FindBinsNumerical.restype = ctypes.c_int
            lib.LGBMT_FindBinsNumerical.argtypes = (
                [ctypes.c_void_p, ctypes.c_int, ctypes.c_longlong,
                 ctypes.c_longlong, ctypes.POINTER(ctypes.c_longlong),
                 ctypes.c_longlong, ctypes.c_int,
                 ctypes.POINTER(ctypes.c_ubyte)]
                + [ctypes.c_int] * 5
                + [ctypes.POINTER(ctypes.c_double)]
                + [ctypes.POINTER(ctypes.c_int)] * 3
                + [ctypes.POINTER(ctypes.c_double)] * 3
                + [ctypes.POINTER(ctypes.c_int)])
            _lib = lib
        except Exception as e:      # noqa: BLE001 — no compiler, no make, ...
            _lib_failed = True
            Log.warning("native ingest library unavailable (%s: %s); "
                        "parsing and binning take the Python paths",
                        type(e).__name__, e)
    return _lib


def ptr(a: np.ndarray, ctype):
    """`a`'s buffer as a ctypes pointer to `ctype`."""
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def parse_dense(path: str, sep: str, label_column: int, has_header: bool,
                n_cols: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """mmap + OpenMP parse of a numeric CSV/TSV -> (X [n, n_cols-1], y [n]).
    None when the native library is unavailable or the parse fails."""
    lib = _load()
    if lib is None or n_cols < 2 or not (0 <= label_column < n_cols):
        return None
    try:
        pathb = path.encode()
        n = lib.LGBMT_CountRows(pathb, int(has_header), sep.encode()[:1])
        if n <= 0:
            return None
        X = np.empty((n, n_cols - 1), dtype=np.float64)
        # NaN-filled: short lines that end before the label column leave
        # y rows unwritten (the C side NaN-fills only the feature row)
        y = np.full(n, np.nan, dtype=np.float64)
        rc = lib.LGBMT_ParseDense(
            pathb, sep.encode()[:1], int(has_header),
            ctypes.c_longlong(n), n_cols, label_column,
            X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            y.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        if rc != 0:
            return None
        return X, y
    except Exception:
        return None


#: the longest dense category-value -> bin table `encode_bins` builds for
#: one column (int32 entries); a column whose largest category value is
#: past it (hashed ids) keeps the Python lookup
CAT_TABLE_MAX = 1 << 22


def encode_bins(X: np.ndarray, mappers: List,
                bins_out: np.ndarray) -> bool:
    """Native ValueToBin over the whole matrix into the feature-major
    uint8 storage (bins_out [F, n_stride]): numerical columns by their
    upper bounds, categorical columns by a dense table from category value
    to bin (`BinMapper.categorical_table`).  Returns False (caller keeps
    the Python path) when a non-trivial feature has >256 bins or a
    category value past `CAT_TABLE_MAX`, or the library is missing.
    Trivial features are skipped (their storage stays zeros), matching
    the Python loop.  Where there are categorical columns they are coded
    in a pass of their own, under the span `dataset/encode_categorical`,
    so that a trace tells their cost from the numerical columns'."""
    from ..runtime import tracing
    from .binning import BIN_TYPE_CATEGORICAL
    lib = _load()
    if lib is None or bins_out.dtype != np.uint8:
        return False
    n, F = X.shape
    if F != len(mappers) or bins_out.shape[0] != F or bins_out.shape[1] < n:
        return False
    offs = np.zeros(F, dtype=np.int64)
    cnts = np.zeros(F, dtype=np.int32)
    miss = np.zeros(F, dtype=np.int32)
    nbin = np.zeros(F, dtype=np.int32)
    triv = np.zeros(F, dtype=np.int32)
    cat_len = np.full(F, -1, dtype=np.int32)
    cat_offs = np.zeros(F, dtype=np.int64)
    chunks, tables = [], []
    off = cat_off = 0
    for f, m in enumerate(mappers):
        if m.is_trivial:
            triv[f] = 1
            continue
        if m.num_bin > 256:
            return False
        miss[f] = int(m.missing_type)
        nbin[f] = int(m.num_bin)
        if m.bin_type == BIN_TYPE_CATEGORICAL:
            table = m.categorical_table(CAT_TABLE_MAX)
            if table is None:
                return False
            cat_offs[f] = cat_off
            cat_len[f] = len(table)
            tables.append(table)
            cat_off += len(table)
            continue
        b = np.asarray(m.bin_upper_bound, dtype=np.float64)
        offs[f] = off
        cnts[f] = len(b)
        chunks.append(b)
        off += len(b)
    bounds = (np.concatenate(chunks) if chunks
              else np.zeros(1, dtype=np.float64))
    cat_table = (np.concatenate(tables) if tables
                 else np.zeros(1, dtype=np.int32))
    is_cat = (cat_len >= 0) & (triv == 0)
    # the passes: (columns left out, span or None)
    if is_cat.any() and (~is_cat & (triv == 0)).any():
        passes = [(triv | is_cat, None), (triv | ~is_cat, int(is_cat.sum()))]
    else:
        passes = [(triv, int(is_cat.sum()) or None)]

    # a C-contiguous float32 or float64 matrix is coded where it lies
    # (the library widens a float32 value as it reads it); anything else
    # is converted a block of rows at a time: a whole-matrix
    # ascontiguousarray of a Higgs-scale X would be a multi-GB transient
    in_place = X.dtype in (np.float32, np.float64) and X.flags.c_contiguous
    block = n if in_place else max(1, (1 << 24) // max(F, 1))
    for b0 in range(0, n, block):
        b1 = min(b0 + block, n)
        Xc = X if in_place else np.ascontiguousarray(X[b0:b1],
                                                     dtype=np.float64)
        for skip, n_cat in passes:
            skip = np.ascontiguousarray(skip, dtype=np.int32)
            span = (tracing.span("dataset/encode_categorical",
                                 columns=n_cat, path="native")
                    if n_cat else contextlib.nullcontext())
            with span:
                rc = lib.LGBMT_EncodeBins(
                    ctypes.c_void_p(Xc.ctypes.data),
                    int(Xc.dtype == np.float32), ctypes.c_longlong(b1 - b0),
                    F,
                    ptr(bounds, ctypes.c_double), ptr(offs, ctypes.c_longlong),
                    ptr(cnts, ctypes.c_int), ptr(miss, ctypes.c_int),
                    ptr(nbin, ctypes.c_int), ptr(skip, ctypes.c_int),
                    ptr(cat_len, ctypes.c_int),
                    ptr(cat_offs, ctypes.c_longlong),
                    ptr(cat_table, ctypes.c_int),
                    ptr(bins_out[:, b0:], ctypes.c_ubyte),
                    ctypes.c_longlong(bins_out.shape[1]))
            if rc != 0:
                return False
    return True


def finds_bins(X: np.ndarray) -> bool:
    """Whether `find_bins` takes X: the library is there and X is a
    float32/float64 matrix (it reads the sampled rows in place)."""
    return (X.ndim == 2 and X.dtype in (np.float32, np.float64)
            and _load() is not None)


def find_bins(X: np.ndarray, sample_idx, skip: np.ndarray, max_bin: int,
              min_data_in_bin: int, use_missing: bool,
              zero_as_missing: bool) -> Optional[List]:
    """`BinMapper.find_bin` over the numerical columns of X from the
    sampled rows, in native threads (the Python routine holds the GIL
    through its loop over a column's distinct values, so a thread pool
    round it buys nothing).  Returns a list with a `BinMapper` per column,
    or None in the place of a column left to the Python routine (`skip`
    set, or a sample the native routine hands back); None altogether,
    before any work, unless `finds_bins(X)`.  The upper bounds are
    byte-equal to the Python routine's."""
    from .binning import BIN_TYPE_NUMERICAL, BinMapper
    if not finds_bins(X):
        return None
    lib = _load()
    F = X.shape[1]
    rows = np.ascontiguousarray(sample_idx, dtype=np.int64)
    skip = np.ascontiguousarray(skip, dtype=np.uint8)
    cap = int(max_bin) + 4
    bounds = np.empty((F, cap), dtype=np.float64)
    ints = np.zeros((4, F), dtype=np.int32)     # num_bin, missing, default, status
    dbls = np.zeros((3, F), dtype=np.float64)   # min, max, sparse rate

    rc = lib.LGBMT_FindBinsNumerical(
        ctypes.c_void_p(X.ctypes.data), int(X.dtype == np.float32),
        X.strides[0], X.strides[1], ptr(rows, ctypes.c_longlong), len(rows),
        F, ptr(skip, ctypes.c_ubyte), int(max_bin), int(min_data_in_bin),
        int(use_missing), int(zero_as_missing), cap,
        ptr(bounds, ctypes.c_double), ptr(ints[0], ctypes.c_int),
        ptr(ints[1], ctypes.c_int), ptr(ints[2], ctypes.c_int),
        ptr(dbls[0], ctypes.c_double), ptr(dbls[1], ctypes.c_double),
        ptr(dbls[2], ctypes.c_double), ptr(ints[3], ctypes.c_int))
    assert rc == 0, rc      # the routine has no failure of its own
    mappers: List = []
    for j in range(F):
        if ints[3, j] != 0:
            mappers.append(None)
            continue
        m = BinMapper()
        m.num_bin = int(ints[0, j])
        m.missing_type = int(ints[1, j])
        m.default_bin = int(ints[2, j])
        m.is_trivial = m.num_bin <= 1
        m.bin_type = BIN_TYPE_NUMERICAL
        m.bin_upper_bound = bounds[j, :m.num_bin].copy()
        m.min_val, m.max_val = float(dbls[0, j]), float(dbls[1, j])
        m.sparse_rate = float(dbls[2, j])
        mappers.append(m)
    return mappers
