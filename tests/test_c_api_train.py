"""C training ABI end-to-end (cpp/c_train.cc).

VERDICT r4 #8 / missing #1: the reference's largest un-matched surface was
the C training ABI (c_api.h:48-460 — LGBM_DatasetCreateFromFile/Mat,
LGBM_BoosterCreate/UpdateOneIter[Custom]).  These tests drive the REAL
entry points through ctypes: dataset creation, field setting, training,
eval, rollback, save, and predict-from-the-same-handle, asserting
bit-parity with the Python engine.  A separate test compiles and runs an
actual C program against the shared library (the embedding path an
external integration would take).
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "cpp", "lib_lightgbm_tpu.so")
TRAINLIB = os.path.join(REPO, "cpp", "lib_lightgbm_tpu_train.so")

F32, F64, I32, I64 = 0, 1, 2, 3


def _lib():
    """The TRAIN library handle: its dlopen pulls the base prediction lib
    (DT_NEEDED + $ORIGIN rpath) and registers the dispatch hooks, and
    dlsym through this handle resolves both surfaces."""
    if not (os.path.exists(TRAINLIB) and os.path.exists(LIB)):
        rc = subprocess.run(["make"], cwd=os.path.join(REPO, "cpp"),
                            capture_output=True)
        if rc.returncode != 0:
            pytest.skip("cannot build cpp library: %s"
                        % rc.stderr.decode()[-500:])
    lib = ctypes.CDLL(TRAINLIB)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    return lib


def test_prediction_lib_has_no_python_dependency():
    """The base prediction library must stay dependency-free (the header
    advertises it): no libpython in its dynamic dependencies, and no
    training symbols either."""
    _lib()  # ensure built
    out = subprocess.run(["ldd", LIB], capture_output=True, text=True)
    if out.returncode != 0:
        pytest.skip("ldd unavailable")
    assert "libpython" not in out.stdout
    base = ctypes.CDLL(LIB)
    assert hasattr(base, "LGBM_BoosterPredictForMat")
    assert not hasattr(base, "LGBM_BoosterCreate")


def _err(lib):
    return lib.LGBM_GetLastError().decode()


def _check(lib, rc):
    assert rc == 0, _err(lib)


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(17)
    X = rng.standard_normal((800, 6)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float32)
    return X, y


PARAMS = "objective=binary num_leaves=15 learning_rate=0.1 verbose=-1 " \
         "min_data_in_leaf=20 metric=auc"
PY_PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
             "verbose": -1, "min_data_in_leaf": 20, "metric": "auc"}


def _c_dataset(lib, X, y=None):
    h = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), F32,
        ctypes.c_int32(X.shape[0]), ctypes.c_int32(X.shape[1]),
        1, b"", None, ctypes.byref(h)))
    if y is not None:
        _check(lib, lib.LGBM_DatasetSetField(
            h, b"label", y.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(len(y)), F32))
    return h


def test_c_train_matches_python(problem):
    """Full C lifecycle: Dataset → Booster → 30 updates → eval → save →
    predict, every output identical to the Python engine run with the
    same params."""
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)

    nd = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(ds, ctypes.byref(nd)))
    assert nd.value == len(y)
    nf = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumFeature(ds, ctypes.byref(nf)))
    assert nf.value == X.shape[1]

    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(30):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 30

    # training-set metric through the C eval surface
    out_len = ctypes.c_int()
    res = (ctypes.c_double * 8)()
    _check(lib, lib.LGBM_BoosterGetEval(bst, 0, ctypes.byref(out_len), res))
    assert out_len.value >= 1
    assert 0.5 < res[0] <= 1.0   # train AUC

    # python reference run, identical params
    pybst = lgb.train(dict(PY_PARAMS), lgb.Dataset(X, label=y),
                      num_boost_round=30)

    # model text identical
    slen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, ctypes.c_int64(0), ctypes.byref(slen), None))
    buf = ctypes.create_string_buffer(slen.value)
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, slen, ctypes.byref(slen), buf))
    c_text = buf.value.decode()
    assert c_text.strip() == pybst.model_to_string().strip()

    # predict THROUGH THE TRAINED HANDLE (the native cache path):
    # bit-identical to the python predictions
    n = X.shape[0]
    out = (ctypes.c_double * n)()
    olen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), F32,
        ctypes.c_int32(n), ctypes.c_int32(X.shape[1]), 1, 0, -1, b"",
        ctypes.byref(olen), out))
    assert olen.value == n
    np.testing.assert_allclose(np.frombuffer(out, count=n),
                               pybst.predict(X), rtol=0, atol=1e-12)

    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_eval_counts_and_names(problem):
    """LGBM_BoosterGetEvalCounts / GetEvalNames size and name the
    LGBM_BoosterGetEval buffers (reference c_api pairing)."""
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    n = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetEvalCounts(bst, ctypes.byref(n)))
    assert n.value == 1  # metric=auc

    bufs = [ctypes.create_string_buffer(128) for _ in range(n.value)]
    arr = (ctypes.c_char_p * n.value)(
        *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
    out_n = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetEvalNames(bst, ctypes.byref(out_n), arr))
    assert out_n.value == n.value
    assert bufs[0].value.decode() == "auc"

    # the count sizes GetEval's buffer exactly
    res = (ctypes.c_double * n.value)()
    out_len = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetEval(bst, 0, ctypes.byref(out_len), res))
    assert out_len.value == n.value

    # a prediction-only handle is rejected like the other training calls
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_train_rollback_and_valid(problem):
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    dsv = _c_dataset(lib, X[:200].copy(), y[:200].copy())
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    _check(lib, lib.LGBM_BoosterAddValidData(bst, dsv))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    out_len = ctypes.c_int()
    res = (ctypes.c_double * 8)()
    _check(lib, lib.LGBM_BoosterGetEval(bst, 1, ctypes.byref(out_len), res))
    assert out_len.value >= 1 and 0.5 < res[0] <= 1.0
    _check(lib, lib.LGBM_BoosterRollbackOneIter(bst))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 4
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))
    _check(lib, lib.LGBM_DatasetFree(dsv))


def test_c_train_custom_objective(problem):
    """UpdateOneIterCustom == python update(fobj=) with the same fixed
    gradients (c_api.h:449 parity)."""
    lib = _lib()
    X, y = problem
    rng = np.random.default_rng(3)
    g = rng.standard_normal(len(y)).astype(np.float32)
    h = np.full(len(y), 0.25, np.float32)

    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterUpdateOneIterCustom(
        bst, g.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        h.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(fin)))

    pybst = lgb.Booster(params=dict(PY_PARAMS),
                        train_set=lgb.Dataset(X, label=y))
    pybst.update(fobj=lambda preds, dset: (g.copy(), h.copy()))

    slen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, ctypes.c_int64(0), ctypes.byref(slen), None))
    buf = ctypes.create_string_buffer(slen.value)
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, slen, ctypes.byref(slen), buf))
    assert buf.value.decode().strip() == pybst.model_to_string().strip()
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_train_from_file():
    """LGBM_DatasetCreateFromFile binds the package parser (label column
    0, reference example format)."""
    data = os.path.join("/root/reference/examples/binary_classification",
                        "binary.train")
    if not os.path.exists(data):
        pytest.skip("reference example data unavailable")
    lib = _lib()
    ds = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromFile(
        data.encode(), b"", None, ctypes.byref(ds)))
    nd = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(ds, ctypes.byref(nd)))
    assert nd.value == 7000
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(3):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 3
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def _csr_parts(M, dtype=np.float64, indptr_dtype=np.int64):
    """Explicit entries for nonzeros; absent = 0.0 (reference CSR
    contract)."""
    mask = M != 0.0
    indptr = np.concatenate([[0], np.cumsum(mask.sum(1))]).astype(indptr_dtype)
    indices = np.nonzero(mask)[1].astype(np.int32)
    return indptr, indices, M[mask].astype(dtype)


def test_c_dataset_from_csr_trains_like_python(problem):
    """LGBM_DatasetCreateFromCSR (ISSUE 8): a CSR-created dataset trains
    a model byte-identical to the Python engine fed the equivalent dense
    matrix — absent entries are 0.0."""
    from lightgbm_tpu import capi
    _lib()
    X, y = problem
    Xs = np.asarray(X, np.float64).copy()
    Xs[Xs < 0] = 0.0                 # make it genuinely sparse
    ip, ix, dv = _csr_parts(Xs)
    ds = capi.TrainDataset.from_csr(ip, ix, dv, Xs.shape[1], "verbose=-1")
    ds.set_field("label", y)
    assert ds.num_data == len(y) and ds.num_feature == Xs.shape[1]
    bst = capi.TrainBooster(ds, PARAMS)
    for _ in range(6):
        bst.update()
    py = lgb.train(dict(PY_PARAMS), lgb.Dataset(Xs, label=y),
                   num_boost_round=6)
    assert bst.model_to_string().strip() == py.model_to_string().strip()


def test_c_dataset_from_csc_matches_csr(problem):
    """LGBM_DatasetCreateFromCSC binds the same rows column-wise."""
    from lightgbm_tpu import capi
    _lib()
    X, y = problem
    Xs = np.asarray(X, np.float64).copy()
    Xs[Xs < 0] = 0.0
    maskT = (Xs != 0.0).T
    col_ptr = np.concatenate([[0], np.cumsum(maskT.sum(1))]).astype(np.int64)
    indices = np.nonzero(maskT)[1].astype(np.int32)
    values = Xs.T[maskT]
    ds = capi.TrainDataset.from_csc(col_ptr, indices, values, Xs.shape[0],
                                    "verbose=-1")
    ds.set_field("label", y)
    bst = capi.TrainBooster(ds, PARAMS)
    for _ in range(3):
        bst.update()
    py = lgb.train(dict(PY_PARAMS), lgb.Dataset(Xs, label=y),
                   num_boost_round=3)
    assert bst.model_to_string().strip() == py.model_to_string().strip()


def test_c_create_by_reference_and_push_rows(problem):
    """LGBM_DatasetCreateByReference + PushRows/PushRowsByCSR (ISSUE 8):
    chunks pushed out of order bin with the REFERENCE mappers, and a
    model trained on the pushed dataset is byte-identical to the Python
    engine on a reference-aligned dense dataset of the same rows."""
    from lightgbm_tpu import capi
    _lib()
    X, y = problem
    rng = np.random.default_rng(31)
    X2 = rng.standard_normal((500, X.shape[1]))
    X2[X2 < -0.5] = 0.0
    y2 = (X2[:, 0] > 0).astype(np.float32)

    ref = capi.TrainDataset.from_mat(np.asarray(X, np.float64), "verbose=-1")
    ref.set_field("label", y)
    assert ref.num_data == len(y)    # constructs the reference

    ds = capi.TrainDataset.by_reference(ref, 500)
    ds.push_rows(X2[300:], start_row=300)       # out of order
    ip, ix, dv = _csr_parts(X2[:300], indptr_dtype=np.int32)
    ds.push_rows_csr(ip, ix, dv, X2.shape[1], start_row=0)
    ds.set_field("label", y2)
    assert ds.num_data == 500
    bst = capi.TrainBooster(ds, PARAMS)
    for _ in range(4):
        bst.update()

    pyref = lgb.Dataset(np.asarray(X, np.float64), label=y)
    pyds = lgb.Dataset(X2, label=y2.astype(np.float64), reference=pyref)
    pybst = lgb.Booster(dict(PY_PARAMS), pyds)
    for _ in range(4):
        pybst.update()
    pybst._drain()
    assert bst.model_to_string().strip() == \
        pybst._model.save_model_to_string().strip()


def test_c_get_subset_save_binary_and_feature_names(problem, tmp_path):
    """LGBM_DatasetGetSubset / SaveBinary / Set+GetFeatureNames
    (ISSUE 8): subset shares the parent mappers; a saved binary cache
    reloads through LGBM_DatasetCreateFromFile."""
    from lightgbm_tpu import capi
    _lib()
    X, y = problem
    ds = capi.TrainDataset.from_mat(np.asarray(X, np.float64), "verbose=-1")
    ds.set_field("label", y)

    names = ["feat_%d" % i for i in range(X.shape[1])]
    ds.set_feature_names(names)
    assert ds.get_feature_names() == names

    sub = ds.get_subset(np.arange(0, 600, 3, dtype=np.int32))
    assert sub.num_data == 200
    assert sub.num_feature == X.shape[1]

    bin_path = str(tmp_path / "ds.bin")
    ds.save_binary(bin_path)
    from lightgbm_tpu.io.dataset import BinnedDataset
    assert BinnedDataset.is_binary_file(bin_path)
    reloaded = capi.TrainDataset.from_file(bin_path, "verbose=-1")
    assert reloaded.num_data == len(y)
    assert reloaded.get_feature_names() == names
    # the reloaded cache trains identically to the in-memory dataset
    b1 = capi.TrainBooster(ds, PARAMS)
    b2 = capi.TrainBooster(reloaded, PARAMS)
    for _ in range(3):
        b1.update()
        b2.update()
    assert b1.model_to_string().strip() == b2.model_to_string().strip()


C_PROGRAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include "lightgbm_tpu_c_api.h"

#define CHECK(rc) do { if ((rc) != 0) { \
  fprintf(stderr, "FAIL: %s\n", LGBM_GetLastError()); return 1; } } while (0)

int main(void) {
  int n = 400, f = 4;
  float *X = malloc(sizeof(float) * n * f);
  float *y = malloc(sizeof(float) * n);
  unsigned s = 123456789u;
  for (int i = 0; i < n * f; ++i) {
    s = s * 1103515245u + 12345u;
    X[i] = ((float)(s >> 16) / 32768.0f) - 1.0f;
  }
  for (int i = 0; i < n; ++i) y[i] = X[i * f] > 0.0f ? 1.0f : 0.0f;

  DatasetHandle ds; BoosterHandle bst;
  CHECK(LGBM_DatasetCreateFromMat(X, 0, n, f, 1, "", NULL, &ds));
  CHECK(LGBM_DatasetSetField(ds, "label", y, n, 0));
  CHECK(LGBM_BoosterCreate(ds, "objective=binary num_leaves=7 verbose=-1",
                           &bst));
  int fin;
  for (int i = 0; i < 5; ++i) CHECK(LGBM_BoosterUpdateOneIter(bst, &fin));
  int it;
  CHECK(LGBM_BoosterGetCurrentIteration(bst, &it));
  if (it != 5) { fprintf(stderr, "iteration %d != 5\n", it); return 1; }
  int64_t olen;
  double *out = malloc(sizeof(double) * n);
  CHECK(LGBM_BoosterPredictForMat(bst, X, 0, n, f, 1, 0, -1, "", &olen,
                                  out));
  int good = 0;
  for (int i = 0; i < n; ++i)
    good += ((out[i] > 0.5) == (y[i] > 0.5f));
  printf("C-ABI train+predict ok: acc=%.3f\n", (double)good / n);
  if ((double)good / n < 0.8) return 1;
  CHECK(LGBM_BoosterFree(bst));
  CHECK(LGBM_DatasetFree(ds));
  return 0;
}
"""


def test_c_program_end_to_end(tmp_path):
    """The out-of-process integration path: compile a real C program
    against the shared library and run it with the embedded interpreter
    finding the package through PYTHONPATH."""
    lib = _lib()  # ensures the .so exists
    del lib
    src = tmp_path / "train_demo.c"
    src.write_text(C_PROGRAM)
    exe = tmp_path / "train_demo"
    cc = subprocess.run(
        ["cc", str(src), "-I", os.path.join(REPO, "cpp"),
         TRAINLIB, LIB, "-Wl,-rpath," + os.path.join(REPO, "cpp"),
         "-o", str(exe)], capture_output=True, text=True)
    if cc.returncode != 0:
        pytest.skip("cc unavailable or link failed: " + cc.stderr[-300:])
    env = dict(os.environ)
    site = os.path.dirname(os.path.dirname(np.__file__))
    env["PYTHONPATH"] = os.pathsep.join([REPO, site])
    env["LIGHTGBM_TPU_ROOT"] = REPO
    # CPU platform for the embedded engine
    env["JAX_PLATFORMS"] = "cpu"
    env["LD_LIBRARY_PATH"] = os.path.join(REPO, "cpp") + os.pathsep + \
        env.get("LD_LIBRARY_PATH", "")
    run = subprocess.run([str(exe)], capture_output=True, text=True,
                         env=env, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    assert "C-ABI train+predict ok" in run.stdout


C_PROGRAM_STREAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include <stdint.h>
#include "lightgbm_tpu_c_api.h"

#define CHECK(rc) do { if ((rc) != 0) { \
  fprintf(stderr, "FAIL: %s\n", LGBM_GetLastError()); return 1; } } while (0)

int main(void) {
  int n = 300, f = 4;
  double *X = malloc(sizeof(double) * n * f);
  float *y = malloc(sizeof(float) * n);
  unsigned s = 987654321u;
  for (int i = 0; i < n * f; ++i) {
    s = s * 1103515245u + 12345u;
    X[i] = ((double)(s >> 16) / 32768.0) - 1.0;
    if (X[i] < -0.4) X[i] = 0.0;  /* sparse-ish */
  }
  for (int i = 0; i < n; ++i) y[i] = X[i * f] > 0.0 ? 1.0f : 0.0f;

  /* CSR of the same matrix: absent entries are the zeros */
  int64_t *indptr = malloc(sizeof(int64_t) * (n + 1));
  int32_t *indices = malloc(sizeof(int32_t) * n * f);
  double *vals = malloc(sizeof(double) * n * f);
  int64_t nnz = 0;
  indptr[0] = 0;
  for (int i = 0; i < n; ++i) {
    for (int j = 0; j < f; ++j) {
      if (X[i * f + j] != 0.0) {
        indices[nnz] = j;
        vals[nnz++] = X[i * f + j];
      }
    }
    indptr[i + 1] = nnz;
  }

  DatasetHandle ds, ds2;
  CHECK(LGBM_DatasetCreateFromCSR(indptr, 3, indices, vals, 1,
                                  (int64_t)(n + 1), nnz, (int64_t)f, "",
                                  NULL, &ds));
  CHECK(LGBM_DatasetSetField(ds, "label", y, n, 0));
  int32_t nd;
  CHECK(LGBM_DatasetGetNumData(ds, &nd));
  if (nd != n) { fprintf(stderr, "num_data %d != %d\n", nd, n); return 1; }

  /* streaming: declare 100 rows against the reference, push 2 chunks */
  CHECK(LGBM_DatasetCreateByReference(ds, 100, &ds2));
  CHECK(LGBM_DatasetPushRows(ds2, X + 50 * f, 1, 50, f, 50));
  CHECK(LGBM_DatasetPushRows(ds2, X, 1, 50, f, 0));
  CHECK(LGBM_DatasetSetField(ds2, "label", y, 100, 0));
  CHECK(LGBM_DatasetGetNumData(ds2, &nd));
  if (nd != 100) { fprintf(stderr, "pushed num_data %d\n", nd); return 1; }

  BoosterHandle bst;
  CHECK(LGBM_BoosterCreate(ds, "objective=binary num_leaves=7 verbose=-1",
                           &bst));
  int fin;
  for (int i = 0; i < 4; ++i) CHECK(LGBM_BoosterUpdateOneIter(bst, &fin));

  int64_t olen;
  double *out = malloc(sizeof(double) * n);
  CHECK(LGBM_BoosterPredictForCSR(bst, indptr, 3, indices, vals, 1,
                                  (int64_t)(n + 1), nnz, (int64_t)f, 0, -1,
                                  "", &olen, out));
  int good = 0;
  for (int i = 0; i < n; ++i) good += ((out[i] > 0.5) == (y[i] > 0.5f));
  printf("C-ABI stream ingest ok: acc=%.3f\n", (double)good / n);
  if ((double)good / n < 0.75) return 1;
  CHECK(LGBM_BoosterFree(bst));
  CHECK(LGBM_DatasetFree(ds));
  CHECK(LGBM_DatasetFree(ds2));
  return 0;
}
"""


def test_c_program_stream_ingest(tmp_path):
    """Compiled-C caller for the streaming ingest block (ISSUE 8):
    CreateFromCSR, CreateByReference + out-of-order PushRows, train, and
    CSR predict through the same handle — the integration path a
    feature-store pipeline would take."""
    lib = _lib()
    del lib
    src = tmp_path / "stream_demo.c"
    src.write_text(C_PROGRAM_STREAM)
    exe = tmp_path / "stream_demo"
    cc = subprocess.run(
        ["cc", str(src), "-I", os.path.join(REPO, "cpp"),
         TRAINLIB, LIB, "-Wl,-rpath," + os.path.join(REPO, "cpp"),
         "-o", str(exe)], capture_output=True, text=True)
    if cc.returncode != 0:
        pytest.skip("cc unavailable or link failed: " + cc.stderr[-300:])
    env = dict(os.environ)
    site = os.path.dirname(os.path.dirname(np.__file__))
    env["PYTHONPATH"] = os.pathsep.join([REPO, site])
    env["LIGHTGBM_TPU_ROOT"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["LD_LIBRARY_PATH"] = os.path.join(REPO, "cpp") + os.pathsep + \
        env.get("LD_LIBRARY_PATH", "")
    run = subprocess.run([str(exe)], capture_output=True, text=True,
                         env=env, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    assert "C-ABI stream ingest ok" in run.stdout


def test_concurrent_predict_and_update(problem):
    """Predict-vs-update thread safety (ADVICE r5 medium): the native
    model cache is resynced after every update; readers must hold the
    handle's shared lock so the resync's free cannot pull the Model* out
    from under an in-flight predict.  Hammers predicts from worker
    threads while the main thread keeps updating — ctypes releases the
    GIL around the C calls, so the C-side locking is genuinely
    exercised; a regression shows up as a crash or corrupt output."""
    import threading

    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    n = X.shape[0]
    stop = threading.Event()
    errors = []

    def predict_loop():
        out = (ctypes.c_double * n)()
        olen = ctypes.c_int64()
        while not stop.is_set():
            rc = lib.LGBM_BoosterPredictForMat(
                bst, X.ctypes.data_as(ctypes.c_void_p), F32,
                ctypes.c_int32(n), ctypes.c_int32(X.shape[1]), 1, 0, -1,
                b"", ctypes.byref(olen), out)
            if rc != 0:
                errors.append(_err(lib))
                return
            p = np.frombuffer(out, count=n)
            if not np.isfinite(p).all() or not ((p >= 0) & (p <= 1)).all():
                errors.append("non-probability output under race")
                return

    threads = [threading.Thread(target=predict_loop) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(8):
            _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not errors, errors
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_predict_for_file_on_training_booster(problem, tmp_path):
    """LGBM_BoosterPredictForFile through a TRAINING booster handle: the
    ModelRef seam resolves the train handle to its native model cache
    under the shared lock, so the file fast path serves both booster
    kinds.  Output must match PredictForMat on the same handle exactly."""
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    data_f = str(tmp_path / "d.tsv")
    np.savetxt(data_f, np.column_stack([y, X]).astype(np.float64),
               delimiter="\t", fmt="%.10g")
    out_f = str(tmp_path / "pred.txt")
    _check(lib, lib.LGBM_BoosterPredictForFile(
        bst, data_f.encode(), 0, 0, -1, b"", out_f.encode()))

    # reference: dense predict on the same (re-parsed) values
    from lightgbm_tpu.io.parser import parse_file
    Xp, _ = parse_file(data_f)
    n = Xp.shape[0]
    ref = np.zeros(n, np.float64)
    olen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, np.ascontiguousarray(Xp).ctypes.data_as(ctypes.c_void_p),
        F64, ctypes.c_int32(n), ctypes.c_int32(Xp.shape[1]), 1, 0, -1,
        b"", ctypes.byref(olen),
        ref.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
    np.testing.assert_array_equal(np.loadtxt(out_f), ref)
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_reset_parameter_matches_python(problem):
    """LGBM_BoosterResetParameter (ISSUE 6 satellite): a mid-training
    learning_rate change through the C ABI lands on the next
    UpdateOneIter, producing a model identical to the Python engine
    doing the same reset_parameter at the same iteration."""
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(4):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    _check(lib, lib.LGBM_BoosterResetParameter(bst, b"learning_rate=0.37"))
    for _ in range(4):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    slen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, ctypes.c_int64(0), ctypes.byref(slen), None))
    buf = ctypes.create_string_buffer(slen.value)
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, slen, ctypes.byref(slen), buf))

    pybst = lgb.Booster(dict(PY_PARAMS), lgb.Dataset(X, label=y))
    for _ in range(4):
        pybst.update()
    pybst.reset_parameter({"learning_rate": 0.37})
    for _ in range(4):
        pybst.update()
    pybst._drain()                      # the async pipeline may still hold
    assert buf.value.decode().strip() == \
        pybst._model.save_model_to_string().strip()

    # a prediction-only (loaded) booster must refuse the training call
    h2 = ctypes.c_void_p()
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterLoadModelFromString(
        buf.value, ctypes.byref(it), ctypes.byref(h2)))
    assert lib.LGBM_BoosterResetParameter(h2, b"learning_rate=0.5") != 0
    assert b"training booster" in lib.LGBM_GetLastError()
    _check(lib, lib.LGBM_BoosterFree(h2))
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_refit_matches_python(problem):
    """LGBM_BoosterRefit (ISSUE 6 satellite): refit to a new window
    through the C ABI keeps every split, replaces the handle's model in
    place, and matches Booster.refit on the same data byte-for-byte —
    the same engine path the online trainer's refit mode drives."""
    lib = _lib()
    X, y = problem
    rng = np.random.default_rng(23)
    X2 = X + 0.05 * rng.standard_normal(X.shape).astype(np.float32)
    y2 = (X2[:, 0] + 0.4 * X2[:, 1] > 0.1).astype(np.float32)

    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(6):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    # python reference: the same training run, then refit
    pybst = lgb.Booster(dict(PY_PARAMS), lgb.Dataset(X, label=y))
    for _ in range(6):
        pybst.update()
    py_refit = pybst.refit(np.asarray(X2, np.float64), y2.astype(np.float64))

    from lightgbm_tpu import capi
    capi.booster_refit(bst, np.asarray(X2, np.float64), y2)

    slen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, ctypes.c_int64(0), ctypes.byref(slen), None))
    buf = ctypes.create_string_buffer(slen.value)
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, slen, ctypes.byref(slen), buf))
    assert buf.value.decode().strip() == \
        py_refit._model.save_model_to_string().strip()

    # the refit model serves predictions through the SAME handle
    n = X2.shape[0]
    out = np.zeros(n, np.float64)
    olen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, np.ascontiguousarray(X2, np.float64).ctypes.data_as(
            ctypes.c_void_p),
        F64, ctypes.c_int32(n), ctypes.c_int32(X2.shape[1]), 1, 0, -1,
        b"", ctypes.byref(olen),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
    np.testing.assert_allclose(out, py_refit.predict(X2),
                               rtol=0, atol=1e-12)
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_c_inner_predict_buffer_trio(problem):
    """ISSUE 11 ABI completion: LGBM_BoosterCalcNumPredict sizes output
    buffers on both booster kinds, and GetNumPredict/GetPredict read the
    engine's incrementally-maintained train/valid scores (objective
    transform applied, class-major GetPredictAt layout) without a
    re-predict.  The engine keeps scores in f32 on device, so parity
    with the offline f64 predict holds to f32 precision."""
    lib = _lib()
    X, y = problem
    ds = _c_dataset(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds, PARAMS.encode(),
                                       ctypes.byref(bst)))
    vX, vy = X[:100], y[:100]
    dv = _c_dataset(lib, vX, vy)
    _check(lib, lib.LGBM_BoosterAddValidData(bst, dv))
    fin = ctypes.c_int()
    for _ in range(8):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    # CalcNumPredict arithmetic: num_class width + leaf-index width
    out64 = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(10), 0, -1, ctypes.byref(out64)))
    assert out64.value == 10
    _check(lib, lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(10), 2, -1, ctypes.byref(out64)))
    assert out64.value == 80                 # 10 rows * 8 trees
    _check(lib, lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(10), 2, 3, ctypes.byref(out64)))
    assert out64.value == 30
    assert lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(10), 7, -1, ctypes.byref(out64)) != 0

    # GetNumPredict sizes; GetPredict matches an offline predict to f32
    n_train = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterGetNumPredict(bst, 0,
                                              ctypes.byref(n_train)))
    assert n_train.value == len(X)
    n_valid = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterGetNumPredict(bst, 1,
                                              ctypes.byref(n_valid)))
    assert n_valid.value == len(vX)
    buf = np.zeros(n_train.value, np.float64)
    olen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterGetPredict(
        bst, 0, ctypes.byref(olen),
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
    assert olen.value == len(X)
    # model text -> offline python predict = the f64 oracle
    slen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, 0, ctypes.byref(slen), None))
    sbuf = ctypes.create_string_buffer(slen.value)
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, -1, slen.value, ctypes.byref(slen), sbuf))
    pyb = lgb.Booster(model_str=sbuf.value.decode())
    np.testing.assert_allclose(buf, pyb.predict(X, device=False),
                               rtol=1e-5, atol=1e-6)
    vbuf = np.zeros(n_valid.value, np.float64)
    _check(lib, lib.LGBM_BoosterGetPredict(
        bst, 1, ctypes.byref(olen),
        vbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_double))))
    np.testing.assert_allclose(vbuf, pyb.predict(vX, device=False),
                               rtol=1e-5, atol=1e-6)
    # out-of-range valid index and loaded boosters fail cleanly
    assert lib.LGBM_BoosterGetPredict(
        bst, 3, ctypes.byref(olen),
        vbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) != 0
    loaded = ctypes.c_void_p()
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterLoadModelFromString(
        sbuf.value, ctypes.byref(it), ctypes.byref(loaded)))
    assert lib.LGBM_BoosterGetNumPredict(
        loaded, 0, ctypes.byref(olen)) != 0
    assert "training boosters" in str(_err(lib))
    _check(lib, lib.LGBM_BoosterCalcNumPredict(       # Calc works on both
        loaded, ctypes.c_int(5), 1, -1, ctypes.byref(out64)))
    assert out64.value == 5
    _check(lib, lib.LGBM_BoosterFree(loaded))
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(dv))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_capi_wrapper_inner_predict(problem):
    """The capi.py wrappers over the trio (TrainBooster.num_predict /
    get_predict / calc_num_predict, NativeBooster.calc_num_predict)."""
    from lightgbm_tpu import capi
    X, y = problem
    ds = capi.TrainDataset.from_mat(X, PARAMS).set_field("label", y)
    bst = capi.TrainBooster(ds, PARAMS)
    for _ in range(4):
        bst.update()
    assert bst.calc_num_predict(16) == 16
    assert bst.calc_num_predict(16, capi.C_API_PREDICT_LEAF_INDEX) == 64
    assert bst.num_predict(0) == len(X)
    inner = bst.get_predict(0)
    pyb = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(inner, pyb.predict(X, device=False),
                               rtol=1e-5, atol=1e-6)
    nb = capi.NativeBooster(model_str=bst.model_to_string())
    assert nb.calc_num_predict(3) == 3
    assert nb.calc_num_predict(3, capi.C_API_PREDICT_LEAF_INDEX) == 12


def test_dataset_dump_text_matches_binned_storage(problem, tmp_path):
    """LGBM_DatasetDumpText (ISSUE 12 ABI satellite): the dump's header
    must describe the dataset and its bin matrix must equal the binned
    storage the Python pipeline produces for the same rows."""
    from lightgbm_tpu import capi
    from lightgbm_tpu.basic import Dataset
    from lightgbm_tpu.config import Config
    X, y = problem
    ds = capi.TrainDataset.from_mat(X.astype(np.float64), "verbose=-1")
    ds.set_field("label", y)
    out = str(tmp_path / "dump.txt")
    ds.dump_text(out)
    lines = open(out).read().splitlines()
    head = dict(ln.split(": ", 1) for ln in lines[:6])
    assert head["num_data"] == str(X.shape[0])
    assert head["num_features"] == str(X.shape[1])
    assert head["has_label"] == "1"
    body_at = lines.index("bin_data:") + 1
    dumped = np.loadtxt(lines[body_at:], dtype=np.int64)
    assert dumped.shape[0] == X.shape[0]
    # same rows through the Python pipeline: identical binned storage
    pyds = Dataset(X.astype(np.float64), label=y, params={"verbose": -1})
    pyds.construct(Config({"verbose": -1}))
    ref = pyds.binned.bins[:, : pyds.binned.num_data].T.astype(np.int64)
    np.testing.assert_array_equal(dumped, ref)


def test_dataset_dump_text_rejects_non_dataset_handle(problem):
    from lightgbm_tpu import capi
    lib = capi.load_train_lib()
    rc = lib.LGBM_DatasetDumpText(None, b"/tmp/nope.txt")
    assert rc != 0
