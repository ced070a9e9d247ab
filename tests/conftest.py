"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding tests use
xla_force_host_platform_device_count=8 so shard_map collectives execute for
real across 8 host devices (SURVEY.md §4: distributed testing without a
cluster).
"""
import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

# Tests run on the 8-device virtual CPU mesh whatever the environment's
# default platform is; the chip is checked by chip_smoke.py, not by pytest.
import jax
jax.config.update("jax_platforms", "cpu")
# persistent XLA compilation cache through the product seam: repeated
# pytest runs skip recompiles.  The directory is $JAX_COMPILATION_CACHE_DIR
# when set, else the fixed <repo>/.jax_cache.
from lightgbm_tpu.runtime import warmup
# min_compile_s=1.0: the suite compiles thousands of tiny programs —
# persisting only >=1s compiles keeps the wall time flat while the
# expensive programs still carry across runs.  Services keep the seam
# default of 0 (a warm start recompiles nothing).
warmup.enable_compile_cache(min_compile_s=1.0)

def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; slow covers multi-process launches
    # and full bench-scale parity runs
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 suite (-m 'not slow')")


REFERENCE_DIR = "/root/reference"
GOLDEN_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          ".golden")
_NPY_CACHE = "/tmp/lgbtpu_data_cache"


def load_svmlight_style(path):
    """Load the reference example TSV files: first column label, rest features.
    Parsed arrays are cached as .npy keyed by path."""
    os.makedirs(_NPY_CACHE, exist_ok=True)
    import hashlib
    key = hashlib.sha1(path.encode()).hexdigest()[:16] + ".npy"
    cached = os.path.join(_NPY_CACHE, key)
    if os.path.exists(cached) and os.path.getmtime(cached) >= os.path.getmtime(path):
        data = np.load(cached)
    else:
        data = np.loadtxt(path)
        np.save(cached, data)
    return data[:, 1:], data[:, 0]


@pytest.fixture(scope="session")
def binary_data():
    X_train, y_train = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/binary_classification/binary.train"))
    X_test, y_test = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/binary_classification/binary.test"))
    return X_train, y_train, X_test, y_test


@pytest.fixture(scope="session")
def regression_data():
    X_train, y_train = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/regression/regression.train"))
    X_test, y_test = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/regression/regression.test"))
    return X_train, y_train, X_test, y_test


def load_libsvm(path, num_features=None):
    """Sparse LibSVM `label idx:val ...` loader (reference lambdarank data)."""
    os.makedirs(_NPY_CACHE, exist_ok=True)
    import hashlib
    key = hashlib.sha1(("%s|libsvm|%s" % (path, num_features)).encode()).hexdigest()[:16] + ".npz"
    cached = os.path.join(_NPY_CACHE, key)
    if os.path.exists(cached) and os.path.getmtime(cached) >= os.path.getmtime(path):
        d = np.load(cached)
        return d["X"], d["y"]
    rows = []
    labels = []
    maxf = 0
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            if not parts:
                continue
            labels.append(float(parts[0]))
            feats = {}
            for tok in parts[1:]:
                k, v = tok.split(":")
                feats[int(k)] = float(v)
                maxf = max(maxf, int(k))
            rows.append(feats)
    nf = num_features or (maxf + 1)
    X = np.zeros((len(rows), nf))
    for i, feats in enumerate(rows):
        for k, v in feats.items():
            X[i, k] = v
    y = np.asarray(labels)
    np.savez(cached, X=X, y=y)
    return X, y


@pytest.fixture(scope="session")
def rank_data():
    X_train, y_train = load_libsvm(
        os.path.join(REFERENCE_DIR, "examples/lambdarank/rank.train"))
    X_test, y_test = load_libsvm(
        os.path.join(REFERENCE_DIR, "examples/lambdarank/rank.test"),
        num_features=X_train.shape[1])
    q_train = np.loadtxt(
        os.path.join(REFERENCE_DIR, "examples/lambdarank/rank.train.query"))
    q_test = np.loadtxt(
        os.path.join(REFERENCE_DIR, "examples/lambdarank/rank.test.query"))
    return X_train, y_train, q_train, X_test, y_test, q_test


@pytest.fixture(scope="session")
def multiclass_data():
    X_train, y_train = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/multiclass_classification/multiclass.train"))
    X_test, y_test = load_svmlight_style(
        os.path.join(REFERENCE_DIR, "examples/multiclass_classification/multiclass.test"))
    return X_train, y_train, X_test, y_test


# shared with chip_smoke.py, which applies the same rule on the chip
from lightgbm_tpu.models.gbdt_model import assert_models_equivalent  # noqa: E402,F401
