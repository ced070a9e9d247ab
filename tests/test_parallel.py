"""Distributed-training tests on the 8-device virtual CPU mesh.

SURVEY.md §4: parity tests compare serial vs data-parallel outputs — the
reference guarantees identical trees modulo float reduction order
(docs/Parallel-Learning-Guide.rst); here the collectives actually execute
across 8 host devices via shard_map.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from lightgbm_tpu.boosting.gbdt import _feature_meta_device
from lightgbm_tpu.boosting.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.parallel.data_parallel import (
    DATA_AXIS, make_data_parallel_train_step, shard_rows)

NDEV = 8


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    if len(devices) < NDEV:
        pytest.skip("needs %d devices (run with xla_force_host_platform_device_count)" % NDEV)
    return Mesh(np.array(devices[:NDEV]), (DATA_AXIS,))


def _problem(n=1024, f=6, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] > 0.2) ^ (X[:, 1] < -0.1)).astype(np.float32)
    return X, y


def test_data_parallel_matches_serial(mesh):
    n = 128 * NDEV
    X, y = _problem(n=n)
    config = Config({"objective": "binary", "max_bin": 32, "num_leaves": 16,
                     "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, config, row_chunk=n)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    gcfg = GrowerConfig(num_leaves=16, max_depth=-1, lambda_l1=0.0, lambda_l2=0.0,
                        max_delta_step=0.0, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad // NDEV)

    label = ds.padded(y)
    score = np.zeros(n_pad, np.float32)
    weight = np.ones(n_pad, np.float32)
    mask = ds.valid_row_mask()
    fmask = jnp.ones(ds.num_features, bool)

    # serial reference
    grow = make_tree_grower(meta, GrowerConfig(**{**gcfg._asdict(), "row_chunk": n_pad}),
                            ds.max_num_bin)
    yy = np.where(label > 0, 1.0, -1.0)
    resp = -yy / (1.0 + np.exp(yy * score))
    grad = (resp * weight).astype(np.float32)
    hess = (np.abs(resp) * (1 - np.abs(resp)) * weight).astype(np.float32)
    vals = jnp.asarray(np.stack([grad * mask, hess * mask, mask], axis=1))
    serial = grow(jnp.asarray(ds.bins), vals, fmask)

    # data-parallel across 8 devices
    step = make_data_parallel_train_step(meta, gcfg, ds.max_num_bin, mesh,
                                         learning_rate=0.1)
    bins_s, score_s, label_s, weight_s, mask_s = shard_rows(
        mesh, ds.bins, score, label, weight, mask)
    new_score, tree = step(bins_s, score_s, label_s, weight_s, mask_s, fmask)

    assert int(tree["num_leaves"]) == int(serial["num_leaves"])
    np.testing.assert_array_equal(np.asarray(tree["split_feature"]),
                                  np.asarray(serial["split_feature"]))
    np.testing.assert_array_equal(np.asarray(tree["split_bin"]),
                                  np.asarray(serial["split_bin"]))
    np.testing.assert_allclose(np.asarray(tree["leaf_value"]),
                               np.asarray(serial["leaf_value"]), rtol=1e-4, atol=1e-6)
    # score update consistency: new_score - score == lr * leaf outputs
    delta = np.asarray(new_score) - score
    assert np.isfinite(delta).all() and (np.abs(delta) > 0).any()


def test_dryrun_multichip_entry():
    import __graft_entry__ as g
    if len(jax.devices()) < NDEV:
        pytest.skip("needs %d devices" % NDEV)
    g.dryrun_multichip(NDEV)


def test_entry_compiles():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert int(out["num_leaves"]) >= 2


def test_feature_parallel_matches_serial():
    from lightgbm_tpu.parallel.feature_parallel import (
        FEATURE_AXIS, make_feature_parallel_train_step, pad_feature_meta,
        pad_features, shard_features)
    devices = jax.devices()
    if len(devices) < NDEV:
        pytest.skip("needs %d devices" % NDEV)
    fmesh = Mesh(np.array(devices[:NDEV]), (FEATURE_AXIS,))
    n = 1024
    X, y = _problem(n=n, f=6)
    config = Config({"objective": "binary", "max_bin": 32, "num_leaves": 16,
                     "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, config, row_chunk=n)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    gcfg = GrowerConfig(num_leaves=16, max_depth=-1, lambda_l1=0.0, lambda_l2=0.0,
                        max_delta_step=0.0, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad)

    label = ds.padded(y)
    score = np.zeros(n_pad, np.float32)
    weight = np.ones(n_pad, np.float32)
    mask = ds.valid_row_mask()
    fmask = np.ones(ds.num_features, bool)

    # serial reference tree
    grow = make_tree_grower(meta, gcfg, ds.max_num_bin)
    yy = np.where(label > 0, 1.0, -1.0)
    resp = -yy / (1.0 + np.exp(yy * score))
    grad = (resp * weight).astype(np.float32)
    hess = (np.abs(resp) * (1 - np.abs(resp)) * weight).astype(np.float32)
    vals = jnp.asarray(np.stack([grad * mask, hess * mask, mask], axis=1))
    serial = grow(jnp.asarray(ds.bins), vals, jnp.asarray(fmask))

    bins_p, fmask_p, f_padded = pad_features(ds.bins, fmask, NDEV)
    meta_p = pad_feature_meta(meta, f_padded)
    step = make_feature_parallel_train_step(meta_p, gcfg, ds.max_num_bin,
                                            fmesh, learning_rate=0.1)
    bins_s, fmask_s, score_s, label_s, weight_s, mask_s = shard_features(
        fmesh, bins_p, fmask_p, score, label, weight, mask)
    new_score, tree = step(bins_s, score_s, label_s, weight_s, mask_s, fmask_s)

    assert int(tree["num_leaves"]) == int(serial["num_leaves"])
    np.testing.assert_array_equal(np.asarray(tree["split_feature"]),
                                  np.asarray(serial["split_feature"]))
    np.testing.assert_array_equal(np.asarray(tree["split_bin"]),
                                  np.asarray(serial["split_bin"]))
    np.testing.assert_allclose(np.asarray(tree["leaf_value"]),
                               np.asarray(serial["leaf_value"]), rtol=1e-4, atol=1e-6)


def test_voting_parallel_matches_serial_with_full_vote(mesh):
    """With 2*top_k >= F the voted subset covers every feature, so the voting
    learner must reproduce the serial tree exactly."""
    from lightgbm_tpu.parallel.voting_parallel import make_voting_parallel_train_step
    n = 128 * NDEV
    X, y = _problem(n=n, f=6)
    config = Config({"objective": "binary", "max_bin": 32, "num_leaves": 16,
                     "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, config, row_chunk=n)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    gcfg = GrowerConfig(num_leaves=16, max_depth=-1, lambda_l1=0.0, lambda_l2=0.0,
                        max_delta_step=0.0, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad // NDEV)
    label = ds.padded(y)
    score = np.zeros(n_pad, np.float32)
    weight = np.ones(n_pad, np.float32)
    mask = ds.valid_row_mask()
    fmask = jnp.ones(ds.num_features, bool)

    grow = make_tree_grower(meta, GrowerConfig(**{**gcfg._asdict(), "row_chunk": n_pad}),
                            ds.max_num_bin)
    yy = np.where(label > 0, 1.0, -1.0)
    resp = -yy / (1.0 + np.exp(yy * score))
    grad = (resp * weight).astype(np.float32)
    hess = (np.abs(resp) * (1 - np.abs(resp)) * weight).astype(np.float32)
    vals = jnp.asarray(np.stack([grad * mask, hess * mask, mask], axis=1))
    serial = grow(jnp.asarray(ds.bins), vals, fmask)

    step = make_voting_parallel_train_step(meta, gcfg, ds.max_num_bin, mesh,
                                           learning_rate=0.1, top_k=6)
    bins_s, score_s, label_s, weight_s, mask_s = shard_rows(
        mesh, ds.bins, score, label, weight, mask)
    new_score, tree = step(bins_s, score_s, label_s, weight_s, mask_s, fmask)

    assert int(tree["num_leaves"]) == int(serial["num_leaves"])
    np.testing.assert_array_equal(np.asarray(tree["split_feature"]),
                                  np.asarray(serial["split_feature"]))
    np.testing.assert_allclose(np.asarray(tree["leaf_value"]),
                               np.asarray(serial["leaf_value"]), rtol=1e-4, atol=1e-6)


def test_voting_parallel_restricted_vote_trains(mesh):
    """With a tight vote budget (2k < F) the tree may differ from serial but
    must still be a valid, finite, multi-leaf tree."""
    from lightgbm_tpu.parallel.voting_parallel import make_voting_parallel_train_step
    n = 128 * NDEV
    X, y = _problem(n=n, f=12, seed=9)
    config = Config({"objective": "binary", "max_bin": 32, "num_leaves": 8,
                     "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, config, row_chunk=n)
    meta = _feature_meta_device(ds)
    n_pad = ds.num_data_padded
    gcfg = GrowerConfig(num_leaves=8, max_depth=-1, lambda_l1=0.0, lambda_l2=0.0,
                        max_delta_step=0.0, min_data_in_leaf=5,
                        min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                        row_chunk=n_pad // NDEV)
    step = make_voting_parallel_train_step(meta, gcfg, ds.max_num_bin, mesh,
                                           learning_rate=0.1, top_k=2)
    label = ds.padded(y)
    score = np.zeros(n_pad, np.float32)
    weight = np.ones(n_pad, np.float32)
    mask = ds.valid_row_mask()
    bins_s, score_s, label_s, weight_s, mask_s = shard_rows(
        mesh, ds.bins, score, label, weight, mask)
    new_score, tree = step(bins_s, score_s, label_s, weight_s, mask_s,
                           jnp.ones(ds.num_features, bool))
    assert int(tree["num_leaves"]) > 1
    assert np.isfinite(np.asarray(tree["leaf_value"])).all()
    assert np.isfinite(np.asarray(new_score)).all()


def test_entry_binds_no_platform():
    """Calling entry() must neither create a device array nor run jitted
    code: a process that touches JAX takes the chip, and the caller decides
    when.  Pinned by running entry() under a platform name that cannot
    initialize: any platform binding inside entry() fails loudly, an
    entry() that stays off JAX returns NumPy example args and succeeds."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_platform"
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "assert all(isinstance(a, np.ndarray) for a in args), args\n"
        "print('ENTRY_OK')\n" % repo)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENTRY_OK" in r.stdout


def test_dryrun_child_is_pinned_to_the_cpu(monkeypatch):
    """The dryrun child must come up on the virtual CPU mesh whatever
    platform the parent's environment names (a CPU-only child is safe while
    the parent holds the chip)."""
    import __graft_entry__ as g

    seen = {}

    def fake_run(cmd, cwd=None, env=None, timeout=None):
        seen.update(env=env, cmd=cmd)

        class R:
            returncode = 0
        return R()

    import subprocess
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(subprocess, "run", fake_run)
    g.dryrun_multichip(4)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"
    assert "_dryrun_multichip_impl(4" in seen["cmd"][-1]


def test_dryrun_stage_lines_carry_wallclock(capsys):
    """Every dryrun stage line must carry a wall-clock timestamp so a red
    run shows where (and for how long) it stalled."""
    import re

    import __graft_entry__ as g

    wd = g._make_watchdog(seconds=30, hard=False)
    wd("probe stage")
    wd.done()
    out = capsys.readouterr().out
    assert re.search(r"^\[\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\] "
                     r"dryrun stage: probe stage \(budget 30s\)$", out,
                     re.M), out
