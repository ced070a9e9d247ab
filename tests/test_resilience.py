"""Fault-tolerant execution runtime (runtime/resilience.py, ISSUE 4).

Every behavior is exercised through the LGBM_TPU_FAULT injection harness:
watchdogged stages, atomic checksummed snapshots,
preemption-safe resume (byte-identical models across a kill/resume
boundary, incl. bagging/DART RNG state), corrupt-snapshot fallback, and
the non-finite sentinel's abort-vs-rollback policy.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt_model import GBDTModel
from lightgbm_tpu.runtime import resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# unit: fault spec, backoff, snapshot file format
# ---------------------------------------------------------------------------

def test_fault_spec_parsing(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FAULT",
                       "slow_predict:30,die_at_iter:7,corrupt_snapshot")
    assert resilience.fault_active("slow_predict")
    assert resilience.fault_arg("die_at_iter") == "7"
    assert resilience.fault_arg("corrupt_snapshot", "x") == "x"
    assert not resilience.fault_active("nan_grad")
    monkeypatch.setenv("LGBM_TPU_FAULT", "explode_reactor")
    with pytest.raises(ValueError, match="unknown fault"):
        resilience.fault_active("slow_predict")


def test_backoff_is_bounded_jittered_deterministic():
    d1 = resilience.backoff_delays(4, base=1.0, cap=3.0, seed=5)
    d2 = resilience.backoff_delays(4, base=1.0, cap=3.0, seed=5)
    assert d1 == d2 and len(d1) == 3
    assert all(0.4 <= d <= 3.0 for d in d1)
    assert resilience.backoff_delays(4, seed=1) != resilience.backoff_delays(4, seed=2)


def test_atomic_write_and_snapshot_validation(tmp_path):
    path = str(tmp_path / "m.txt.snapshot_iter_2")
    body = resilience._with_footer("tree\nnum_leaves=2\n", {"total_iter": 2})
    resilience.atomic_write(path, body)
    assert resilience.validate_snapshot(path) == (True, "ok")
    assert resilience.load_snapshot_state(path)["total_iter"] == 2
    # no stray tmp files from the atomic write
    assert [f for f in os.listdir(tmp_path)] == ["m.txt.snapshot_iter_2"]
    # truncation (torn write) and bit flips both fail the checksum
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[: len(raw) // 2])
    ok, reason = resilience.validate_snapshot(path)
    assert not ok
    flipped = raw.replace(b"num_leaves=2", b"num_leaves=3")
    open(path, "wb").write(flipped)
    ok, reason = resilience.validate_snapshot(path)
    assert not ok and "checksum" in reason
    # a plain model file without a footer is not a valid snapshot
    open(path, "w").write("tree\nnum_leaves=2\n")
    assert not resilience.validate_snapshot(path)[0]


def test_snapshot_retention_keeps_last_k(tmp_path):
    X, y = _data()
    bst = lgb.Booster({"objective": "binary", "verbose": -1},
                      lgb.Dataset(X, label=y))
    out = str(tmp_path / "m.txt")
    for i in range(5):
        bst.update()
        resilience.write_snapshot(bst, out, retention=2)
    snaps = resilience.snapshot_paths(out)
    assert [it for it, _ in snaps] == [5, 4]
    # the kept snapshots are valid and loadable as models
    for _, p in snaps:
        assert resilience.validate_snapshot(p)[0]
        assert GBDTModel.load_model(p).current_iteration > 0


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_soft_timeout_names_culprit_and_dumps_threads(tmp_path):
    report = str(tmp_path / "stages.json")
    wd = resilience.Watchdog(1, hard=False, report_path=report,
                             label="test stage")
    wd("fast stage", seconds=30)
    wd("stuck stage", seconds=1)
    with pytest.raises(resilience.StageTimeout, match="stuck stage"):
        time.sleep(5)
    wd.done()
    rep = json.load(open(report))
    assert rep["culprit"] == "stuck stage"
    assert [s["name"] for s in rep["stages"]] == ["fast stage", "stuck stage"]
    assert all("t_start" in s for s in rep["stages"])
    # faulthandler tracebacks of this (main) thread are in the report
    assert "test_watchdog_soft_timeout" in rep["tracebacks"]


def test_watchdog_stage_scope_records_errors(tmp_path):
    wd = resilience.Watchdog(30, hard=False,
                             report_path=str(tmp_path / "r.json"))
    with wd.stage_scope("good"):
        pass
    with pytest.raises(RuntimeError):
        with wd.stage_scope("bad"):
            raise RuntimeError("boom")
    rep = json.load(open(tmp_path / "r.json"))
    by_name = {s["name"]: s["status"] for s in rep["stages"]}
    assert by_name == {"good": "ok", "bad": "error"}
    assert rep["culprit"] == "bad"


# ---------------------------------------------------------------------------
# snapshot / resume: byte-identical continuation
# ---------------------------------------------------------------------------

def _data(n=400, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float64)
    return X, y


def _cli(tmpdir, args, fault=None, check=True):
    """Run the CLI in a subprocess (abrupt-death faults use os._exit, so
    in-process is not an option) on the CPU platform; the CLI turns on
    the same compile cache the suite uses."""
    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULT", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    if fault:
        env["LGBM_TPU_FAULT"] = fault
    r = subprocess.run([sys.executable, "-m", "lightgbm_tpu"] + args,
                       cwd=str(tmpdir), env=env, timeout=240,
                       capture_output=True, text=True)
    if check and r.returncode != 0:
        raise AssertionError("CLI rc=%d\nstdout:%s\nstderr:%s"
                             % (r.returncode, r.stdout[-2000:],
                                r.stderr[-2000:]))
    return r


_TRAIN_ARGS = ["task=train", "objective=binary", "num_trees=8",
               "num_leaves=15", "bagging_freq=2", "bagging_fraction=0.7",
               "feature_fraction=0.8", "seed=7", "verbose=-1"]


@pytest.fixture(scope="module")
def cli_resume_runs(tmp_path_factory):
    """One shared set of CLI runs: uninterrupted baseline, a run killed
    abruptly at iteration 5 with its newest snapshot corrupted, and the
    resumed continuation.  Several tests assert on the artifacts."""
    d = tmp_path_factory.mktemp("resume")
    X, y = _data()
    data = np.column_stack([y, X])
    np.savetxt(d / "train.tsv", data, delimiter="\t", fmt="%.8g")
    common = _TRAIN_ARGS + ["data=train.tsv"]

    # A: uninterrupted 8 iterations (snapshots on, same schedule)
    _cli(d, common + ["output_model=a.txt", "snapshot_freq=2"])
    # B: dies abruptly (os._exit 137) entering iteration 5; the newest
    # surviving snapshot (iter 4) is corrupted by a torn-write fault
    r_crash = _cli(d, common + ["output_model=b.txt", "snapshot_freq=2"],
                   fault="die_at_iter:5,corrupt_snapshot:4", check=False)
    # validity as the resume run will find it (it re-writes snapshots
    # at 4/6/8 afterwards, overwriting the corrupt one)
    post_crash = {
        "model_written": (d / "b.txt").exists(),
        "ok2": resilience.validate_snapshot(
            str(d / "b.txt.snapshot_iter_2"))[0],
        "ok4": resilience.validate_snapshot(
            str(d / "b.txt.snapshot_iter_4"))[0],
    }
    # C: resume=true must skip the corrupt iter-4 snapshot, fall back to
    # iter 2, and retrain to a byte-identical model
    r_resume = _cli(d, common + ["output_model=b.txt", "snapshot_freq=2",
                                 "resume=true"])
    return d, r_crash, r_resume, post_crash


def test_abrupt_death_leaves_snapshots_not_models(cli_resume_runs):
    d, r_crash, _, post_crash = cli_resume_runs
    assert r_crash.returncode == 137          # the injected abrupt death
    assert not post_crash["model_written"]    # died before the final save
    assert post_crash["ok2"], "the iteration-2 snapshot must survive valid"
    assert not post_crash["ok4"], "the torn-write fault must invalidate " \
        "the iteration-4 snapshot"


def test_resume_falls_back_past_corrupt_snapshot_with_warning(cli_resume_runs):
    d, _, r_resume, _pc = cli_resume_runs
    text = r_resume.stdout + r_resume.stderr
    assert "snapshot_iter_4" in text and "invalid" in text
    assert "Resuming from snapshot" in text and "snapshot_iter_2" in text


def test_resume_reproduces_uninterrupted_model_byte_for_byte(cli_resume_runs):
    d, _, _, _pc = cli_resume_runs
    a = (d / "a.txt").read_bytes()
    b = (d / "b.txt").read_bytes()
    assert a == b, "resumed model differs from the uninterrupted run"


def test_no_stray_tmp_files_next_to_snapshots(cli_resume_runs):
    d, _, _, _pc = cli_resume_runs
    stray = [f for f in os.listdir(d) if ".tmp" in f]
    assert stray == [], stray


def test_sigterm_writes_final_snapshot_and_resume_is_byte_identical(
        cli_resume_runs):
    """Acceptance: SIGTERM mid-training writes a valid final snapshot and
    resume=true reproduces the uninterrupted model byte-for-byte."""
    d, _, _, _pc = cli_resume_runs
    common = _TRAIN_ARGS + ["data=train.tsv"]
    r = _cli(d, common + ["output_model=c.txt"],
             fault="sigterm_at_iter:5")
    assert "preempt" in (r.stdout + r.stderr).lower()
    assert not (d / "c.txt").exists(), \
        "a preempted run must not pretend it finished"
    snaps = resilience.snapshot_paths(str(d / "c.txt"))
    assert len(snaps) == 1
    it, snap = snaps[0]
    assert resilience.validate_snapshot(snap)[0]
    _cli(d, common + ["output_model=c.txt", "resume=true"])
    assert (d / "c.txt").read_bytes() == (d / "a.txt").read_bytes()


def test_sigterm_mid_window_resume_byte_identical(cli_resume_runs):
    """ISSUE 13 window-boundary matrix: SIGTERM landing while a
    boost_window=4 run has a window open truncates to the reported
    iteration at the preemption boundary (exact snapshot replay), writes
    a valid final snapshot, and resume=true reproduces the UNWINDOWED
    uninterrupted model byte-for-byte."""
    d, _, _, _pc = cli_resume_runs
    common = _TRAIN_ARGS + ["data=train.tsv", "boost_window=4"]
    r = _cli(d, common + ["output_model=w.txt"], fault="sigterm_at_iter:5")
    assert "preempt" in (r.stdout + r.stderr).lower()
    assert not (d / "w.txt").exists(), \
        "a preempted run must not pretend it finished"
    snaps = resilience.snapshot_paths(str(d / "w.txt"))
    assert len(snaps) == 1
    assert resilience.validate_snapshot(snaps[0][1])[0]
    _cli(d, common + ["output_model=w.txt", "resume=true"])
    assert (d / "w.txt").read_bytes() == (d / "a.txt").read_bytes()


def test_window_snapshot_capture_mid_window_byte_identical():
    """capture_training_state landing mid-window settles the open window
    at the reported iteration (scores AND RNG streams), and both the
    interrupted-then-restored run and the uninterrupted windowed run are
    byte-identical to the sequential model (ISSUE 13)."""
    X, y = _data(seed=12)
    params = {"objective": "binary", "num_leaves": 12, "verbose": -1,
              "seed": 5, "bagging_freq": 2, "bagging_fraction": 0.6,
              "boost_window": 4}
    seq = {k: v for k, v in params.items() if k != "boost_window"}
    bst_a = lgb.Booster(dict(seq), lgb.Dataset(X, label=y))
    for _ in range(8):
        bst_a.update()
    ma = bst_a.model_to_string()

    bst_w = lgb.Booster(dict(params), lgb.Dataset(X, label=y))
    snap_state = snap_model = None
    for i in range(8):
        bst_w.update()
        if i + 1 == 3:            # a boost_window=4 window is open here
            snap_state = resilience.capture_training_state(bst_w)
            snap_model = bst_w._model.save_model_to_string()
    assert bst_w.model_to_string() == ma
    assert snap_model.count("Tree=") == 3, \
        "the mid-window capture must see exactly the reported iterations"

    init = GBDTModel.load_model_from_string(snap_model)
    bst_b = lgb.Booster(dict(params), lgb.Dataset(X, label=y),
                        init_model=init)
    resilience.restore_training_state(bst_b, snap_state)
    for _ in range(5):
        bst_b.update()
    assert bst_b.model_to_string() == ma


def test_dart_resume_in_process_byte_identical():
    """DART's drop RNG + tree-weight ledger cross the snapshot boundary
    (the issue calls this out explicitly): resuming mid-run must replay
    the exact same dropout decisions as the uninterrupted run."""
    X, y = _data(seed=3)
    params = {"objective": "binary", "boosting": "dart", "drop_rate": 0.5,
              "drop_seed": 11, "num_leaves": 12, "verbose": -1, "seed": 3}
    bst_a = lgb.Booster(dict(params), lgb.Dataset(X, label=y))
    snap_state = None
    for i in range(8):
        bst_a.update()
        if i + 1 == 4:
            snap_state = resilience.capture_training_state(bst_a)
            snap_model = bst_a._model.save_model_to_string()
    ma = bst_a._model.save_model_to_string()

    init = GBDTModel.load_model_from_string(snap_model)
    bst_b = lgb.Booster(dict(params), lgb.Dataset(X, label=y),
                        init_model=init)
    resilience.restore_training_state(bst_b, snap_state)
    for _ in range(4):
        bst_b.update()
    assert bst_b._model.save_model_to_string() == ma


def test_resume_state_shape_mismatch_degrades_gracefully():
    """A snapshot from a DIFFERENT dataset must not poison training:
    restore detects the shape mismatch, warns, and falls back to plain
    continued-training semantics."""
    X, y = _data(seed=4)
    bst = lgb.Booster({"objective": "binary", "verbose": -1},
                      lgb.Dataset(X, label=y))
    bst.update()
    state = resilience.capture_training_state(bst)
    X2, y2 = _data(n=256, seed=5)
    bst2 = lgb.Booster({"objective": "binary", "verbose": -1},
                       lgb.Dataset(X2, label=y2))
    resilience.restore_training_state(bst2, state)   # must not raise
    bst2.update()
    assert bst2.num_trees() == 1


# ---------------------------------------------------------------------------
# non-finite sentinel
# ---------------------------------------------------------------------------

def test_sentinel_abort_names_iteration(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FAULT", "nan_grad:2")
    X, y = _data(seed=6)
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "sentinel_nonfinite": "abort"},
                      lgb.Dataset(X, label=y))
    bst.update()
    bst.update()
    with pytest.raises(resilience.NonFiniteDetected,
                       match="iteration 2"):
        bst.update()


def test_sentinel_rollback_discards_iteration_and_stops(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_FAULT", "nan_grad:2")
    X, y = _data(seed=6)
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "sentinel_nonfinite": "rollback"},
                      lgb.Dataset(X, label=y))
    assert bst.update() is False
    assert bst.update() is False
    assert bst.update() is True          # poisoned iter -> rolled back, done
    assert bst.num_trees() == 2          # the poisoned tree was discarded
    assert np.isfinite(bst._engine.raw_train_score()).all()
    pred = bst.predict(X[:32])
    assert np.isfinite(pred).all()


def test_sentinel_off_by_default_costs_nothing(monkeypatch):
    # with the policy off the injected fault is never even consulted
    monkeypatch.setenv("LGBM_TPU_FAULT", "nan_grad:0")
    X, y = _data(seed=6)
    bst = lgb.Booster({"objective": "binary", "verbose": -1},
                      lgb.Dataset(X, label=y))
    assert bst.update() is False
    assert bst.num_trees() == 1


# ---------------------------------------------------------------------------
# distributed bring-up: timeout + bounded retry, named failure
# ---------------------------------------------------------------------------

def test_init_distributed_retries_then_names_coordinator_and_rank(
        monkeypatch):
    import jax
    from lightgbm_tpu.parallel import launch

    calls = []

    def failing_initialize(**kwargs):
        calls.append(kwargs)
        raise ConnectionError("connect refused")

    monkeypatch.setattr(jax.distributed, "initialize", failing_initialize)
    monkeypatch.setattr(launch.resilience, "backoff_delays",
                        lambda *a, **k: [0.0, 0.0])
    with pytest.raises(RuntimeError) as ei:
        launch.init_distributed(machines="10.255.0.1:12400,10.255.0.2:12400",
                                node_rank=1, attempts=3, timeout_s=1)
    msg = str(ei.value)
    assert "10.255.0.1:12400" in msg          # coordinator named
    assert "rank 1 of 2" in msg               # rank named
    assert "3 attempt" in msg
    assert len(calls) == 3                    # bounded retry, no hang
    if "initialization_timeout" in calls[0]:
        assert calls[0]["initialization_timeout"] == 1


def test_init_distributed_succeeds_after_transient_failure(monkeypatch):
    import jax
    from lightgbm_tpu.parallel import launch

    calls = []

    def flaky_initialize(**kwargs):
        calls.append(kwargs)
        if len(calls) < 2:
            raise ConnectionError("transient")

    monkeypatch.setattr(jax.distributed, "initialize", flaky_initialize)
    monkeypatch.setattr(launch.resilience, "backoff_delays",
                        lambda *a, **k: [0.0, 0.0])
    rank = launch.init_distributed(machines="10.255.0.1:1,10.255.0.2:1",
                                   node_rank=0, attempts=3, timeout_s=1)
    assert rank == 0 and len(calls) == 2


# ---------------------------------------------------------------------------
# ISSUE 6 satellites: deeper resume-scan and pipeline-drain preemption
# ---------------------------------------------------------------------------

def test_resume_scan_past_three_mixed_corrupt_snapshots(tmp_path):
    """One resume scan must step past >=3 differently broken snapshots
    (truncated, bit-flipped, footer stripped) to the newest VALID one."""
    X, y = _data(seed=9)
    bst = lgb.Booster({"objective": "binary", "verbose": -1},
                      lgb.Dataset(X, label=y))
    out = str(tmp_path / "m.txt")
    for i in range(5):
        bst.update()
        resilience.write_snapshot(bst, out)
    paths = {it: p for it, p in resilience.snapshot_paths(out)}
    raw5 = open(paths[5], "rb").read()
    open(paths[5], "wb").write(raw5[: len(raw5) // 3])          # truncated
    raw4 = open(paths[4], "rb").read()
    open(paths[4], "wb").write(raw4.replace(b"leaf_value", b"leaf_valXe"))
    raw3 = open(paths[3]).read()                                # footerless
    open(paths[3], "w").write(raw3.split(
        resilience._STATE_PREFIX)[0])
    snap, state = resilience.find_resume_snapshot(out)
    assert snap == paths[2]
    assert state["total_iter"] == 2
    # and all three invalid ones have distinct failure reasons
    reasons = {it: resilience.validate_snapshot(paths[it])[1]
               for it in (3, 4, 5)}
    assert all(not resilience.validate_snapshot(paths[it])[0]
               for it in (3, 4, 5)), reasons


@pytest.mark.slow
def test_sigterm_during_pipeline_drain_depth2(tmp_path):
    """SIGTERM landing while the async dispatch pipeline is in flight at
    pipeline_depth=2 still produces rc=0 and a VALID final snapshot (the
    preemption callback drains before capturing state), and the resumed
    model is byte-identical to an uninterrupted depth-2 run.  Slow-marked
    (ISSUE 12 truncation fix): two full CLI subprocess runs ~18s; the
    depth-1 SIGTERM byte-identity pin stays tier-1."""
    X, y = _data()
    np.savetxt(tmp_path / "train.tsv", np.column_stack([y, X]),
               delimiter="\t", fmt="%.8g")
    common = _TRAIN_ARGS + ["data=train.tsv", "pipeline_depth=2"]
    _cli(tmp_path, common + ["output_model=a.txt"])
    r = _cli(tmp_path, common + ["output_model=b.txt"],
             fault="sigterm_at_iter:5")
    assert r.returncode == 0
    assert "preempt" in (r.stdout + r.stderr).lower()
    assert not (tmp_path / "b.txt").exists()
    snaps = resilience.snapshot_paths(str(tmp_path / "b.txt"))
    assert len(snaps) == 1
    ok, reason = resilience.validate_snapshot(snaps[0][1])
    assert ok, reason
    _cli(tmp_path, common + ["output_model=b.txt", "resume=true"])
    assert (tmp_path / "b.txt").read_bytes() == \
        (tmp_path / "a.txt").read_bytes()
