"""bench.py's section functions at toy scale on the CPU: the record shapes
the sections emit, and that a failing stage fails the run (bench.main
itself refuses any platform but a TPU — tests/test_chip_smoke.py)."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import lightgbm_tpu as lgb

PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 15,
          "max_bin": 63, "learning_rate": 0.1, "verbose": -1}


def _small_booster(n=5000):
    X, y = bench.synth_higgs(n)
    bst = lgb.Booster(dict(PARAMS), lgb.Dataset(X, label=y))
    for _ in range(2):
        bst.update()
    return bst


PHASE_KEYS = {"grad_fill_ms", "tree_grow_ms", "score_update_ms",
              "tree_assemble_host_ms"}


def test_phase_times_healthy_at_reduced_scale():
    """One piecewise iteration through every stage must produce timings,
    plus the normalized self-consistency block (ISSUE 13 satellite: the
    piecewise absolutes can exceed sec_per_iter, so the record must
    carry fractions that always sum to 1)."""
    out = bench.phase_times(_small_booster(), reps=1)
    assert set(out) == PHASE_KEYS | {"piecewise_total_ms", "phase_frac"}
    assert all(out[k] >= 0.0 for k in PHASE_KEYS)
    assert set(out["phase_frac"]) == PHASE_KEYS
    assert abs(sum(out["phase_frac"].values()) - 1.0) < 1e-3
    assert out["piecewise_total_ms"] >= max(out[k] for k in PHASE_KEYS)


def test_phase_failure_fails_the_run():
    """A stage that fails raises out of phase_times: a failed section must
    fail the bench, not become a note beside a number."""
    import pytest
    bst = _small_booster()

    def boom(*a, **k):
        raise RuntimeError("injected stage death")

    bst._engine._fast._fill_class = boom
    with pytest.raises(RuntimeError, match="injected stage death"):
        bench.phase_times(bst, reps=1)


def test_phase_times_midscale_runs_reduced():
    """The mid-scale fresh-booster variant (what a full-scale run records
    instead of piecewise at the headline scale) tags the scale it
    measured at."""
    X, y = bench.synth_higgs(4000)
    out = bench.phase_times_midscale(X, y, PARAMS, 2000)
    assert out.get("measured_at_rows") == 2000
    assert PHASE_KEYS <= set(out)


def test_predict_bench_record_shape():
    """BENCH_PREDICT at toy scale: the record must carry the rows/sec
    triple and the depth-bound evidence the acceptance gate reads."""
    env = {"BENCH_PREDICT_ROWS": "2048", "BENCH_PREDICT_TREES": "20",
           "BENCH_PREDICT_LEAVES": "31"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rec = bench.bench_predict()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    for key in ("engine_rows_per_sec", "scan_rows_per_sec",
                "host_rows_per_sec", "speedup_vs_scan", "depth_iters"):
        assert key in rec
    assert rec["depth_iters"] < rec["scan_depth_iters"]
    assert np.isfinite(rec["max_abs_diff_vs_host_raw"])


def test_serve_bench_record_shape():
    """BENCH_SERVE at toy scale: the record must carry the latency
    percentiles, rows/sec, swap latency and the zero-drop evidence the
    acceptance gate reads."""
    env = {"BENCH_SERVE_CLIENTS": "3", "BENCH_SERVE_SECONDS": "1.6",
           "BENCH_SERVE_TREES": "12", "BENCH_SERVE_LEAVES": "15",
           "BENCH_SERVE_BATCH": "4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rec = bench.bench_serve()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    for key in ("rows_per_sec", "latency_ms", "swap_latency_s", "shed",
                "batches_device", "batches_host", "requests"):
        assert key in rec
    assert rec["requests"] > 0
    assert rec["latency_ms"]["p99"] >= rec["latency_ms"]["p50"]
    # the mid-run hot swap must have been observed by a client
    assert rec["swap_latency_s"] is not None


def test_ingest_bench_record_shape():
    """BENCH_INGEST at toy scale (ISSUE 8): the record must carry the
    four rows/sec readings and the cross-path bins-identical pin."""
    env = {"BENCH_INGEST_ROWS": "3000"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rec = bench.bench_ingest()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    for key in ("file_parse_rows_per_sec", "dense_push_rows_per_sec",
                "csr_push_rows_per_sec", "binary_cache_rows_per_sec",
                "push_speedup_vs_file_parse"):
        assert key in rec and rec.get(key) is not None, key
        if key.endswith("rows_per_sec"):
            assert rec[key] > 0
    assert rec["bins_identical_across_paths"] is True


def test_window_bench_record_shape():
    """BENCH_WINDOW at toy scale (ISSUE 13): the on/off A/B must report
    both arms' sec/iter + dispatch/fetch counts off the same booster,
    with the window arm's dispatch and fetch counts strictly lower."""
    env = {"BENCH_WINDOW": "4", "BENCH_WINDOW_ITERS": "8"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rec = bench.bench_window(_small_booster(), 8)
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    assert rec["boost_window"] == 4
    for arm in ("on", "off"):
        for key in ("sec_per_iter", "dispatches_per_iter",
                    "fetches_per_iter"):
            assert rec[arm][key] >= 0, (arm, key, rec)
    assert rec["on"]["dispatches_per_iter"] < rec["off"]["dispatches_per_iter"]
    assert rec["on"]["fetches_per_iter"] < rec["off"]["fetches_per_iter"]
    assert rec["dispatch_reduction"] >= 2
