"""Bench-trajectory collator (ISSUE 10 satellite, helper/bench_history.py).

Synthetic BENCH_r* / BENCH_WINDOW_r* fixtures must collate into a
non-empty trajectory with NO latest-round regression (the acceptance
gate), and the regression detector must actually fire on a synthetic
>10% drop — with cross-shape rounds never compared.
"""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "helper"))

import bench_history  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_round(d, n, parsed=None, tail=""):
    rec = {"n": n, "rc": 0, "tail": tail}
    if parsed is not None:
        rec["parsed"] = parsed
    (d / ("BENCH_r%02d.json" % n)).write_text(json.dumps(rec))


def _attrib(dispatch_s, device_s, drain_s, dispatches):
    return {"attrib": {"per_iter": {
        "dispatch_s": dispatch_s, "device_wait_s": device_s,
        "drain_s": drain_s, "dispatches_per_iter": dispatches}}}


def _write_fixture_rounds(d):
    """Two bench rounds and a window A/B round at one shape, improving."""
    shape = {"n_rows": 60_000, "platform": "tpu"}
    _write_round(d, 4, dict(shape, value=0.25, vs_baseline=0.12))
    _write_round(d, 5, dict(shape, value=0.26, vs_baseline=0.125))
    window = dict(shape, value=0.27, vs_baseline=0.13,
                  **_attrib(0.0123, 0.0456, 0.0078, 0.5))
    (d / "BENCH_WINDOW_r13.json").write_text(json.dumps(
        {"n": 13, "rc": 0, "tail": "", "parsed": window}))
    return window


def test_fixture_rounds_collate_clean(tmp_path):
    # both artifact families collate into one trajectory (ISSUE 14: the
    # attrib decomposition rides BENCH_r* and BENCH_WINDOW_r* alike)
    fix = _write_fixture_rounds(tmp_path)
    rep = bench_history.run(str(tmp_path))
    assert rep["rounds"] == 3
    assert len(rep["trajectory"]) == 3
    latest = rep["trajectory"][-1]
    assert latest["round"] == 13
    assert latest["file"] == "BENCH_WINDOW_r13.json"
    # values come from the fixtures, not thin air
    assert latest["iters_per_sec"] == fix["value"]
    # the attrib series landed, in ms
    attr = fix["attrib"]["per_iter"]
    assert latest["dispatches_per_iter"] == attr["dispatches_per_iter"]
    assert latest["attrib_dispatch_ms"] == \
        round(attr["dispatch_s"] * 1000, 3)
    assert latest["attrib_drain_ms"] == round(attr["drain_s"] * 1000, 3)
    r5 = [r for r in rep["trajectory"] if r["round"] == 5][0]
    assert r5["iters_per_sec"] == 0.26 and r5["vs_baseline"] == 0.125
    # the acceptance gate: the regression check runs clean
    assert rep["latest_regressions"] == [], rep["latest_regressions"]


def test_cli_exits_zero_on_clean_fixtures(tmp_path):
    _write_fixture_rounds(tmp_path)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "helper", "bench_history.py"),
         str(tmp_path)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "3 round(s) collated" in r.stdout


def test_synthetic_regression_is_flagged(tmp_path):
    base = {"value": 1.0, "vs_baseline": 0.5, "n_rows": 100,
            "platform": "cpu"}
    _write_round(tmp_path, 1, dict(base))
    _write_round(tmp_path, 2, dict(base, value=0.85, vs_baseline=0.42))
    rep = bench_history.run(str(tmp_path))
    assert rep["rounds"] == 2
    flagged = {f["series"] for f in rep["latest_regressions"]}
    assert "iters_per_sec" in flagged and "vs_baseline" in flagged
    f = [x for x in rep["latest_regressions"]
         if x["series"] == "iters_per_sec"][0]
    assert f["best_prior_round"] == 1 and f["drop_pct"] == 15.0


def test_cross_shape_rounds_never_compared(tmp_path):
    _write_round(tmp_path, 1, {"value": 1.0, "n_rows": 2_000_000,
                               "platform": "tpu"})
    # much slower, but a DIFFERENT shape/platform: not a regression
    _write_round(tmp_path, 2, {"value": 0.2, "n_rows": 100_000,
                               "platform": "cpu"})
    rep = bench_history.run(str(tmp_path))
    assert rep["regressions"] == []


def test_historical_drop_does_not_fail_latest(tmp_path):
    shape = {"n_rows": 100, "platform": "cpu"}
    _write_round(tmp_path, 1, dict(shape, value=1.0))
    _write_round(tmp_path, 2, dict(shape, value=0.5))    # historical drop
    _write_round(tmp_path, 3, dict(shape, value=0.99))   # recovered
    rep = bench_history.run(str(tmp_path))
    assert [f["round"] for f in rep["regressions"]] == [2]
    assert rep["latest_regressions"] == []


def test_tail_fallback_parses_red_round(tmp_path):
    """A round whose driver failed to parse still contributes when its
    tail carries the bench JSON line."""
    parsed = {"value": 0.3, "n_rows": 100, "platform": "cpu"}
    _write_round(tmp_path, 1, None,
                 tail="noise\n%s\nmore noise" % json.dumps(parsed))
    rep = bench_history.run(str(tmp_path))
    assert rep["rounds"] == 1
    assert rep["trajectory"][0]["iters_per_sec"] == 0.3


def test_section_series_collated(tmp_path):
    p1 = {"value": 1.0, "n_rows": 100, "platform": "cpu",
          "predict": {"engine_rows_per_sec": 1000.0, "rows": 10,
                      "n_trees": 5}}
    p2 = {"value": 1.0, "n_rows": 100, "platform": "cpu",
          "predict": {"engine_rows_per_sec": 400.0, "rows": 10,
                      "n_trees": 5}}
    _write_round(tmp_path, 1, p1)
    _write_round(tmp_path, 2, p2)
    rep = bench_history.run(str(tmp_path))
    assert rep["trajectory"][0]["predict_rows_per_sec"] == 1000.0
    assert any(f["series"] == "predict_rows_per_sec"
               for f in rep["latest_regressions"])


# ---------------------------------------------------------------------------
# SIM_r*.json collation + schema gate (ISSUE 11)
# ---------------------------------------------------------------------------

def _sim_scenario(p99=0.05, staleness=2.0, capacity=300.0, ok=True):
    return {
        "objective": "binary",
        "latency_s": {"p50": p99 / 3, "p99": p99, "count": 100,
                      "mean": p99 / 2},
        "staleness_s": {"p50": staleness, "p99": staleness * 2,
                        "count": 50, "mean": staleness},
        "capacity_rows_per_sec_per_replica": capacity,
        "classes": {"gold": {"priority": 0, "offered": 10, "completed": 10,
                             "shed": 0, "shed_rate": 0.0, "reasons": {}}},
        "verification": {"ok": 100},
        "ok": ok,
    }


def _write_sim(d, n, scenarios, replicas=2, duration=20.0):
    rec = {"artifact": "SIM_r%02d" % n, "schema_version": 1,
           "replicas": replicas, "duration_s": duration, "ok": True,
           "scenarios": scenarios}
    (d / ("SIM_r%02d.json" % n)).write_text(json.dumps(rec))
    return rec


def test_dispatches_per_iter_rise_is_flagged(tmp_path):
    """The ISSUE 13 series is LOWER-is-better: a >10% RISE in
    BENCH_ATTRIB's dispatches_per_iter at the same shape flags, a drop
    (boost_window progress) never does."""
    shape = {"value": 1.0, "n_rows": 100, "platform": "cpu"}
    att = lambda d: {"attrib": {"per_iter": {"dispatches_per_iter": d}}}
    _write_round(tmp_path, 1, {**shape, **att(2.0)})
    _write_round(tmp_path, 2, {**shape, **att(0.5)})     # window win: fine
    _write_round(tmp_path, 3, {**shape, **att(0.8)})     # 60% rise: flags
    rep = bench_history.run(str(tmp_path))
    assert rep["trajectory"][1]["dispatches_per_iter"] == 0.5
    flagged = [f for f in rep["latest_regressions"]
               if f["series"] == "dispatches_per_iter"]
    assert len(flagged) == 1
    assert flagged[0]["best_prior_round"] == 2
    assert flagged[0]["higher_is_better"] is False
    # rounds 1->2 (the improvement) never flagged
    assert all(f["round"] != 2 for f in rep["regressions"]
               if f["series"] == "dispatches_per_iter")


def test_attrib_time_series_collate_in_ms_and_rise_flags(tmp_path):
    """ISSUE 14 satellite: the attrib dispatch/device-wait/drain pieces
    collate (in ms) and a >10% rise at the same shape flags — the
    per-piece trajectory across BENCH_r*/BENCH_WINDOW_r* is what tells
    the next hardware window WHICH piece moved."""
    shape = {"value": 1.0, "n_rows": 100, "platform": "cpu"}

    def att(dispatch, wait, drain):
        return {"attrib": {"per_iter": {"dispatch_s": dispatch,
                                        "device_wait_s": wait,
                                        "drain_s": drain}}}
    _write_round(tmp_path, 1, {**shape, **att(0.100, 0.020, 0.010)})
    (tmp_path / "BENCH_WINDOW_r02.json").write_text(json.dumps(
        {"parsed": {**shape, **att(0.050, 0.019, 0.010)}}))  # better: fine
    _write_round(tmp_path, 3, {**shape, **att(0.080, 0.045, 0.010)})
    rep = bench_history.run(str(tmp_path))
    rows = {r["file"]: r for r in rep["trajectory"]}
    assert rows["BENCH_WINDOW_r02.json"]["attrib_dispatch_ms"] == 50.0
    assert rows["BENCH_r03.json"]["attrib_device_wait_ms"] == 45.0
    flagged = {f["series"] for f in rep["latest_regressions"]}
    # dispatch rose 60% vs the window round's 50ms, device-wait rose
    # >100% vs round 2's 19ms; drain never moved
    assert {"attrib_dispatch_ms", "attrib_device_wait_ms"} <= flagged
    assert "attrib_drain_ms" not in flagged


def test_sim_artifact_schema_validates():
    good = {"artifact": "SIM_r11", "schema_version": 1, "replicas": 2,
            "duration_s": 20.0, "ok": True,
            "scenarios": {"binary": _sim_scenario()}}
    assert bench_history.validate_sim_artifact(good) == []
    # a malformed sim run fails LOUDLY, field by field
    assert bench_history.validate_sim_artifact({"artifact": "SIM_rX"})
    bad = json.loads(json.dumps(good))
    del bad["scenarios"]["binary"]["latency_s"]
    assert any("latency_s" in p
               for p in bench_history.validate_sim_artifact(bad))
    bad2 = json.loads(json.dumps(good))
    bad2["scenarios"]["binary"]["classes"]["gold"].pop("shed_rate")
    assert any("shed_rate" in p
               for p in bench_history.validate_sim_artifact(bad2))


def test_sim_rounds_collate_and_regressions_flag(tmp_path):
    """p99 is lower-better (a rise flags), capacity higher-better (a
    drop flags); same-shape rounds only."""
    _write_sim(tmp_path, 11, {"binary": _sim_scenario(p99=0.05,
                                                      capacity=300)})
    _write_sim(tmp_path, 12, {"binary": _sim_scenario(p99=0.08,
                                                      capacity=250)})
    rep = bench_history.run(str(tmp_path))
    assert rep["sim_rounds"] == 2
    assert rep["invalid_sim_artifacts"] == []
    flagged = {f["series"] for f in rep["sim_latest_regressions"]}
    assert "p99_latency_s" in flagged
    assert "capacity_rows_per_sec_per_replica" in flagged
    # an improvement never flags
    for d in tmp_path.glob("SIM_r*.json"):
        d.unlink()
    _write_sim(tmp_path, 11, {"binary": _sim_scenario(p99=0.08,
                                                      capacity=200)})
    _write_sim(tmp_path, 12, {"binary": _sim_scenario(p99=0.05,
                                                      capacity=300)})
    rep = bench_history.run(str(tmp_path))
    assert rep["sim_latest_regressions"] == []


def test_sim_cross_shape_rounds_never_compared(tmp_path):
    _write_sim(tmp_path, 11, {"binary": _sim_scenario(p99=0.01)},
               replicas=2)
    _write_sim(tmp_path, 12, {"binary": _sim_scenario(p99=0.5)},
               replicas=4)     # different fleet size: not comparable
    rep = bench_history.run(str(tmp_path))
    assert rep["sim_latest_regressions"] == []


def test_malformed_sim_artifact_fails_the_run(tmp_path):
    """A SIM file that doesn't validate lands in invalid_sim_artifacts
    and fails the collation — a malformed sim run can never collate as
    silent zeros."""
    _write_round(tmp_path, 1, parsed={"value": 1.0, "n_rows": 10,
                                      "platform": "cpu"})
    (tmp_path / "SIM_r11.json").write_text(json.dumps(
        {"artifact": "SIM_r11", "scenarios": {}}))
    rep = bench_history.run(str(tmp_path))
    assert rep["invalid_sim_artifacts"]
    assert rep["sim_rounds"] == 0
    assert rep["latest_regressions"] == []   # bench side is clean...
    # ...yet the would-be CLI verdict is failure (main() gates on
    # invalid_sim_artifacts exactly like latest regressions)
    assert bool(rep["latest_regressions"] or rep["sim_latest_regressions"]
                or rep["invalid_sim_artifacts"])


# ---------------------------------------------------------------------------
# quality-firewall artifacts (CHAOS_QUALITY_r*.json, ISSUE 12)
# ---------------------------------------------------------------------------

def _quality_rec(round_no=12, quarantined=175, rejections=1, rollbacks=1,
                 window=5, bad_outside=0, byte_verified=True):
    return {
        "artifact": "CHAOS_QUALITY_r%d" % round_no,
        "schema_version": 1,
        "ok": True,
        "phases": {
            "ingest_gate": {
                "quarantined_total": quarantined,
                "gate_rejections": rejections,
                "gate_passes": 5,
                "published_generations": [1, 2, 4, 5, 6],
                "rejected_cycles": [3],
                "nonfinite_predictions": 0,
                "ok": True,
            },
            "canary": {
                "rollback_count": rollbacks,
                "canary_fraction": 0.25,
                "responses_bad_outside_canary": bad_outside,
                "canary_batches_to_rollback": window,
                "rollback_byte_verified": byte_verified,
                "canary_events": {"start": 1, "rollback": 1},
                "canary_batches": {"canary": 10, "incumbent": 30},
                "ok": True,
            },
        },
    }


def _write_quality(tmp_path, round_no, rec):
    (tmp_path / ("CHAOS_QUALITY_r%02d.json" % round_no)).write_text(
        json.dumps(rec))


def test_committed_quality_artifact_validates():
    path = os.path.join(REPO, "CHAOS_QUALITY_r12.json")
    rec = json.load(open(path))
    assert bench_history.validate_quality_artifact(rec) == []
    assert rec["ok"] is True


def test_quality_trajectory_and_detection_window_regression(tmp_path):
    _write_quality(tmp_path, 12, _quality_rec(window=5))
    _write_quality(tmp_path, 13, _quality_rec(13, window=9))
    rep = bench_history.run(str(tmp_path))
    assert rep["quality_rounds"] == 2
    rows = rep["quality_trajectory"]
    assert rows[0]["quarantined_total"] == 175
    assert rows[0]["rollback_count"] == 1
    # the canary detection window WIDENED >10%: flagged on the latest
    flags = rep["quality_latest_regressions"]
    assert flags and flags[0]["series"] == "canary_batches_to_rollback"


def test_quality_artifact_schema_gates(tmp_path):
    # a regressed generation reaching the non-canary fleet is INVALID
    bad = _quality_rec(bad_outside=3)
    assert any("non-canary" in p
               for p in bench_history.validate_quality_artifact(bad))
    # an unverified rollback is INVALID
    bad2 = _quality_rec(byte_verified=None)
    assert any("byte-verified" in p
               for p in bench_history.validate_quality_artifact(bad2))
    _write_quality(tmp_path, 12, bad)
    rep = bench_history.run(str(tmp_path))
    assert rep["invalid_quality_artifacts"]
    assert rep["quality_rounds"] == 0


# ---------------------------------------------------------------------------
# cold-start artifacts (BENCH_COLD_r*.json, ISSUE 15)
# ---------------------------------------------------------------------------

def _cold_mode(ready=0.25, first=0.3, sha="a" * 64):
    return {"time_to_ready_s": ready, "time_to_first_response_s": first,
            "verified": True, "steady_retraces": 0, "pred_sha256": sha,
            "served_by": "device"}


def _cold_rec(n=15, manifest_ready=0.25, join=1.7, warm_overhead=0.7,
              platform="cpu", n_trees=100, **over):
    rec = {
        "artifact": "BENCH_COLD_r%02d" % n, "schema_version": 1,
        "platform": platform, "n_trees": n_trees, "ok": True,
        "modes": {"cold": _cold_mode(0.9, 1.2),
                  "cache": _cold_mode(0.3, 0.4),
                  "manifest": _cold_mode(manifest_ready, manifest_ready)},
        "train": {"cold": {"startup_overhead_s": 2.5},
                  "warm": {"startup_overhead_s": warm_overhead},
                  "model_identical": True},
        "predictions_identical": True,
        "replica_join": {"join_to_first_response_s": join,
                         "verified": True},
    }
    rec.update(over)
    return rec


def _write_cold(d, n, rec):
    (d / ("BENCH_COLD_r%02d.json" % n)).write_text(json.dumps(rec))


def test_committed_coldstart_artifact_validates():
    path = os.path.join(REPO, "BENCH_COLD_r15.json")
    rec = json.load(open(path))
    assert bench_history.validate_coldstart_artifact(rec) == []
    assert rec["ok"] is True
    # the acceptance bar: warm-start >= 2x faster than cold startup
    assert rec["speedup"]["train_startup_overhead_cold_over_warm"] >= 2.0


def test_coldstart_trajectory_and_rise_flags(tmp_path):
    """Every startup series is lower-is-better: a >10% rise in
    join-to-first-response or warm startup overhead flags the latest
    round; same-shape rounds only."""
    _write_cold(tmp_path, 15, _cold_rec(15, join=1.5, warm_overhead=0.6))
    _write_cold(tmp_path, 16, _cold_rec(16, join=2.5, warm_overhead=0.9))
    rep = bench_history.run(str(tmp_path))
    assert rep["coldstart_rounds"] == 2
    assert rep["invalid_coldstart_artifacts"] == []
    flagged = {f["series"] for f in rep["coldstart_latest_regressions"]}
    assert "join_to_first_response_s" in flagged
    assert "train_startup_overhead_warm_s" in flagged
    # improvements never flag; cross-shape rounds never compared
    for p in tmp_path.glob("BENCH_COLD_r*.json"):
        p.unlink()
    _write_cold(tmp_path, 15, _cold_rec(15, join=2.5))
    _write_cold(tmp_path, 16, _cold_rec(16, join=1.0, n_trees=40))
    rep = bench_history.run(str(tmp_path))
    assert rep["coldstart_latest_regressions"] == []


def test_coldstart_schema_gates(tmp_path):
    # an unverified mode is INVALID, as are steady-state retraces, a
    # prediction divergence across start modes, or changed trained bits
    bad = _cold_rec()
    bad["modes"]["cache"]["verified"] = False
    assert any("byte-verified" in p
               for p in bench_history.validate_coldstart_artifact(bad))
    bad2 = _cold_rec()
    bad2["modes"]["manifest"]["steady_retraces"] = 2
    assert any("zero-retrace" in p
               for p in bench_history.validate_coldstart_artifact(bad2))
    bad3 = _cold_rec(predictions_identical=False)
    assert any("predictions_identical" in p
               for p in bench_history.validate_coldstart_artifact(bad3))
    bad4 = _cold_rec()
    bad4["train"]["model_identical"] = False
    assert any("trained bits" in p
               for p in bench_history.validate_coldstart_artifact(bad4))
    _write_cold(tmp_path, 15, bad4)
    rep = bench_history.run(str(tmp_path))
    assert rep["invalid_coldstart_artifacts"]
    assert rep["coldstart_rounds"] == 0


# ---------------------------------------------------------------------------
# wire data-plane artifacts (BENCH_WIRE_r*.json, ISSUE 16)
# ---------------------------------------------------------------------------

def _wire_path(req=1000.0, p99=2.0, verified=True, mismatch=0):
    return {"req_per_sec": req, "rows_per_sec": req * 8, "p50_ms": 1.0,
            "p99_ms": p99, "completed": 100, "rejected": 0,
            "verified": verified, "prediction_mismatches": mismatch}


def _wire_rec(round_n=16, json_rps=500.0, uds_rps=4000.0, **over):
    rec = {
        "artifact": "BENCH_WIRE_r%02d" % round_n, "schema_version": 1,
        "round": round_n, "platform": "cpu", "rows_per_request": 8,
        "conns": 4, "model": {"n_trees": 100, "num_leaves": 63,
                              "n_feat": 28, "n_out": 1},
        "paths": {"json_tcp": _wire_path(json_rps),
                  "binary_tcp": _wire_path(uds_rps * 0.9),
                  "binary_uds": _wire_path(uds_rps),
                  "c_client_uds": _wire_path(uds_rps * 0.95)},
        "offered": {"offered_per_sec": 12000.0, "p99_ms": 5.0,
                    "verified": True, "prediction_mismatches": 0},
        "speedup": {"binary_uds_over_json": uds_rps / json_rps},
        "gates": {"binary_uds_ge_5x_json": True, "offered_ge_10k": True,
                  "c_client_green": True, "zero_mismatches": True},
        "ok": True,
    }
    rec.update(over)
    return rec


def _write_wire(tmp_path, n, rec):
    (tmp_path / ("BENCH_WIRE_r%02d.json" % n)).write_text(json.dumps(rec))


def test_wire_artifact_validates_and_collates(tmp_path):
    assert bench_history.validate_wire_artifact(_wire_rec()) == []
    _write_wire(tmp_path, 16, _wire_rec())
    rep = bench_history.run(str(tmp_path))
    assert rep["wire_rounds"] == 1
    assert rep["invalid_wire_artifacts"] == []
    row = rep["wire_trajectory"][0]
    assert row["binary_uds_req_per_sec"] == 4000.0
    assert row["speedup_binary_uds_over_json"] == 8.0


def test_wire_schema_gates(tmp_path):
    """Unverified responses, any prediction mismatch, or a failed gate
    make the artifact INVALID — never a merely slow round."""
    bad = _wire_rec()
    bad["paths"]["binary_uds"]["verified"] = False
    assert any("byte-verified" in p
               for p in bench_history.validate_wire_artifact(bad))
    bad2 = _wire_rec()
    bad2["paths"]["json_tcp"]["prediction_mismatches"] = 3
    assert any("mismatch" in p
               for p in bench_history.validate_wire_artifact(bad2))
    bad3 = _wire_rec()
    bad3["gates"]["binary_uds_ge_5x_json"] = False
    assert any("gate" in p
               for p in bench_history.validate_wire_artifact(bad3))
    # mismatches in OPTIONAL paths (the C client) also invalidate
    bad4 = _wire_rec()
    bad4["paths"]["c_client_uds"]["prediction_mismatches"] = 1
    assert any("c_client_uds" in p
               for p in bench_history.validate_wire_artifact(bad4))
    _write_wire(tmp_path, 16, bad)
    rep = bench_history.run(str(tmp_path))
    assert rep["invalid_wire_artifacts"] and rep["wire_rounds"] == 0


def test_wire_regression_flags_same_shape_only(tmp_path):
    _write_wire(tmp_path, 16, _wire_rec(16, uds_rps=4000.0))
    _write_wire(tmp_path, 17, _wire_rec(17, uds_rps=3000.0))  # -25%: flags
    rep = bench_history.run(str(tmp_path))
    assert any(f["series"] == "binary_uds_req_per_sec"
               for f in rep["wire_latest_regressions"])
    # a different shape (1-row frames) is never compared
    for p in tmp_path.glob("BENCH_WIRE_r*.json"):
        p.unlink()
    _write_wire(tmp_path, 16, _wire_rec(16, uds_rps=4000.0))
    _write_wire(tmp_path, 17, _wire_rec(17, uds_rps=300.0,
                                        rows_per_request=1))
    rep = bench_history.run(str(tmp_path))
    assert rep["wire_latest_regressions"] == []
