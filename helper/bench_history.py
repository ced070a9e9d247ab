#!/usr/bin/env python
"""Bench + sim trajectory collator (ISSUE 10 / ISSUE 11 satellites).

This tool turns ``BENCH_r*.json`` driver artifacts (none are committed
any more: the old platform's records went in PR 21, and the ledger takes
over with ROADMAP A1) into one trajectory table (iters/sec, vs_baseline, per-section rows/sec) and
flags any round that regressed more than ``REGRESSION_THRESHOLD``
against the best PRIOR round measured at the same shape — cross-scale
comparisons (a 2M-row CPU round vs a 200k-row fallback round) are
meaningless and are never compared.

ISSUE 11 extends the same treatment to the production-sim artifacts
(``SIM_r*.json`` from exp/prod_sim.py): per-scenario p99 latency
(lower is better — a rise past the threshold flags) and capacity in
rows/sec/replica (higher is better — a drop flags), compared only
between rounds with the same replica count and duration.  Every SIM
artifact is schema-validated first (`validate_sim_artifact`); a
malformed sim run fails the collation loudly instead of collating as
zeros.

ISSUE 12 adds the quality-firewall artifacts (``CHAOS_QUALITY_r*.json``
from exp/chaos_quality.py): schema-validated like the sims (a rollback
that is not byte-verified, or a regressed generation reaching the
non-canary fleet, is an INVALID artifact), with the quarantine / gate /
rollback counts carried in the trajectory and the canary detection
window (batches-to-rollback, lower is better) under the same >10 %
regression-flag treatment.

ISSUE 16 adds the wire data-plane artifacts (``BENCH_WIRE_r*.json``
from exp/bench_wire.py): request rates per path (JSON/TCP vs binary
TCP vs binary UDS vs the compiled C client, higher is better) and the
binary/offered p99 tails (lower is better) under the same same-shape
>10 % treatment, behind a schema gate that makes an unverified
response or any JSON-vs-binary prediction mismatch an INVALID
artifact — throughput at wrong answers is not throughput.

ISSUE 20 extends the wire treatment to the shared-memory ring
transport: a ``binary_shm`` path series (req/s higher-better, p99
lower-better) plus the ``speedup_shm_over_uds`` trajectory column,
and — from artifact schema v2 on — a hard gate that the ``shm_plane``
section is present, byte-verified, and carries exactly zero prediction
mismatches (v1 artifacts from r16 stay valid without it).

Artifact shape (bench): the driver wraps each round's bench stdout as
``{"n": round, "rc": ..., "parsed": <bench JSON>, "tail": ...}``; when
``parsed`` is missing the last JSON-looking line of ``tail`` is tried.
SIM artifacts are written directly by exp/prod_sim.py (schema_version
stamped).

Run standalone (``python helper/bench_history.py``; exit 1 when a
regression is flagged or a SIM artifact is malformed) or through the
tier-1 pin in ``tests/test_bench_history.py`` (committed fixtures
collate clean; synthetic drops ARE flagged)."""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a round is flagged when its value drops more than this fraction below
#: the best prior same-shape round
REGRESSION_THRESHOLD = 0.10

#: (series name, path into the parsed bench JSON, shape-key paths —
#: values compare only between rounds whose shape keys all match)
SERIES: Tuple[Tuple[str, Tuple[str, ...], Tuple[Tuple[str, ...], ...]], ...] = (
    ("iters_per_sec", ("value",),
     (("n_rows",), ("platform",))),
    ("vs_baseline", ("vs_baseline",),
     (("n_rows",), ("platform",))),
    ("predict_rows_per_sec", ("predict", "engine_rows_per_sec"),
     (("predict", "rows"), ("predict", "n_trees"))),
    ("serve_rows_per_sec", ("serve", "rows_per_sec"),
     (("serve", "n_trees"), ("serve", "clients"))),
    ("ingest_push_rows_per_sec", ("ingest", "dense_push_rows_per_sec"),
     (("ingest", "rows"),)),
    ("online_cycles_per_sec", ("online", "cycles_per_sec"),
     (("online", "rows"), ("online", "cycles"))),
)

#: like SERIES but LOWER is better — a RISE past the threshold flags.
#: dispatches_per_iter is BENCH_ATTRIB's device-program launch count per
#: iteration (ISSUE 13): the boost_window collapse of the dispatch loop
#: must not silently regress between rounds.  ISSUE 14 adds the rest of
#: the attrib decomposition (dispatch / device-wait / drain, reported in
#: ms): the per-piece trajectory across BENCH_r*/BENCH_WINDOW_r* rounds
#: is what tells the next hardware window WHICH piece moved.
SERIES_LOWER: Tuple[Tuple[str, Tuple[str, ...],
                          Tuple[Tuple[str, ...], ...]], ...] = (
    ("dispatches_per_iter",
     ("attrib", "per_iter", "dispatches_per_iter"),
     (("n_rows",), ("platform",))),
    ("attrib_dispatch_ms",
     ("attrib", "per_iter", "dispatch_s"),
     (("n_rows",), ("platform",))),
    ("attrib_device_wait_ms",
     ("attrib", "per_iter", "device_wait_s"),
     (("n_rows",), ("platform",))),
    ("attrib_drain_ms",
     ("attrib", "per_iter", "drain_s"),
     (("n_rows",), ("platform",))),
)

#: value transform per series (the attrib seconds render as ms)
_SERIES_SCALE: Dict[str, float] = {
    "attrib_dispatch_ms": 1000.0,
    "attrib_device_wait_ms": 1000.0,
    "attrib_drain_ms": 1000.0,
}


def _series_value(rec: Any, name: str, path: Tuple[str, ...]) -> Any:
    v = _get(rec, path)
    if isinstance(v, (int, float)) and name in _SERIES_SCALE:
        return round(v * _SERIES_SCALE[name], 3)
    return v


def _get(d: Any, path: Tuple[str, ...]) -> Optional[Any]:
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def _parse_artifact(path: str) -> Optional[Dict[str, Any]]:
    """One round's parsed bench JSON, or None when the round left no
    usable record (red round: rc != 0 and nothing parsed)."""
    try:
        with open(path) as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    parsed = art.get("parsed")
    if isinstance(parsed, dict) and "value" in parsed:
        out = dict(parsed)
        out["_round"] = int(art.get("n", 0))
        out["_rc"] = art.get("rc")
        return out
    # fall back: last {...} line of the captured tail
    for line in reversed((art.get("tail") or "").splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                out = json.loads(line)
            except ValueError:
                continue
            if "value" in out:
                out["_round"] = int(art.get("n", 0))
                out["_rc"] = art.get("rc")
                return out
    return None


def load_rounds(repo: str = REPO) -> List[Dict[str, Any]]:
    """Every parseable BENCH_r*.json AND BENCH_WINDOW_r*.json, sorted by
    round number.  The window A/B artifacts carry the same parsed bench
    JSON (incl. the ``attrib`` section) at their own shape, so the
    same-shape guard keeps them from ever being compared against the
    full-scale rounds."""
    rounds = []
    for stem, pattern in (("BENCH_r*.json", r"BENCH_r(\d+)\.json$"),
                          ("BENCH_WINDOW_r*.json",
                           r"BENCH_WINDOW_r(\d+)\.json$")):
        for path in glob.glob(os.path.join(repo, stem)):
            m = re.search(pattern, path)
            if not m:
                continue
            rec = _parse_artifact(path)
            if rec is not None:
                if not rec.get("_round"):
                    rec["_round"] = int(m.group(1))
                rec["_file"] = os.path.basename(path)
                rounds.append(rec)
    return sorted(rounds, key=lambda r: (r["_round"], r["_file"]))


def trajectory(rounds: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One row per round: the SERIES values plus identifying shape."""
    rows = []
    for rec in rounds:
        row: Dict[str, Any] = {
            "round": rec["_round"], "file": rec.get("_file"),
            "n_rows": rec.get("n_rows"),
            "platform": rec.get("platform"),
            "sec_per_iter": rec.get("sec_per_iter"),
        }
        for name, path, _ in SERIES + SERIES_LOWER:
            v = _series_value(rec, name, path)
            if v is not None:
                row[name] = v
        rows.append(row)
    return rows


def regressions(rounds: List[Dict[str, Any]],
                threshold: float = REGRESSION_THRESHOLD
                ) -> List[Dict[str, Any]]:
    """Rounds whose series value moved > threshold the WRONG way vs the
    best PRIOR round at the same shape (below best for SERIES, above
    best for SERIES_LOWER)."""
    flags: List[Dict[str, Any]] = []
    for name, path, shape_paths, higher_better in \
            [s + (True,) for s in SERIES] + \
            [s + (False,) for s in SERIES_LOWER]:
        best: Dict[Tuple, Tuple[float, int]] = {}
        for rec in rounds:
            v = _series_value(rec, name, path)
            if not isinstance(v, (int, float)):
                continue
            shape = tuple(repr(_get(rec, sp)) for sp in shape_paths)
            prior = best.get(shape)
            if prior is not None and prior[0] > 0:
                worse = (v < prior[0] * (1.0 - threshold) if higher_better
                         else v > prior[0] * (1.0 + threshold))
                if worse:
                    flags.append({
                        "round": rec["_round"], "series": name,
                        "value": v, "best_prior": prior[0],
                        "best_prior_round": prior[1],
                        "drop_pct": round(abs(1.0 - v / prior[0]) * 100, 1),
                        "higher_is_better": higher_better,
                        "shape": shape,
                    })
            better = (prior is None or
                      (v > prior[0] if higher_better else v < prior[0]))
            if better:
                best[shape] = (float(v), rec["_round"])
    return sorted(flags, key=lambda f: (f["round"], f["series"]))


# ---------------------------------------------------------------------------
# quality-firewall artifacts (CHAOS_QUALITY_r*.json, ISSUE 12)
# ---------------------------------------------------------------------------

#: (series name, artifact-relative path, higher_is_better) — only the
#: canary detection window is treated as a performance series (how many
#: canary batches degradation took to catch; lower is better); the
#: quarantine/gate/rollback COUNTS are correctness evidence carried in
#: the trajectory rows and gated by the schema, not thresholds.
QUALITY_SERIES: Tuple[Tuple[str, Tuple[str, ...], bool], ...] = (
    ("canary_batches_to_rollback",
     ("phases", "canary", "canary_batches_to_rollback"), False),
)

_QUALITY_P1_REQUIRED = (
    ("quarantined_total", int),
    ("gate_rejections", int),
    ("published_generations", list),
    ("rejected_cycles", list),
    ("nonfinite_predictions", int),
    ("ok", bool),
)
_QUALITY_P2_REQUIRED = (
    ("rollback_count", int),
    ("canary_fraction", (int, float)),
    ("responses_bad_outside_canary", int),
    ("canary_events", dict),
    ("canary_batches", dict),
    ("ok", bool),
)


def validate_quality_artifact(rec: Any) -> List[str]:
    """Schema problems of one CHAOS_QUALITY artifact (empty = valid)."""
    problems: List[str] = []
    if not isinstance(rec, dict):
        return ["artifact is not a JSON object"]
    if not str(rec.get("artifact", "")).startswith("CHAOS_QUALITY_"):
        problems.append("artifact name %r does not start with "
                        "CHAOS_QUALITY_" % rec.get("artifact"))
    if not isinstance(rec.get("schema_version"), int):
        problems.append("schema_version missing or not an int")
    if not isinstance(rec.get("ok"), bool):
        problems.append("ok flag missing")
    phases = rec.get("phases")
    if not isinstance(phases, dict) or "ingest_gate" not in phases:
        problems.append("phases.ingest_gate missing")
        return problems
    p1 = phases["ingest_gate"]
    for key, typ in _QUALITY_P1_REQUIRED:
        if not isinstance(p1.get(key), typ):
            problems.append("ingest_gate: %s missing or wrong type" % key)
    p2 = phases.get("canary")
    if p2 is not None:
        for key, typ in _QUALITY_P2_REQUIRED:
            if not isinstance(p2.get(key), typ):
                problems.append("canary: %s missing or wrong type" % key)
        if p2.get("responses_bad_outside_canary"):
            problems.append("canary: responses_bad_outside_canary must be "
                            "0 — a regressed generation reached the "
                            "non-canary fleet")
        if p2.get("rollback_count") and \
                p2.get("rollback_byte_verified") is not True:
            problems.append("canary: rollback happened but was not "
                            "byte-verified against the restored "
                            "generation")
    return problems


def load_quality_rounds(repo: str = REPO):
    """(valid CHAOS_QUALITY rounds sorted, problems of invalid ones)."""
    rounds: List[Dict[str, Any]] = []
    problems: List[str] = []
    for path in glob.glob(os.path.join(repo, "CHAOS_QUALITY_r*.json")):
        m = re.search(r"CHAOS_QUALITY_r(\d+)\.json$", path)
        if not m:
            continue
        base = os.path.basename(path)
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except (OSError, ValueError) as e:
            problems.append("%s: unreadable (%s)" % (base, e))
            continue
        bad = validate_quality_artifact(rec)
        if bad:
            problems.append("%s: %s" % (base, "; ".join(bad)))
            continue
        rec["_round"] = int(m.group(1))
        rec["_file"] = base
        rounds.append(rec)
    return sorted(rounds, key=lambda r: r["_round"]), problems


def quality_trajectory(rounds: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One row per round: the firewall's counts + the canary window."""
    rows = []
    for rec in rounds:
        p1 = rec["phases"]["ingest_gate"]
        p2 = rec["phases"].get("canary") or {}
        rows.append({
            "round": rec["_round"], "ok": rec.get("ok"),
            "quarantined_total": p1.get("quarantined_total"),
            "gate_rejections": p1.get("gate_rejections"),
            "published_generations": len(
                p1.get("published_generations") or []),
            "rollback_count": p2.get("rollback_count"),
            "canary_batches_to_rollback":
                p2.get("canary_batches_to_rollback"),
            "canary_fraction": p2.get("canary_fraction"),
        })
    return rows


def quality_regressions(rounds: List[Dict[str, Any]],
                        threshold: float = REGRESSION_THRESHOLD
                        ) -> List[Dict[str, Any]]:
    """Rounds whose QUALITY_SERIES moved > threshold the wrong way vs
    the best prior round at the same canary_fraction."""
    flags: List[Dict[str, Any]] = []
    for name, path, higher_better in QUALITY_SERIES:
        best: Dict[Tuple, Tuple[float, int]] = {}
        for rec in rounds:
            v = _get(rec, path)
            if not isinstance(v, (int, float)):
                continue
            shape = (repr(_get(rec, ("phases", "canary",
                                     "canary_fraction"))),)
            prior = best.get(shape)
            if prior is not None and prior[0] > 0:
                worse = (v < prior[0] * (1.0 - threshold) if higher_better
                         else v > prior[0] * (1.0 + threshold))
                if worse:
                    flags.append({
                        "round": rec["_round"], "series": name,
                        "value": v, "best_prior": prior[0],
                        "best_prior_round": prior[1],
                        "change_pct": round((v / prior[0] - 1.0) * 100, 1),
                        "shape": shape,
                    })
            better = (prior is None or
                      (v > prior[0] if higher_better else v < prior[0]))
            if better:
                best[shape] = (float(v), rec["_round"])
    return sorted(flags, key=lambda f: (f["round"], f["series"]))


# ---------------------------------------------------------------------------
# cold-start artifacts (BENCH_COLD_r*.json, ISSUE 15)
# ---------------------------------------------------------------------------

#: (series name, artifact-relative path, higher_is_better) — every
#: startup series is lower-is-better: time-to-ready and
#: join-to-first-response regressing past the threshold flags.
COLD_SERIES: Tuple[Tuple[str, Tuple[str, ...], bool], ...] = (
    ("coldstart_ready_manifest_s",
     ("modes", "manifest", "time_to_ready_s"), False),
    ("coldstart_first_response_manifest_s",
     ("modes", "manifest", "time_to_first_response_s"), False),
    ("join_to_first_response_s",
     ("replica_join", "join_to_first_response_s"), False),
    ("train_startup_overhead_warm_s",
     ("train", "warm", "startup_overhead_s"), False),
)

_COLD_MODE_REQUIRED = (
    ("time_to_ready_s", (int, float)),
    ("time_to_first_response_s", (int, float)),
    ("verified", bool),
    ("steady_retraces", int),
    ("pred_sha256", str),
)


def validate_coldstart_artifact(rec: Any) -> List[str]:
    """Schema problems of one BENCH_COLD artifact (empty = valid).  The
    hard gates ride the schema: unverified responses, steady-state
    retraces, or non-identical predictions across start modes make the
    artifact INVALID, not just slow."""
    problems: List[str] = []
    if not isinstance(rec, dict):
        return ["artifact is not a JSON object"]
    if not str(rec.get("artifact", "")).startswith("BENCH_COLD_"):
        problems.append("artifact name %r does not start with BENCH_COLD_"
                        % rec.get("artifact"))
    if not isinstance(rec.get("schema_version"), int):
        problems.append("schema_version missing or not an int")
    if not isinstance(rec.get("ok"), bool):
        problems.append("ok flag missing")
    modes = rec.get("modes")
    if not isinstance(modes, dict):
        problems.append("modes missing")
        return problems
    for mode in ("cold", "cache", "manifest"):
        sec = modes.get(mode)
        if not isinstance(sec, dict):
            problems.append("mode %r missing" % mode)
            continue
        for key, typ in _COLD_MODE_REQUIRED:
            if not isinstance(sec.get(key), typ):
                problems.append("mode %r: %s missing or wrong type"
                                % (mode, key))
        if sec.get("verified") is False:
            problems.append("mode %r: response was NOT byte-verified "
                            "against the offline predictor" % mode)
        if sec.get("steady_retraces"):
            problems.append("mode %r: steady-state retraces recorded "
                            "(the zero-retrace pin must hold under every "
                            "start mode)" % mode)
    if rec.get("predictions_identical") is not True:
        problems.append("predictions_identical must be true — start "
                        "modes changed the served bytes")
    train = rec.get("train")
    if not isinstance(train, dict):
        problems.append("train section missing")
    else:
        for mode in ("cold", "warm"):
            sec = train.get(mode)
            if not isinstance(sec, dict) or not isinstance(
                    sec.get("startup_overhead_s"), (int, float)):
                problems.append("train %r: startup_overhead_s missing"
                                % mode)
        if train.get("model_identical") is not True:
            problems.append("train: model_identical must be true — the "
                            "persistent cache changed the trained bits")
    join = rec.get("replica_join")
    if join is not None:
        if not isinstance(join.get("join_to_first_response_s"),
                          (int, float)):
            problems.append("replica_join: join_to_first_response_s "
                            "missing")
        if join.get("verified") is not True:
            problems.append("replica_join: first response was not "
                            "byte-verified")
    return problems


def load_coldstart_rounds(repo: str = REPO):
    """(valid BENCH_COLD rounds sorted, problems of invalid ones)."""
    rounds: List[Dict[str, Any]] = []
    problems: List[str] = []
    for path in glob.glob(os.path.join(repo, "BENCH_COLD_r*.json")):
        m = re.search(r"BENCH_COLD_r(\d+)\.json$", path)
        if not m:
            continue
        base = os.path.basename(path)
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except (OSError, ValueError) as e:
            problems.append("%s: unreadable (%s)" % (base, e))
            continue
        bad = validate_coldstart_artifact(rec)
        if bad:
            problems.append("%s: %s" % (base, "; ".join(bad)))
            continue
        rec["_round"] = int(m.group(1))
        rec["_file"] = base
        rounds.append(rec)
    return sorted(rounds, key=lambda r: r["_round"]), problems


def coldstart_trajectory(rounds: List[Dict[str, Any]]
                         ) -> List[Dict[str, Any]]:
    rows = []
    for rec in rounds:
        row: Dict[str, Any] = {
            "round": rec["_round"], "platform": rec.get("platform"),
            "n_trees": rec.get("n_trees"), "ok": rec.get("ok"),
            "coldstart_ready_cold_s": _get(
                rec, ("modes", "cold", "time_to_ready_s")),
            "ready_speedup": _get(
                rec, ("speedup", "ready_cold_over_manifest")),
        }
        for name, path, _ in COLD_SERIES:
            v = _get(rec, path)
            if v is not None:
                row[name] = v
        rows.append(row)
    return rows


def coldstart_regressions(rounds: List[Dict[str, Any]],
                          threshold: float = REGRESSION_THRESHOLD
                          ) -> List[Dict[str, Any]]:
    """Rounds whose startup series ROSE > threshold vs the best prior
    round at the same (platform, n_trees) shape."""
    flags: List[Dict[str, Any]] = []
    for name, path, higher_better in COLD_SERIES:
        best: Dict[Tuple, Tuple[float, int]] = {}
        for rec in rounds:
            v = _get(rec, path)
            if not isinstance(v, (int, float)):
                continue
            shape = (repr(rec.get("platform")), repr(rec.get("n_trees")))
            prior = best.get(shape)
            if prior is not None and prior[0] > 0:
                worse = (v < prior[0] * (1.0 - threshold) if higher_better
                         else v > prior[0] * (1.0 + threshold))
                if worse:
                    flags.append({
                        "round": rec["_round"], "series": name,
                        "value": v, "best_prior": prior[0],
                        "best_prior_round": prior[1],
                        "change_pct": round((v / prior[0] - 1.0) * 100, 1),
                        "shape": shape,
                    })
            better = (prior is None or
                      (v > prior[0] if higher_better else v < prior[0]))
            if better:
                best[shape] = (float(v), rec["_round"])
    return sorted(flags, key=lambda f: (f["round"], f["series"]))


# ---------------------------------------------------------------------------
# wire data-plane artifacts (BENCH_WIRE_r*.json, ISSUE 16)
# ---------------------------------------------------------------------------

#: (series name, artifact-relative path, higher_is_better) — request
#: rates are higher-better, tail latency lower-better.  Shape key is
#: (platform, rows_per_request, conns, n_trees): a 1-row round must
#: never be compared against an 8-row round.
WIRE_SERIES: Tuple[Tuple[str, Tuple[str, ...], bool], ...] = (
    ("json_req_per_sec", ("paths", "json_tcp", "req_per_sec"), True),
    ("binary_tcp_req_per_sec",
     ("paths", "binary_tcp", "req_per_sec"), True),
    ("binary_uds_req_per_sec",
     ("paths", "binary_uds", "req_per_sec"), True),
    ("c_client_req_per_sec",
     ("paths", "c_client_uds", "req_per_sec"), True),
    ("fastconfig_req_per_sec",
     ("paths", "c_fastconfig", "req_per_sec"), True),
    # shared-memory ring transport (ISSUE 20, artifact schema v2) —
    # absent from pre-ring (v1) artifacts and silently skipped there
    ("shm_req_per_sec", ("paths", "binary_shm", "req_per_sec"), True),
    ("binary_uds_p99_ms", ("paths", "binary_uds", "p99_ms"), False),
    ("shm_p99_ms", ("paths", "binary_shm", "p99_ms"), False),
    ("offered_p99_ms", ("offered", "p99_ms"), False),
)

#: keys every socket-path section must carry; `verified` false or a
#: nonzero mismatch count is an INVALID artifact, not a slow one —
#: throughput at wrong answers is not throughput.
_WIRE_PATH_REQUIRED = (
    ("req_per_sec", (int, float)),
    ("verified", bool),
    ("prediction_mismatches", int),
)


def validate_wire_artifact(rec: Any) -> List[str]:
    """Schema problems of one BENCH_WIRE artifact (empty = valid)."""
    problems: List[str] = []
    if not isinstance(rec, dict):
        return ["artifact is not a JSON object"]
    if not str(rec.get("artifact", "")).startswith("BENCH_WIRE_"):
        problems.append("artifact name %r does not start with BENCH_WIRE_"
                        % rec.get("artifact"))
    if not isinstance(rec.get("schema_version"), int):
        problems.append("schema_version missing or not an int")
    if not isinstance(rec.get("ok"), bool):
        problems.append("ok flag missing")
    paths = rec.get("paths")
    if not isinstance(paths, dict) or not paths:
        problems.append("paths missing or empty")
        return problems
    sv = rec.get("schema_version")
    required_paths = ["json_tcp", "binary_tcp", "binary_uds"]
    if isinstance(sv, int) and sv >= 2:
        # the shm ring transport (ISSUE 20) is part of the contract
        # from schema v2 on; r16-era v1 artifacts stay valid without it
        required_paths.append("binary_shm")
    for pname in required_paths:
        sec = paths.get(pname)
        if not isinstance(sec, dict):
            problems.append("path %r missing" % pname)
            continue
        for key, typ in _WIRE_PATH_REQUIRED:
            if not isinstance(sec.get(key), typ):
                problems.append("path %r: %s missing or wrong type"
                                % (pname, key))
        if sec.get("verified") is False:
            problems.append("path %r: responses were NOT byte-verified "
                            "against the offline predictor" % pname)
        if sec.get("prediction_mismatches"):
            problems.append("path %r: %s prediction mismatch(es) — the "
                            "wire bytes disagreed with the offline "
                            "predictor" % (pname,
                                           sec["prediction_mismatches"]))
    if isinstance(sv, int) and sv >= 2:
        plane = rec.get("shm_plane")
        if not isinstance(plane, dict):
            problems.append("shm_plane section missing (required from "
                            "schema v2)")
        else:
            if plane.get("verified") is not True:
                problems.append("shm_plane: responses were NOT "
                                "byte-verified against the offline "
                                "predictor")
            if plane.get("prediction_mismatches") != 0:
                problems.append("shm_plane: prediction_mismatches must "
                                "be exactly 0, got %r"
                                % (plane.get("prediction_mismatches"),))
    for pname, sec in paths.items():
        if isinstance(sec, dict) and sec.get("prediction_mismatches"):
            if not any(pname in p for p in problems):
                problems.append("path %r: %s prediction mismatch(es)"
                                % (pname, sec["prediction_mismatches"]))
    offered = rec.get("offered")
    if not isinstance(offered, dict) or not isinstance(
            offered.get("offered_per_sec"), (int, float)):
        problems.append("offered section missing offered_per_sec")
    gates = rec.get("gates")
    if not isinstance(gates, dict):
        problems.append("gates section missing")
    else:
        for g, val in sorted(gates.items()):
            if val is not True:
                problems.append("gate %r did not hold" % g)
    return problems


def load_wire_rounds(repo: str = REPO):
    """(valid BENCH_WIRE rounds sorted, problems of invalid ones)."""
    rounds: List[Dict[str, Any]] = []
    problems: List[str] = []
    for path in glob.glob(os.path.join(repo, "BENCH_WIRE_r*.json")):
        m = re.search(r"BENCH_WIRE_r(\d+)\.json$", path)
        if not m:
            continue
        base = os.path.basename(path)
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except (OSError, ValueError) as e:
            problems.append("%s: unreadable (%s)" % (base, e))
            continue
        bad = validate_wire_artifact(rec)
        if bad:
            problems.append("%s: %s" % (base, "; ".join(bad)))
            continue
        rec["_round"] = int(m.group(1))
        rec["_file"] = base
        rounds.append(rec)
    return sorted(rounds, key=lambda r: r["_round"]), problems


def _wire_shape(rec: Dict[str, Any]) -> Tuple:
    return (repr(rec.get("platform")),
            repr(rec.get("rows_per_request")),
            repr(rec.get("conns")),
            repr(_get(rec, ("model", "n_trees"))))


def wire_trajectory(rounds: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    rows = []
    for rec in rounds:
        row: Dict[str, Any] = {
            "round": rec["_round"], "platform": rec.get("platform"),
            "rows_per_request": rec.get("rows_per_request"),
            "conns": rec.get("conns"), "ok": rec.get("ok"),
            "speedup_binary_uds_over_json": _get(
                rec, ("speedup", "binary_uds_over_json")),
            "speedup_shm_over_uds": _get(
                rec, ("speedup", "shm_over_uds")),
            "offered_per_sec": _get(rec, ("offered", "offered_per_sec")),
        }
        for name, path, _ in WIRE_SERIES:
            v = _get(rec, path)
            if v is not None:
                row[name] = v
        rows.append(row)
    return rows


def wire_regressions(rounds: List[Dict[str, Any]],
                     threshold: float = REGRESSION_THRESHOLD
                     ) -> List[Dict[str, Any]]:
    """Rounds whose wire series moved > threshold the WRONG way vs the
    best prior round at the same shape."""
    flags: List[Dict[str, Any]] = []
    for name, path, higher_better in WIRE_SERIES:
        best: Dict[Tuple, Tuple[float, int]] = {}
        for rec in rounds:
            v = _get(rec, path)
            if not isinstance(v, (int, float)):
                continue
            shape = _wire_shape(rec)
            prior = best.get(shape)
            if prior is not None and prior[0] > 0:
                worse = (v < prior[0] * (1.0 - threshold) if higher_better
                         else v > prior[0] * (1.0 + threshold))
                if worse:
                    flags.append({
                        "round": rec["_round"], "series": name,
                        "value": v, "best_prior": prior[0],
                        "best_prior_round": prior[1],
                        "change_pct": round((v / prior[0] - 1.0) * 100, 1),
                        "shape": shape,
                    })
            better = (prior is None or
                      (v > prior[0] if higher_better else v < prior[0]))
            if better:
                best[shape] = (float(v), rec["_round"])
    return sorted(flags, key=lambda f: (f["round"], f["series"]))


# ---------------------------------------------------------------------------
# production-sim artifacts (SIM_r*.json, ISSUE 11)
# ---------------------------------------------------------------------------

#: (series name, scenario-relative path, higher_is_better)
SIM_SERIES: Tuple[Tuple[str, Tuple[str, ...], bool], ...] = (
    ("p99_latency_s", ("latency_s", "p99"), False),
    ("staleness_p50_s", ("staleness_s", "p50"), False),
    ("capacity_rows_per_sec_per_replica",
     ("capacity_rows_per_sec_per_replica",), True),
    # elastic-fleet efficiency (ISSUE 17): cost per verified outcome
    # and how fast added capacity clears an SLO breach — both lower-
    # better; absent from pre-fleet artifacts and silently skipped
    ("fleet_replica_s_per_1M_verified",
     ("fleet", "replica_seconds_per_million_verified"), False),
    ("fleet_scale_up_reaction_s",
     ("fleet", "scale_up_reaction_s_max"), False),
)

#: scenario keys every SIM artifact must carry with these types; the
#: schema gate that makes a malformed sim run fail loudly
_SIM_SCENARIO_REQUIRED = (
    ("objective", str),
    ("latency_s", dict),
    ("staleness_s", dict),
    ("capacity_rows_per_sec_per_replica", (int, float)),
    ("classes", dict),
    ("verification", dict),
    ("ok", bool),
)


def validate_sim_artifact(rec: Any) -> List[str]:
    """Schema problems of one SIM artifact dict (empty = valid)."""
    problems: List[str] = []
    if not isinstance(rec, dict):
        return ["artifact is not a JSON object"]
    if not str(rec.get("artifact", "")).startswith("SIM_"):
        problems.append("artifact name %r does not start with SIM_"
                        % rec.get("artifact"))
    if not isinstance(rec.get("schema_version"), int):
        problems.append("schema_version missing or not an int")
    if not isinstance(rec.get("replicas"), int) or rec.get("replicas", 0) < 1:
        problems.append("replicas missing or < 1")
    if not isinstance(rec.get("ok"), bool):
        problems.append("ok flag missing")
    scenarios = rec.get("scenarios")
    if not isinstance(scenarios, dict) or not scenarios:
        problems.append("scenarios missing or empty")
        return problems
    for name, sec in scenarios.items():
        if not isinstance(sec, dict):
            problems.append("scenario %r is not an object" % name)
            continue
        for key, typ in _SIM_SCENARIO_REQUIRED:
            if not isinstance(sec.get(key), typ):
                problems.append("scenario %r: %s missing or wrong type"
                                % (name, key))
        for hkey in ("latency_s", "staleness_s"):
            h = sec.get(hkey)
            if isinstance(h, dict):
                for q in ("p50", "p99", "count"):
                    if q not in h:
                        problems.append("scenario %r: %s.%s missing"
                                        % (name, hkey, q))
        for cname, cls in (sec.get("classes") or {}).items():
            if not isinstance(cls, dict):
                problems.append("scenario %r: class %r is not an object"
                                % (name, cname))
                continue
            for key in ("priority", "offered", "completed", "shed",
                        "shed_rate", "reasons"):
                if key not in cls:
                    problems.append("scenario %r: class %r misses %s"
                                    % (name, cname, key))
        # the fleet correctness gate (ISSUE 17): every completed
        # response must carry a verification verdict — a gap means the
        # byte-verifier silently skipped responses, which voids the
        # artifact's zero-mismatch claim
        vt, lc = sec.get("verified_total"), sec.get("loadgen_completed")
        if isinstance(vt, int) and isinstance(lc, int) and vt != lc:
            problems.append("scenario %r: verified_total %d != "
                            "loadgen_completed %d (unverified "
                            "completions)" % (name, vt, lc))
    return problems


def load_sim_rounds(repo: str = REPO):
    """(valid rounds sorted by number, problems of the invalid ones)."""
    rounds: List[Dict[str, Any]] = []
    problems: List[str] = []
    for path in glob.glob(os.path.join(repo, "SIM_r*.json")):
        m = re.search(r"SIM_r(\d+)\.json$", path)
        if not m:
            continue
        base = os.path.basename(path)
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except (OSError, ValueError) as e:
            problems.append("%s: unreadable (%s)" % (base, e))
            continue
        bad = validate_sim_artifact(rec)
        if bad:
            problems.append("%s: %s" % (base, "; ".join(bad)))
            continue
        rec["_round"] = int(m.group(1))
        rec["_file"] = base
        rounds.append(rec)
    return sorted(rounds, key=lambda r: r["_round"]), problems


def sim_trajectory(rounds: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """One row per (round, scenario) with the SIM_SERIES values."""
    rows = []
    for rec in rounds:
        for scen, sec in sorted(rec["scenarios"].items()):
            row: Dict[str, Any] = {
                "round": rec["_round"], "scenario": scen,
                "replicas": rec.get("replicas"),
                "duration_s": rec.get("duration_s"),
                "ok": sec.get("ok"),
            }
            for name, path, _ in SIM_SERIES:
                v = _get(sec, path)
                if v is not None:
                    row[name] = v
            rows.append(row)
    return rows


def sim_regressions(rounds: List[Dict[str, Any]],
                    threshold: float = REGRESSION_THRESHOLD
                    ) -> List[Dict[str, Any]]:
    """Rounds whose scenario series moved > threshold the WRONG way vs
    the best prior round at the same (scenario, replicas, duration)."""
    flags: List[Dict[str, Any]] = []
    for name, path, higher_better in SIM_SERIES:
        best: Dict[Tuple, Tuple[float, int]] = {}
        for rec in rounds:
            for scen, sec in sorted(rec["scenarios"].items()):
                v = _get(sec, path)
                if not isinstance(v, (int, float)):
                    continue
                shape = (scen, repr(rec.get("replicas")),
                         repr(rec.get("duration_s")))
                prior = best.get(shape)
                if prior is not None and prior[0] > 0:
                    worse = (v < prior[0] * (1.0 - threshold)
                             if higher_better
                             else v > prior[0] * (1.0 + threshold))
                    if worse:
                        flags.append({
                            "round": rec["_round"], "scenario": scen,
                            "series": name, "value": v,
                            "best_prior": prior[0],
                            "best_prior_round": prior[1],
                            "change_pct": round(
                                (v / prior[0] - 1.0) * 100, 1),
                            "shape": shape,
                        })
                better = (prior is None or
                          (v > prior[0] if higher_better else v < prior[0]))
                if better:
                    best[shape] = (float(v), rec["_round"])
    return sorted(flags, key=lambda f: (f["round"], f["scenario"],
                                        f["series"]))


def run(repo: str = REPO,
        threshold: float = REGRESSION_THRESHOLD) -> Dict[str, Any]:
    """Trajectory + all per-round regression flags.  The CHECK gates on
    the LATEST round only (``latest_regressions``): the tool runs after
    every round, so an old round's drop was that round's report — only a
    fresh drop should fail the current one.  SIM artifacts collate
    alongside with the same latest-round gating, plus a hard schema
    gate: an invalid SIM artifact always fails."""
    rounds = load_rounds(repo)
    flags = regressions(rounds, threshold)
    latest = rounds[-1]["_round"] if rounds else None
    sim_rounds, sim_problems = load_sim_rounds(repo)
    sim_flags = sim_regressions(sim_rounds, threshold)
    sim_latest = sim_rounds[-1]["_round"] if sim_rounds else None
    q_rounds, q_problems = load_quality_rounds(repo)
    q_flags = quality_regressions(q_rounds, threshold)
    q_latest = q_rounds[-1]["_round"] if q_rounds else None
    c_rounds, c_problems = load_coldstart_rounds(repo)
    c_flags = coldstart_regressions(c_rounds, threshold)
    c_latest = c_rounds[-1]["_round"] if c_rounds else None
    w_rounds, w_problems = load_wire_rounds(repo)
    w_flags = wire_regressions(w_rounds, threshold)
    w_latest = w_rounds[-1]["_round"] if w_rounds else None
    return {"rounds": len(rounds),
            "wire_rounds": len(w_rounds),
            "wire_latest_round": w_latest,
            "wire_trajectory": wire_trajectory(w_rounds),
            "wire_regressions": w_flags,
            "wire_latest_regressions": [f for f in w_flags
                                        if f["round"] == w_latest],
            "invalid_wire_artifacts": w_problems,
            "coldstart_rounds": len(c_rounds),
            "coldstart_latest_round": c_latest,
            "coldstart_trajectory": coldstart_trajectory(c_rounds),
            "coldstart_regressions": c_flags,
            "coldstart_latest_regressions": [f for f in c_flags
                                             if f["round"] == c_latest],
            "invalid_coldstart_artifacts": c_problems,
            "latest_round": latest,
            "trajectory": trajectory(rounds),
            "regressions": flags,
            "latest_regressions": [f for f in flags
                                   if f["round"] == latest],
            "sim_rounds": len(sim_rounds),
            "sim_latest_round": sim_latest,
            "sim_trajectory": sim_trajectory(sim_rounds),
            "sim_regressions": sim_flags,
            "sim_latest_regressions": [f for f in sim_flags
                                       if f["round"] == sim_latest],
            "invalid_sim_artifacts": sim_problems,
            "quality_rounds": len(q_rounds),
            "quality_latest_round": q_latest,
            "quality_trajectory": quality_trajectory(q_rounds),
            "quality_regressions": q_flags,
            "quality_latest_regressions": [f for f in q_flags
                                           if f["round"] == q_latest],
            "invalid_quality_artifacts": q_problems}


def main(argv=None) -> int:
    """Collate the artifacts in argv[0] (default: the repo root)."""
    argv = sys.argv[1:] if argv is None else argv
    rep = run(argv[0] if argv else REPO)
    cols = ["round", "n_rows", "platform", "iters_per_sec", "vs_baseline",
            "sec_per_iter"]
    print("bench_history: %d round(s) collated" % rep["rounds"])
    header = "  ".join("%-13s" % c for c in cols)
    print(header)
    for row in rep["trajectory"]:
        print("  ".join("%-13s" % (row.get(c, "-"),) for c in cols))
    for f in rep["regressions"]:
        kind = ("REGRESSION" if f["round"] == rep["latest_round"]
                else "historical regression")
        direction = ("below" if f.get("higher_is_better", True)
                     else "above")
        print("%s: round %d %s = %s is %.1f%% %s round %d's %s"
              % (kind, f["round"], f["series"], f["value"], f["drop_pct"],
                 direction, f["best_prior_round"], f["best_prior"]))
    print(json.dumps(rep["trajectory"][-1] if rep["trajectory"] else {}))
    if rep["sim_rounds"] or rep["invalid_sim_artifacts"]:
        print("bench_history: %d sim round(s) collated" % rep["sim_rounds"])
        sim_cols = ["round", "scenario", "p99_latency_s", "staleness_p50_s",
                    "capacity_rows_per_sec_per_replica", "ok"]
        print("  ".join("%-13s" % c for c in sim_cols))
        for row in rep["sim_trajectory"]:
            print("  ".join("%-13s" % (row.get(c, "-"),) for c in sim_cols))
        for f in rep["sim_regressions"]:
            kind = ("SIM REGRESSION"
                    if f["round"] == rep["sim_latest_round"]
                    else "historical sim regression")
            print("%s: round %d %s %s = %s moved %+.1f%% vs round %d's %s"
                  % (kind, f["round"], f["scenario"], f["series"],
                     f["value"], f["change_pct"], f["best_prior_round"],
                     f["best_prior"]))
        for p in rep["invalid_sim_artifacts"]:
            print("INVALID SIM ARTIFACT: %s" % p)
    if rep["quality_rounds"] or rep["invalid_quality_artifacts"]:
        print("bench_history: %d quality round(s) collated"
              % rep["quality_rounds"])
        q_cols = ["round", "quarantined_total", "gate_rejections",
                  "rollback_count", "canary_batches_to_rollback", "ok"]
        print("  ".join("%-13s" % c for c in q_cols))
        for row in rep["quality_trajectory"]:
            print("  ".join("%-13s" % (row.get(c, "-"),) for c in q_cols))
        for f in rep["quality_regressions"]:
            kind = ("QUALITY REGRESSION"
                    if f["round"] == rep["quality_latest_round"]
                    else "historical quality regression")
            print("%s: round %d %s = %s moved %+.1f%% vs round %d's %s"
                  % (kind, f["round"], f["series"], f["value"],
                     f["change_pct"], f["best_prior_round"],
                     f["best_prior"]))
        for p in rep["invalid_quality_artifacts"]:
            print("INVALID QUALITY ARTIFACT: %s" % p)
    if rep["coldstart_rounds"] or rep["invalid_coldstart_artifacts"]:
        print("bench_history: %d coldstart round(s) collated"
              % rep["coldstart_rounds"])
        c_cols = ["round", "platform", "coldstart_ready_manifest_s",
                  "join_to_first_response_s",
                  "train_startup_overhead_warm_s", "ok"]
        print("  ".join("%-13s" % c for c in c_cols))
        for row in rep["coldstart_trajectory"]:
            print("  ".join("%-13s" % (row.get(c, "-"),) for c in c_cols))
        for f in rep["coldstart_regressions"]:
            kind = ("COLDSTART REGRESSION"
                    if f["round"] == rep["coldstart_latest_round"]
                    else "historical coldstart regression")
            print("%s: round %d %s = %s moved %+.1f%% vs round %d's %s"
                  % (kind, f["round"], f["series"], f["value"],
                     f["change_pct"], f["best_prior_round"],
                     f["best_prior"]))
        for p in rep["invalid_coldstart_artifacts"]:
            print("INVALID COLDSTART ARTIFACT: %s" % p)
    if rep["wire_rounds"] or rep["invalid_wire_artifacts"]:
        print("bench_history: %d wire round(s) collated"
              % rep["wire_rounds"])
        w_cols = ["round", "json_req_per_sec", "binary_uds_req_per_sec",
                  "speedup_binary_uds_over_json", "offered_p99_ms", "ok"]
        print("  ".join("%-13s" % c for c in w_cols))
        for row in rep["wire_trajectory"]:
            print("  ".join("%-13s" % (row.get(c, "-"),) for c in w_cols))
        for f in rep["wire_regressions"]:
            kind = ("WIRE REGRESSION"
                    if f["round"] == rep["wire_latest_round"]
                    else "historical wire regression")
            print("%s: round %d %s = %s moved %+.1f%% vs round %d's %s"
                  % (kind, f["round"], f["series"], f["value"],
                     f["change_pct"], f["best_prior_round"],
                     f["best_prior"]))
        for p in rep["invalid_wire_artifacts"]:
            print("INVALID WIRE ARTIFACT: %s" % p)
    failed = bool(rep["latest_regressions"]
                  or rep["sim_latest_regressions"]
                  or rep["invalid_sim_artifacts"]
                  or rep["quality_latest_regressions"]
                  or rep["invalid_quality_artifacts"]
                  or rep["coldstart_latest_regressions"]
                  or rep["invalid_coldstart_artifacts"]
                  or rep["wire_latest_regressions"]
                  or rep["invalid_wire_artifacts"])
    if not failed:
        print("bench_history: OK (latest round has no >%.0f%% regression)"
              % (REGRESSION_THRESHOLD * 100))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
