#!/usr/bin/env python
"""Quality-firewall chaos soak (ISSUE 12 acceptance) — CHAOS_QUALITY_r12.

Drives the three-stage model-quality firewall end to end, with real
subprocesses on both sides of the publish seam, under the three new
data/model fault modes:

* **Phase 1 — ingest quarantine + eval gate** (`poison_rows`,
  `label_flip`): a `task=train_online` subprocess is relaunched across
  fault windows while the stream file grows.  Poisoned rows must land
  in the quarantine (never a window), the label-flipped cycle's
  candidate must be REJECTED by the pre-publish gate (persisted as
  ``rejected_<cycle>.txt``, a generation-number hole in the publish
  dir), and — the headline pin — **every published generation, when
  evaluated offline on a clean holdout, never regresses beyond the gate
  tolerance vs its predecessor and never emits a non-finite
  prediction**: injected poison never reaches a published model.
* **Phase 2 — canary + automatic rollback** (`regress_model`): the
  trainer subprocess publishes on a clock with the K-th publish
  sabotaged AFTER its own gate (the regression the offline gate cannot
  see); a serving-replica subprocess consumes the lineage with
  ``canary_fraction`` routing and labeled traffic.  Pins: the bad
  generation is **never served as the incumbent** (zero responses name
  it outside its canary window), the `CanaryPolicy` rolls the fleet
  back (durable ROLLBACK marker in the publish dir), and the rollback
  is **byte-verified** — post-rollback responses equal the restored
  generation's offline predictions for the served path.

Every count in the committed artifact is scraped from the METRICS
REGISTRY (the trainer's ``$LGBM_TPU_METRICS_FILE`` snapshots, the
replica's in-process snapshot), not from driver-side bookkeeping.

Usage:  python exp/chaos_quality.py [artifact.json] [--quick]
        python exp/chaos_quality.py --serve-replica <cfg.json> <out.json>
Env:    CHAOS_QUALITY_SEED, CHAOS_QUALITY_TIMEOUT
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.runtime import publish, resilience, telemetry  # noqa: E402

SCHEMA_VERSION = 1
ARTIFACT_NAME = "CHAOS_QUALITY_r12"

#: shared training surface: deterministic so relaunches replay cleanly
TRAIN_PARAMS = ["objective=binary", "num_leaves=7", "min_data_in_leaf=5",
                "metric=binary_logloss", "seed=7", "verbose=-1"]
GATE_ARGS = ["publish_gate_tolerance=0.1", "publish_gate_holdout=0.25",
             "online_quarantine_limit=0.6"]
N_FEATURES = 6


def gen_rows(n: int, rng: np.random.Generator) -> np.ndarray:
    X = rng.standard_normal((n, N_FEATURES))
    y = (X[:, 0] + 0.4 * X[:, 1]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float64)
    return np.column_stack([y, X])


def _append(path: str, rows: np.ndarray) -> None:
    with open(path, "a") as fh:
        np.savetxt(fh, rows, delimiter="\t", fmt="%.8g")


def _run_trainer(workdir: str, cycles: int, fault: Optional[str],
                 metrics_file: str, interval: float = 0.0,
                 timeout: float = 240.0) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULT", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
                "LGBM_TPU_METRICS_FILE": metrics_file})
    if fault:
        env["LGBM_TPU_FAULT"] = fault
    args = ([sys.executable, "-m", "lightgbm_tpu", "task=train_online",
             "data=train.tsv", "output_model=m.txt",
             "online_cycles=%d" % cycles, "online_rounds=2",
             "online_interval=%g" % interval, "publish_retention=1000",
             "publish_grace=600"] + TRAIN_PARAMS + GATE_ARGS)
    return subprocess.run(args, cwd=workdir, env=env, timeout=timeout,
                          capture_output=True, text=True)


def _scrape_counter(metrics_file: str, name: str,
                    by: Optional[str] = None) -> Dict[str, float]:
    """Per-label sums of one counter family from the LAST registry
    snapshot in a $LGBM_TPU_METRICS_FILE export."""
    out: Dict[str, float] = {}
    try:
        with open(metrics_file) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        snap = json.loads(lines[-1])
    except (OSError, ValueError, IndexError):
        return out
    fam = snap.get("metrics", {}).get(name, {})
    for entry in fam.get("series", []):
        key = entry.get("labels", {}).get(by, "_total") if by else "_total"
        out[key] = out.get(key, 0.0) + float(entry.get("value", 0.0))
    return out


def _logloss(model_text: str, X: np.ndarray, y: np.ndarray) -> float:
    import lightgbm_tpu as lgb
    bst = lgb.Booster(model_str=model_text, params={"verbose": -1})
    p = np.clip(np.asarray(bst.predict(X)), 1e-12, 1 - 1e-12)
    if not np.isfinite(p).all():
        return float("inf")
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


# ---------------------------------------------------------------------------
# phase 1: quarantine + gate
# ---------------------------------------------------------------------------

def run_phase1(workdir: str, seed: int = 11,
               launch_timeout: float = 240.0) -> Dict[str, Any]:
    os.makedirs(workdir, exist_ok=True)
    rng = np.random.default_rng(seed)
    data = os.path.join(workdir, "train.tsv")
    np.savetxt(data, gen_rows(700, rng), delimiter="\t", fmt="%.8g")
    eval_rows = gen_rows(1500, np.random.default_rng(seed + 999))
    X_eval, y_eval = eval_rows[:, 1:], eval_rows[:, 0]

    launches: List[Dict[str, Any]] = []
    flip_cycle = 3
    plan = [
        # (target cycle count, fault, tag)
        (2, "poison_rows:0.25", "poison"),
        (4, "label_flip:%d" % flip_cycle, "flip"),
        (6, None, "clean"),
    ]
    for i, (cycles, fault, tag) in enumerate(plan, 1):
        mfile = os.path.join(workdir, "metrics_l%d.json" % i)
        r = _run_trainer(workdir, cycles, fault, mfile,
                         timeout=launch_timeout)
        launches.append({
            "tag": tag, "fault": fault, "cycles_target": cycles,
            "rc": r.returncode,
            "quarantined": _scrape_counter(
                mfile, "lgbm_ingest_quarantined_total", by="reason"),
            "gate": _scrape_counter(mfile, "lgbm_publish_gate_total",
                                    by="verdict"),
            "cycles": _scrape_counter(mfile, "lgbm_online_cycles_total",
                                      by="status"),
        })
        if r.returncode != 0:
            launches[-1]["stderr_tail"] = (r.stderr or "")[-1500:]
            break
        _append(data, gen_rows(250, rng))

    pub_dir = os.path.join(workdir, "m.txt.pub")
    published: Dict[int, str] = {}
    for gen, path in publish.generation_paths(pub_dir):
        ok, _ = publish.validate_generation(path)
        if ok:
            with open(path) as fh:
                published[gen] = publish._split_validate(  # noqa: SLF001
                    fh.read())[0]
    rejections = publish.rejection_paths(pub_dir)

    # offline quality ledger: every published generation scored on a
    # CLEAN eval set — the "no poison was ever published" proof
    quality_by_gen = {g: _logloss(t, X_eval, y_eval)
                      for g, t in sorted(published.items())}
    regressions = []
    gens = sorted(quality_by_gen)
    for a, b in zip(gens, gens[1:]):
        la, lb = quality_by_gen[a], quality_by_gen[b]
        if not math.isfinite(lb) or (lb - la) / max(abs(la), 1e-12) > 0.15:
            regressions.append({"from_gen": a, "to_gen": b,
                                "logloss": [la, lb]})

    quarantined_total = sum(
        sum(lnch["quarantined"].values()) for lnch in launches)
    gate_rejects = sum(lnch["gate"].get("reject", 0) for lnch in launches)
    gate_passes = sum(lnch["gate"].get("pass", 0)
                      + lnch["gate"].get("no_incumbent", 0)
                      for lnch in launches)
    rec = {
        "launches": launches,
        "published_generations": gens,
        "rejected_cycles": [c for c, _ in rejections],
        "quarantined_total": int(quarantined_total),
        "gate_rejections": int(gate_rejects),
        "gate_passes": int(gate_passes),
        "offline_logloss_by_generation": {str(g): round(v, 6)
                                          for g, v in
                                          quality_by_gen.items()},
        "published_regressions": regressions,
        "nonfinite_predictions": sum(
            1 for v in quality_by_gen.values() if not math.isfinite(v)),
    }
    rec["ok"] = bool(
        all(lnch["rc"] == 0 for lnch in launches)
        and len(launches) == len(plan)
        and quarantined_total > 0                      # poison was caught
        and gate_rejects >= 1                          # the flip was caught
        and flip_cycle in rec["rejected_cycles"]       # ...and persisted
        and flip_cycle not in gens                     # ...and never shipped
        and rec["nonfinite_predictions"] == 0
        and not regressions                            # published lineage
        and len(gens) >= 4)                            # only ever improves
    return rec


# ---------------------------------------------------------------------------
# phase 2: canary + rollback (the serving replica subprocess)
# ---------------------------------------------------------------------------

def run_serve_replica(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One serving replica under canary routing + labeled traffic.
    Every response is verified against the offline predictor for the
    generation+path it reports; the record carries the full response
    ledger, the rollback byte-verification, and the registry snapshot."""
    from lightgbm_tpu.runtime.loadgen import ResponseVerifier
    from lightgbm_tpu.runtime.policy import CanaryPolicy
    from lightgbm_tpu.runtime.serving import ServingRuntime

    rng = np.random.default_rng(cfg["seed"])
    probe = rng.standard_normal((8, N_FEATURES))
    labels = (probe[:, 0] + 0.4 * probe[:, 1] > 0).astype(np.float64)
    pol = CanaryPolicy(min_samples=4, patience=2, error_ratio=1.4,
                       error_margin=0.02, promote_after=40)
    rt = ServingRuntime(publish_dir=cfg["pub_dir"], params={"verbose": -1},
                        poll_interval_s=0.05,
                        canary_fraction=float(cfg["canary_fraction"]),
                        canary_policy=pol)
    verifier = ResponseVerifier(probe, pub_dir=cfg["pub_dir"],
                                params={"verbose": -1})
    rt.start()
    deadline = time.monotonic() + 60
    while rt.generation() is None and time.monotonic() < deadline:
        time.sleep(0.05)
    if rt.generation() is None:
        rt.stop()
        raise RuntimeError("replica: no generation in %r" % cfg["pub_dir"])

    responses: List[Dict[str, Any]] = []
    verify_counts: Dict[str, int] = {}
    idx = np.arange(len(probe))
    rollback_verified = None
    rollbacks_seen = 0
    t_end = time.monotonic() + float(cfg["duration_s"])
    while time.monotonic() < t_end:
        incumbent_before = rt.generation()
        canary_before = rt.canary_generation()
        try:
            res = rt.predict(probe, label=labels, deadline_s=5.0)
        except BaseException as e:       # noqa: BLE001 — ledger
            responses.append({"error": "%s: %s" % (type(e).__name__, e)})
            time.sleep(0.05)
            continue
        verdict = verifier.verify(res, idx)
        verify_counts[verdict] = verify_counts.get(verdict, 0) + 1
        responses.append({
            "generation": res.generation, "served_by": res.served_by,
            "incumbent_at_submit": incumbent_before,
            "canary_at_submit": canary_before,
            "verdict": verdict,
        })
        if len(rt.rollback_events) > rollbacks_seen:
            # rollback byte-verification, AT the rollback moment (before
            # a later publish can open a fresh canary or promote): the
            # fleet must now serve the restored generation and its
            # responses must equal that generation's offline predictions
            rollbacks_seen = len(rt.rollback_events)
            restored = rt.rollback_events[-1]["pinned_generation"]
            ok = False
            for _ in range(30):
                r2 = rt.predict(probe, deadline_s=5.0)
                if r2.generation != restored:
                    continue             # a canary-window batch; retry
                refs = verifier.refs(restored)
                ok = bool(refs is not None and np.array_equal(
                    np.asarray(r2.values), refs[r2.served_by][idx]))
                break
            rollback_verified = ok if rollback_verified is None \
                else (rollback_verified and ok)
        time.sleep(float(cfg.get("request_interval_s", 0.04)))

    stats = rt.stats()
    snap = telemetry.snapshot("chaos_quality_replica")
    rt.stop()
    return {
        "responses": responses,
        "verify_counts": verify_counts,
        "stats": {k: stats[k] for k in
                  ("completed", "swaps", "rollbacks", "promotes",
                   "canary_batches", "batches_device", "batches_host")},
        "rollback_events": stats.get("rollback_events", []),
        "rollback_byte_verified": rollback_verified,
        "final_generation": rt.generation(),
        "rollback_marker": publish.read_rollback_marker(cfg["pub_dir"]),
        "snapshot": snap,
    }


def run_phase2(workdir: str, seed: int = 11, canary_fraction: float = 0.25,
               launch_timeout: float = 300.0) -> Dict[str, Any]:
    os.makedirs(workdir, exist_ok=True)
    rng = np.random.default_rng(seed)
    data = os.path.join(workdir, "train.tsv")
    np.savetxt(data, gen_rows(700, rng), delimiter="\t", fmt="%.8g")
    pub_dir = os.path.join(workdir, "m.txt.pub")
    mfile = os.path.join(workdir, "metrics_trainer.json")
    bad_cycle = 3

    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULT", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
                "LGBM_TPU_METRICS_FILE": mfile,
                "LGBM_TPU_FAULT": "regress_model:%d" % bad_cycle})
    interval = 1.5
    cycles = 5
    trainer_args = ([sys.executable, "-m", "lightgbm_tpu",
                     "task=train_online", "data=train.tsv",
                     "output_model=m.txt", "online_cycles=%d" % cycles,
                     "online_rounds=2", "online_interval=%g" % interval,
                     "publish_retention=1000", "publish_grace=600"]
                    + TRAIN_PARAMS + GATE_ARGS)
    t_log = open(os.path.join(workdir, "trainer.log"), "w")
    trainer = subprocess.Popen(trainer_args, cwd=workdir, env=env,
                               stdout=t_log, stderr=subprocess.STDOUT)
    try:
        # wait for generation 1, then launch the replica SUBPROCESS
        sub = publish.ModelSubscriber(pub_dir, attempts=1)
        deadline = time.monotonic() + 120
        while sub.resolve_once() is None:
            if trainer.poll() is not None:
                raise RuntimeError("trainer died before first publish")
            if time.monotonic() > deadline:
                raise RuntimeError("no generation published in time")
            time.sleep(0.1)
        cfg = {"pub_dir": pub_dir, "seed": seed + 1,
               "canary_fraction": canary_fraction,
               "duration_s": interval * (cycles + 3)}
        cfg_path = os.path.join(workdir, "replica.json")
        out_path = os.path.join(workdir, "replica.out.json")
        with open(cfg_path, "w") as fh:
            json.dump(cfg, fh)
        renv = dict(env)
        renv.pop("LGBM_TPU_FAULT", None)
        renv.pop("LGBM_TPU_METRICS_FILE", None)
        rlog = open(os.path.join(workdir, "replica.log"), "w")
        replica = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--serve-replica",
             cfg_path, out_path],
            cwd=workdir, env=renv, stdout=rlog, stderr=subprocess.STDOUT)
        rrc = replica.wait(timeout=launch_timeout)
        rlog.close()
        if rrc != 0:
            with open(rlog.name) as fh:
                raise RuntimeError("replica failed rc=%d: %s"
                                   % (rrc, fh.read()[-2000:]))
        trc = trainer.wait(timeout=launch_timeout)
    finally:
        if trainer.poll() is None:
            trainer.kill()
            trainer.wait(timeout=30)
        t_log.close()
    with open(out_path) as fh:
        rep = json.load(fh)

    canary_events = _sum_snapshot_counter(rep["snapshot"],
                                          "lgbm_canary_events_total",
                                          by="event")
    canary_batches = _sum_snapshot_counter(rep["snapshot"],
                                           "lgbm_canary_batches_total",
                                           by="kind")
    # the regressed generation must NEVER have been the incumbent: every
    # response naming it must have been a canary-window batch
    bad_outside_canary = [
        r for r in rep["responses"]
        if r.get("generation") == bad_cycle
        and r.get("incumbent_at_submit") == bad_cycle]
    bad_responses = sum(1 for r in rep["responses"]
                        if r.get("generation") == bad_cycle)
    verify = rep["verify_counts"]
    rec = {
        "trainer_rc": trc,
        "bad_generation": bad_cycle,
        "canary_fraction": canary_fraction,
        "responses_total": len(rep["responses"]),
        "responses_bad_generation": int(bad_responses),
        "responses_bad_outside_canary": len(bad_outside_canary),
        "verify_counts": verify,
        "canary_events": {k: int(v) for k, v in canary_events.items()},
        "canary_batches": {k: int(v) for k, v in canary_batches.items()},
        "rollback_count": int(rep["stats"]["rollbacks"]),
        "canary_batches_to_rollback": (
            rep["rollback_events"][-1].get("canary_batches")
            if rep["rollback_events"] else None),
        "rollback_byte_verified": rep["rollback_byte_verified"],
        "rollback_marker": rep["rollback_marker"],
        "final_generation": rep["final_generation"],
        "trainer_generations": _scrape_counter(
            mfile, "lgbm_online_cycles_total", by="status"),
    }
    total_batches = sum(canary_batches.values())
    canary_share = (canary_batches.get("canary", 0) / total_batches
                    if total_batches else 0.0)
    rec["canary_batch_share"] = round(canary_share, 4)
    rec["ok"] = bool(
        trc == 0
        and rec["rollback_count"] >= 1
        and canary_events.get("rollback", 0) >= 1
        and rec["responses_bad_outside_canary"] == 0
        and bad_cycle in rep["rollback_marker"].get("bad_generations", [])
        and rec["rollback_byte_verified"] is True
        and verify.get("ok", 0) > 0
        and verify.get("mismatch", 0) == 0
        and verify.get("wrong_generation", 0) == 0
        # routing held the canary near its configured share
        and canary_share <= canary_fraction + 0.15)
    return rec


def _sum_snapshot_counter(snap: Dict[str, Any], name: str,
                          by: Optional[str] = None) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for entry in snap.get("metrics", {}).get(name, {}).get("series", []):
        key = entry.get("labels", {}).get(by, "_total") if by else "_total"
        out[key] = out.get(key, 0.0) + float(entry.get("value", 0.0))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_soak(workdir: str, seed: int = 11, quick: bool = False,
             launch_timeout: float = 300.0) -> Dict[str, Any]:
    t0 = time.monotonic()
    rec: Dict[str, Any] = {
        "artifact": ARTIFACT_NAME,
        "schema_version": SCHEMA_VERSION,
        "t_start": resilience.wallclock(),
        "seed": seed,
        "phases": {},
    }
    rec["phases"]["ingest_gate"] = run_phase1(
        os.path.join(workdir, "phase1"), seed=seed,
        launch_timeout=launch_timeout)
    if not quick:
        rec["phases"]["canary"] = run_phase2(
            os.path.join(workdir, "phase2"), seed=seed,
            launch_timeout=launch_timeout)
    rec["elapsed_s"] = round(time.monotonic() - t0, 1)
    rec["ok"] = all(p["ok"] for p in rec["phases"].values())
    return rec


def main(argv: List[str]) -> int:
    if len(argv) > 1 and argv[1] == "--serve-replica":
        with open(argv[2]) as fh:
            cfg = json.load(fh)
        rec = run_serve_replica(cfg)
        resilience.atomic_write(argv[3], json.dumps(rec))
        return 0
    import tempfile
    quick = "--quick" in argv
    args = [a for a in argv[1:] if not a.startswith("--")]
    artifact = args[0] if args else os.path.join(REPO,
                                                 ARTIFACT_NAME + ".json")
    seed = int(os.environ.get("CHAOS_QUALITY_SEED", "11"))
    timeout = float(os.environ.get("CHAOS_QUALITY_TIMEOUT", "300"))
    with tempfile.TemporaryDirectory(prefix="lgbm_chaos_q_") as wd:
        rec = run_soak(wd, seed=seed, quick=quick, launch_timeout=timeout)
    from helper.bench_history import validate_quality_artifact
    problems = validate_quality_artifact(rec)
    if problems:
        debug = artifact + ".invalid"
        resilience.atomic_write(debug, json.dumps(rec, indent=1) + "\n")
        print("chaos_quality: INVALID artifact (debug copy at %s): %s"
              % (debug, "; ".join(problems)))
        return 2
    resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
    p1 = rec["phases"]["ingest_gate"]
    p2 = rec["phases"].get("canary", {})
    print("chaos_quality: ok=%s quarantined=%d gate_rejections=%d "
          "published=%s rollbacks=%s rollback_byte_verified=%s "
          "elapsed=%.0fs artifact=%s"
          % (rec["ok"], p1["quarantined_total"], p1["gate_rejections"],
             p1["published_generations"], p2.get("rollback_count", "-"),
             p2.get("rollback_byte_verified", "-"), rec["elapsed_s"],
             artifact), flush=True)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
