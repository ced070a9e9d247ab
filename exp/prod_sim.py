#!/usr/bin/env python
"""Closed-loop production simulation (ISSUE 11 acceptance).

Exercises everything PRs 6-10 built as ONE system under load: a
deterministic open-loop load generator (runtime/loadgen.py) drives a
REPLICATED serving fleet — N `ServingRuntime` subprocesses sharing one
publish directory through the concurrent-reader subscriber seam — while
the continuous trainer (`task=train_online`, its own subprocess) ingests
a GROWING stream and publishes on its absolute-clock schedule, and
`LGBM_TPU_FAULT` device kill/stall churn runs throughout.  The serving
replicas exercise the full ISSUE 11 knob set: priority classes with
per-class queue reservations, per-model quotas, and the queue-depth
hysteresis autoscale/shed policy.

Three scenarios ride the same harness: **binary**, **multiclass**, and
**lambdarank** ranking (the online path's newest workload — the stream
carries a query-id column, the rolling window trims on group
boundaries).

Every number in the committed ``SIM_r11.json`` artifact is scraped from
the METRICS REGISTRY of the replica processes (latency/staleness
histograms, per-class offered/shed counters, verification verdicts,
policy decisions), not from client-side stopwatches.  The correctness
bar is the chaos-soak bar, continuously applied: zero wrong-generation
responses and byte-identity of every completed response against the
offline predictor for the generation it reports.

ISSUE 17 adds ``--fleet``: the ELASTIC variant of the same harness — a
`FleetController` (runtime/fleet.py) autoscales replica subprocesses
against a p99 SLO under >=10x the r11 offered load, across a
120-tenant model zoo with bounded LRU residency, `die_at_spawn` +
mid-run SIGKILL churn, shed strictly as the last resort.  Artifact:
``SIM_r17.json``; runbook: docs/PRODSIM.md "Autoscaler runbook".

Usage:  python exp/prod_sim.py [artifact.json] [--quick]
        (default artifact: SIM_r11.json at the repo root; --quick runs
        the reduced binary-only smoke the tier-1 test uses)
        python exp/prod_sim.py [artifact.json] --fleet [--quick]
        (elastic-fleet scenarios -> SIM_r17.json; --quick runs the
        short diurnal-only smoke, gates not expected to pass at that
        duration)
        python exp/prod_sim.py --replica <cfg.json> <out.json>
        (internal: one serving replica + load generator)
Env:    PROD_SIM_SEED, PROD_SIM_REPLICAS, PROD_SIM_DURATION,
        PROD_SIM_LOAD_SCALE (--fleet: scales every shape's rps)
"""
from __future__ import annotations

import glob
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.runtime import publish, resilience, telemetry, \
    tracing  # noqa: E402

SCHEMA_VERSION = 1

#: trace-artifact schema (ISSUE 14): the merged Perfetto timeline +
#: machine gates committed as TRACE_r*.json
TRACE_SCHEMA_VERSION = 1

#: merged-trace size bound for the committed artifact (newest slices
#: kept; the cut is recorded, never silent)
TRACE_MAX_EVENTS = 20000

#: serving-side fault windows a replica's churn thread draws from
#: (None = quiet step); the armed fault kills or stalls every device
#: batch, so the replica must degrade to the host path and recover.
FAULT_POOL = [None, None, "die_at_predict:1", "slow_predict:0.6"]

#: the three workloads; `query` marks the ranking stream layout
#: (label, qid, features) consumed via query_column=0.
SCENARIOS: Dict[str, Dict[str, Any]] = {
    "binary": {
        "objective": "binary", "n_features": 8, "num_class": 1,
        "shape": {"kind": "diurnal"},
        "train_params": {"objective": "binary", "num_leaves": 15},
    },
    "multiclass": {
        "objective": "multiclass", "n_features": 8, "num_class": 4,
        "shape": {"kind": "bursty"},
        "train_params": {"objective": "multiclass", "num_class": 4,
                         "num_leaves": 15},
    },
    "lambdarank": {
        "objective": "lambdarank", "n_features": 8, "num_class": 1,
        "query": True, "query_rows": 8,
        "shape": {"kind": "step"},
        "train_params": {"objective": "lambdarank", "num_leaves": 15,
                         "min_data_in_leaf": 5},
    },
}


# ---------------------------------------------------------------------------
# stream data
# ---------------------------------------------------------------------------

def gen_rows(spec: Dict[str, Any], n: int, rng: np.random.Generator,
             next_qid: int = 0):
    """(file_rows, next_qid): one deterministic chunk of the scenario's
    stream file.  Ranking rows carry a globally increasing qid column so
    appended chunks keep query groups contiguous."""
    f = spec["n_features"]
    X = rng.standard_normal((n, f))
    score = X[:, 0] + 0.4 * X[:, 1] + 0.3 * rng.standard_normal(n)
    if spec["objective"] == "binary":
        y = (score > 0).astype(np.float64)
    elif spec["objective"] == "multiclass":
        edges = np.quantile(score, np.linspace(0, 1, spec["num_class"] + 1))
        y = np.clip(np.searchsorted(edges, score) - 1, 0,
                    spec["num_class"] - 1).astype(np.float64)
    else:                                   # lambdarank relevance 0..3
        y = np.clip((score * 1.5 + 1.5), 0, 3).round().astype(np.float64)
    if spec.get("query"):
        qsz = spec["query_rows"]
        n_groups = int(math.ceil(n / qsz))
        qid = np.repeat(np.arange(next_qid, next_qid + n_groups), qsz)[:n]
        rows = np.column_stack([y, qid.astype(np.float64), X])
        return rows, next_qid + n_groups
    return np.column_stack([y, X]), next_qid


class StreamAppender(threading.Thread):
    """Grows the scenario's stream file on an interval, so the trainer's
    tail-append ingest and the rolling window both actually move."""

    def __init__(self, path: str, spec: Dict[str, Any], rows_per_append: int,
                 interval_s: float, seed: int, next_qid: int):
        super().__init__(name="sim-appender", daemon=True)
        self.path = path
        self.spec = spec
        self.rows_per_append = rows_per_append
        self.interval_s = interval_s
        self.rng = np.random.default_rng(seed)
        self.next_qid = next_qid
        self.appended = 0
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.interval_s):
            rows, self.next_qid = gen_rows(self.spec, self.rows_per_append,
                                           self.rng, self.next_qid)
            with open(self.path, "a") as fh:
                np.savetxt(fh, rows, delimiter="\t", fmt="%.8g")
            self.appended += len(rows)

    def stop(self) -> None:
        self._halt.set()


# ---------------------------------------------------------------------------
# replica subprocess
# ---------------------------------------------------------------------------

def _make_shape(cfg_shape: Dict[str, Any], duration_s: float):
    from lightgbm_tpu.runtime.loadgen import TrafficShape
    kind = cfg_shape.get("kind", "diurnal")
    base = float(cfg_shape.get("base_rps", 30))
    peak = float(cfg_shape.get("peak_rps", 120))
    if kind == "diurnal":
        return TrafficShape.diurnal(base, peak, period_s=duration_s)
    if kind == "bursty":
        return TrafficShape.bursty(base, peak,
                                   period_s=max(duration_s / 4, 1.0),
                                   burst_len_s=max(duration_s / 16, 0.25))
    if kind == "step":
        third = duration_s / 3.0
        return TrafficShape.step([(third, base), (third, peak),
                                  (third, (base + peak) / 2)])
    raise ValueError("unknown shape kind %r" % kind)


class _FaultChurn(threading.Thread):
    """Seeded serving-fault windows: arm LGBM_TPU_FAULT for a step, then
    clear it for at least as long (the breaker needs quiet windows to
    run its recovery probe)."""

    def __init__(self, seed: int, step_s: float, ledger: List[str]):
        super().__init__(name="sim-fault-churn", daemon=True)
        self.rng = np.random.default_rng(seed)
        self.step_s = step_s
        self.ledger = ledger
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.step_s):
            fault = FAULT_POOL[int(self.rng.integers(0, len(FAULT_POOL)))]
            if fault is None:
                continue
            os.environ["LGBM_TPU_FAULT"] = fault
            self.ledger.append(fault)
            if self._halt.wait(self.step_s):
                break
        os.environ.pop("LGBM_TPU_FAULT", None)

    def stop(self) -> None:
        self._halt.set()
        os.environ.pop("LGBM_TPU_FAULT", None)


def run_replica(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """One serving replica: runtime + policy + fault churn + verifying
    load generator.  Returns the machine-readable record (ledger +
    runtime stats + the replica's full metrics snapshot)."""
    from lightgbm_tpu.runtime.loadgen import (LoadGenerator, RequestClass,
                                              ResponseVerifier)
    from lightgbm_tpu.runtime.policy import AutoscaleShedPolicy
    from lightgbm_tpu.runtime.serving import ServingRuntime

    tracing.set_context("replica_%s" % cfg["scenario"])
    spec = SCENARIOS[cfg["scenario"]]
    rng = np.random.default_rng(cfg["seed"])
    probe = rng.standard_normal((64, spec["n_features"]))
    policy = AutoscaleShedPolicy(**cfg.get("policy", {}))
    rt = ServingRuntime(
        publish_dir=cfg["pub_dir"], params={"verbose": -1},
        max_queue=int(cfg.get("max_queue", 64)),
        batch_window_s=0.002,
        predict_deadline_s=float(cfg.get("predict_deadline_s", 0.5)),
        breaker_cooldown_s=0.3, poll_interval_s=0.05,
        priority_levels=3, quotas=cfg.get("quotas") or None,
        policy=policy)
    rt.start()
    deadline = time.monotonic() + 60
    while rt.generation() is None and time.monotonic() < deadline:
        time.sleep(0.05)
    if rt.generation() is None:
        rt.stop()
        raise RuntimeError("replica: no generation appeared in %r"
                           % cfg["pub_dir"])

    classes = [RequestClass("gold", priority=0, weight=1.0, rows=2),
               RequestClass("silver", priority=1, weight=2.0, rows=4),
               RequestClass("bulk", priority=2, weight=3.0, rows=8)]
    shape = _make_shape(dict(spec["shape"], **cfg.get("shape", {})),
                        cfg["duration_s"])
    verifier = ResponseVerifier(probe, pub_dir=cfg["pub_dir"],
                                params={"verbose": -1})
    faults: List[str] = []
    churn = _FaultChurn(cfg["seed"] + 7,
                        step_s=float(cfg.get("fault_step_s", 1.0)),
                        ledger=faults)
    gen = LoadGenerator(rt, classes, shape, cfg["duration_s"], probe,
                        seed=cfg["seed"], verifier=verifier,
                        deadline_s=float(cfg.get("deadline_s", 2.0)),
                        # ISSUE 14: every 8th request is traced end to
                        # end; the ledger's `trace` section carries the
                        # stage-sum-vs-client-latency accounting
                        trace_every=int(cfg.get("trace_every", 8)))
    churn.start()
    try:
        ledger = gen.run()
    finally:
        churn.stop()
        churn.join(timeout=10)
        os.environ.pop("LGBM_TPU_FAULT", None)
    # post-churn settle so the breaker can demonstrate recovery
    time.sleep(0.3)
    stats = rt.stats()
    rt.stop()
    # flush this replica's flight recorder now (the atexit dump would
    # fire too, but an explicit flush cannot be lost to a hard exit)
    tracing.export_to_dir()
    return {
        "ledger": ledger,
        "stats": {k: stats[k] for k in
                  ("admitted", "completed", "rows_served", "batches_device",
                   "batches_host", "swaps", "degradations", "recoveries",
                   "rejected", "shed_active", "priority_levels")},
        "policy_decisions": policy.decisions,
        "faults_injected": faults,
        "final_generation": stats["generations"].get("default"),
        "snapshot": telemetry.snapshot("prod_sim_replica"),
    }


# ---------------------------------------------------------------------------
# registry scraping (the artifact's numbers)
# ---------------------------------------------------------------------------

def _hist_state(snapshots: List[Dict[str, Any]], name: str
                ) -> Dict[str, Any]:
    """Merged histogram state (summed counts over every replica and
    label set) for one metric family."""
    buckets = list(telemetry.METRIC_TABLE[name].get(
        "buckets", telemetry.LATENCY_BUCKETS_S))
    counts = [0] * len(buckets)
    total, cnt = 0.0, 0
    for snap in snapshots:
        for entry in snap.get("metrics", {}).get(name, {}).get("series", []):
            for i, v in enumerate(entry.get("counts", [])):
                counts[i] += v
            total += entry.get("sum", 0.0)
            cnt += entry.get("count", 0)
    return {"buckets": buckets, "counts": counts, "sum": total, "count": cnt}


def _sum_counter(snapshots: List[Dict[str, Any]], name: str,
                 by: Optional[str] = None) -> Dict[str, float]:
    """Summed counter values across replicas, keyed by label `by` (or
    "_total" when by is None)."""
    out: Dict[str, float] = {}
    for snap in snapshots:
        for entry in snap.get("metrics", {}).get(name, {}).get("series", []):
            key = entry.get("labels", {}).get(by, "_total") \
                if by else "_total"
            out[key] = out.get(key, 0.0) + entry.get("value", 0.0)
    return out


def _quantiles(state: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "p50": telemetry.quantile_from_state(state, 0.5),
        "p99": telemetry.quantile_from_state(state, 0.99),
        "count": state["count"],
        "mean": round(state["sum"] / state["count"], 6)
        if state["count"] else None,
    }


def collate_scenario(name: str, replica_records: List[Dict[str, Any]],
                     duration_s: float, trainer_info: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """One scenario's artifact section, scraped from the replicas'
    registry snapshots."""
    snaps = [r["snapshot"] for r in replica_records]
    ledgers = [r["ledger"] for r in replica_records]
    n_rep = len(replica_records)
    rows = _sum_counter(snaps, "lgbm_serve_rows_total").get("_total", 0.0)
    offered = _sum_counter(snaps, "lgbm_loadgen_offered_total", by="cls")
    verify = _sum_counter(snaps, "lgbm_loadgen_verified_total", by="result")
    policy = _sum_counter(snaps, "lgbm_policy_decisions_total", by="action")

    # per-priority-class outcome matrix -> per-class shed ledger
    class_names = {0: "gold", 1: "silver", 2: "bulk"}
    by_class: Dict[str, Dict[str, float]] = {}
    for snap in snaps:
        fam = snap.get("metrics", {}).get("lgbm_serve_class_requests_total",
                                          {})
        for entry in fam.get("series", []):
            lab = entry.get("labels", {})
            cls = lab.get("cls", "?")
            slot = by_class.setdefault(cls, {})
            slot[lab.get("outcome", "?")] = \
                slot.get(lab.get("outcome", "?"), 0.0) + entry["value"]
    classes: Dict[str, Any] = {}
    for p, cname in class_names.items():
        outcomes = by_class.get("p%d" % p, {})
        done = outcomes.get("completed", 0.0)
        shed = sum(v for k, v in outcomes.items() if k != "completed")
        off = offered.get(cname, 0.0)
        classes[cname] = {
            "priority": p,
            "offered": int(off),
            "completed": int(done),
            "shed": int(shed),
            "shed_rate": round(shed / off, 4) if off else 0.0,
            "reasons": {k: int(v) for k, v in outcomes.items()
                        if k != "completed"},
        }

    faults = sum((r["faults_injected"] for r in replica_records), [])
    # per-request stage decomposition accounting (ISSUE 14): every
    # sampled request's queue/gather/device/drain sum must land within
    # one latency-bucket width of its client-observed latency
    trace_secs = [led.get("trace") for led in ledgers
                  if led.get("trace")]
    trace_sec = {
        "sampled": sum(t["sampled"] for t in trace_secs),
        "with_stages": sum(t["with_stages"] for t in trace_secs),
        "stage_sum_within_bucket": sum(t["stage_sum_within_bucket"]
                                       for t in trace_secs),
        "stage_sum_max_err_s": max(
            (t["stage_sum_max_err_s"] for t in trace_secs
             if t["stage_sum_max_err_s"] is not None), default=None),
        "ok": bool(trace_secs) and all(t["ok"] for t in trace_secs),
    } if trace_secs else None
    sec = {
        "objective": SCENARIOS[name]["objective"],
        "replicas": n_rep,
        "duration_s": duration_s,
        "shape": ledgers[0]["shape"] if ledgers else None,
        "offered_total": int(sum(offered.values())),
        "offered_rps_mean": round(sum(offered.values())
                                  / max(duration_s, 1e-9), 2),
        "latency_s": _quantiles(_hist_state(snaps,
                                            "lgbm_serve_latency_seconds")),
        "staleness_s": _quantiles(_hist_state(
            snaps, "lgbm_serve_staleness_seconds")),
        "capacity_rows_per_sec_per_replica": round(
            rows / max(duration_s, 1e-9) / max(n_rep, 1), 2),
        "classes": classes,
        "verification": {k: int(v) for k, v in verify.items()},
        "non_machine_readable_rejections": sum(
            led["non_machine_readable_rejections"] for led in ledgers),
        "hard_errors": sum((led["hard_errors"] for led in ledgers), [])[:10],
        "served_by": {
            "device": sum(led["served_by"].get("device", 0)
                          for led in ledgers),
            "host": sum(led["served_by"].get("host", 0) for led in ledgers)},
        "degradations": sum(r["stats"]["degradations"]
                            for r in replica_records),
        "recoveries": sum(r["stats"]["recoveries"] for r in replica_records),
        "swaps": sum(r["stats"]["swaps"] for r in replica_records),
        "policy_decisions": {k: int(v) for k, v in policy.items()},
        "faults_injected": faults,
        "final_generations": [r["final_generation"]
                              for r in replica_records],
        "trainer": trainer_info,
    }
    # every completed response must have produced a verdict — a silent
    # verification undercount (e.g. a dead client-pool thread) fails the
    # scenario even when the verdicts that DID land are all clean
    sec["loadgen_completed"] = sum(
        sum(c["completed"] for c in led["classes"].values())
        for led in ledgers)
    sec["verified_total"] = int(sum(verify.values()))
    if trace_sec is not None:
        sec["trace"] = trace_sec
    wrong = sec["verification"].get("wrong_generation", 0) \
        + sec["verification"].get("mismatch", 0) \
        + sec["verification"].get("unverifiable", 0)
    sec["ok"] = bool(
        sec["verification"].get("ok", 0) > 0
        and sec["verified_total"] == sec["loadgen_completed"]
        and wrong == 0
        and not sec["hard_errors"]
        and sec["non_machine_readable_rejections"] == 0
        and trainer_info.get("generations", 0) >= 2
        and min(g or 0 for g in sec["final_generations"]) >= 2
        # churn must actually have pushed traffic onto the host path
        and (not faults or sec["served_by"]["host"] > 0)
        # sampled tracing ran: every stage sum within its bucket width
        and (trace_sec is None or trace_sec["ok"]))
    return sec


# ---------------------------------------------------------------------------
# merged-trace verification (the TRACE_r* artifact's machine gates)
# ---------------------------------------------------------------------------

def verify_merged_trace(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Machine gates over one merged timeline (ISSUE 14 acceptance):

    * ``request_chain_ok`` — some trace id carries a loadgen client
      span AND the server-side device + drain stage slices (the
      loadgen → serving → device batch → drain chain);
    * ``publish_link_ok`` — some publish flow arrow starts in one
      process (the trainer) and ends in ANOTHER (a replica's swap-in):
      the trainer cycle → publish → subscriber link;
    * ``cycle_spans`` / ``serve_batches`` — both sides of the system
      actually recorded their timelines.
    """
    evs = doc.get("traceEvents", [])
    by_trace: Dict[str, set] = {}
    for e in evs:
        t = (e.get("args") or {}).get("trace")
        if t:
            by_trace.setdefault(t, set()).add(str(e.get("name")))
    request_chain = sum(
        1 for names in by_trace.values()
        if {"req/device", "req/drain"} <= names
        and any(n.startswith("client request") for n in names))
    s_pids = {e.get("id"): e.get("pid") for e in evs if e.get("ph") == "s"}
    cross_links = sum(1 for e in evs if e.get("ph") == "f"
                      and e.get("id") in s_pids
                      and e.get("pid") != s_pids[e.get("id")])
    cycles = sum(1 for e in evs
                 if str(e.get("name", "")).startswith("cycle "))
    batches = sum(1 for e in evs if e.get("name") == "serve batch")
    rec = {
        "events": len([e for e in evs if e.get("ph") != "M"]),
        "processes": len({e.get("pid") for e in evs}),
        "request_chains": request_chain,
        "request_chain_ok": request_chain > 0,
        "publish_cross_process_links": cross_links,
        "publish_link_ok": cross_links > 0,
        "cycle_spans": cycles,
        "serve_batches": batches,
    }
    rec["ok"] = bool(rec["request_chain_ok"] and rec["publish_link_ok"]
                     and cycles > 0 and batches > 0)
    return rec


# ---------------------------------------------------------------------------
# one scenario end to end
# ---------------------------------------------------------------------------

def run_scenario(name: str, workdir: str, replicas: int = 2,
                 duration_s: float = 20.0, interval_s: float = 3.0,
                 seed: int = 11, initial_rows: int = 1200,
                 window_rows: int = 2000, log=print) -> Dict[str, Any]:
    spec = SCENARIOS[name]
    sdir = os.path.join(workdir, name)
    os.makedirs(sdir, exist_ok=True)
    pub_dir = os.path.join(sdir, "pub")
    data_path = os.path.join(sdir, "stream.tsv")

    rng = np.random.default_rng(seed)
    rows, next_qid = gen_rows(spec, initial_rows, rng)
    np.savetxt(data_path, rows, delimiter="\t", fmt="%.8g")

    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULT", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the trainer and every replica share ONE persistent compile cache
    # (warmup.enable_compile_cache: the same fixed directory in every
    # process) instead of each paying the cold compile
    # every process of the fleet self-collects its trace ring here
    # (ISSUE 14): the trainer's cycles + publishes, each replica's
    # requests/batches/swaps — merged below into ONE timeline
    traces_dir = os.path.join(sdir, "traces")
    env[tracing.TRACE_DIR_ENV] = traces_dir
    # one causal umbrella for the scenario's whole fleet: every child's
    # root spans parent under this context (the env-seed passthrough)
    env[tracing.TRACEPARENT_ENV] = tracing.make_traceparent(
        tracing.new_trace_id(), tracing.new_span_id())

    # -- the continuous trainer: its own process, publishing forever ------
    train_args = ["task=train_online", "data=" + data_path,
                  "output_model=" + os.path.join(sdir, "model.txt"),
                  "publish_dir=" + pub_dir,
                  "online_interval=%g" % interval_s,
                  "online_cycles=0", "online_rounds=3",
                  "online_window_rows=%d" % window_rows,
                  # retention must cover the whole run: the verifier
                  # re-reads any generation a response names
                  "publish_retention=1000", "publish_grace=600",
                  "verbose=-1"]
    if spec.get("query"):
        train_args.append("query_column=0")
    for k, v in spec["train_params"].items():
        train_args.append("%s=%s" % (k, v))
    t_log = open(os.path.join(sdir, "trainer.log"), "w")
    trainer = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu"] + train_args,
        cwd=sdir, env=env, stdout=t_log, stderr=subprocess.STDOUT)

    appender = StreamAppender(data_path, spec,
                              rows_per_append=max(window_rows // 8, 100),
                              interval_s=max(interval_s / 2, 0.5),
                              seed=seed + 1, next_qid=next_qid)
    appender.start()

    try:
        # wait for generation 1 before pointing replicas at the dir
        sub = publish.ModelSubscriber(pub_dir, attempts=1)
        deadline = time.monotonic() + max(duration_s * 3, 120)
        while sub.resolve_once() is None:
            if trainer.poll() is not None:
                raise RuntimeError(
                    "trainer died before the first publish (see %s)"
                    % t_log.name)
            if time.monotonic() > deadline:
                raise RuntimeError("no generation published in time")
            time.sleep(0.1)

        # -- the replica fleet -------------------------------------------
        procs = []
        for r in range(replicas):
            cfg = {"scenario": name, "pub_dir": pub_dir,
                   "duration_s": duration_s, "seed": seed + 100 * (r + 1),
                   "quotas": {"default": 0.75},
                   "policy": {"high_watermark": 0.6, "low_watermark": 0.2,
                              "patience": 3, "interval_s": 0.05},
                   "fault_step_s": max(duration_s / 12, 0.5)}
            cfg_path = os.path.join(sdir, "replica%d.json" % r)
            out_path = os.path.join(sdir, "replica%d.out.json" % r)
            with open(cfg_path, "w") as fh:
                json.dump(cfg, fh)
            rlog = open(os.path.join(sdir, "replica%d.log" % r), "w")
            procs.append((subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--replica",
                 cfg_path, out_path],
                cwd=sdir, env=env, stdout=rlog, stderr=subprocess.STDOUT),
                out_path, rlog))
        records = []
        for proc, out_path, rlog in procs:
            rc = proc.wait(timeout=duration_s * 6 + 180)
            rlog.close()
            if rc != 0:
                with open(rlog.name) as fh:
                    raise RuntimeError("replica failed (rc=%d): %s"
                                       % (rc, fh.read()[-2000:]))
            with open(out_path) as fh:
                records.append(json.load(fh))
    finally:
        appender.stop()
        trainer.send_signal(signal.SIGTERM)
        try:
            trainer.wait(timeout=60)
        except subprocess.TimeoutExpired:
            trainer.kill()
        t_log.close()

    latest = publish.ModelPublisher(pub_dir).latest_valid()
    trainer_info = {
        "generations": latest.generation if latest else 0,
        "interval_s": interval_s,
        "rows_appended": appender.appended,
        "exit_rc": trainer.returncode,
    }
    sec = collate_scenario(name, records, duration_s, trainer_info)
    # fuse the fleet's per-process trace rings into ONE timeline and
    # gate it: the request chain and the publish→subscriber link must
    # both be visible in the merged view (ISSUE 14 acceptance)
    trace_files = sorted(glob.glob(os.path.join(traces_dir, "trace_*.json")))
    if trace_files:
        merged_path = os.path.join(sdir, "trace_merged.json")
        merged = tracing.merge_traces(trace_files, out_path=merged_path,
                                      max_events=TRACE_MAX_EVENTS)
        sec["trace_merged"] = dict(verify_merged_trace(merged),
                                   files=len(trace_files),
                                   file=merged_path)
        sec["ok"] = bool(sec["ok"] and sec["trace_merged"]["ok"])
    log("prod_sim[%s]: ok=%s offered=%d p99=%.3fs staleness_p50=%.1fs "
        "capacity=%.0f rows/s/replica sheds=%s gens=%s"
        % (name, sec["ok"], sec["offered_total"],
           sec["latency_s"]["p99"] or -1, sec["staleness_s"]["p50"] or -1,
           sec["capacity_rows_per_sec_per_replica"],
           {c: v["shed"] for c, v in sec["classes"].items()},
           trainer_info["generations"]))
    return sec


def run_sim(workdir: str, scenarios: Optional[List[str]] = None,
            replicas: int = 2, duration_s: float = 20.0,
            interval_s: float = 3.0, seed: int = 11,
            log=print) -> Dict[str, Any]:
    t0 = time.monotonic()
    out: Dict[str, Any] = {
        "artifact": "SIM_r11",
        "schema_version": SCHEMA_VERSION,
        "t_start": resilience.wallclock(),
        "replicas": replicas,
        "duration_s": duration_s,
        "seed": seed,
        "scenarios": {},
    }
    for name in (scenarios or list(SCENARIOS)):
        out["scenarios"][name] = run_scenario(
            name, workdir, replicas=replicas, duration_s=duration_s,
            interval_s=interval_s, seed=seed, log=log)
    out["elapsed_s"] = round(time.monotonic() - t0, 1)
    out["ok"] = bool(out["scenarios"]) and all(
        s["ok"] for s in out["scenarios"].values())
    return out


# ---------------------------------------------------------------------------
# elastic-fleet scenarios (ISSUE 17): SLO-driven autoscaling at 10x the
# r11 offered load, with a model-zoo tenant mix and fault churn killing
# replicas mid-scale-up
# ---------------------------------------------------------------------------

#: r11's committed offered_rps_mean for the binary scenario — the
#: baseline the >=10x fleet-load gate measures against (SIM_r11.json)
R11_OFFERED_RPS_MEAN = 149.75

#: registered model-zoo tenants per replica (bounded residency holds
#: only `max_resident` of them loaded; the rest page in on demand)
FLEET_TENANTS = 120

#: tenants that actually receive bulk traffic — more than
#: `max_resident` minus the default lineage, so LRU page-in/evict churn
#: runs for the whole scenario
FLEET_HOT_TENANTS = 8

FLEET_SCENARIOS: Dict[str, Dict[str, Any]] = {
    "fleet_diurnal": {
        "objective": "binary", "n_features": 8,
        "shape": {"kind": "diurnal", "base_rps": 700, "peak_rps": 2600},
        # the FIRST scale-up dies during its prewarm, before /healthz
        # ever answers ready — the relaunch path on the most expensive
        # death window (armed for every replica; only the matching
        # fleet spawn ordinal dies)
        "fault": "die_at_spawn:2",
    },
    "fleet_bursty": {
        "objective": "binary", "n_features": 8,
        # base leaves ONE replica slack between bursts (pressure breaks
        # per burst instead of fusing bursts into one long episode);
        # the burst itself saturates the whole box
        "shape": {"kind": "bursty", "base_rps": 800, "peak_rps": 3800},
        # the SECOND scale-up dies mid-prewarm: bursty's first episode
        # rides on one base replica, so killing spawn 2 would fuse the
        # burst and the relaunch into one fault-stretched episode the
        # reaction gate can't attribute to the autoscaler
        "fault": "die_at_spawn:3",
    },
}


def _train_fleet_model(workdir: str, spec: Dict[str, Any],
                       seed: int) -> str:
    """One small real booster, trained once per sim run — every zoo
    tenant publishes the SAME text, so the byte-verifier's
    generation->reference map stays unambiguous across tenants."""
    from lightgbm_tpu.basic import Booster, Dataset
    path = os.path.join(workdir, "fleet_model.txt")
    if os.path.exists(path):
        with open(path) as fh:
            return fh.read()
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((600, spec["n_features"]))
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    ds = Dataset(X, label=y, params={"verbose": -1})
    bst = Booster(params={"objective": "binary", "num_leaves": 15,
                          "verbose": -1}, train_set=ds)
    for _ in range(3):
        bst.update()
    text = bst.model_to_string()
    resilience.atomic_write(path, text)
    return text


def _publish_zoo(sdir: str, text: str) -> Dict[str, str]:
    """default + FLEET_TENANTS published model dirs (generation 1
    each); returns the model_id -> dir map the replica spec registers."""
    models: Dict[str, str] = {}
    for mid in ["default"] + ["t%03d" % i for i in range(FLEET_TENANTS)]:
        d = os.path.join(sdir, "zoo", mid)
        publish.ModelPublisher(d).publish(text)
        models[mid] = d
    return models


class _ReplicaKiller(threading.Thread):
    """SIGKILL one READY replica partway through the run — the abrupt
    fleet-level death (no drain, no final snapshot scrape) the
    controller must absorb with a relaunch."""

    def __init__(self, controller, at_s: float, ledger: List[str]):
        super().__init__(name="sim-replica-killer", daemon=True)
        self.controller = controller
        self.at_s = at_s
        self.ledger = ledger
        self._halt = threading.Event()

    def run(self) -> None:
        if self._halt.wait(self.at_s):
            return
        with self.controller._lock:         # noqa: SLF001 — sim harness
            ready = [h for h in self.controller.replicas
                     if h.ready and not h.retiring]
            if not ready:
                return
            victim = max(ready, key=lambda h: h.spawned_mono)
            try:
                victim.proc.kill()
                self.ledger.append("sigkill:%s" % victim.name)
            except OSError:
                pass

    def stop(self) -> None:
        self._halt.set()


def collate_fleet_scenario(name: str, ledger: Dict[str, Any],
                           fleet: Dict[str, Any],
                           snaps: List[Dict[str, Any]],
                           duration_s: float) -> Dict[str, Any]:
    """One fleet scenario's artifact section: loadgen ledger (client
    side — completions and byte-verification verdicts) + controller
    report (scale events, reactions, replica-seconds) + the replicas'
    last scraped registry snapshots (latency/staleness/residency)."""
    spec = FLEET_SCENARIOS[name]
    verification = {k: int(v) for k, v in
                    (ledger.get("verification") or {}).items()}
    completed = sum(c["completed"] for c in ledger["classes"].values())
    verified = int(sum(verification.values()))
    ok_verified = verification.get("ok", 0)
    residency = _sum_counter(snaps, "lgbm_serve_residency_events_total",
                             by="event")
    rows = _sum_counter(snaps, "lgbm_serve_rows_total").get("_total", 0.0)
    replica_s = float(fleet.get("replica_seconds") or 0.0)
    reactions = list(fleet.get("reactions_s") or [])
    reaction_max = max(reactions) if reactions else None
    shed_on_decisions = [d for d in (fleet.get("events") or [])
                         if d["action"] == "shed_on"]
    # shed is last resort: every shed_on grant must land while the
    # policy target is pinned at max_replicas
    tl = fleet.get("timeline") or []
    max_replicas = int(fleet["policy"]["max_replicas"])

    def _target_at(t_s: float) -> Optional[int]:
        at = None
        for row in tl:
            if row["t_s"] <= t_s:
                at = row["target"]
        return at

    shed_only_at_max = all(
        (_target_at(e["t_s"]) or max_replicas) >= max_replicas
        for e in shed_on_decisions)
    spawn_to_ready = [e["spawn_to_ready_s"]
                      for e in (fleet.get("events") or [])
                      if e["action"] == "ready"]
    offered_x = (ledger["offered_rps_mean"] / R11_OFFERED_RPS_MEAN
                 if R11_OFFERED_RPS_MEAN else 0.0)
    wrong = verification.get("wrong_generation", 0) \
        + verification.get("mismatch", 0) \
        + verification.get("unverifiable", 0)
    sec: Dict[str, Any] = {
        "objective": spec["objective"],
        "replicas": max_replicas,
        "duration_s": duration_s,
        "shape": ledger["shape"],
        "offered_total": int(ledger["offered_total"]),
        "offered_rps_mean": ledger["offered_rps_mean"],
        "max_lag_s": ledger["max_lag_s"],
        "latency_s": _quantiles(_hist_state(snaps,
                                            "lgbm_serve_latency_seconds")),
        "staleness_s": _quantiles(_hist_state(
            snaps, "lgbm_serve_staleness_seconds")),
        "capacity_rows_per_sec_per_replica": round(
            rows / max(replica_s, 1e-9), 2),
        "classes": ledger["classes"],
        "verification": verification,
        "non_machine_readable_rejections":
            ledger["non_machine_readable_rejections"],
        "hard_errors": ledger["hard_errors"][:10],
        "served_by": dict(ledger["served_by"]),
        "loadgen_completed": completed,
        "verified_total": verified,
        "fleet": {
            "min_replicas": int(fleet["policy"]["min_replicas"]),
            "max_replicas": max_replicas,
            "scale_ups": int(fleet["scale_ups"]),
            "scale_downs": int(fleet["scale_downs"]),
            "relaunches": int(fleet["relaunches"]),
            "replica_seconds": round(replica_s, 3),
            "replica_seconds_per_million_verified": round(
                replica_s * 1e6 / ok_verified, 1) if ok_verified else None,
            "reactions_s": reactions,
            "scale_up_reaction_s_max": reaction_max,
            "spawn_to_ready_s": spawn_to_ready,
            "offered_x_r11": round(offered_x, 2),
            "shed_only_at_max": bool(shed_only_at_max),
            "shed_grants": len(shed_on_decisions),
            "faults_injected": fleet.get("faults_injected", []),
            "residency": {k: int(v) for k, v in residency.items()},
            "events": [e for e in (fleet.get("events") or [])
                       if e["action"] != "ready"],
            "timeline": tl,
        },
    }
    sec["ok"] = bool(
        ok_verified > 0
        and wrong == 0
        and verified == completed
        and not sec["hard_errors"]
        and sec["non_machine_readable_rejections"] == 0
        and sec["fleet"]["scale_ups"] >= 2
        and sec["fleet"]["scale_downs"] >= 1
        and sec["fleet"]["relaunches"] >= 1
        and (reaction_max is not None and reaction_max <= 15.0)
        and shed_only_at_max
        and offered_x >= 10.0)
    return sec


def run_fleet_scenario(name: str, workdir: str, duration_s: float = 40.0,
                       seed: int = 17, max_replicas: int = 4,
                       load_scale: float = 1.0,
                       log=print) -> Dict[str, Any]:
    """One elastic-fleet scenario end to end: zoo publish -> controller
    (min 1, max `max_replicas` replicas) -> verified open-loop load at
    >=10x r11 through the binary wire -> fault churn (die_at_spawn on
    the first scale-up + SIGKILL of a ready replica) -> collate."""
    from lightgbm_tpu.runtime.fleet import FleetClient, FleetController
    from lightgbm_tpu.runtime.loadgen import (LoadGenerator, RequestClass,
                                              ResponseVerifier)
    from lightgbm_tpu.runtime.policy import FleetScalePolicy

    spec = FLEET_SCENARIOS[name]
    sdir = os.path.join(workdir, name)
    os.makedirs(sdir, exist_ok=True)
    text = _train_fleet_model(workdir, spec, seed)
    models = _publish_zoo(sdir, text)

    # the whole fleet shares ONE persistent compile cache (the seam's
    # fixed directory): the first replica pays the compile, every later
    # spawn starts warm
    replica_spec = {
        "models": models,
        "params": {"verbose": -1},
        "response_dtype": "float32",
        "max_queue": 256,
        # the per-replica capacity knob: 8 rows per device dispatch
        # bounds one replica's throughput, so added replicas add real
        # capacity (and the autoscaler has something to scale)
        "max_batch_rows": 8,
        "batch_window_s": 0.002,
        "predict_deadline_s": 5.0,
        "poll_interval_s": 0.1,
        "priority_levels": 3,
        "quotas": {"default": 0.6, "*": 0.2},
        "max_resident": 6,
        "shed_policy": True,
        "shed_high": 0.85, "shed_low": 0.5, "shed_patience": 4,
    }
    # high watermark sits BELOW the p2 class reservation cutoff (bulk
    # sheds at depth_frac 1/3): the fleet scales before the lowest
    # class starts shedding, and sheds only once replicas are maxed
    # the p99 SLO budgets one model-zoo page-in (the bulk tenants LRU-
    # cycle through max_resident slots all run, so the steady-state p99
    # rides the page-in wait, not pure queueing — an SLO below that
    # floor would read permanent pressure no replica count can clear)
    # the low watermark sits ABOVE the page-in depth floor (~0.10 —
    # queued requests waiting on zoo page-ins keep that much depth at
    # ANY replica count), or the trough would never read as slack
    policy = FleetScalePolicy(
        min_replicas=1, max_replicas=max_replicas,
        slo_p99_s=0.3, high_watermark=0.25, low_watermark=0.15,
        patience=3, scale_down_patience=6, interval_s=0.5)
    ctl = FleetController(
        os.path.join(sdir, "fleet"), replica_spec, policy=policy,
        interval_s=0.5, spawn_grace_s=60.0,
        env={"LGBM_TPU_FAULT": spec["fault"], "JAX_PLATFORMS": "cpu"})
    faults: List[str] = [spec["fault"]]
    ctl.start()
    ctl.wait_ready(1, timeout=120)

    rng = np.random.default_rng(seed)
    probe = rng.standard_normal((64, spec["n_features"]))
    shape_cfg = dict(spec["shape"])
    for k in ("base_rps", "peak_rps"):
        shape_cfg[k] = shape_cfg[k] * load_scale
    shape = _make_shape(shape_cfg, duration_s)
    hot = ["t%03d" % i for i in range(FLEET_HOT_TENANTS)]
    classes = [RequestClass("gold", priority=0, weight=1.0, rows=1),
               RequestClass("silver", priority=1, weight=2.0, rows=2)]
    classes += [RequestClass("bulk-%s" % mid, priority=2, model_id=mid,
                             weight=3.0 / len(hot), rows=4)
                for mid in hot]
    # wire responses are float32 — verify against the SAME
    # deterministic narrowing of the exact f64 reference
    verifier = ResponseVerifier(probe, pub_dir=models["default"],
                                params={"verbose": -1},
                                value_dtype=np.float32)
    cli = FleetClient(ctl, workers=96, predict_deadline_s=5.0,
                      request_timeout_s=10.0)
    gen = LoadGenerator(cli, classes, shape, duration_s, probe,
                        seed=seed, verifier=verifier, deadline_s=2.0,
                        waiters=16, trace_every=0)
    killer = _ReplicaKiller(ctl, at_s=duration_s * 0.55, ledger=faults)
    killer.start()
    try:
        ledger = gen.run()
    finally:
        killer.stop()
        cli.close()
    # cooldown: zero offered load while the controller keeps ticking —
    # the contraction half of elasticity (slack streak -> shed grant
    # returned -> scale-downs) needs a guaranteed trough to land in,
    # and the timeline should show the fleet actually letting go
    time.sleep(10.0)
    # final scrape before teardown so the artifact's histograms carry
    # the whole run (dead replicas keep their LAST scraped snapshot)
    snaps = []
    with ctl._lock:                          # noqa: SLF001 — sim harness
        for h in ctl.replicas + ctl.retired:
            if h.last_snapshot is not None:
                snaps.append(h.last_snapshot)
    fleet = ctl.stop()
    fleet["faults_injected"] = faults
    sec = collate_fleet_scenario(name, ledger, fleet, snaps, duration_s)
    fl = sec["fleet"]
    log("prod_sim[%s]: ok=%s offered=%.0f rps (%.1fx r11) ups=%d "
        "downs=%d relaunches=%d reaction_max=%s spawn_ready=%s "
        "rs/1Mverified=%s resident_events=%s"
        % (name, sec["ok"], sec["offered_rps_mean"], fl["offered_x_r11"],
           fl["scale_ups"], fl["scale_downs"], fl["relaunches"],
           fl["scale_up_reaction_s_max"],
           ["%.2f" % s for s in fl["spawn_to_ready_s"]],
           fl["replica_seconds_per_million_verified"],
           fl["residency"]))
    return sec


def run_fleet_sim(workdir: str, scenarios: Optional[List[str]] = None,
                  duration_s: float = 40.0, seed: int = 17,
                  max_replicas: int = 4, load_scale: float = 1.0,
                  log=print) -> Dict[str, Any]:
    t0 = time.monotonic()
    out: Dict[str, Any] = {
        "artifact": "SIM_r17",
        "schema_version": SCHEMA_VERSION,
        "t_start": resilience.wallclock(),
        "replicas": max_replicas,
        "duration_s": duration_s,
        "seed": seed,
        "r11_offered_rps_mean": R11_OFFERED_RPS_MEAN,
        "scenarios": {},
    }
    for name in (scenarios or list(FLEET_SCENARIOS)):
        out["scenarios"][name] = run_fleet_scenario(
            name, workdir, duration_s=duration_s, seed=seed,
            max_replicas=max_replicas, load_scale=load_scale, log=log)
    out["elapsed_s"] = round(time.monotonic() - t0, 1)
    out["ok"] = bool(out["scenarios"]) and all(
        s["ok"] for s in out["scenarios"].values())
    return out


def main(argv: List[str]) -> int:
    if len(argv) > 1 and argv[1] == "--replica":
        with open(argv[2]) as fh:
            cfg = json.load(fh)
        rec = run_replica(cfg)
        resilience.atomic_write(argv[3], json.dumps(rec))
        return 0
    import tempfile
    if "--fleet" in argv:
        # ISSUE 17: the elastic-fleet sim — autoscaling controller +
        # model-zoo replicas at >=10x the r11 offered load
        args = [a for a in argv[1:] if not a.startswith("--")]
        artifact = args[0] if args else os.path.join(REPO, "SIM_r17.json")
        quick = "--quick" in argv
        seed = int(os.environ.get("PROD_SIM_SEED", "17"))
        duration = float(os.environ.get("PROD_SIM_DURATION",
                                        "12" if quick else "40"))
        load_scale = float(os.environ.get("PROD_SIM_LOAD_SCALE", "1.0"))
        scenarios = ["fleet_diurnal"] if quick else None
        with tempfile.TemporaryDirectory(prefix="lgbm_fleet_sim_") as wd:
            rec = run_fleet_sim(wd, scenarios=scenarios,
                                duration_s=duration, seed=seed,
                                load_scale=load_scale)
        from helper.bench_history import validate_sim_artifact
        problems = validate_sim_artifact(rec)
        if problems:
            print("prod_sim: INVALID artifact: %s" % "; ".join(problems))
            return 2
        resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
        print("prod_sim: ok=%s scenarios=%s elapsed=%.0fs artifact=%s"
              % (rec["ok"], ",".join(rec["scenarios"]), rec["elapsed_s"],
                 artifact), flush=True)
        return 0 if rec["ok"] else 1
    quick = "--quick" in argv
    args = [a for a in argv[1:] if not a.startswith("--")]
    artifact = args[0] if args else os.path.join(REPO, "SIM_r11.json")
    seed = int(os.environ.get("PROD_SIM_SEED", "11"))
    replicas = int(os.environ.get("PROD_SIM_REPLICAS", "2"))
    duration = float(os.environ.get("PROD_SIM_DURATION",
                                    "8" if quick else "20"))
    trace_out = os.environ.get("PROD_SIM_TRACE_OUT")
    with tempfile.TemporaryDirectory(prefix="lgbm_prod_sim_") as wd:
        rec = run_sim(wd, scenarios=["binary"] if quick else None,
                      replicas=replicas, duration_s=duration,
                      interval_s=2.0 if quick else 3.0, seed=seed)
        # the committed trace artifact (ISSUE 14): ONE merged Perfetto
        # timeline (loadgen → serving → device → drain chain + trainer
        # cycle → publish → subscriber link) with its machine gates —
        # built while the workdir still holds the per-process rings
        if trace_out:
            merged_doc = None
            gates = {}
            for name, sec in rec["scenarios"].items():
                tm = sec.get("trace_merged")
                if tm is None:
                    continue
                gates[name] = {k: v for k, v in tm.items() if k != "file"}
                if merged_doc is None and os.path.exists(tm["file"]):
                    with open(tm["file"]) as fh:
                        merged_doc = json.load(fh)
            trace_art = {
                "artifact": os.path.splitext(
                    os.path.basename(trace_out))[0],
                "schema_version": TRACE_SCHEMA_VERSION,
                "replicas": replicas,
                "seed": seed,
                "gates": gates,
                "stage_sum": {name: sec.get("trace")
                              for name, sec in rec["scenarios"].items()},
                "ok": bool(gates) and all(g["ok"] for g in gates.values())
                and all((sec.get("trace") or {}).get("ok")
                        for sec in rec["scenarios"].values()),
                "trace": merged_doc,
            }
            resilience.atomic_write(trace_out,
                                    json.dumps(trace_art) + "\n")
            print("prod_sim: trace artifact ok=%s -> %s (%d events, "
                  "%d processes)"
                  % (trace_art["ok"], trace_out,
                     (merged_doc or {}).get("otherData", {})
                     .get("events", 0),
                     max((g.get("processes", 0)
                          for g in gates.values()), default=0)),
                  flush=True)
        for sec in rec["scenarios"].values():
            # the merged-trace file lives in the (deleted) workdir; keep
            # the gates, drop the dangling path from the SIM artifact
            if "trace_merged" in sec:
                sec["trace_merged"].pop("file", None)
    # a malformed artifact must fail loudly, not land in the repo
    from helper.bench_history import validate_sim_artifact
    problems = validate_sim_artifact(rec)
    if problems:
        print("prod_sim: INVALID artifact: %s" % "; ".join(problems))
        return 2
    resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
    print("prod_sim: ok=%s scenarios=%s elapsed=%.0fs artifact=%s"
          % (rec["ok"], ",".join(rec["scenarios"]), rec["elapsed_s"],
             artifact), flush=True)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
