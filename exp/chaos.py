#!/usr/bin/env python
"""Chaos soak for the continuous-training service (ISSUE 6 acceptance).

Runs `task=train_online` to a target number of publish cycles while a
relauncher injects a randomized `LGBM_TPU_FAULT` into every launch
(abrupt deaths, preemption signals, torn publishes, mid-publish deaths,
corrupted snapshots, stage stalls) and a high-frequency subscriber
polls the publish directory throughout.  The two pins, asserted here
and in tests/test_continuous.py:

* **zero corrupt observations** — the subscriber never once resolves a
  torn, partial, or checksum-invalid model (torn files on disk are
  fine; RESOLVING one is the failure);
* **byte-identical generations** — every published generation's model
  text equals the same generation from an uninterrupted baseline run
  (deaths rewind to the last cycle boundary and replay
  deterministically; republishes reuse the snapshot's own model text).

Usage:  python exp/chaos.py [cycles] [artifact.json]
        (defaults: 24 cycles, CHAOS_r06.json at the repo root)
Env:    CHAOS_SEED, CHAOS_MAX_FAULTS, CHAOS_LAUNCH_TIMEOUT
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.runtime import publish, resilience  # noqa: E402

#: service parameters shared by the baseline and every churn launch —
#: byte-identity is only meaningful when the training run is otherwise
#: identical.  bagging + feature_fraction keep the host RNG streams in
#: play (their state crossing kill/resume boundaries is the hard part).
TRAIN_PARAMS = ["objective=binary", "num_leaves=15", "bagging_freq=2",
                "bagging_fraction=0.7", "feature_fraction=0.8", "seed=7",
                "verbose=-1"]

#: the fault pool one churn launch draws from.  `{K}` is replaced with an
#: iteration shortly AHEAD of current progress (a fault behind the clock
#: would either never fire or fire before any work happened — both
#: useless).  The relauncher injects `max_faulted_launches` of these,
#: then lets a clean launch carry the service to its cycle target.
FAULT_POOL = [
    "sigterm_at_iter:{K}",
    "die_at_iter:{K}",
    "torn_write:1",
    "die_at_publish:1",
    "corrupt_snapshot,die_at_iter:{K}",
]


def make_data(path: str, n: int = 400, f: int = 6, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float64)
    np.savetxt(path, np.column_stack([y, X]), delimiter="\t", fmt="%.8g")


def _service_env(fault: Optional[str]) -> Dict[str, str]:
    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULT", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    if fault:
        env["LGBM_TPU_FAULT"] = fault
    return env


def _service_args(workdir: str, cycles: int, rounds: int, interval: float,
                  extra: Optional[List[str]] = None) -> List[str]:
    return (["task=train_online", "data=train.tsv", "output_model=m.txt",
             "online_cycles=%d" % cycles, "online_rounds=%d" % rounds,
             "online_interval=%g" % interval]
            + TRAIN_PARAMS + (extra or []))


def run_service(workdir: str, cycles: int, rounds: int = 2,
                interval: float = 0.0, fault: Optional[str] = None,
                extra: Optional[List[str]] = None,
                timeout: float = 180.0) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu"]
        + _service_args(workdir, cycles, rounds, interval, extra),
        cwd=workdir, env=_service_env(fault), timeout=timeout,
        capture_output=True, text=True)


def _progress_iters(workdir: str) -> int:
    """Current training progress (total iterations) as the relauncher
    sees it: the newest valid snapshot's counter, falling back to 0."""
    _, state = resilience.find_resume_snapshot(
        os.path.join(workdir, "m.txt"), log=_QuietLog())
    return int(state["total_iter"]) if state else 0


class _QuietLog:
    def warning(self, *a):
        pass

    info = warning


class Poller(threading.Thread):
    """High-frequency subscriber: resolves the newest generation over and
    over, deep-validating each NEW (generation, bytes) it sees by parsing
    the model text with the real model loader.  `corrupt_observed` is the
    chaos ledger — it must end at zero."""

    def __init__(self, pub_dir: str, hz: float = 50.0):
        super().__init__(name="chaos-poller", daemon=True)
        self.sub = publish.ModelSubscriber(pub_dir, attempts=1)
        self.period = 1.0 / hz
        self.stop_evt = threading.Event()
        self.polls = 0
        self.corrupt_observed = 0
        self.errors: List[str] = []
        self.seen: Dict[int, str] = {}           # generation -> model text

    def _deep_validate(self, rec) -> None:
        from lightgbm_tpu.models.gbdt_model import GBDTModel
        try:
            model = GBDTModel.load_model_from_string(rec.model_text)
            if model.current_iteration <= 0:
                raise ValueError("empty model")
        except Exception as e:                   # noqa: BLE001 — ledger
            self.corrupt_observed += 1
            self.errors.append("generation %d: %s" % (rec.generation, e))

    def run(self) -> None:
        while not self.stop_evt.is_set():
            self.polls += 1
            rec = self.sub.resolve_once()
            if rec is not None and self.seen.get(rec.generation) \
                    != rec.model_text:
                if rec.generation in self.seen:
                    # a generation's bytes may only ever change from a
                    # torn file to the repaired republish — and a torn
                    # file can never resolve; seeing two DIFFERENT valid
                    # texts for one generation would be a lie to servers
                    self.corrupt_observed += 1
                    self.errors.append(
                        "generation %d changed bytes after publication"
                        % rec.generation)
                else:
                    self._deep_validate(rec)
                    self.seen[rec.generation] = rec.model_text
            self.stop_evt.wait(self.period)

    def stop(self) -> None:
        self.stop_evt.set()
        self.join(timeout=10)


def run_soak(workdir: str, cycles: int = 24, rounds: int = 2,
             interval: float = 0.05, seed: int = 11,
             max_faulted_launches: Optional[int] = None,
             launch_timeout: float = 180.0,
             extra_args: Optional[List[str]] = None,
             fault_pool: Optional[List[Optional[str]]] = None) -> Dict:
    """One full soak: baseline + churn + comparison.  Returns the
    machine-readable record (also the CHAOS_r06.json artifact schema)."""
    t0 = time.monotonic()
    rng = random.Random(seed)
    pool = list(FAULT_POOL if fault_pool is None else fault_pool)
    base_dir = os.path.join(workdir, "baseline")
    churn_dir = os.path.join(workdir, "churn")
    os.makedirs(base_dir)
    os.makedirs(churn_dir)
    make_data(os.path.join(base_dir, "train.tsv"))
    make_data(os.path.join(churn_dir, "train.tsv"))

    # -- baseline: one uninterrupted run, every generation retained ----------
    r = run_service(base_dir, cycles, rounds, interval,
                    extra=["publish_retention=0"] + (extra_args or []),
                    timeout=launch_timeout * 2)
    if r.returncode != 0:
        raise RuntimeError("baseline service failed rc=%d\n%s"
                           % (r.returncode, (r.stderr or "")[-2000:]))
    baseline: Dict[int, str] = {}
    for gen, path in publish.generation_paths(
            os.path.join(base_dir, "m.txt.pub")):
        ok_gen, reason = publish.validate_generation(path)
        assert ok_gen, (path, reason)
        with open(path) as fh:
            baseline[gen] = publish._split_validate(fh.read())[0]

    # -- churn: relaunch under randomized faults while a subscriber polls ----
    poller = Poller(os.path.join(churn_dir, "m.txt.pub"))
    poller.start()
    launches: List[Dict] = []
    max_faults = max_faulted_launches if max_faulted_launches is not None \
        else int(os.environ.get("CHAOS_MAX_FAULTS", "10"))
    ok = False
    try:
        for _attempt in range(cycles + 12):
            faulted = sum(1 for lnch in launches if lnch["fault"])
            fault = rng.choice(pool) if faulted < max_faults else None
            if fault and "{K}" in fault:
                fault = fault.replace(
                    "{K}", str(_progress_iters(churn_dir)
                               + rng.randint(1, 2 * rounds)))
            r = run_service(churn_dir, cycles, rounds, interval,
                            fault=fault, extra=extra_args,
                            timeout=launch_timeout)
            launches.append({"fault": fault, "rc": r.returncode})
            # rc 0 = target reached OR clean preemption exit; only the
            # former ends the churn (a preempted launch leaves the latest
            # generation short of the target)
            if r.returncode == 0 and _latest_gen(churn_dir) >= cycles:
                ok = True
                break
    finally:
        time.sleep(0.2)                  # let the poller see the last gen
        poller.stop()

    # -- comparison ----------------------------------------------------------
    churn_final: Dict[int, str] = {}
    for gen, path in publish.generation_paths(
            os.path.join(churn_dir, "m.txt.pub")):
        with open(path) as fh:
            split = publish._split_validate(fh.read())
        if split is not None:
            churn_final[gen] = split[0]
    observed = dict(poller.seen)
    observed.update(churn_final)         # pruned-before-polled gens, if any
    mismatched = [g for g, text in observed.items()
                  if baseline.get(g) is not None and baseline[g] != text]
    checked = [g for g in observed if baseline.get(g) is not None]

    rec = {
        "artifact": "CHAOS_r06",
        "t_start": resilience.wallclock(),
        "cycles_target": cycles,
        "cycles_run": max(observed) if observed else 0,
        "ok": bool(ok and max(observed or [0]) >= cycles),
        "launches": len(launches),
        "faults_injected": [lnch["fault"] for lnch in launches
                            if lnch["fault"]],
        "launch_rcs": [lnch["rc"] for lnch in launches],
        "subscriber": {
            "polls": poller.polls,
            "resolved": poller.sub.resolved_count,
            "skipped_invalid": poller.sub.skipped_invalid,
            "corrupt_observed": poller.corrupt_observed,
            "corruption_errors": poller.errors,
        },
        "byte_identity": {
            "generations_checked": len(checked),
            "mismatched": mismatched,
        },
        "elapsed_s": round(time.monotonic() - t0, 1),
    }
    rec["ok"] = bool(rec["ok"] and poller.corrupt_observed == 0
                     and not mismatched
                     and len(checked) >= cycles)
    return rec


def _latest_gen(workdir: str) -> int:
    gens = publish.generation_paths(os.path.join(workdir, "m.txt.pub"))
    return gens[0][0] if gens else 0


def main(argv: List[str]) -> int:
    import tempfile
    cycles = int(argv[1]) if len(argv) > 1 else 24
    artifact = argv[2] if len(argv) > 2 else os.path.join(REPO,
                                                          "CHAOS_r06.json")
    seed = int(os.environ.get("CHAOS_SEED", "11"))
    timeout = float(os.environ.get("CHAOS_LAUNCH_TIMEOUT", "180"))
    with tempfile.TemporaryDirectory(prefix="lgbm_chaos_") as wd:
        rec = run_soak(wd, cycles=cycles, seed=seed,
                       launch_timeout=timeout)
    resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
    print("chaos soak: ok=%s cycles=%d/%d launches=%d faults=%d "
          "polls=%d corrupt_observed=%d mismatched=%d artifact=%s"
          % (rec["ok"], rec["cycles_run"], rec["cycles_target"],
             rec["launches"], len(rec["faults_injected"]),
             rec["subscriber"]["polls"],
             rec["subscriber"]["corrupt_observed"],
             len(rec["byte_identity"]["mismatched"]), artifact),
          flush=True)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
