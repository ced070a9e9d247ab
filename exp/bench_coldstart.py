#!/usr/bin/env python
"""BENCH_COLDSTART: warm-start measurement harness (ISSUE 15).

Startup used to be an unmeasured quantity: PR 11's autoscale policy can
shed but cannot ADD a replica because nobody knew what a replica join
costs.  This harness measures exactly that, in subprocesses (a cold
start only exists in a fresh process — in-process jit caches would lie):

* **cold** — an EMPTY persistent compile cache, no manifest
  (smallest-bucket prewarm compiles fresh).
* **cache** — ``$JAX_COMPILATION_CACHE_DIR`` pointing at a warm cache
  dir, no manifest: the first compile of each program becomes a disk
  load.
* **manifest** — warm cache AND the publish dir's ``warmup.json``
  present: the runtime precompiles every manifest bucket BEFORE
  ``/healthz`` opens, so the first real request pays nothing.

Per mode the child reports **time-to-ready** (ServingRuntime construct →
admission open with a generation loaded) and **time-to-first-verified-
response** (→ first response byte-verified against the offline
predictor for its reported generation + path), plus the steady-state
zero-retrace pin (xla_obs) over follow-up batches and a sha256 of the
response bytes — the parent gates that every mode produced IDENTICAL
predictions.

The **train** section measures the start the fleet actually pays most
for: the fused-step family a `train_online` relaunch recompiles.  A
fresh process builds a booster and times its FIRST iteration (trace +
compile + run) and a steady iteration; ``startup_overhead_s`` =
first − steady isolates the cold-start cost from the fixed work.  The
acceptance gate (``ready_bar``) rides this number: warm
(persistent-cache) startup overhead must be ≥ 2× smaller than cold on
the CPU fallback — the serving-side predictor programs compile in
sub-seconds on XLA:CPU (their per-mode timings are still recorded and
trend-tracked), and the trained model text is
pinned BYTE-IDENTICAL cold vs warm (a persistent cache can never
change bits).

The **replica_join** section is the prod-sim scenario the autoscaler
needs: while a publisher keeps publishing fresh generations (the live
fleet), a brand-new replica process joins against the SAME publish dir
with cache+manifest armed — ``join_to_first_response_s`` is wall clock
from process spawn (interpreter + jax import included) to its first
byte-verified response.

Usage:
    python exp/bench_coldstart.py [--quick] [--out OUT.json]
    python exp/bench_coldstart.py --artifact BENCH_COLD_r15.json
    python exp/bench_coldstart.py --child cfg.json out.json   (internal)

The artifact is schema-validated (`helper.bench_history.
validate_coldstart_artifact`) before it is written — a malformed run
fails loudly instead of committing zeros; `helper/bench_history.py`
collates BENCH_COLD_r*.json with the same >10% same-shape regression
flags as the bench/sim trajectories (lower is better).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA_VERSION = 1

#: child must come up, serve, verify and pin steady state within this
CHILD_TIMEOUT_S = 300

#: the acceptance bar (ISSUE 15): warm-start (persistent-cache) startup
#: overhead must be at least this many times smaller than the cold
#: start's on the CPU fallback (measured on the trainer's fused-step
#: family, where XLA:CPU compile time actually lives)
READY_SPEEDUP_BAR = 2.0


# ---------------------------------------------------------------------------
# child: one measured start in a fresh process
# ---------------------------------------------------------------------------

def child_main(cfg_path: str, out_path: str) -> int:
    t_entry = time.monotonic()
    with open(cfg_path) as fh:
        cfg = json.load(fh)
    import jax
    jax.config.update("jax_platforms", cfg.get("platform", "cpu"))
    if cfg.get("role") == "train":
        return _train_child(cfg, out_path, t_entry)
    import numpy as np

    from lightgbm_tpu.basic import Booster
    from lightgbm_tpu.runtime import publish as pubmod
    from lightgbm_tpu.runtime import resilience, xla_obs
    from lightgbm_tpu.runtime.serving import ServingRuntime
    import_s = time.monotonic() - t_entry

    t0 = time.monotonic()
    rt = ServingRuntime(publish_dir=cfg["pub_dir"],
                        params={"verbose": -1},
                        poll_interval_s=0.05, batch_window_s=0.001,
                        export_manifest=bool(cfg.get("export_manifest")))
    rt.start()
    deadline = time.monotonic() + 60
    while rt.generation() is None and time.monotonic() < deadline:
        time.sleep(0.02)
    time_to_ready = time.monotonic() - t0

    rng = np.random.default_rng(int(cfg.get("probe_seed", 5)))
    probe = rng.standard_normal((int(cfg["probe_rows"]),
                                 int(cfg["n_features"])))
    rec = rt.predict(probe)
    time_to_first = time.monotonic() - t0
    first_response_unix = time.time()

    # byte-verify against the offline predictor for the reported
    # generation + path (the chaos-soak / loadgen bar)
    gen_path = os.path.join(cfg["pub_dir"],
                            pubmod._gen_name(rec.generation))  # noqa: SLF001
    with open(gen_path, "rb") as fh:
        raw = fh.read().decode("utf-8", "replace")
    split = pubmod._split_validate(raw)                        # noqa: SLF001
    verified = False
    if split is not None:
        ref = Booster(params={"verbose": -1}, model_str=split[0]).predict(
            probe, device=(rec.served_by == "device"))
        verified = bool(np.array_equal(np.asarray(rec.values).reshape(-1),
                                       np.asarray(ref).reshape(-1)))

    # steady-state zero-retrace pin: further same-shape batches compile
    # NOTHING, whichever start mode this was
    xla_obs.mark_steady(True)
    try:
        for _ in range(3):
            rt.predict(probe)
    finally:
        xla_obs.mark_steady(False)
    retraces = list(xla_obs.LEDGER.retraces)

    from lightgbm_tpu.runtime import warmup
    out = {
        "mode": cfg.get("mode"),
        "platform": jax.default_backend(),
        "import_s": round(import_s, 4),
        "time_to_ready_s": round(time_to_ready, 4),
        "time_to_first_response_s": round(time_to_first, 4),
        "first_response_unix": round(first_response_unix, 4),
        "generation": rec.generation,
        "served_by": rec.served_by,
        "verified": verified,
        "pred_sha256": hashlib.sha256(
            np.ascontiguousarray(np.asarray(rec.values)).tobytes()
        ).hexdigest(),
        "steady_retraces": len(retraces),
        "retrace_sites": [r["site"] for r in retraces][:8],
        "compiles": xla_obs.total_compiles(),
        "prewarm_events": rt.prewarm_events,
        "cache": warmup.cache_status(),
    }
    rt.stop()
    resilience.atomic_write(out_path, json.dumps(out, indent=1) + "\n")
    return 0


def _train_child(cfg: Dict[str, Any], out_path: str,
                 t_entry: float) -> int:
    """One trainer start in a fresh process: first iteration (trace +
    compile + run) vs a steady iteration on the same booster — the
    difference IS the cold-start overhead a `train_online` relaunch
    pays before its first cycle can train."""
    import numpy as np

    import bench
    import lightgbm_tpu as lgb
    from lightgbm_tpu.runtime import resilience, warmup
    import_s = time.monotonic() - t_entry
    warmup.enable_compile_cache()

    X, y = bench.synth_higgs(int(cfg["rows"]))
    params = {"objective": "binary", "num_leaves": int(cfg["num_leaves"]),
              "max_bin": 255, "learning_rate": 0.1, "verbose": -1,
              "seed": 7}
    t0 = time.monotonic()
    bst = lgb.Booster(dict(params), lgb.Dataset(X, label=y))
    build_s = time.monotonic() - t0
    t0 = time.monotonic()
    bst.update()
    bst._engine.flush()
    first_iter_s = time.monotonic() - t0
    t0 = time.monotonic()
    bst.update()
    bst._engine.flush()
    steady_iter_s = time.monotonic() - t0
    bst._drain()
    model_sha = hashlib.sha256(
        bst._model.save_model_to_string().encode()).hexdigest()
    out = {
        "mode": cfg.get("mode"),
        "import_s": round(import_s, 4),
        "build_s": round(build_s, 4),
        "first_iter_s": round(first_iter_s, 4),
        "steady_iter_s": round(steady_iter_s, 4),
        "startup_overhead_s": round(max(first_iter_s - steady_iter_s,
                                        0.0), 4),
        "model_sha256": model_sha,
        "cache": warmup.cache_status(),
    }
    resilience.atomic_write(out_path, json.dumps(out, indent=1) + "\n")
    return 0


# ---------------------------------------------------------------------------
# parent: orchestrate the modes + the replica join
# ---------------------------------------------------------------------------

def _spawn_child(workdir: str, tag: str, cfg: Dict[str, Any],
                 env: Dict[str, str]) -> Dict[str, Any]:
    cfg_path = os.path.join(workdir, "child_%s.json" % tag)
    out_path = os.path.join(workdir, "child_%s.out.json" % tag)
    with open(cfg_path, "w") as fh:
        json.dump(cfg, fh)
    log_path = os.path.join(workdir, "child_%s.log" % tag)
    t_spawn = time.time()
    with open(log_path, "w") as log:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             cfg_path, out_path],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            timeout=CHILD_TIMEOUT_S)
    if r.returncode != 0 or not os.path.exists(out_path):
        with open(log_path) as fh:
            raise RuntimeError("coldstart child %s failed (rc=%d): %s"
                               % (tag, r.returncode, fh.read()[-2000:]))
    with open(out_path) as fh:
        rec = json.load(fh)
    rec["spawn_unix"] = round(t_spawn, 4)
    if "first_response_unix" in rec:
        rec["spawn_to_first_response_s"] = round(
            rec["first_response_unix"] - t_spawn, 4)
    return rec


class _Publisher(threading.Thread):
    """The live fleet's trainer stand-in for the replica-join scenario:
    keeps publishing fresh generations while the joining replica comes
    up (so the join races real publish/prune churn)."""

    def __init__(self, pub, make_text, interval_s: float):
        super().__init__(name="coldstart-publisher", daemon=True)
        self.pub = pub
        self.make_text = make_text
        self.interval_s = interval_s
        self.published = 0
        self._halt = threading.Event()

    def run(self) -> None:
        gen = 1
        while not self._halt.wait(self.interval_s):
            gen += 1
            self.pub.publish(self.make_text(gen), meta={"cycle": gen})
            self.published += 1

    def stop(self) -> None:
        self._halt.set()


def run_coldstart(workdir: str, quick: bool = True,
                  platform: Optional[str] = None,
                  log=print) -> Dict[str, Any]:
    import bench
    from lightgbm_tpu.runtime import publish as pubmod

    # every child takes the platform for itself, one child at a time; the
    # parent stays off JAX so it never holds the chip a child needs
    platform = platform or os.environ.get("BENCH_COLDSTART_PLATFORM") \
        or "cpu"
    n_trees, num_leaves, n_feat = (40, 31, 8) if quick else (100, 63, 28)
    probe_rows = int(os.environ.get("BENCH_COLDSTART_PROBE_ROWS", 200))

    pub_dir = os.path.join(workdir, "pub")
    cache_base = os.path.join(workdir, "compile_cache")
    manifest_keep = os.path.join(workdir, "warmup.json.keep")
    manifest_path = os.path.join(pub_dir, "warmup.json")

    def make_text(seed: int) -> str:
        return bench.synth_serving_model(
            n_trees, num_leaves, n_feat, seed=seed).save_model_to_string()

    pub = pubmod.ModelPublisher(pub_dir, keep_last=4, grace_s=600)
    pub.publish(make_text(1), meta={"cycle": 1})

    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO + os.pathsep + base_env.get("PYTHONPATH",
                                                             "")
    base_env.pop("LGBM_TPU_FAULT", None)
    # "cold" = an empty cache directory of its own (the seam is always on)
    cold_env = dict(base_env, JAX_COMPILATION_CACHE_DIR=os.path.join(
        workdir, "empty_cache"))
    cache_env = dict(base_env, JAX_COMPILATION_CACHE_DIR=cache_base)

    def cfg(mode: str, export_manifest: bool = False) -> Dict[str, Any]:
        return {"mode": mode, "pub_dir": pub_dir, "platform": platform,
                "n_features": n_feat, "probe_rows": probe_rows,
                "probe_seed": 5, "export_manifest": export_manifest}

    def stash_manifest() -> None:
        if os.path.exists(manifest_path):
            shutil.copyfile(manifest_path, manifest_keep)
            os.unlink(manifest_path)

    modes: Dict[str, Dict[str, Any]] = {}
    # 1. cold: no cache, no manifest; exports the manifest for later
    modes["cold"] = _spawn_child(workdir, "cold", cfg("cold", True),
                                 cold_env)
    stash_manifest()
    log("coldstart[cold]: ready %.2fs first_response %.2fs"
        % (modes["cold"]["time_to_ready_s"],
           modes["cold"]["time_to_first_response_s"]))
    # 2. cache seed: populates the persistent cache (diagnostics only —
    #    it runs as cold as mode 1, but with the cache WRITE cost on top)
    modes["cache_seed"] = _spawn_child(workdir, "seed", cfg("cache_seed"),
                                       cache_env)
    stash_manifest()
    # 3. cache: warm persistent cache, no manifest
    modes["cache"] = _spawn_child(workdir, "cache", cfg("cache"),
                                  cache_env)
    stash_manifest()
    log("coldstart[cache]: ready %.2fs first_response %.2fs"
        % (modes["cache"]["time_to_ready_s"],
           modes["cache"]["time_to_first_response_s"]))
    # 4. manifest: warm cache AND the shape manifest back in place
    shutil.copyfile(manifest_keep, manifest_path)
    modes["manifest"] = _spawn_child(workdir, "manifest", cfg("manifest"),
                                     cache_env)
    log("coldstart[manifest]: ready %.2fs first_response %.2fs "
        "(prewarm %s)"
        % (modes["manifest"]["time_to_ready_s"],
           modes["manifest"]["time_to_first_response_s"],
           [e.get("outcome") for e in modes["manifest"]["prewarm_events"]]))

    # 5. the trainer's fused-step family, cold vs warm (the gate): a
    #    fresh trainer process's first-iteration overhead with and
    #    without the persistent cache
    train_rows = int(os.environ.get("BENCH_COLDSTART_TRAIN_ROWS",
                                    8000 if quick else 20000))
    train_leaves = int(os.environ.get("BENCH_COLDSTART_TRAIN_LEAVES", 255))
    train_cold_env = dict(base_env, JAX_COMPILATION_CACHE_DIR=os.path.join(
        workdir, "train_empty_cache"))
    train_cache_env = dict(base_env, JAX_COMPILATION_CACHE_DIR=os.path.join(
        workdir, "train_cache"))

    def tcfg(mode: str) -> Dict[str, Any]:
        return {"role": "train", "mode": mode, "platform": platform,
                "rows": train_rows, "num_leaves": train_leaves}

    train = {"rows": train_rows, "num_leaves": train_leaves}
    train["cold"] = _spawn_child(workdir, "train_cold", tcfg("cold"),
                                 train_cold_env)
    train["seed"] = _spawn_child(workdir, "train_seed", tcfg("seed"),
                                 train_cache_env)
    train["warm"] = _spawn_child(workdir, "train_warm", tcfg("warm"),
                                 train_cache_env)
    train["model_identical"] = (train["cold"]["model_sha256"]
                                == train["warm"]["model_sha256"])
    train_speedup = (train["cold"]["startup_overhead_s"]
                     / max(train["warm"]["startup_overhead_s"], 1e-9))
    log("coldstart[train]: startup overhead cold %.2fs vs warm %.2fs "
        "(%.1fx; steady %.2fs/iter; model identical: %s)"
        % (train["cold"]["startup_overhead_s"],
           train["warm"]["startup_overhead_s"], train_speedup,
           train["warm"]["steady_iter_s"], train["model_identical"]))

    # 6. replica join mid-run: live publisher churn + a fresh warm replica
    publisher = _Publisher(pub, make_text, interval_s=1.0)
    publisher.start()
    try:
        join = _spawn_child(workdir, "join", cfg("join"), cache_env)
    finally:
        publisher.stop()
        publisher.join(timeout=10)
    replica_join = {
        "mode": "manifest",
        "join_to_first_response_s": join["spawn_to_first_response_s"],
        "time_to_ready_s": join["time_to_ready_s"],
        "time_to_first_response_s": join["time_to_first_response_s"],
        "import_s": join["import_s"],
        "generation_served": join["generation"],
        "generations_published_during_join": publisher.published,
        "verified": join["verified"],
        "steady_retraces": join["steady_retraces"],
    }
    log("coldstart[join]: spawn->first verified response %.2fs "
        "(%d generations published during the join)"
        % (replica_join["join_to_first_response_s"],
           replica_join["generations_published_during_join"]))

    gate_modes = ("cold", "cache", "manifest")
    hashes = {modes[m]["pred_sha256"] for m in gate_modes}
    ready_speedup = (modes["cold"]["time_to_ready_s"]
                     / max(modes["manifest"]["time_to_ready_s"], 1e-9))
    first_speedup = (modes["cold"]["time_to_first_response_s"]
                     / max(modes["manifest"]["time_to_first_response_s"],
                           1e-9))
    rec = {
        "schema_version": SCHEMA_VERSION,
        "platform": modes["cold"]["platform"],
        "n_trees": n_trees, "num_leaves": num_leaves,
        "n_features": n_feat, "probe_rows": probe_rows,
        "modes": modes,
        "train": train,
        "speedup": {
            # the acceptance gate: warm-start vs cold startup overhead
            # on the trainer's fused-step family (XLA compile lives
            # there on CPU; serving compiles are sub-second disk-cheap)
            "train_startup_overhead_cold_over_warm": round(train_speedup,
                                                           2),
            "ready_bar": READY_SPEEDUP_BAR,
            # trend-tracked serving ratios (compile-light on XLA:CPU)
            "serve_ready_cold_over_manifest": round(ready_speedup, 2),
            "serve_first_response_cold_over_manifest": round(first_speedup,
                                                             2),
        },
        "predictions_identical": len(hashes) == 1,
        "replica_join": replica_join,
        "note": "cold = empty persistent cache, no manifest; cache = warm "
                "jax compilation cache; manifest = cache + "
                "warmup.json bucket prewarm before /healthz opens.  "
                "Byte-identity and the zero-retrace pin hold under every "
                "start mode; join runs against live publish churn; the "
                ">=2x gate rides the trainer's startup overhead "
                "(first-iteration minus steady-iteration wall), cold vs "
                "warm persistent cache, with the trained model pinned "
                "byte-identical.",
    }
    rec["ok"] = bool(
        rec["predictions_identical"]
        and all(modes[m]["verified"] for m in gate_modes)
        and all(modes[m]["steady_retraces"] == 0 for m in gate_modes)
        and all(modes[m]["served_by"] == "device" for m in gate_modes)
        and replica_join["verified"]
        and replica_join["steady_retraces"] == 0
        and train["model_identical"]
        and train_speedup >= READY_SPEEDUP_BAR)
    return rec


def main(argv: List[str]) -> int:
    if len(argv) > 1 and argv[1] == "--child":
        return child_main(argv[2], argv[3])
    import tempfile

    from lightgbm_tpu.runtime import resilience
    quick = "--quick" in argv
    out_path = None
    artifact = None
    args = argv[1:]
    for flag, slot in (("--out", "out"), ("--artifact", "artifact")):
        if flag in args:
            i = args.index(flag)
            v = args[i + 1]
            if slot == "out":
                out_path = v
            else:
                artifact = v
    with tempfile.TemporaryDirectory(prefix="lgbm_coldstart_") as wd:
        rec = run_coldstart(wd, quick=quick or artifact is None)
    if artifact:
        name = os.path.splitext(os.path.basename(artifact))[0]
        rec = dict({"artifact": name}, **rec)
        from helper.bench_history import validate_coldstart_artifact
        problems = validate_coldstart_artifact(rec)
        if problems:
            print("bench_coldstart: INVALID artifact: %s"
                  % "; ".join(problems))
            return 2
        resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
        print("bench_coldstart: ok=%s -> %s" % (rec["ok"], artifact))
    elif out_path:
        resilience.atomic_write(out_path, json.dumps(rec) + "\n")
        print("bench_coldstart: ok=%s -> %s" % (rec["ok"], out_path))
    else:
        print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
