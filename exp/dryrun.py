#!/usr/bin/env python
"""Watchdogged multichip dryrun wrapper -> artifact JSON.

Runs `dryrun_multichip(n)` — lgb.train with every parallel tree_learner
over a virtual n-device CPU mesh — and leaves a diagnosable artifact
whatever happens:

* every dryrun stage runs under the resilience watchdog with wall-clock
  timestamps, and the rolling stage trail is embedded in the artifact;
* on a timeout, the artifact carries the faulthandler tracebacks of all
  threads and NAMES the culprit stage;
* a red run attaches the doctor bundle.

The mesh is CPU-only (a child pinned to JAX_PLATFORMS=cpu), so this is
safe to run while another process holds the chip.  The chip itself is
checked by chip_smoke.py.

Usage:  python exp/dryrun.py [n_devices] [artifact.json]
Env:    LGBM_TPU_DRYRUN_BUDGET (s, default 240)
"""
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.runtime import resilience  # noqa: E402


def main(argv):
    n_devices = int(argv[1]) if len(argv) > 1 else int(
        os.environ.get("NDEV", "8"))
    artifact = argv[2] if len(argv) > 2 else os.path.join(
        REPO, "MULTICHIP_local.json")
    budget = float(os.environ.get("LGBM_TPU_DRYRUN_BUDGET", "240"))
    t0 = time.monotonic()
    rec = {"n_devices": n_devices, "ok": False, "skipped": False,
           "rc": None, "wrapper": "exp/dryrun.py", "budget_s": budget,
           "t_start": resilience.wallclock()}

    # -- the dryrun itself, stage-watchdogged ----------------------------
    report_path = os.path.join(tempfile.gettempdir(),
                               "lgbm_tpu_dryrun_stages_%d.json" % os.getpid())
    metrics_path = os.path.join(tempfile.gettempdir(),
                                "lgbm_tpu_dryrun_metrics_%d.jsonl"
                                % os.getpid())
    env = dict(os.environ)
    env["LGBM_TPU_STAGE_REPORT"] = report_path
    # mesh metrics block (ISSUE 10): the dryrun child flushes its
    # registry here; the artifact embeds the {host}-labeled merge
    env["LGBM_TPU_METRICS_FILE"] = metrics_path
    remaining = max(budget - (time.monotonic() - t0), 30.0)
    code = ("import __graft_entry__ as g; g.dryrun_multichip(%d)"
            % n_devices)
    try:
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           timeout=remaining, capture_output=True, text=True)
        rec["rc"] = r.returncode
        rec["ok"] = r.returncode == 0
        rec["tail"] = ((r.stdout or "") + (r.stderr or ""))[-4000:]
    except subprocess.TimeoutExpired as e:
        rec["rc"] = 124
        rec["tail"] = (_txt(e.stdout) + _txt(e.stderr))[-4000:]
        rec["note"] = ("wrapper budget exceeded — the stage trail below "
                       "names the culprit")

    # the rolling stage report survives any way the subprocess died;
    # the tolerant reader degrades a torn/missing file to "no trail"
    try:
        stage_rep = resilience.read_stage_report(report_path)
        if stage_rep is not None:
            rec["stages"] = stage_rep.get("stages", [])
            rec["culprit_stage"] = stage_rep.get("culprit")
            if stage_rep.get("tracebacks"):
                rec["tracebacks"] = stage_rep["tracebacks"]
        else:
            rec["stages"] = []
            rec["culprit_stage"] = None
    finally:
        try:
            os.unlink(report_path)
        except OSError:
            pass

    # per-host metrics block: the child's last registry snapshot, merged
    # through the same {host}-labeling path a real multi-host gather uses
    try:
        from lightgbm_tpu.runtime import telemetry
        with open(metrics_path) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        if lines:
            snap = json.loads(lines[-1])
            hosts = ({"0": snap} if "metrics" in snap
                     and "hosts" not in snap else None)
            rec["host_metrics"] = (telemetry.merge_host_snapshots(hosts)
                                   if hosts is not None else snap)
    except (OSError, ValueError):
        pass
    finally:
        try:
            os.unlink(metrics_path)
        except OSError:
            pass

    if not rec["ok"]:
        # a red artifact ships home WITH its evidence: the doctor bundle
        # lands next to the artifact and its manifest rides inside it
        # (probe=False: the bundle must not take the platform)
        try:
            from lightgbm_tpu.runtime.doctor import collect_debug_bundle
            bundle = collect_debug_bundle(
                out_dir=os.path.dirname(os.path.abspath(artifact)) or ".",
                tag="dryrun", probe=False,
                stage_reports=[report_path], artifact_dir=REPO,
                note="attached by exp/dryrun.py on rc=%s" % rec["rc"])
            rec["debug_bundle"] = {"path": bundle["path"],
                                   "manifest": bundle["manifest"]}
        except Exception as e:   # noqa: BLE001 — artifact must still land
            rec["debug_bundle"] = {"error": "%s: %s"
                                   % (type(e).__name__, e)}

    rec["elapsed_s"] = round(time.monotonic() - t0, 1)
    rec["within_budget"] = rec["elapsed_s"] <= budget
    resilience.atomic_write(artifact, json.dumps(rec, indent=1) + "\n")
    print("dryrun wrapper: ok=%s rc=%s elapsed=%.1fs artifact=%s"
          % (rec["ok"], rec["rc"], rec["elapsed_s"], artifact), flush=True)
    return 0 if rec["ok"] else 1


def _txt(v):
    if v is None:
        return ""
    return v.decode("utf-8", "replace") if isinstance(v, bytes) else v


if __name__ == "__main__":
    sys.exit(main(sys.argv))
