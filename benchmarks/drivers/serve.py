"""Open-loop serving: independent callers of an in-process
`ServingRuntime`, arriving as a seeded Poisson process on an absolute
clock (lib/arrivals.py).

Set-up: the serving model and a pool of rows from the seed; the pool's
scores from `Booster.predict(device=True)` in calls of the largest
serving bucket (per-row outputs do not depend on what else is in the
batch, tests/test_serving.py), a sample of them held to the plain
float64 reference; `ServingRuntime(model_str=...)` with its
constructor's defaults; every bucket of `warm_buckets` warmed through
`submit`, one request at a time.  Window: every request of the plan is
submitted at its due instant by this thread, and a collector thread
takes the outcomes in order (the batcher is first in, first out, so
waiting in order stamps each completion when it happens).  Latency is
completion less the instant the request was DUE.

Correct means: every response has the bits of the pool's scores, came
from the device, the breaker stayed closed and nothing degraded.  A
rejected, expired or wrong response is `failed`.
"""
import threading
import time

import numpy as np

from benchmarks.lib import arrivals, scoring, synth

#: how long the collector waits for one outcome: the runtime's own
#: limits (10 s deadline, 30 s predict deadline) end every request first
WAIT_S = 60.0


def setup(run):
    from lightgbm_tpu.runtime.serving import ServingRuntime
    mix = run.traffic
    model, text, bst = scoring.build_model(run)
    with run.timed("data_s"):
        pool = synth.feature_rows(mix["pool_rows"], run.config["features"],
                                  (run.seed, 2))
    with run.timed("expected_s"):
        step = max(mix["warm_buckets"])
        expected = np.concatenate([
            bst.predict(pool[lo:lo + step], device=True)
            for lo in range(0, len(pool), step)])
        ref = scoring.check_against_reference(
            model, pool, expected, mix["reference_sample"], (run.seed, 5))
    with run.timed("runtime_s"):
        rt = ServingRuntime(model_str=text).start()
    with run.timed("warmup_s"):
        for bucket in mix["warm_buckets"]:
            rt.submit(pool[:bucket]).wait(timeout=WAIT_S)
    run.state.update(model=model, rt=rt, pool=pool, expected=expected,
                     reference=ref, stats0=rt.stats())
    run.trees = model.trees
    run.say("serve", trees=len(model.trees), pool_rows=len(pool),
            reference=ref, **run.setup)


def window(run, seconds):
    from lightgbm_tpu.runtime.serving import ServeRejected
    rt, pool = run.state["rt"], run.state["pool"]
    limit = run.traffic["trace_seconds"] if run.trace else seconds
    plan = arrivals.plan(run.traffic, limit, (run.seed, 6))
    n = len(plan["due"])
    requests = [None] * n               # the runtime's futures
    outcome = [None] * n                # "ok" | a rejection's reason
    results = [None] * n
    done_at = np.full(n, np.nan)
    handed = threading.Semaphore(0)

    def submit(i):
        lo = int(plan["start"][i])
        try:
            with run.span("bench/submit"):
                requests[i] = rt.submit(pool[lo:lo + int(plan["rows"][i])])
        except ServeRejected as e:
            outcome[i] = e.reason
        handed.release()

    def collect():
        for i in range(n):
            handed.acquire()
            if requests[i] is None:
                continue
            try:
                with run.span("bench/wait"):
                    results[i] = requests[i].wait(timeout=WAIT_S)
                outcome[i] = "ok"
            except ServeRejected as e:
                outcome[i] = e.reason
            done_at[i] = time.monotonic()

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    t0, submitted = arrivals.submit_loop(plan["due"], submit)
    collector.join()
    wall = time.monotonic() - t0
    run.window.update(plan=plan, outcome=outcome, results=results,
                      t0=t0, submitted=submitted, done_at=done_at,
                      seconds=limit, wall_s=wall)
    run.window["metrics"] = summarize(run)


def summarize(run):
    """The window's numbers from the benchmark's own per-request
    records (not from the runtime's histograms)."""
    w = run.window
    plan, limit = w["plan"], w["seconds"]
    ok = np.array([o == "ok" for o in w["outcome"]])
    due_at = w["t0"] + plan["due"]
    latency_ms = (w["done_at"] - due_at)[ok] * 1e3
    lag_ms = (w["submitted"] - due_at) * 1e3
    in_window = ok & (w["done_at"] <= w["t0"] + limit)
    # requests submitted and not yet completed, at each submission
    finished = np.sort(w["done_at"][ok])
    backlog = np.arange(1, len(ok) + 1) \
        - np.searchsorted(finished, w["submitted"], side="right")
    by_class = [int(np.sum(plan["class"] == c))
                for c in range(len(run.traffic["request_rows"]))]
    reasons = {}
    for o in w["outcome"]:
        if o != "ok":
            reasons[o] = reasons.get(o, 0) + 1
    w["summary"] = s = {
        "offered": int(len(ok)), "completed": int(ok.sum()),
        "completed_in_window": int(in_window.sum()),
        "not_ok": reasons, "requests_by_class": by_class,
        "offered_rps": len(ok) / limit,
        "rows_per_s": float(plan["rows"][in_window].sum() / limit),
        "p50_ms": float(np.percentile(latency_ms, 50)) if ok.any() else None,
        "p99_ms": float(np.percentile(latency_ms, 99)) if ok.any() else None,
        "max_ms": float(latency_ms.max()) if ok.any() else None,
        "max_lag_ms": float(lag_ms.max()) if len(ok) else 0.0,
        "backlog_median": float(np.median(backlog)) if len(ok) else 0.0,
        "backlog_end": int(backlog[-1]) if len(ok) else 0,
        "drain_s": w["wall_s"] - limit,
    }
    run.say("window", **s)
    return {"serve_p99_ms": s["p99_ms"]}


def verify(run):
    w, expected = run.window, run.state["expected"]
    plan = w["plan"]
    wrong, not_device = 0, 0
    for i, res in enumerate(w["results"]):
        if res is None:
            continue
        lo = int(plan["start"][i])
        want = expected[lo:lo + int(plan["rows"][i])]
        if not np.array_equal(np.asarray(res.values).reshape(-1), want):
            wrong += 1
        not_device += res.served_by != "device"
    stats = run.window["stats"] = run.state["rt"].stats()
    not_ok = sum(o != "ok" for o in w["outcome"])
    checks = {
        "reference": run.state["reference"], "wrong": wrong,
        "not_from_device": int(not_device),
        "degradations": stats["degradations"],
        "breaker": stats["breaker"]["state"],
        "batches_host": stats["batches_host"],
        "batches_device": stats["batches_device"]
        - run.state["stats0"]["batches_device"],
    }
    ok = (run.state["reference"]["ok"] and wrong == 0 and not_device == 0
          and stats["degradations"] == 0 and stats["batches_host"] == 0
          and stats["breaker"]["state"] == "closed")
    return {"correct": bool(ok), "attempted": len(w["outcome"]),
            "failed": int(not_ok + wrong), "checks": checks}


def teardown(run):
    run.state["rt"].stop()
