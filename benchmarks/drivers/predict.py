"""Closed-loop batch scoring: one caller of
`Booster(model_str=...).predict(X, device=True)`.

Set-up: the serving model and a pool of rows from the seed, the
`Booster`, and one warm-up call for each distinct call size of the mix
(rounded up to a power of two, the predictor's own bucketing).  Window:
calls cut from the pool in turn, each the next `rows` of the mix's size
classes, until the seconds are spent (a traced window: `trace_seconds`).
A call counts when its scores are on the host.

Correct means: a sample of the first call's rows agrees with the plain
float64 reference (lib/scoring.py) within its tolerance, and every
later call on the same rows returned the same bits as the first.
"""
import time

import numpy as np

from benchmarks.lib import arrivals, scoring, synth

#: call sizes drawn before the window, then cycled
PLANNED_CALLS = 4096


def setup(run):
    mix = run.traffic
    model, _, bst = scoring.build_model(run)
    with run.timed("data_s"):
        pool = synth.feature_rows(mix["pool_rows"], run.config["features"],
                                  (run.seed, 2))
        sizes, _ = arrivals.request_rows(
            mix["request_rows"], PLANNED_CALLS,
            np.random.default_rng((run.seed, 4)))
    with run.timed("warmup_s"):
        for rows in sorted({int(s) for s in sizes}):
            bucket = max(16, 1 << (rows - 1).bit_length())
            bst.predict(pool[:min(bucket, len(pool))], device=True)
    run.state.update(model=model, bst=bst, pool=pool, sizes=sizes)
    run.trees = model.trees
    run.say("predict", trees=len(model.trees), pool_rows=len(pool),
            **run.setup)


def window(run, seconds):
    bst, pool, sizes = (run.state[k] for k in ("bst", "pool", "sizes"))
    limit = run.traffic["trace_seconds"] if run.trace else seconds
    first, calls, mismatched = {}, [], 0
    at = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < limit:
        rows = int(sizes[len(calls) % len(sizes)])
        if at + rows > len(pool):
            at = 0
        t_call = time.perf_counter()
        with run.span("bench/predict_call"):
            out = bst.predict(pool[at:at + rows], device=True)
        calls.append((at, rows, time.perf_counter() - t_call))
        seen = first.setdefault((at, rows), out)
        if seen is not out and not np.array_equal(seen, out):
            mismatched += 1
        at += rows
    wall = time.perf_counter() - t0
    done = sum(c[1] for c in calls)
    run.window.update(calls=calls, seconds=wall, rows=done, first=first,
                      mismatched=mismatched,
                      metrics={"predict_rows_per_s": done / wall})
    run.say("window", calls=len(calls), rows=done, seconds=wall,
            rows_per_s=done / wall,
            call_s=[round(c[2], 4) for c in calls[:64]])


def verify(run):
    calls = run.window["calls"]
    at, rows, _ = calls[0]
    ref = scoring.check_against_reference(
        run.state["model"], run.state["pool"][at:at + rows],
        run.window["first"][(at, rows)], run.traffic["reference_sample"],
        (run.seed, 5))
    checks = {"reference": ref, "repeat_mismatches": run.window["mismatched"],
              "repeated_calls": len(calls) - len(run.window["first"])}
    failed = run.window["mismatched"]
    return {"correct": ref["ok"] and failed == 0, "attempted": len(calls),
            "failed": int(failed), "checks": checks}
