#!/usr/bin/env python3
"""The device operations of a train cell's phases, one by one: a traced
run of the cell (the benchmark's own `run_cell`, three iterations), then
every operation whose `tf_op` carries one of the named scopes (by default
the ranking fill's: `lgbm.grad`, `lgbm.grad_pairs`, `lgbm.grad_permute`),
summed by HLO instruction: self seconds an iteration, calls, the
instruction's text.  The per-layer metrics give the phases' totals; this
names what is inside them.  The phase `none` lists the operations that
carry no scope at all (a while loop's entry copy, say).

    python3 exp/rank_phase_ops.py [--workload msltr-train] [--seed N]
        [--phases grad,score,tree_update,none] [--out chiprun_out/x.json]
"""
import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
PHASES = ("grad", "grad_pairs", "grad_permute")
UNSCOPED = "none"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="msltr-train")
    ap.add_argument("--seed", type=int, default=3000000412)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated phases of grower2.PHASES, and "
                         "`none` for the operations under no scope")
    ap.add_argument("--out", help="also write the report to this file")
    args = ap.parse_args()
    phases_wanted = tuple(args.phases.split(","))

    from benchmarks import run as bench
    from benchmarks.lib import progspans, xplane
    result = bench.run_cell(args.workload, args.seed, 20.0, True,
                            keep_trace=True)
    iters = 3       # the mix's trace_iters
    kept = os.path.join(ROOT, bench.OUT_DIR,
                        "%s.s%d.xplane.pb" % (args.workload, args.seed))
    trace = xplane.load(kept)
    by_plane = progspans.op_phases(kept)
    lo, hi = trace.window_ns()
    total = defaultdict(lambda: [0, 0])
    for dev in trace.devices:
        phases = by_plane.get("/device:TPU:%d" % dev.ordinal, {})
        for op in dev.ops:
            phase = phases.get(op.name) or UNSCOPED
            if phase in phases_wanted and op.end_ns > lo \
                    and op.start_ns < hi:
                rec = total[(phase, op.name)]
                rec[0] += op.self_ns
                rec[1] += 1
    os.remove(kept)
    report = {"workload": args.workload, "seed": args.seed,
              "metrics": {k: v["value"] for k, v in result["metrics"].items()
                          if k.startswith(("rank.", "step.", "grower.",
                                           "kernel.", "train_"))}}
    for phase in phases_wanted:
        ops = sorted(((ns, n, name) for (p, name), (ns, n) in total.items()
                      if p == phase), reverse=True)
        report[phase] = {
            "s_per_iter": sum(ns for ns, _, _ in ops) / 1e9 / iters,
            "ops": [{"s_per_iter": round(ns / 1e9 / iters, 6),
                     "calls_per_iter": n / iters, "hlo": name[:400]}
                    for ns, n, name in ops[:args.top]]}
    text = json.dumps(report, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
