#!/usr/bin/env python3
"""The device operations of a ranking cell's gradient fill, one by one:
a traced run of the cell (the benchmark's own `run_cell`, three
iterations), then every operation whose `tf_op` carries one of the fill's
scopes (`lgbm.grad`, `lgbm.grad_pairs`, `lgbm.grad_permute`), summed by
HLO instruction: self seconds an iteration, calls, the instruction's
text.  The per-layer metrics give the phases' totals; this names what is
inside them.

    python3 exp/rank_phase_ops.py [--workload msltr-train] [--seed N]
"""
import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
PHASES = ("grad", "grad_pairs", "grad_permute")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="msltr-train")
    ap.add_argument("--seed", type=int, default=3000000412)
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()

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
            phase = phases.get(op.name)
            if phase in PHASES and op.end_ns > lo and op.start_ns < hi:
                rec = total[(phase, op.name)]
                rec[0] += op.self_ns
                rec[1] += 1
    os.remove(kept)
    report = {"workload": args.workload, "seed": args.seed,
              "metrics": {k: v["value"] for k, v in result["metrics"].items()
                          if k.startswith(("rank.", "step."))}}
    for phase in PHASES:
        ops = sorted(((ns, n, name) for (p, name), (ns, n) in total.items()
                      if p == phase), reverse=True)
        report[phase] = {
            "s_per_iter": sum(ns for ns, _, _ in ops) / 1e9 / iters,
            "ops": [{"s_per_iter": round(ns / 1e9 / iters, 6),
                     "calls_per_iter": n / iters, "hlo": name[:400]}
                    for ns, n, name in ops[:args.top]]}
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
