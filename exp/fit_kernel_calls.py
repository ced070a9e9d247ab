"""Per-call and per-row cost of the two segment kernels inside a real
training step: one traced run of a train cell of the benchmark, each
`_partition_segment*` / `_segment_histogram` call's device time laid
against the rows of the node it worked on.

The trace gives one event a kernel call, in time order; the trees the
window trained give the rows.  Internal nodes are numbered in split order,
so in a tree the i-th partition moved `internal_count[i]` rows, the first
histogram read the root and the one after the i-th partition read the
smaller child of node i (the subtraction trick gives the larger).  That
child is also the one the partition staged: the grower puts the larger
child first in the parent's range and the kernel moves the second again
(no bagging in a train cell, so the masked counts are the raw ones).  On
a mesh a chip holds its share of each node: rows are divided by the chips
and the first chip's events are read.

Least squares over every call of the window:
    partition  seconds = call + per_row * rows + per_staged_row * staged
               (pass A reads every row, pass B moves the staged again)
    histogram  seconds = call + per_row * rows
and the same with `rows` alone for the partition.  Prints the traced run's
result line (the per-layer metrics as `benchmarks/run.py --trace 1` gives
them), then one JSON line with the fits, also written to
chiprun_out/fit_kernel_calls.<workload>.s<seed>.json.

    python exp/fit_kernel_calls.py --workload higgs-train --seed 11

A partition that is several kernel calls a split (the column-block
engine: the split window's snapshot and a pass a 512-lane block) is
fitted on the sum of a split's calls.  `--partition-engine E` makes the
run on engine E whatever `grower2.partition_engine` would choose for the
shape (an experiment's override, for racing two engines in one cell:
PERF.md section 6, PR 37), and `--seconds S` makes an UNTRACED run of S
seconds instead (the cell's `train_s_per_iter` on that engine; no fit).
"""
import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

PARTITION = re.compile(r"^%?_partition_segment")
HISTOGRAM = re.compile(r"^%?_segment_histogram")


def node_rows(tree):
    """(rows of every split node, the staged among them, rows of every
    histogram) of one tree, in the order the kernels ran."""
    from benchmarks.lib import opbytes
    ni = int(tree.num_leaves) - 1
    parent = np.asarray(tree.internal_count[:ni], np.int64)
    smaller = np.minimum(*opbytes._child_counts(tree))
    return parent, smaller, np.concatenate([parent[:1], smaller])


def fit(columns, seconds):
    """Least squares of seconds on [1, *columns]: coefficients, r^2."""
    A = np.column_stack([np.ones(len(seconds))] + list(columns))
    coef, *_ = np.linalg.lstsq(A, seconds, rcond=None)
    resid = seconds - A @ coef
    r2 = 1.0 - float(resid @ resid) / float(
        ((seconds - seconds.mean()) ** 2).sum())
    return [float(c) for c in coef], r2


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="higgs-train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--partition-engine", default=None,
                    choices=("pallas-rmw", "pallas-blocks", "pallas-acc",
                             "lax"))
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="an untraced window of this many seconds, no fit")
    args = ap.parse_args(argv)

    from benchmarks import run as brun
    if args.partition_engine:
        from lightgbm_tpu.boosting import grower2
        grower2.partition_engine = lambda *shape: args.partition_engine

    # keep the harness's Run as it is made (no subclass: the train driver
    # finds the harness by the run's own module)
    runs = []
    make_run = brun.Run.__init__

    def keep(self, *a, **kw):
        make_run(self, *a, **kw)
        if args.partition_engine:
            self.config["engines"]["partition"] = args.partition_engine
        runs.append(self)

    brun.Run.__init__ = keep
    result = brun.run_cell(args.workload, args.seed, args.seconds,
                           not args.seconds)
    print(json.dumps(result), flush=True)
    if args.seconds:
        return 0
    run = runs[0]
    chips = run.cell["chips"]
    lo, hi = run.xtrace.window_ns()
    ops = [o for o in run.xtrace.devices[0].ops
           if o.start_ns >= lo and o.end_ns <= hi]
    part_s = np.array([o.self_ns for o in ops if PARTITION.search(o.name)],
                      np.float64) / 1e9
    hist_s = np.array([o.self_ns for o in ops if HISTOGRAM.search(o.name)],
                      np.float64) / 1e9
    rows = [node_rows(t) for t in run.trees]
    part_rows = np.concatenate([r[0] for r in rows]) / chips
    part_staged = np.concatenate([r[1] for r in rows]) / chips
    hist_rows = np.concatenate([r[2] for r in rows]) / chips
    if len(part_rows) and len(part_s) > len(part_rows) \
            and len(part_s) % len(part_rows) == 0:
        # several kernel calls a split, in time order: their sum
        part_s = part_s.reshape(len(part_rows), -1).sum(axis=1)
    if len(part_s) != len(part_rows) or len(hist_s) != len(hist_rows):
        sys.exit("fit_kernel_calls: %d partition and %d histogram events "
                 "for %d splits and %d histograms of the window's trees"
                 % (len(part_s), len(hist_s), len(part_rows),
                    len(hist_rows)))

    out = {"workload": args.workload, "seed": args.seed, "chips": chips,
           "trees": len(run.trees), "device": result["device"],
           "engines": run.state["bst"]._engine.engines,
           # the growth loop's rounds a tree beside the trees' leaves: a
           # round a split, none after the last leaf that can split
           "leaves": [int(t.num_leaves) for t in run.trees],
           "split_rounds_per_tree":
               run.state["bst"]._engine.split_rounds_per_tree()}
    (call, per_row, per_staged), r2 = fit([part_rows, part_staged], part_s)
    out["partition"] = {
        "calls": len(part_s), "seconds": float(part_s.sum()),
        "row_touches": float(part_rows.sum()),
        "staged_rows": float(part_staged.sum()),
        "ns_per_row_touch": float(part_s.sum() / part_rows.sum() * 1e9),
        "call_us": call * 1e6, "per_row_ns": per_row * 1e9,
        "per_staged_row_ns": per_staged * 1e9, "r2": r2,
        "smallest_calls_us": sorted(float(s) * 1e6 for s in part_s)[:5]}
    (call, per_row), r2 = fit([part_rows], part_s)
    out["partition_rows_only"] = {"call_us": call * 1e6,
                                  "per_row_ns": per_row * 1e9, "r2": r2}
    (call, per_row), r2 = fit([hist_rows], hist_s)
    out["histogram"] = {
        "calls": len(hist_s), "seconds": float(hist_s.sum()),
        "rows": float(hist_rows.sum()),
        "ns_per_row": float(hist_s.sum() / hist_rows.sum() * 1e9),
        "call_us": call * 1e6, "per_row_ns": per_row * 1e9, "r2": r2,
        "smallest_calls_us": sorted(float(s) * 1e6 for s in hist_s)[:5]}
    line = json.dumps(out)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "fit_kernel_calls.%s.s%d.json"
                           % (args.workload, args.seed)), "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
