"""What `bosch-train`'s trees would cost, WITHOUT the chip: the cell's own
table (benchmarks/tasks/binary_missing.py, a million rows, every signal
column and a sixth of the others) through scikit-learn's histogram GBDT
with LightGBM's hessian floor patched in, and a tree's seconds modelled
from the rows its splits move:

    10 ms + 22 ns a row partitioned + 13.3 ns a row staged
          + 31 ns a row histogrammed (the root's and the smaller
            children's) + 0.1 ms a split

(the rates `exp/fit_kernel_calls.py` read on the chip at 1,024 lanes, PR
37).  It walks the benchmark's window (4 warm-up trees, then trees until
20 s are spent) and prints the window's seconds an iteration, the
held-out AUC at several cuts, and how a tree's seconds differ from the
next tree's.  Several seeds then say how `train_s_per_iter` and the AUC
would SPREAD between seeds, which is what a generator for this cell is
judged by; a seed takes two minutes on four cores and no chip time.

Held against the chip on the first generator of PR 37 it read 0.222 s an
iteration for 0.2011, 26.6 leaves a tree for 25.5, 6.8M rows partitioned
for 6.6M, AUC 0.9930 for 0.9930; PERF.md section 6 has what it said of
the generator that is shipped and what the chip said then.  A number
from here is a prediction, never a reading: it is written nowhere under
the name of a device metric.

    python exp/sim_tree_costs.py --seed N [--set NAME=VALUE ...]

`--set` overrides constants of the task's module (as exp/tree_costs.py).
One JSON line; the trees one by one in chiprun_out/sim_tree_costs.*.json.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

ROWS, HELD_OUT, WARMUP, WINDOW_S = 1000000, 183747, 4, 20.0
HESSIAN_FLOOR = 100.0


def tree_costs(nodes):
    """Leaves, rows partitioned and staged, and the modelled seconds of
    one tree from scikit-learn's node table."""
    internal = nodes[nodes["is_leaf"] == 0]
    count = nodes["count"].astype(np.float64)
    staged = float(np.minimum(count[internal["left"]],
                              count[internal["right"]]).sum())
    part = float(internal["count"].sum())
    seconds = 0.010 + 22e-9 * part + 13.3e-9 * staged \
        + 31e-9 * (ROWS + staged) + 1e-4 * len(internal)
    return {"leaves": len(internal) + 1, "rows_partitioned": part,
            "rows_staged": staged, "seconds": seconds,
            "largest_nodes": [int(c) for c in
                              np.sort(internal["count"])[::-1][:5]]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=VALUE")
    ap.add_argument("--keep", type=int, default=6,
                    help="every k-th column that is no signal column")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from benchmarks import run as brun
    task = brun.load_module(os.path.join(REPO, "benchmarks", "tasks",
                                         "binary_missing.py"))
    overrides = {k: json.loads(v) for k, v in
                 (item.split("=", 1) for item in args.set)}
    for name, value in overrides.items():
        assert hasattr(task, name), name
        setattr(task, name, value)
    t0 = time.time()
    plant = task.plant()
    X, y = task.parts_task(ROWS, (args.seed, 0))
    Xh, yh = task.parts_task(HELD_OUT, (args.seed, 1))
    keep = sorted({int(c) for c in plant.signal_columns}
                  | {int(c) for c in plant.first[:-1]}
                  | set(range(0, task.FEATURES, args.keep)))
    X, Xh = np.ascontiguousarray(X[:, keep]), np.ascontiguousarray(Xh[:, keep])

    import sklearn.ensemble._hist_gradient_boosting.gradient_boosting as gb
    from sklearn.ensemble import HistGradientBoostingClassifier
    from sklearn.metrics import roc_auc_score

    class Grower(gb.TreeGrower):
        def __init__(self, *a, **kw):
            kw["min_hessian_to_split"] = HESSIAN_FLOOR
            super().__init__(*a, **kw)

    gb.TreeGrower = Grower
    est = HistGradientBoostingClassifier(
        learning_rate=0.1, max_iter=120, max_leaf_nodes=255, max_depth=None,
        min_samples_leaf=1, l2_regularization=0.0, max_bins=63,
        early_stopping=False, warm_start=True, random_state=0)
    while True:
        est.fit(X, y)
        trees = [tree_costs(p[0].nodes) for p in est._predictors]
        spent = np.cumsum([t["seconds"] for t in trees[WARMUP:]])
        if spent[-1] >= WINDOW_S or est.max_iter >= 400:
            break
        est.max_iter += 20 + int((WINDOW_S - spent[-1])
                                 / np.mean([t["seconds"] for t in trees[-10:]]))
    n = min(int(np.searchsorted(spent, WINDOW_S)) + 1, len(spent))
    window = trees[WARMUP:WARMUP + n]
    seconds = np.array([t["seconds"] for t in window])
    every = est._predictors
    aucs = {}
    for cut in (8, 16, 30, 48, 64, WARMUP + n):
        est._predictors = every[:cut]
        aucs[str(cut)] = float(roc_auc_score(yh, est.decision_function(Xh)))
    est._predictors = every
    out = {"seed": args.seed, "set": overrides, "columns": len(keep),
           "positives": int(y.sum()),
           "train_s_per_iter": float(spent[n - 1] / n), "iters": n,
           "auc_at": aucs,
           "leaves": float(np.mean([t["leaves"] for t in window])),
           "rows_partitioned": float(np.mean(
               [t["rows_partitioned"] for t in window])),
           "rows_staged": float(np.mean([t["rows_staged"] for t in window])),
           # the standard deviation of a tree's seconds about its
           # neighbours': the drift through a run taken out
           "tree_to_tree_s": float(np.sqrt(np.mean(np.diff(seconds) ** 2) / 2)),
           "ten_tree_means_s": [
               round(float(np.mean([t["seconds"] for t in trees[i:i + 10]])),
                     4) for i in range(0, len(trees), 10)],
           "wall_s": round(time.time() - t0, 1)}
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    name = "sim_tree_costs.s%d%s.json" % (
        args.seed, "." + args.tag if args.tag else "")
    with open(os.path.join(REPO, "chiprun_out", name), "w") as fh:
        json.dump(dict(out, trees=trees), fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
