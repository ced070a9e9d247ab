#!/usr/bin/env python3
"""A control for what a train cell's `correct` can tell (`epsilon-train`
unless `--workload` names another, as `msltr-train`): the cell run
once as it is and once with the histogram in the nearest precision below
the program's own, both on one seed, and the two first trees side by side.

    python3 exp/epsilon_precision_control.py run --variant sound --seed N
    python3 exp/epsilon_precision_control.py run --variant bf16 --seed N
    python3 exp/epsilon_precision_control.py compare --seed N

`sound` is the program as it stands: the histogram kernel splits every
gradient and hessian into three bf16 parts, so the MXU's one bf16 pass sums
them exactly into f32.  `bf16` is a copy of the package (under
chiprun_out/, never the tree) in which the kernel keeps the first part
only: each gradient rounded to 8 bits before it is summed, which is what
the MXU does to an f32 matmul left at the default precision.  The
partition, the split search and the harness are the same files in both.

A run is the benchmark's own `open_cell`, `setup`, `window` and `verify`;
its verdict and tree 0's splits go to chiprun_out/control/.  One chip, one
process: give each variant a call to `python3` of its own.
"""
import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "control")
WORKLOAD = "epsilon-train"

#: the second and third part of the histogram kernel's decomposition (eight
#: spaces deep; the partition kernel's own, in `_bf16_parts`, stays exact)
PARTS = re.compile(
    r"^ {8}mid = r1\.astype\(jnp\.bfloat16\)\.astype\(jnp\.float32\)\n"
    r" {8}lo = r1 - mid\n", re.M)
FIRST_PART_ONLY = ("        mid = jnp.zeros_like(r1)\n"
                   "        lo = jnp.zeros_like(r1)\n")
HIST_KERNELS = 1


def bf16_package():
    """A copy of `lightgbm_tpu` whose histogram kernel sums bf16-rounded
    gradients; returns the directory to put first on `sys.path`."""
    src = os.path.join(OUT, "bf16_src")
    shutil.rmtree(src, ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "lightgbm_tpu"),
                    os.path.join(src, "lightgbm_tpu"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "cpp"), os.path.join(src, "cpp"))
    path = os.path.join(src, "lightgbm_tpu", "ops", "pallas_segment.py")
    with open(path) as fh:
        text, n = PARTS.subn(FIRST_PART_ONLY, fh.read())
    if n != HIST_KERNELS:
        raise SystemExit("%d decompositions patched, not %d: the kernels "
                         "have changed under this control" % (n, HIST_KERNELS))
    with open(path, "w") as fh:
        fh.write(text)
    return src


def out_path(variant, seed, workload=WORKLOAD):
    name = variant if workload == WORKLOAD else "%s.%s" % (workload, variant)
    return os.path.join(OUT, "%s.s%d.json" % (name, seed))


def run(variant, seed, seconds, manifest=None, require_tpu=True,
        workload=WORKLOAD):
    os.makedirs(OUT, exist_ok=True)
    sys.path.insert(0, ROOT)
    if variant == "bf16":
        sys.path.insert(0, bf16_package())
    from benchmarks import run as bench
    import lightgbm_tpu
    _, cell, driver, counter = bench.open_cell(
        workload, seed, False, manifest_path=manifest,
        require_tpu=require_tpu)
    driver.setup(cell)
    before = counter.snapshot()
    driver.window(cell, seconds)
    compiled = counter.delta(before)["requests"]
    verdict = driver.verify(cell)
    verdict["correct"] = bool(verdict["correct"] and not compiled)
    tree = cell.state["bst"]._engine.model.trees[0]
    splits = int(tree.num_leaves) - 1
    record = {
        "variant": variant, "seed": seed, "package": lightgbm_tpu.__file__,
        "correct": verdict["correct"], "compiled_in_window": compiled,
        "checks": verdict["checks"], "metrics": cell.window["metrics"],
        "tree0": {key: [float(v) for v in getattr(tree, key)[:splits]]
                  for key in ("split_feature", "threshold", "split_gain")},
    }
    with open(out_path(variant, seed, workload), "w") as fh:
        json.dump(record, fh, indent=1, default=str)
    print(json.dumps({k: record[k] for k in ("variant", "seed", "correct",
                                             "metrics")}
                     | {"tree0": verdict["checks"]["tree0"],
                        "engines": verdict["checks"]["engines"]}))


def compare(seed, workload=WORKLOAD):
    """Tree 0 of the two variants on one seed (so on the same bins): the
    splits made in the same order at the same place, and the control's
    readings beside the sound run's."""
    sound, control = (json.load(open(out_path(v, seed, workload)))
                      for v in ("sound", "bf16"))
    a, b = sound["tree0"], control["tree0"]
    pairs = [list(zip(t["split_feature"], t["threshold"])) for t in (a, b)]
    same = sum(x == y for x, y in zip(*pairs))
    first = next((i for i, (x, y) in enumerate(zip(*pairs)) if x != y), None)
    report = {
        "seed": seed, "splits": [len(p) for p in pairs],
        "same_split_in_order": same, "first_split_that_differs": first,
        "same_splits_as_a_set": len(set(pairs[0]) & set(pairs[1])),
        "root_gain": [a["split_gain"][0], b["split_gain"][0]],
    }
    for name, rec in (("sound", sound), ("bf16", control)):
        t0 = rec["checks"]["tree0"]
        report[name] = {
            "correct": rec["correct"], "counts_ok": t0["counts_ok"],
            "max_count_diff": t0["max_count_diff"],
            "max_value_diff": t0["max_value_diff"],
            "heldout_quality": rec["metrics"].get("heldout_quality"),
            "train_s_per_iter": rec["metrics"]["train_s_per_iter"]}
    print(json.dumps(report))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("action", choices=("run", "compare"))
    ap.add_argument("--variant", choices=("sound", "bf16"), default="sound")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--workload", default=WORKLOAD,
                    help="another train cell than %s" % WORKLOAD)
    ap.add_argument("--manifest", default=None,
                    help="a manifest whose `epsilon-train` is cut to size, "
                         "with --cpu: a rehearsal of this script, no reading")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.action == "run":
        run(args.variant, args.seed, args.seconds, args.manifest,
            not args.cpu, args.workload)
    else:
        compare(args.seed, args.workload)


if __name__ == "__main__":
    main()
