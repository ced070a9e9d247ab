"""Closed-loop training: one trainer calling `Booster.update()`.

Set-up: the task's data and its held-out rows from the seed,
`lgb.Dataset` constructed, the `Booster` (the configuration's `params`,
with the mix's own `params` over them: bagging, GOSS or quantized
gradients are mixes), `warmup_iters` iterations and a drain (every
shape the window uses is compiled or loaded by then).  Window:
`update()` until the seconds are spent, then the drain that hands every
tree to the host; the time is divided by the iterations that ran.  A
traced window runs `trace_iters` iterations instead.

What is being learned is not this file's business.  The configuration
names a learning task in `task` (`binary` where it names none) and
`tasks/<task>.py` makes the data, says what else `lgb.Dataset` needs of
it (query sizes, categorical columns), recomputes the first tree for
its objective and scores the held-out rows (tasks/binary.py lists the
four functions).  Every learning task runs under this driver, so a
per-layer reader with `DRIVERS = ("train",)` reads all of them.

Correct means: the partition-ordered fast path with the engines the
configuration names; on a mesh, the payload on as many distinct devices
as the cell has chips; every tree of the window has more than one leaf;
the first tree is the function the task's plain reference computes from
the raw data (row counts equal -- past 2^24 rows, within what float32
counting loses -- and leaf values within the configuration's
`leaf_value_atol`); and the held-out measure of the model cut at
`quality_at_iter` lies in the band recorded when the cell was defined.
A task's `first_tree` may name further numbers it compared, under
`compared` ({name: [value, limit]}): they follow the driver's own in
the result's `compared` and never replace one.
"""
import os
import sys
import time

import numpy as np

#: the first tree's leaf values against float64 sums, where a
#: configuration states no `leaf_value_atol` of its own: the subtraction
#: trick hands a small leaf the absolute rounding of its largest
#: ancestor's f32 gradient sum (chip_smoke.LEAF_VALUE_ATOL, PR 21:
#: largest seen 1.4e-4 at 10.5M rows)
LEAF_VALUE_ATOL = 5e-4


def load_task(run):
    """tasks/<task>.py beside this run's drivers, by the loader that
    found this file (run.py, as a script or as a module)."""
    harness = sys.modules[type(run).__module__]
    name = run.config.get("task", "binary")
    return harness.load_module(
        os.path.join(run.bench_dir, "tasks", name + ".py"))


def setup(run):
    import lightgbm_tpu as lgb
    cfg, mix = run.config, run.traffic
    params = dict(cfg["params"], **mix.get("params", {}))
    task = load_task(run)
    with run.timed("data_s"):
        data = task.make(cfg, run.seed, 0)
        held = task.make(cfg, run.seed, 1)
    with run.timed("dataset_s"):
        train_set = lgb.Dataset(data["X"], label=data["y"], params=params,
                                **task.dataset_args(data)).construct()
    with run.timed("booster_s"):
        bst = lgb.Booster(params, train_set)
    with run.timed("warmup_s"):
        for _ in range(mix["warmup_iters"]):
            bst.update()
        bst.current_iteration()             # drains the dispatch pipeline
    run.state.update(bst=bst, task=task, data=data, held=held,
                     binning=train_set.binned.binning)
    run.say("train", rows=data["X"].shape[0], features=data["X"].shape[1],
            binning=run.state["binning"], **run.setup)


def window(run, seconds):
    bst, mix = run.state["bst"], run.traffic
    returned = []                           # when each update() came back
    t0 = time.perf_counter()
    while (len(returned) < mix["trace_iters"] if run.trace
           else time.perf_counter() - t0 < seconds):
        with run.span("bench/update"):
            finished = bst.update()
        returned.append(time.perf_counter() - t0)
        if finished:
            break
    with run.span("bench/drain"):
        bst.current_iteration()
    wall = time.perf_counter() - t0
    iters = len(returned)
    if not run.trace and iters < mix["min_iters"]:
        raise RuntimeError("%d iterations in %.1f s: fewer than min_iters=%d"
                           % (iters, seconds, mix["min_iters"]))
    run.window.update(iters=iters, seconds=wall,
                      metrics={"train_s_per_iter": wall / iters})
    steps = np.diff([0.0] + returned)
    run.say("window", iters=iters, seconds=wall, s_per_iter=wall / iters,
            drain_s=wall - returned[-1],
            update_return_s=[round(float(s), 4) for s in steps])


def _on_distinct_devices(payload):
    return len({s.device.id for s in payload.addressable_shards})


def verify(run):
    cfg, mix = run.config, run.traffic
    bst = run.state["bst"]
    eng = bst._engine
    trees = eng.model.trees
    warm, iters = mix["warmup_iters"], run.window["iters"]
    run.trees = trees[warm:warm + iters]
    fast = eng._fast
    run.state.update(lanes=int(fast.P), storage_columns=int(fast.G),
                     payload_rows=int(fast.n_rows),
                     wide_index=bool(fast.wide_idx))
    leaves = [int(t.num_leaves) for t in run.trees]
    failed = sum(n < 2 for n in leaves) + (warm + iters - len(trees))
    checks = {
        "fast_path": bool(eng._fast_active),
        "engines": eng.engines,
        "payload": {"rows": int(fast.n_rows), "lanes": int(fast.P),
                    "wide_index": bool(fast.wide_idx),
                    "devices": _on_distinct_devices(fast.payload)},
        "trees_on_host": len(trees), "leaves_min": min(leaves, default=0),
    }
    ok = (checks["fast_path"] and eng.engines == cfg["engines"]
          and checks["payload"]["devices"] == run.cell["chips"]
          and failed == 0)

    t0 = time.perf_counter()
    task = run.state["task"]
    atol = cfg.get("leaf_value_atol", LEAF_VALUE_ATOL)
    tree0 = dict(task.first_tree(trees[0], run.state["data"], cfg),
                 leaf_value_atol=atol)
    task_compared = tree0.pop("compared", {})
    checks["tree0"] = tree0
    ok = ok and tree0["counts_ok"] and tree0["max_value_diff"] <= atol

    cut = cfg["quality_at_iter"]
    if len(trees) >= cut:
        value = float(task.heldout(trees[:cut], run.state["held"], cfg))
        lo, hi = cfg["quality_band"]
        checks["heldout"] = {"metric": cfg["quality_metric"], "at_iter": cut,
                             "value": value, "band": [lo, hi]}
        ok = ok and lo <= value <= hi
        run.window["metrics"]["heldout_quality"] = value
    checks["verify_s"] = time.perf_counter() - t0
    compared = {
        "trees_failed": [int(failed), 0],
        "payload_devices": [checks["payload"]["devices"], run.cell["chips"]],
        "tree0_max_count_diff": [tree0.get("max_count_diff"),
                                 tree0.get("count_slack_max")],
        "tree0_max_value_diff": [tree0["max_value_diff"], atol],
    }
    if "heldout" in checks:
        compared["heldout_in_band"] = [checks["heldout"]["value"],
                                       checks["heldout"]["band"]]
    for name, pair in task_compared.items():    # never over the driver's
        compared.setdefault(name, pair)
    return {"correct": bool(ok), "attempted": iters, "failed": int(failed),
            "checks": checks, "compared": compared}
