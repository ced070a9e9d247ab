#!/usr/bin/env python3
"""The loops of a train cell's compiled step, by trip count: the cell's
own data at `--rows` rows (the step's loops do not depend on the rows),
its own `Dataset` and `Booster`, `gbdt.step` compiled for the device that
is there, and every `while` instruction of the compiled module with the
trip count XLA found for it, the scopes of its body's operations and
whether it sits inside another loop.

    python3 exp/step_loops.py [--workload expo-cat-train] [--rows 200000]

What it is for: no loop of the step should have the BIN COUNT as its trip
count (PERF.md section 6, PR 33: the categorical search walked 256 sorted
positions in a `lax.scan`, twice a child, where `max_cat_threshold`
positions do anything).
"""
import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def while_loops(hlo_text):
    """[{name, trip_count, computation, scopes}] for the `while`
    instructions of an HLO module's text."""
    loops, comp = [], None
    scopes_in = {}
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$", line)
        if head:
            comp = head.group(1)
            continue
        for scope in re.findall(r"lgbm\.([a-z_]+)", line):
            scopes_in.setdefault(comp, set()).add(scope)
        m = re.search(r"%?([\w.-]+) = .* while\(", line)
        if m:
            body = re.search(r"body=%?([\w.-]+)", line).group(1)
            trips = re.search(r'"known_trip_count":\{"n":"(\d+)"', line)
            loops.append({"name": m.group(1), "in": comp, "body": body,
                          "trip_count": int(trips.group(1)) if trips
                          else None})
    for loop in loops:
        loop["scopes"] = sorted(scopes_in.get(loop["body"], ()))
    return loops


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="expo-cat-train")
    ap.add_argument("--rows", type=int, default=200000)
    ap.add_argument("--seed", type=int, default=3000000799)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from benchmarks import run as bench
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    cfg = dict(bench.load_json(os.path.join(ROOT, entry["file"])),
               rows=args.rows)
    task = bench.load_module(os.path.join(
        bench.BENCH_DIR, "tasks", cfg.get("task", "binary") + ".py"))
    data = task.make(cfg, args.seed, 0)
    params = dict(cfg["params"], verbose=-1)
    bst = lgb.Booster(params, lgb.Dataset(
        data["X"], label=data["y"], params=params,
        **task.dataset_args(data)))
    bst.update()
    bst.current_iteration()             # drains the dispatch pipeline
    eng = bst._engine
    fs = eng._fast
    compiled = fs._step.lower(fs.payload, fs.aux, eng._feature_sample(),
                              jnp.float32(0.1), jnp.int32(0)).compile()
    loops = while_loops(compiled.as_text())
    report = {"workload": args.workload, "rows": args.rows,
              "device": jax.devices()[0].device_kind,
              "max_num_bin": int(eng.train_set.max_num_bin),
              "while": sorted(loops, key=lambda w: -(w["trip_count"] or 0))}
    for w in report["while"]:
        print("%-28s trips %-6s in %-28s body scopes %s"
              % (w["name"], w["trip_count"], w["in"], ",".join(w["scopes"])))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "step_loops.%s.json"
                           % args.workload), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({k: report[k] for k in ("workload", "device",
                                             "max_num_bin")}
                     | {"trip_counts": [w["trip_count"]
                                        for w in report["while"]]}))


if __name__ == "__main__":
    main()
