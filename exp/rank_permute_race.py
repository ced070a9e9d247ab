#!/usr/bin/env python3
"""Time the row-set permutations of the ranking objective's gradient
fill, alone, at the `msltr` cell's sizes: what `gradients_in_order`
(objective/rank.py) does today beside the ways it could be done.

    python3 exp/rank_permute_race.py [--rows 2270296] [--slots 3400000]

Rows in a random order (the payload's partition order after a few
trees), each with the slot of its query's row.  Printed: milliseconds a
call, the median of `--reps` calls after a warm-up, a line a variant.
The readings chose between the variants (PERF.md section 6, PR 31); they
are no speed of record.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2270296)
    ap.add_argument("--slots", type=int, default=3400000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    if not args.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("a reading needs the chip (--cpu rehearses)")

    n, T = args.rows, args.slots
    rng = np.random.default_rng(0)
    guard = 256
    row = np.concatenate([rng.permutation(n), np.full(guard, n)])
    slot_of_row = np.sort(rng.choice(T, n, replace=False)).astype(np.int32)
    row = jnp.asarray(row.astype(np.int32))
    table = jnp.asarray(np.concatenate([slot_of_row, [T]]).astype(np.int32))
    score = jnp.asarray(rng.normal(size=n + guard).astype(np.float32))
    g = jnp.asarray(rng.normal(size=T + 1).astype(np.float32))
    h = jnp.asarray(rng.normal(size=T + 1).astype(np.float32))
    slot = table[row]
    arrays = (table, row, slot, score, g, h)

    # every array is an ARGUMENT of the jitted variant: closed over, the
    # whole of it is a constant expression and XLA folds it away
    variants = {
        "slot_of_row[row] (int gather)": (
            lambda table, row, slot, score, g, h: table[row]),
        "scatter set, unique, drop": (
            lambda table, row, slot, score, g, h:
            jnp.full(T, -1e30, jnp.float32).at[slot].set(
                score, mode="drop", unique_indices=True)),
        "scatter set, not flagged unique": (
            lambda table, row, slot, score, g, h:
            jnp.full(T, -1e30, jnp.float32).at[slot].set(
                score, mode="drop")),
        "two gathers g[slot], h[slot]": (
            lambda table, row, slot, score, g, h: (g[slot], h[slot])),
        "two gathers, promised in bounds": (
            lambda table, row, slot, score, g, h:
            (g.at[slot].get(mode="promise_in_bounds"),
             h.at[slot].get(mode="promise_in_bounds"))),
        "one gather of [2, T] columns": (
            lambda table, row, slot, score, g, h:
            jnp.stack([g, h])[:, slot]),
        "one gather of [T, 2] rows": (
            lambda table, row, slot, score, g, h:
            jnp.stack([g, h], axis=1)[slot]),
        "one gather of [T, 8] rows": (
            lambda table, row, slot, score, g, h:
            jnp.stack([g, h] + [g] * 6, axis=1)[slot]),
        "sort rows by slot (score rides)": (
            lambda table, row, slot, score, g, h:
            jax.lax.sort((slot, score), num_keys=1)),
    }
    out = {}
    for name, fn in variants.items():
        f = jax.jit(fn)
        jax.block_until_ready(f(*arrays))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*arrays))
            times.append(time.perf_counter() - t0)
        out[name] = round(1e3 * float(np.median(times)), 3)
        print("%-36s %8.3f ms" % (name, out[name]), flush=True)
    print(json.dumps({"rows": n, "slots": T, "ms": out,
                      "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
