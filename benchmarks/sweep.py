#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one set-up, then one window
at each offered rate of a ladder, in one process on the chip.

    python3 benchmarks/sweep.py --workload higgs-serve --rates 50,100,200,400 \\
        [--manifest benchmarks/held/manifest.json] [--seconds 10] [--seed 1]

The knee is the highest rate that the system SUSTAINS: at least 99.5% of
the offered requests complete inside the window, none is rejected or
expires, and the backlog at the end of the window is no deeper than its
median over the window.  The mix's file then takes `knee_rps` and a
`rate_phases` of 0.8 times it by hand; the benchmark itself never
searches for a rate.  Prints one `[bench] sweep` line a rate and a last
line with the knee; writes nothing else.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.run import Refused, open_cell

SUSTAINED_SHARE = 0.995


def sustained(summary):
    return (summary["completed_in_window"]
            >= SUSTAINED_SHARE * summary["offered"]
            and not summary["not_ok"]
            and summary["backlog_end"] <= max(summary["backlog_median"], 1))


def sweep(workload, rates, seconds, seed, **where):
    """One set-up, one window a rate; returns the knee (None if no rate
    was sustained).  `where` is `open_cell`'s keywords."""
    _, run, driver, _ = open_cell(workload, seed, False, **where)
    driver.setup(run)
    knee = None
    try:
        for rate in sorted(rates):
            run.traffic = dict(run.traffic, rate_phases=[[1.0, rate]])
            run.window = {}
            driver.window(run, seconds)
            verdict = driver.verify(run)
            ok = sustained(run.window["summary"]) and verdict["correct"]
            run.say("sweep", rate_rps=rate, sustained=ok,
                    wrong=verdict["checks"]["wrong"])
            if ok:
                knee = rate
    finally:
        driver.teardown(run)
    return knee, run.device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="offered requests per second, comma-separated")
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        knee, device = sweep(args.workload,
                             [float(r) for r in args.rates.split(",")],
                             args.seconds, args.seed,
                             manifest_path=args.manifest)
    except Refused as e:
        print("benchmarks/sweep.py: refused: %s" % e, file=sys.stderr)
        return 2
    print(json.dumps({"knee_rps": knee, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
