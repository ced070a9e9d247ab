"""Seconds between the arrivals of the trees of the window's successive
iterations, the median: the assembler thread's `fetch/pipeline_drain`
closes when tree k is on the host, so the interval between two such
closes is the device's time for an iteration as the PROGRAM sees it,
profiler off (the device idles under 1% of a window).  A drain is paired
with its iteration by its `iteration` label, by its parent id on a
program without the label."""
from statistics import median

from benchmarks.lib import iterspans

LAYER = "boosting-loop"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    evs = iterspans.events()
    arrivals = [iterspans.tree_arrival_ns(it, evs)
                for it in iterspans.window(run, evs)]
    steps = [b - a for a, b in zip(arrivals, arrivals[1:])
             if a is not None and b is not None]
    return median(steps) / 1e9 if steps else None
