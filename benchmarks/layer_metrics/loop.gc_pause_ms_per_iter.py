"""Milliseconds per iteration of the window in which Python collected
garbage, on any thread (a collection holds the interpreter, so it stops
the dispatch thread whichever thread runs it): the program's `host/gc`
spans, as far as they lie inside the window's `train/iteration` spans.
None on a program that records no host account."""
from benchmarks.lib import iterspans

LAYER = "boosting-loop"
UNIT = "ms"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    evs = iterspans.events()
    iters = iterspans.window(run, evs)
    if not iterspans.accounted(iters):
        return None
    paused = sum(iterspans.overlap_ns(e, iters) for e in evs
                 if e.name == "host/gc")
    return paused / 1e6 / len(iters)
