"""Blocking device-to-host fetches per iteration of the window
(`runtime/syncs.py`), on any thread."""
LAYER = "boosting-loop"
UNIT = "count"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    return run.counts["syncs"]["total"] / run.window["iters"]
