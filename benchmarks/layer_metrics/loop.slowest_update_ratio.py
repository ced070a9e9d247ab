"""The window's longest `Booster.update()` over its median one, from the
program's `train/iteration` spans: 1.0 is a window in which no
iteration stood out; one stall of 2.5 s in a window of 0.52 s
iterations reads 4.8."""
from statistics import median

from benchmarks.lib import iterspans

LAYER = "boosting-loop"
UNIT = "ratio"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    walls = [it.dur_ns for it in iterspans.window(run, iterspans.events())]
    return max(walls) / median(walls) if walls and median(walls) else None
