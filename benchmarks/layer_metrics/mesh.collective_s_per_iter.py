"""Device seconds per iteration inside collective operations (all-reduce,
reduce-scatter, all-gather, collective-permute, all-to-all), a chip's
average.  Nothing on one chip."""
import re

from benchmarks.lib import xplane

PATTERN = re.compile(r"^%?(all-reduce|reduce-scatter|all-gather|"
                     r"collective-permute|all-to-all)")
LAYER = "mesh"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    if run.xtrace is None or len(run.xtrace.devices) < 2:
        return None
    seconds = xplane.mean_seconds_matching(run.xtrace, PATTERN)
    return seconds / run.window["iters"]
