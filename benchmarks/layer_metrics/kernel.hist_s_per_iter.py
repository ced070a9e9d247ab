"""Device seconds per iteration inside the histogram kernel (the Pallas
one-hot histogram of `ops/pallas_segment.py`), a chip's average.
Today's trace names the kernel's custom call after the jitted wrapper,
`%_segment_histogram.<n>`: the pattern matches that
(PERF.md, "Names in the trace")."""
import re

from benchmarks.lib import xplane

PATTERN = re.compile(r"^%?_segment_histogram")
LAYER = "segment-kernels"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    seconds = xplane.mean_seconds_matching(run.xtrace, PATTERN)
    return None if seconds is None else seconds / run.window["iters"]
