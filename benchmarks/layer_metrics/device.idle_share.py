"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, on the chip
that idled most."""
from benchmarks.lib import xplane

LAYER = "device"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = None


def read(run):
    trace = run.xtrace
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window_ns()
    if hi <= lo:
        return None
    busy = min(xplane.length(xplane.clip(d.busy, lo, hi))
               for d in trace.devices)
    return 100.0 * (1.0 - busy / (hi - lo))
