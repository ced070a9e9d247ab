"""Share of a chip's collective time during which no other operation ran
on that chip (what the collectives cost the iteration), the worst chip.
Nothing on one chip."""
from benchmarks.lib import xplane

LAYER = "mesh"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    trace = run.xtrace
    if trace is None or len(trace.devices) < 2:
        return None
    pattern = run.reader("mesh.collective_s_per_iter").PATTERN
    lo, hi = trace.window_ns()
    shares = []
    for d in trace.devices:
        coll = xplane.intervals_matching(d, pattern, lo, hi)
        if not coll:
            continue
        compute = xplane.leaf_intervals(d, lo, hi, exclude=pattern)
        shares.append(xplane.length(xplane.subtract(coll, compute))
                      / xplane.length(coll))
    return 100.0 * max(shares) if shares else None
