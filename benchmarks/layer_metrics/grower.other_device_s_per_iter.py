"""Device seconds per iteration in neither segment kernel nor a
collective: split search, gradient fill, score update, histogram
subtraction and whatever the loop itself costs.  The per-split fixed
cost of ROADMAP A5 shows here."""
from benchmarks.lib import xplane

LAYER = "grower-split-search"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    if run.xtrace is None or not run.xtrace.devices:
        return None
    busy_s, _ = xplane.busy_seconds(run.xtrace)
    named = sum(run.metric(m) or 0.0 for m in (
        "kernel.partition_s_per_iter", "kernel.hist_s_per_iter",
        "mesh.collective_s_per_iter"))
    return busy_s / run.window["iters"] - named
