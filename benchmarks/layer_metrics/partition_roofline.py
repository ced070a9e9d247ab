"""The partition kernel's share of its roofline: the least time a chip
needs to read and write every split's parent rows once
(lib/opbytes.py over lib/peaks.json), over the kernel's device time."""
from benchmarks.lib import opbytes

LAYER = "segment-kernels"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    seconds = run.metric("kernel.partition_s_per_iter")
    if not seconds or run.peaks is None or not run.trees:
        return None
    lanes = run.state["lanes"]
    n_bytes, n_ops = opbytes.partition_bytes(run.trees, lanes), 0
    least, bound = opbytes.least_seconds(n_bytes, n_ops, run.peaks,
                                         chips=run.cell["chips"])
    run.say("roofline", kernel="partition", bytes=n_bytes, ops=n_ops,
            least_s_per_iter=least / len(run.trees), bound=bound)
    return 100.0 * least / len(run.trees) / seconds
