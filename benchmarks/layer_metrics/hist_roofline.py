"""The histogram kernel's share of its roofline: the least time a chip
needs for the rows the window's trees histogram, the larger of their
bytes over the HBM peak and their one-hot products over the bf16 peak
(lib/opbytes.py over lib/peaks.json), over the kernel's device time."""
from benchmarks.lib import opbytes

LAYER = "segment-kernels"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    seconds = run.metric("kernel.hist_s_per_iter")
    if not seconds or run.peaks is None or not run.trees:
        return None
    lanes = run.state["lanes"]
    n_bytes = opbytes.histogram_bytes(run.trees, lanes)
    n_ops = opbytes.histogram_ops(
        run.trees, run.state["storage_columns"],
        run.config["params"]["max_bin"] + 1)
    least, bound = opbytes.least_seconds(n_bytes, n_ops, run.peaks,
                                         chips=run.cell["chips"])
    run.say("roofline", kernel="histogram", bytes=n_bytes, ops=n_ops,
            least_s_per_iter=least / len(run.trees), bound=bound)
    return 100.0 * least / len(run.trees) / seconds
