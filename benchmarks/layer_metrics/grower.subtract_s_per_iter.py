"""Device seconds per iteration in the histogram subtraction: self time
of the operations traced under `jax.named_scope("lgbm.subtract")` (the
parent's histogram read, parent minus smaller child, the two selects and
the writes into the histogram state), a chip's average."""
from benchmarks.lib import progspans

LAYER = "grower-split-search"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    return progspans.phase_s_per_iter(run, "subtract")
