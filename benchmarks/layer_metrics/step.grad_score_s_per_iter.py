"""Device seconds per iteration in the two ends of the fused step that
pass over every row: the gradient fill before the tree
(`jax.named_scope("lgbm.grad")`) and the score update after it
(`lgbm.score`), self time of their operations, a chip's average."""
from benchmarks.lib import progspans

LAYER = "boosting-loop"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    return progspans.phase_s_per_iter(run, "grad", "score")
