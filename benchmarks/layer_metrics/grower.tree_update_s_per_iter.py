"""Device seconds per iteration in the tree's own book-keeping: self
time of the operations traced under `jax.named_scope("lgbm.tree_update")`
and no narrower phase (the root's per-row output written into the
payload, the choice of the leaf to split, the state and record writes
after each split), a chip's average."""
from benchmarks.lib import progspans

LAYER = "grower-split-search"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    return progspans.phase_s_per_iter(run, "tree_update")
