"""Device seconds per iteration in the split search: self time of the
operations traced under `jax.named_scope("lgbm.split_search")` (the
root's and every pair of children's `find_best_split*`), a chip's
average.  The scope is read from the `tf_op` of each operation's
metadata (lib/progspans.py)."""
from benchmarks.lib import progspans

LAYER = "grower-split-search"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    return progspans.phase_s_per_iter(run, "split_search")
