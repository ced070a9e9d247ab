"""Device seconds per iteration in the categorical split search: self
time of the operations traced under `jax.named_scope("lgbm.cat_search")`
(entered by ops/split.py INSIDE `lgbm.split_search`, round the sort of a
histogram's bins and the walk over the sorted ones, for the root and for
every pair of children), a chip's average.  An operation's phase is its
innermost scope, so on a cell with categorical columns
`grower.split_search_s_per_iter` reads what is left of the split search
outside this scope (the numerical search and the choice between the
two).  None for a program without the scope, and for data without a
categorical column (the step then has no such search)."""
from benchmarks.lib import progspans

LAYER = "grower-split-search"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    found = progspans.phase_seconds(run)
    if not found or "cat_search" not in found:
        return None
    return found["cat_search"] / run.window["iters"]
