"""Device seconds per iteration in the per-query pairwise program of a
ranking objective: the ranks within each query, the pair terms and their
reductions (`jax.named_scope("lgbm.grad_pairs")`, entered inside
`lgbm.grad` by `objective/rank.py`), self time of its operations, a
chip's average.  None for a program or an objective without the scope."""
from benchmarks.lib import progspans

LAYER = "objective"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    found = progspans.phase_seconds(run)
    if not found or "grad_pairs" not in found:
        return None
    return found["grad_pairs"] / run.window["iters"]
