"""Device seconds per iteration in every move of a ranking objective's
scores and gradients between the payload's partition order, original row
order and the query slots (`jax.named_scope("lgbm.grad_permute")`,
entered inside `lgbm.grad`): gathers and scatters over the row set, self
time of its operations, a chip's average.  None for a program or an
objective without the scope."""
from benchmarks.lib import progspans

LAYER = "objective"
UNIT = "s"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(run):
    found = progspans.phase_seconds(run)
    if not found or "grad_permute" not in found:
        return None
    return found["grad_permute"] / run.window["iters"]
