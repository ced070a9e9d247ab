"""Pair slots the ranking objective's program computes an iteration over
the pairs that exist, sum(n_q^2) over the queries (the objective's
`counters` after `init`: `pair_slots`, `pairs`): 1 would be a program
that pads nothing; a layout that pads every query to the longest reads
the square of the longest over the mean square.  None for an objective
without the counters."""
LAYER = "objective"
UNIT = "ratio"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    engine = getattr(run.state.get("bst"), "_engine", None)
    counters = getattr(getattr(engine, "objective", None), "counters", None)
    if not counters or not counters.get("pairs"):
        return None
    return counters["pair_slots"] / counters["pairs"]
