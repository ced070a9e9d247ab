"""Of the splits of the window's trees, the share (%) that are
categorical: the booster's counters `categorical_splits` / `splits`
(`_FastState.counters`: an entry a finished tree, read off the tree's
own fetch, so they cost no dispatch), summed over the window's
iterations, the last the booster ran (`verify` calls no `update()`).
None for a program without the counters."""
LAYER = "grower-split-search"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    engine = getattr(run.state.get("bst"), "_engine", None)
    fast = getattr(engine, "_fast", None)
    counters = getattr(fast, "counters", None)
    trees = int(run.window.get("iters", 0)) * int(getattr(fast, "K", 1))
    if not counters or not trees or len(counters["splits"]) < trees:
        return None
    splits = sum(counters["splits"][-trees:])
    if not splits:
        return None
    return 100.0 * sum(counters["categorical_splits"][-trees:]) / splits
