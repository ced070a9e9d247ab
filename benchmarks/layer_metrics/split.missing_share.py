"""Of the splits of the window's trees, the share (%) made on a column
that has a bin for missing values (a NaN bin, or the zero bin with
`zero_as_missing`): those splits scan both directions, carry a
`default_left` the data decided, and route the rows without a value by
it.  The booster's counters `missing_splits` / `splits`
(`_FastState.counters`: an entry a finished tree, read off the tree's
own fetch, so they cost no dispatch), summed over the window's
iterations, the last the booster ran (`verify` calls no `update()`).
None for a program without the counters."""
LAYER = "grower-split-search"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def window_counts(run, *names):
    """The named counters summed over the window's trees, or None where
    the program has not all of them or the window no tree."""
    engine = getattr(run.state.get("bst"), "_engine", None)
    fast = getattr(engine, "_fast", None)
    counters = getattr(fast, "counters", None) or {}
    trees = int(run.window.get("iters", 0)) * int(getattr(fast, "K", 1))
    if not trees or any(len(counters.get(n, ())) < trees for n in names):
        return None
    return [sum(counters[n][-trees:]) for n in names]


def read(run):
    counts = window_counts(run, "missing_splits", "splits")
    if not counts or not counts[1]:
        return None
    return 100.0 * counts[0] / counts[1]
