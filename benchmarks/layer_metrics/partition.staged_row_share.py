"""Of the rows the window's trees partitioned, the share (%) that were
staged: the rows of the children that lay SECOND in their parents'
ranges, which the partition kernels park in the scratch buffer and move
once more (pass B), where a row of the first child is read once and
written once.  The booster's counters `rows_staged` / `rows_partitioned`
(`_FastState.counters`: an entry a finished tree, the kernels' own
returned counts summed in the grower's loop and read off the tree's own
fetch, so they cost no dispatch), summed over the window's iterations,
the last the booster ran (`verify` calls no `update()`).  A program that
always stages the right child reads the right children's share of the
rows; one that stages the smaller child reads under 50%.  None for a
program without the counters."""
LAYER = "segment-kernels"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    engine = getattr(run.state.get("bst"), "_engine", None)
    fast = getattr(engine, "_fast", None)
    counters = getattr(fast, "counters", None) or {}
    trees = int(run.window.get("iters", 0)) * int(getattr(fast, "K", 1))
    staged = counters.get("rows_staged")
    if not staged or not trees or len(staged) < trees:
        return None
    partitioned = sum(counters["rows_partitioned"][-trees:])
    if not partitioned:
        return None
    return 100.0 * sum(staged[-trees:]) / partitioned
