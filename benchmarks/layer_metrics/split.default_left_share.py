"""Of the window's splits on a column with a bin for missing values, the
share (%) that send the rows WITHOUT a value LEFT: the direction is the
data's (the side on which the gain is larger), so a task whose missing
rows look now like the low readings and now like the high ones reads
strictly between 0 and 100.  The booster's counters
`default_left_splits` / `missing_splits` (`split.missing_share` has the
sum over the window's trees).  None for a program without the counters
or a window without such a split."""
LAYER = "grower-split-search"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    counts = run.reader("split.missing_share").window_counts(
        run, "default_left_splits", "missing_splits")
    if not counts or not counts[1]:
        return None
    return 100.0 * counts[0] / counts[1]
