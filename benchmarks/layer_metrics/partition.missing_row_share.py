"""Of the rows the window's trees partitioned, the share (%) that their
split's column had NO VALUE for: the partition kernels route those by the
split's `default_left` flag and not by its threshold (the `miss` term of
the predicate).  The booster's counters `rows_missing` /
`rows_partitioned`: `rows_missing` is summed a split from the split
leaf's own histogram, its count at the column's missing bin
(`split.missing_share` has the sum over the window's trees).  None for a
program without the counters."""
LAYER = "segment-kernels"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    counts = run.reader("split.missing_share").window_counts(
        run, "rows_missing", "rows_partitioned")
    if not counts or not counts[1]:
        return None
    return 100.0 * counts[0] / counts[1]
