"""The least work a tree needs from the two segment kernels, computed
from the trees the window trained and the payload's width.

Partition.  Every split reorders its parent's rows in place, so the
parent's rows are read once and written once:
    sum over internal nodes of internal_count x lanes x 4 B x 2.
Histogram.  With the subtraction trick only the smaller child of a split
is histogrammed, after the root's whole segment:
    rows = root count + sum over splits of min(left count, right count),
    bytes = rows x lanes x 4 B (the row is read once),
    operations = rows x features x bins x PARTS x 2: the one-hot matrix
    product on the MXU that is this system's histogram (seven bf16
    part-columns: gradient and hessian in three exact parts each, and
    the count).
Bytes are those of the payload as it is laid out (bin ids ride f32
lanes): a narrower payload lowers the bytes and the time together, and
the share says how close a kernel is to streaming its own layout.

The least time is the larger of bytes over the HBM peak and operations
over the bf16 peak; `least_seconds` says which of the two binds.  On a
mesh the rows are spread evenly, so a chip's share is the total over the
chips.
"""
import numpy as np

BYTES_PER_LANE = 4
HIST_PARTS = 7


def _child_counts(tree):
    """(left count, right count) of every internal node."""
    ni = int(tree.num_leaves) - 1
    internal = np.asarray(tree.internal_count[:ni], np.int64)
    leaf = np.asarray(tree.leaf_count, np.int64)

    def count(children):
        c = np.asarray(children[:ni], np.int64)
        return np.where(c >= 0, internal[np.maximum(c, 0)], leaf[~np.minimum(c, -1)])

    return count(tree.left_child), count(tree.right_child)


def partition_rows(trees):
    """Rows moved by the splits of these trees (each once)."""
    return int(sum(np.asarray(t.internal_count[:int(t.num_leaves) - 1],
                              np.int64).sum() for t in trees))


def histogram_rows(trees):
    """Rows histogrammed for these trees."""
    total = 0
    for t in trees:
        if int(t.num_leaves) < 2:
            continue
        left, right = _child_counts(t)
        total += int(t.internal_count[0]) + int(np.minimum(left, right).sum())
    return total


def partition_bytes(trees, lanes):
    return partition_rows(trees) * lanes * BYTES_PER_LANE * 2


def histogram_bytes(trees, lanes):
    return histogram_rows(trees) * lanes * BYTES_PER_LANE


def histogram_ops(trees, features, bins):
    return histogram_rows(trees) * features * bins * HIST_PARTS * 2


def least_seconds(n_bytes, n_ops, peaks, chips=1):
    """(seconds, "bytes" | "ops"): the least time one chip of `chips`
    can take for its share."""
    by_bytes = n_bytes / chips / peaks["hbm_bytes_per_s"]
    by_ops = n_ops / chips / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
