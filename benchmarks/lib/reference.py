"""The plain reference every configuration is held to.

A gradient-boosted tree model is its trees, so the reference is a tree
walked in numpy on the raw float64 features: no bins, no payload, no
kernels, nothing of the program but the arrays of the `Tree` it
produced.  Three uses:

* `predict_raw`: the sum of leaf values, against the device predictor;
* `tree0_check`: every training row routed through the first tree's
  thresholds, each leaf's row count against `leaf_count` (equal; past
  2^24 rows within what float32 counting loses, `count_slack`) and
  each leaf's value against -sum(g) / (sum(h) + lambda_l2) *
  learning_rate in float64.  The first tree's gradients follow from the labels and
  the initial score alone (binary log-loss from the average: two
  distinct gradients and one hessian), so this shares no code with the
  grower, the histogram or the partition, and a wrong one of those
  moves a row or a sum;
* `leaf_index` itself, for the held-out metric.

Rows are walked in chunks across threads (lib/parallel.py).

`X` is a dense [n, F] array or a scipy.sparse table.  A sparse one is
made CSR once, where a walk enters, and is never densified: a walk reads
one value a row and a level, the stored value or 0.0 where the row
stores none, which is what LightGBM means by an absent entry.  That 0.0
then meets the node's missing type as a stored 0.0 does (zero-as-missing
routes it by `default_left`, otherwise it is compared with the
threshold), and a stored NaN is a NaN.  A chunk is a row slice, which of
CSR is views of its arrays, so a level costs O(rows) and never O(rows x
columns).
"""
import numpy as np
from scipy import sparse

from benchmarks.lib import parallel

MISSING_ZERO, MISSING_NAN = 1, 2
K_ZERO = 1e-35          # the reference implementation's kZeroThreshold


def flat_table(tree):
    """One table of (internal nodes, then leaves) in which a leaf points
    at itself, so a walk needs no mask: (feature, threshold, missing
    type, default-left, left, right, slot of leaf 0)."""
    nl = int(tree.num_leaves)
    ni = nl - 1
    size = ni + nl
    feat = np.zeros(size, np.int64)
    thr = np.full(size, np.inf)
    miss = np.zeros(size, np.int8)
    dleft = np.zeros(size, bool)
    left = np.arange(size, dtype=np.int64)
    right = np.arange(size, dtype=np.int64)
    if ni:
        dt = np.asarray(tree.decision_type[:ni], np.int64)
        if np.any(dt & 1):
            raise ValueError("the plain reference walks numerical splits "
                             "only; this tree has a categorical one")
        feat[:ni] = tree.split_feature[:ni]
        thr[:ni] = tree.threshold[:ni]
        miss[:ni] = (dt >> 2) & 3
        dleft[:ni] = (dt & 2) != 0
        for dst, child in ((left, tree.left_child), (right, tree.right_child)):
            c = np.asarray(child[:ni], np.int64)
            dst[:ni] = np.where(c >= 0, c, ni + ~c)
    return feat, thr, miss, dleft, left, right, ni


def _depths(left, right, ni):
    """Depth of every slot of the flat table (the root is 0)."""
    depth = np.zeros(len(left), np.int64)
    for node in range(ni):          # children are created after parents
        depth[left[node]] = depth[right[node]] = depth[node] + 1
    return depth


def _walkable(X):
    """X as a walk reads it: a dense array as it is, a sparse table as
    CSR (a CSR table itself, not a copy)."""
    return X.tocsr() if sparse.issparse(X) else X


def _at(X, rows, cols):
    """X[rows[i], cols[i]] for every i: of a CSR table the stored value,
    or 0.0 where the row stores none (scipy's own sampling, in X's
    dtype, as a dense read is)."""
    if sparse.issparse(X):
        return np.asarray(X[rows, cols]).reshape(-1)
    return X[rows, cols]


def _walk(table, depth, X):
    """The leaf of each row of X, down at most `depth` levels."""
    feat, thr, miss, dleft, left, right, ni = table
    node = np.zeros(X.shape[0], np.int64)
    rows = np.arange(X.shape[0])
    for _ in range(depth):
        x = _at(X, rows, feat[node])
        m = miss[node]
        nan = np.isnan(x)
        x = np.where(nan & (m != MISSING_NAN), 0.0, x)
        missing = ((m == MISSING_ZERO) & (np.abs(x) <= K_ZERO)) \
            | ((m == MISSING_NAN) & nan)
        go_left = np.where(missing, dleft[node], x <= thr[node])
        node = np.where(go_left, left[node], right[node])
    return node - ni


def _walker(tree):
    """(flat table, depth) of a tree, made once for all row chunks."""
    table = flat_table(tree)
    return table, int(_depths(table[4], table[5], table[6]).max())


def leaf_index(tree, X):
    """The leaf each row of X [n, F] falls into."""
    X = _walkable(X)
    table, depth = _walker(tree)
    out = np.empty(X.shape[0], np.int64)

    def part(_, lo, hi):
        out[lo:hi] = _walk(table, depth, X[lo:hi])

    parallel.for_chunks(parallel.even_bounds(X.shape[0]), part)
    return out


def predict_raw(trees, X):
    """Raw score of each row: the sum of its leaves' values, float64,
    tree after tree."""
    X = _walkable(X)
    walkers = [_walker(t) + (np.asarray(t.leaf_value, np.float64),)
               for t in trees]
    out = np.empty(X.shape[0], np.float64)

    def part(_, lo, hi):
        acc = np.zeros(hi - lo)
        for table, depth, values in walkers:
            acc += values[_walk(table, depth, X[lo:hi])]
        out[lo:hi] = acc

    parallel.for_chunks(parallel.even_bounds(X.shape[0]), part)
    return out


def sigmoid(raw):
    return 1.0 / (1.0 + np.exp(-raw))


def count_slack(tree, leaf_rows):
    """How far each leaf's `leaf_count` may stand from `leaf_rows`, the
    rows that fall into it.  The program counts in float32 (the
    histogram's count channel summed over bins and shards, and the
    parent-less-sibling subtraction): exact below 2^24 rows, so there
    the slack is 0 and the counts must be EQUAL.  A count of 2^24 or
    more is rounded where it is made, and the loss is handed down to
    everything below it; nothing new is lost once a node is under 2^24.
    So a leaf's slack grows with the ancestors that hold 2^24 rows or
    more, not with its depth: 8 half-units in the last place of the
    total for each.  At 40M rows that is 16 rows for each such ancestor
    (one to three of them) against leaves of 157,000 rows on average;
    eight chip runs were off by at most 6 (PR 22), and a misrouted
    segment moves thousands."""
    n = int(leaf_rows.sum())
    if n < 1 << 24:
        return np.zeros(len(leaf_rows), np.int64)
    half_ulp = 1 << (n.bit_length() - 25)
    _, _, _, _, left, right, ni = flat_table(tree)
    rows = np.zeros(len(left), np.int64)
    rows[ni:] = leaf_rows
    for node in reversed(range(ni)):        # children come after parents
        rows[node] = rows[left[node]] + rows[right[node]]
    big = np.zeros(len(left), np.int64)     # ancestors of 2^24 rows or more
    for node in range(ni):
        big[left[node]] = big[right[node]] = \
            big[node] + (rows[node] >= 1 << 24)
    return 8 * half_ulp * big[ni:]


def tree0_check(tree, X, y, learning_rate, lambda_l2=0.0):
    """Hold the first tree of a binary log-loss model, boosted from the
    average, to the data it was grown on.  Returns what was compared:
    `counts_ok` (see `count_slack`), `max_value_diff`, and the leaves
    and rows."""
    nl = int(tree.num_leaves)
    leaf = leaf_index(tree, X)
    count = np.bincount(leaf, minlength=nl)
    n_pos = np.bincount(leaf, weights=(y > 0), minlength=nl)
    p = float(np.mean(y > 0))
    init = float(np.log(p / (1.0 - p)))
    # y = 1: g = p - 1; y = 0: g = p; h = p (1 - p) for every row
    grad = n_pos * (p - 1.0) + (count - n_pos) * p
    hess = count * p * (1.0 - p)
    expect = init - grad / (hess + lambda_l2) * learning_rate
    got = np.asarray(tree.leaf_value[:nl], np.float64)
    off = np.abs(count - np.asarray(tree.leaf_count[:nl], np.int64))
    slack = count_slack(tree, count)
    return {
        "leaves": nl, "rows": int(X.shape[0]),
        "counts_ok": bool((off <= slack).all()),
        "max_count_diff": int(off.max()), "leaves_off": int((off > 0).sum()),
        "count_slack_max": int(slack.max()),
        "max_value_diff": float(np.abs(got - expect).max()),
    }
