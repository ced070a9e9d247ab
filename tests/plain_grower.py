"""A plain leaf-wise grower: float64 numpy, one leaf at a time.

What the system's fused step computes with kernels, a histogram pool, a
subtraction trick and a partition-ordered payload, computed here the slow
way so that the two share nothing below the bin boundaries:

* the histogram of a leaf by `np.add.at` over its rows, every column;
* the best split by trying every (column, threshold) and keeping the
  first largest gain, with the reference's gain formula
  (feature_histogram.hpp GetSplitGains / CalculateSplittedLeafOutput:
  G_l^2 / (H_l + l2) + G_r^2 / (H_r + l2) less the leaf's own, the
  hessian sums seeded with kEpsilon) and its two leaf constraints;
* the partition of a leaf by a boolean mask over its rows.

Numerical columns without missing values only (what the wide dense
configurations hold).  Rows are binned here from the raw values and each
column's upper bounds; a threshold's value is its bin's upper bound and
rows with `bin <= threshold bin` go left, as `Tree.split` records it.
"""
import numpy as np

from lightgbm_tpu.models.tree import Tree

K_EPSILON = 1e-15


def bin_rows(X, upper_bounds):
    """[n, F] bin of every value: the first bin whose upper bound is not
    below it (the last bound is +inf)."""
    return np.stack([np.searchsorted(b[:-1], X[:, j], side="left")
                     for j, b in enumerate(upper_bounds)], axis=1)


def leaf_histogram(bins, grad, hess, rows, num_bins):
    """[F, num_bins, 3] sums of (gradient, hessian, 1) over `rows`."""
    n_features = bins.shape[1]
    hist = np.zeros((n_features, num_bins, 3), np.float64)
    cols = np.broadcast_to(np.arange(n_features), (len(rows), n_features))
    for channel, values in enumerate((grad[rows], hess[rows],
                                      np.ones(len(rows)))):
        np.add.at(hist[:, :, channel], (cols, bins[rows]),
                  values[:, None])
    return hist


def leaf_gain(sum_g, sum_h, l2):
    return sum_g * sum_g / (sum_h + l2)


def best_split(hist, n_bins, l2, min_data_in_leaf, min_sum_hessian):
    """(gain, column, threshold bin, left sums, right sums) of the first
    largest gain over every column and every threshold, or None where no
    split is allowed or none gains.  The right side of a threshold is the
    sum of the bins above it and the left is what is left of the leaf
    (the reference's one scan of a column without missing values)."""
    total = hist[0].sum(axis=0)                 # every column holds the leaf
    sum_g, sum_h, count = total[0], total[1] + 2 * K_EPSILON, total[2]
    # above[f, t]: the sums over bins t + 1 and up
    above = np.cumsum(hist[:, ::-1], axis=1)[:, ::-1]
    above = np.concatenate([above[:, 1:], np.zeros_like(above[:, :1])],
                           axis=1)
    right_g, right_h, right_c = (above[:, :, 0], above[:, :, 1] + K_EPSILON,
                                 above[:, :, 2])
    left_g, left_h, left_c = sum_g - right_g, sum_h - right_h, count - right_c
    thresholds = np.arange(hist.shape[1])[None, :] < \
        (np.asarray(n_bins) - 1)[:, None]
    allowed = (thresholds
               & (left_c >= min_data_in_leaf) & (right_c >= min_data_in_leaf)
               & (left_h >= min_sum_hessian) & (right_h >= min_sum_hessian))
    with np.errstate(divide="ignore", invalid="ignore"):    # an empty side
        gain = leaf_gain(left_g, left_h, l2) \
            + leaf_gain(right_g, right_h, l2) - leaf_gain(sum_g, sum_h, l2)
    gain = np.where(allowed & (gain > 0), gain, -np.inf)
    col, t = np.unravel_index(np.argmax(gain), gain.shape)
    if not np.isfinite(gain[col, t]):
        return None
    return (gain[col, t], int(col), int(t),
            (left_g[col, t], left_h[col, t], left_c[col, t]),
            (right_g[col, t], right_h[col, t], right_c[col, t]))


def grow_tree(bins, upper_bounds, grad, hess, num_leaves, learning_rate,
              l2=0.0, min_data_in_leaf=20, min_sum_hessian=1e-3):
    """One leaf-wise tree: (`Tree`, leaf of every row).  The leaf with the
    largest gain splits next; its left child keeps its number."""
    num_bins = max(len(b) for b in upper_bounds)
    n_bins = [len(b) for b in upper_bounds]
    tree = Tree(num_leaves)
    leaf_of = np.zeros(len(bins), np.int64)

    def candidate(leaf):
        rows = np.flatnonzero(leaf_of == leaf)
        return best_split(leaf_histogram(bins, grad, hess, rows, num_bins),
                          n_bins, l2, min_data_in_leaf, min_sum_hessian)

    candidates = {0: candidate(0)}
    root_g, root_h = grad.sum(), hess.sum()
    tree.leaf_value[0] = -root_g / (root_h + l2) * learning_rate
    tree.leaf_count[0] = len(bins)
    while tree.num_leaves < num_leaves:
        open_leaves = [leaf for leaf, c in candidates.items() if c is not None]
        if not open_leaves:
            break
        leaf = max(open_leaves, key=lambda leaf: (candidates[leaf][0], -leaf))
        gain, col, t, left, right = candidates.pop(leaf)
        new_leaf = tree.num_leaves
        tree.split(leaf, col, t, float(upper_bounds[col][t]),
                   -left[0] / (left[1] + l2) * learning_rate,
                   -right[0] / (right[1] + l2) * learning_rate,
                   int(left[2]), int(right[2]), gain,
                   missing_type=0, default_left=True)
        goes_right = (leaf_of == leaf) & (bins[:, col] > t)
        leaf_of[goes_right] = new_leaf
        candidates[leaf] = candidate(leaf)
        candidates[new_leaf] = candidate(new_leaf)
    return tree, leaf_of


def sigmoid(raw):
    return 1.0 / (1.0 + np.exp(-raw))


def log_loss(y, raw):
    p = np.clip(sigmoid(raw), 1e-15, 1 - 1e-15)
    return float(-np.mean(np.where(y > 0, np.log(p), np.log(1 - p))))


def boost_binary(X, y, upper_bounds, num_trees, **tree_params):
    """Binary log-loss boosting from the average: (trees with the initial
    score in the first, training loss after each tree)."""
    bins = bin_rows(X, upper_bounds)
    p = float(np.mean(y > 0))
    init = float(np.log(p / (1.0 - p)))
    raw = np.full(len(X), init)
    trees, losses = [], []
    for _ in range(num_trees):
        prob = sigmoid(raw)
        tree, leaf_of = grow_tree(bins, upper_bounds, prob - (y > 0),
                                  prob * (1.0 - prob), **tree_params)
        raw += tree.leaf_value[leaf_of]
        if not trees:
            tree.leaf_value[:tree.num_leaves] += init
        trees.append(tree)
        losses.append(log_loss(y, raw))
    return trees, losses
