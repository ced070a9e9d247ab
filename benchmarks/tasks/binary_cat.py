"""The learning task `binary_cat`: log-loss on an event log whose columns
are mostly CODES (month, day, weekday, carrier, origin, destination),
given to the trainer as `categorical_feature`, with two numerical columns
(departure time, distance) beside them: the shape of the airline on-time
data ("Data Expo 2009") behind the reference's Expo experiment.

The four functions drivers/train.py asks of a task (tasks/binary.py lists
them), and everything of the task they stand on: the generator, a walk of
a tree whose nodes may hold a SET OF CATEGORY VALUES (what the model file
holds, not bins), and the reference's categorical split search written as
the sequential walk it is.  Nothing here imports the program's
arithmetic.  The search is from the published description
(docs/Features.rst, "Optimal Split for Categorical Features", and
`feature_histogram.hpp` FindBestThresholdCategorical):

    keep the categories with cat_smooth rows or more, sort them by
        sum_g / (sum_h + cat_smooth)
    from either end of that order, at most
        min(max_cat_threshold, (kept + 1) / 2) steps:
        add the category to the left side and to the current group
        left side under min_data_in_leaf rows or min_sum_hessian: next
        right side under min_data_in_leaf or min_data_per_group rows,
            or under min_sum_hessian: stop this direction
        group under min_data_per_group rows: next
        close the group; the gain with lambda_l2 + cat_l2 in place of
            lambda_l2; keep the first strictly largest

and of binning, which decides what the search is offered: categories get
bins in the order of their counts until `max_bin` bins are used and 99% of
the rows are covered; every value without a bin shares the LAST bin, which
the search is not offered unless every category has a bin of its own.
"""
import sys

import numpy as np

from benchmarks.lib import parallel, quality

#: rows per generation chunk; part of the data's definition
CHUNK_ROWS = 1 << 19
#: seed of the task itself (every category's effect, the code of every
#: carrier and airport), fixed across runs: every seed draws new rows of
#: the SAME task
TASK_SEED = 2009

COLUMNS = ("Month", "DayofMonth", "DayOfWeek", "DepTime", "UniqueCarrier",
           "Origin", "Dest", "Distance")
MONTH, DAY, WEEKDAY, DEPTIME, CARRIER, ORIGIN, DEST, DISTANCE = range(8)
#: how many distinct codes each categorical column holds: 12 + 31 + 7 + 22
#: + 313 + 313 and the two numerical columns are the 700 columns of the
#: one-hot form in the reference's table
CARDINALITY = {MONTH: 12, DAY: 31, WEEKDAY: 7, CARRIER: 22, ORIGIN: 313,
               DEST: 313}
#: the first code of a column: calendar columns count from 1, carriers and
#: airports are codes from 0 in an order that says nothing of their size
FIRST_CODE = {MONTH: 1, DAY: 1, WEEKDAY: 1, CARRIER: 0, ORIGIN: 0, DEST: 0}
#: airports by size: AIRPORTS_REGULAR of them on a power law (the largest
#: holds 9.7% of the flights, the smallest 0.053%), ONE small field at
#: SMALL_AIRPORT_SHARE, and the rest, airstrips at AIRSTRIP_SHARE each
#: (about 50 rows of 10M: under min_data_per_group, and a handful in the
#: 200,000 rows bins are found from).  254 + 1 = 255 = max_bin: the 255
#: largest get bins, the airstrips share the small field's, the last,
#: which is never sent left; so the search is offered the 254 regular
#: airports whatever sample the bins were found from (`offered` checks
#: that the rows drawn keep these gaps)
AIRPORTS_REGULAR = 254
AIRPORT_POWER = 1.25
AIRPORT_SHIFT = 4
SMALL_AIRPORT_SHARE = 1e-4
AIRSTRIP_SHARE = 5e-6
#: standard deviation of a category's effect on the latent, by column
#: (origin, carrier and weekday the strongest, as delays are), the rise
#: through the day, the term in log-distance, the evening's extra at the
#: HUBS largest origins, and the noise
EFFECT_SD = {MONTH: 0.30, DAY: 0.06, WEEKDAY: 0.35, CARRIER: 0.45,
             ORIGIN: 0.60, DEST: 0.30}
DAY_RISE = 0.8
DISTANCE_TERM = 0.10
HUBS = 30
HUB_EVENING = 0.6
NOISE_SD = 1.0
#: the latent is cut here: 21.5% of the rows are positive (the 78.5% point
#: of 4M rows of TASK_SEED's task).  Not 20%: the first tree's two
#: gradients and its hessian are then -0.8, 0.2 and 0.16, which bfloat16
#: rounds by one and the same 0.098%, so a histogram in bfloat16 would
#: give the leaves of a float32 one and the control that sets
#: `leaf_value_atol` would see nothing; within 0.05% of 21.5% it moves
#: three leaves in four by 1.4e-4 or more
LATENT_CUT = 1.6584
#: tree 0's `max_value_diff` is the third quartile of the leaves'
#: differences, or a LONE_LEAF_ROOM-th of the largest (`first_tree`)
LONE_LEAF_ROOM = 32.0
#: the root's gain, recomputed in float64 for the split the program chose,
#: against the best the plain search finds: relative
ROOT_GAIN_RTOL = 1e-4
K_EPSILON = 1e-15
#: the reference's defaults for finding bins (bin_construct_sample_cnt,
#: min_data_in_bin): what `offered` stands on
BIN_SAMPLE = 200000
MIN_DATA_IN_BIN = 3


# -- the generator -----------------------------------------------------------

def _shares(col):
    """[cardinality] shares of the rows, by the category's RANK in size."""
    n = CARDINALITY[col]
    if col in (ORIGIN, DEST):
        regular = (np.arange(AIRPORTS_REGULAR) + AIRPORT_SHIFT) \
            ** -AIRPORT_POWER
        rest = np.full(n - AIRPORTS_REGULAR, AIRSTRIP_SHARE)
        rest[0] = SMALL_AIRPORT_SHARE
        return np.concatenate([regular / regular.sum() * (1 - rest.sum()),
                               rest])
    if col == CARRIER:
        w = (np.arange(n) + 2.0) ** -1.1
    elif col == MONTH:
        w = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31], float)
    elif col == DAY:
        w = np.array([12.0] * 28 + [11, 11, 7])
    else:                                           # fewer flights on Saturday
        w = np.array([1.0, 1.0, 1.0, 1.0, 1.0, 0.85, 0.95])
    return w / w.sum()


def task_tables():
    """{column: (code of each size rank, effect of each size rank)} for
    the categorical columns, from TASK_SEED alone."""
    rng = np.random.default_rng(TASK_SEED)
    tables = {}
    for col in sorted(CARDINALITY):
        n = CARDINALITY[col]
        code = np.arange(n) + FIRST_CODE[col]
        if col in (CARRIER, ORIGIN, DEST):
            code = rng.permutation(code)
        tables[col] = (code, EFFECT_SD[col] * rng.standard_normal(n))
    return tables


def flights_task(n_rows, seed):
    """(X [n_rows, 8] float32, y [n_rows] float32): rows made in fixed
    chunks, chunk i from its own stream, so the data depends on the
    arguments and never on the number of threads."""
    tables = task_tables()
    cdfs = {col: np.cumsum(_shares(col)) for col in CARDINALITY}
    X = np.empty((n_rows, len(COLUMNS)), np.float32)
    y = np.empty(n_rows, np.float32)
    bounds = parallel.fixed_bounds(n_rows, CHUNK_ROWS)
    seeds = np.random.SeedSequence(seed).spawn(len(bounds) - 1)

    def fill(i, lo, hi):
        rng = np.random.default_rng(seeds[i])
        n = hi - lo
        latent = np.zeros(n)
        ranks = {}
        for col in sorted(CARDINALITY):
            cdf = cdfs[col]
            rank = np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1]),
                              len(cdf) - 1)
            code, effect = tables[col]
            X[lo:hi, col] = code[rank]
            latent += effect[rank]
            ranks[col] = rank
        # the day's two peaks, in minutes; the column holds HHMM
        evening = rng.random(n) < 0.55
        minute = np.where(evening, 17.0 + 2.5 * rng.standard_normal(n),
                          8.5 + 2.0 * rng.standard_normal(n)) * 60.0
        minute = np.clip(np.rint(minute), 0, 1439).astype(np.int64)
        X[lo:hi, DEPTIME] = 100 * (minute // 60) + minute % 60
        miles = np.clip(np.rint(np.exp(6.4 + 0.75 * rng.standard_normal(n))),
                        30, 4960)
        X[lo:hi, DISTANCE] = miles
        hour = minute / 60.0
        latent += DAY_RISE * np.clip((hour - 5.0) / 19.0, 0.0, 1.0)
        latent += DISTANCE_TERM * (np.log(miles) - 6.4) / 0.75
        latent += HUB_EVENING * ((ranks[ORIGIN] < HUBS) & (hour >= 17.0))
        latent += NOISE_SD * rng.standard_normal(n)
        y[lo:hi] = latent > LATENT_CUT

    parallel.for_chunks(bounds, fill)
    return X, y


def make(cfg, seed, part):
    if cfg["features"] != len(COLUMNS):
        raise ValueError("the task `binary_cat` makes %d columns, not %d"
                         % (len(COLUMNS), cfg["features"]))
    rows = cfg["heldout_rows"] if part else cfg["rows"]
    X, y = flights_task(rows, (seed, part))
    return {"X": X, "y": y,
            "categorical_feature": list(cfg["categorical_feature"])}


def dataset_args(data):
    return {"categorical_feature": data["categorical_feature"]}


# -- a tree walked on raw values, categorical nodes by their value sets ------

def category_sets(tree):
    """{internal node: sorted array of the category values sent LEFT} for
    the categorical nodes of a tree, from the bitsets its model file
    holds (`cat_boundaries`, `cat_threshold`: 32 values a word)."""
    ni = int(tree.num_leaves) - 1
    sets = {}
    for node in range(ni):
        if int(tree.decision_type[node]) & 1:
            k = int(tree.threshold[node])
            lo, hi = tree.cat_boundaries[k], tree.cat_boundaries[k + 1]
            words = np.asarray(tree.cat_threshold[lo:hi], np.uint64)
            bits = (words[:, None] >> np.arange(32, dtype=np.uint64)) & 1
            sets[node] = np.flatnonzero(bits.reshape(-1))
    return sets


def flat_table(tree):
    """One table of (internal nodes, then leaves) in which a leaf points
    at itself: (feature, threshold, is categorical, membership [slots,
    values], left, right, slot of leaf 0).  A numerical node sends
    x <= threshold left; a categorical node sends left the rows whose
    value, truncated, is in its set, and everything else (a value the
    training rows never showed, a negative one) right, where the
    reference sends it; NaN reads as 0 in either kind of node, as the
    reference reads it where the training data had none."""
    nl = int(tree.num_leaves)
    ni = nl - 1
    size = ni + nl
    sets = category_sets(tree)
    width = max([int(s[-1]) + 1 for s in sets.values() if len(s)] + [1])
    feat = np.zeros(size, np.int64)
    thr = np.full(size, np.inf)
    is_cat = np.zeros(size, bool)
    member = np.zeros((size, width), bool)
    left = np.arange(size, dtype=np.int64)
    right = np.arange(size, dtype=np.int64)
    if ni:
        dt = np.asarray(tree.decision_type[:ni], np.int64)
        if np.any((dt >> 2) & 3 == 2):
            raise ValueError("the task `binary_cat` has no missing values; "
                             "this tree has a NaN-aware node")
        feat[:ni] = tree.split_feature[:ni]
        thr[:ni] = tree.threshold[:ni]
        is_cat[:ni] = (dt & 1) != 0
        for node, values in sets.items():
            member[node, values] = True
        for dst, child in ((left, tree.left_child), (right, tree.right_child)):
            c = np.asarray(child[:ni], np.int64)
            dst[:ni] = np.where(c >= 0, c, ni + ~c)
    depth = np.zeros(size, np.int64)
    for node in range(ni):              # children are created after parents
        depth[left[node]] = depth[right[node]] = depth[node] + 1
    return (feat, thr, is_cat, member, left, right, ni), int(depth.max())


def _walk(table, depth, X):
    feat, thr, is_cat, member, left, right, ni = table
    width = member.shape[1]
    node = np.zeros(len(X), np.int64)
    rows = np.arange(len(X))
    for _ in range(depth):
        x = X[rows, feat[node]].astype(np.float64)
        x = np.where(np.isnan(x), 0.0, x)   # no node here is NaN-aware
        inside = (x > -1.0) & (x < width)
        value = np.where(inside, x, 0.0).astype(np.int64)
        go_left = np.where(is_cat[node], inside & member[node, value],
                           x <= thr[node])
        node = np.where(go_left, left[node], right[node])
    return node - ni


def leaf_index(tree, X):
    """The leaf each row of X [n, 8] falls into."""
    table, depth = flat_table(tree)
    out = np.empty(len(X), np.int64)

    def part(_, lo, hi):
        out[lo:hi] = _walk(table, depth, X[lo:hi])

    parallel.for_chunks(parallel.even_bounds(len(X)), part)
    return out


def predict_raw(trees, X):
    """Raw score of each row: the sum of its leaves' values, float64."""
    walkers = [flat_table(t) + (np.asarray(t.leaf_value, np.float64),)
               for t in trees]
    out = np.empty(len(X), np.float64)

    def part(_, lo, hi):
        acc = np.zeros(hi - lo)
        for table, depth, values in walkers:
            acc += values[_walk(table, depth, X[lo:hi])]
        out[lo:hi] = acc

    parallel.for_chunks(parallel.even_bounds(len(X)), part)
    return out


# -- the plain split search ---------------------------------------------------

def leaf_gain(sum_g, sum_h, l2):
    """The gain of keeping (sum_g, sum_h) as one leaf (no L1, no
    max_delta_step: the configuration has neither)."""
    return sum_g * sum_g / (sum_h + l2)


def categorical_search(g, h, c, sum_g, sum_h, num_data, p, full):
    """FindBestThresholdCategorical over one column's histogram: g, h, c
    [bins] float64 by bin, the LAST bin the one that takes what has no bin
    (not offered unless `full`); sum_h with the reference's 2 kEpsilon.
    Returns (raw gain, bins sent left, left (sum_g, sum_h, count)) or
    None where no split stands; the gain is before the parent's is taken
    off.  Both modes: one bin against the rest where the column has at
    most max_cat_to_onehot bins, else the sorted walk."""
    l2 = p["lambda_l2"]
    used_bin = len(c) - 1 + bool(full)
    best = None                         # (gain, bins, (lg, lh, lc))
    if len(c) <= p["max_cat_to_onehot"]:
        for t in range(used_bin):
            if c[t] < p["min_data_in_leaf"] \
                    or h[t] < p["min_sum_hessian_in_leaf"]:
                continue
            if num_data - c[t] < p["min_data_in_leaf"]:
                continue
            other_h = sum_h - h[t] - K_EPSILON
            if other_h < p["min_sum_hessian_in_leaf"]:
                continue
            gain = leaf_gain(sum_g - g[t], other_h, l2) \
                + leaf_gain(g[t], h[t] + K_EPSILON, l2)
            if best is None or gain > best[0]:
                best = (gain, [t], (g[t], h[t] + K_EPSILON, c[t]))
        return best
    kept = [t for t in range(used_bin) if c[t] >= p["cat_smooth"]]
    kept.sort(key=lambda t: g[t] / (h[t] + p["cat_smooth"]))   # stable
    used = len(kept)
    l2 += p["cat_l2"]
    max_num_cat = min(p["max_cat_threshold"], (used + 1) // 2)
    for direction, start in ((1, 0), (-1, used - 1)):
        lg, lh, lc, group = 0.0, K_EPSILON, 0.0, 0.0
        pos = start
        for i in range(min(used, max_num_cat)):
            t = kept[pos]
            pos += direction
            lg += g[t]
            lh += h[t]
            lc += c[t]
            group += c[t]
            if lc < p["min_data_in_leaf"] \
                    or lh < p["min_sum_hessian_in_leaf"]:
                continue
            rc = num_data - lc
            if rc < p["min_data_in_leaf"] or rc < p["min_data_per_group"]:
                break
            rh = sum_h - lh
            if rh < p["min_sum_hessian_in_leaf"]:
                break
            if group < p["min_data_per_group"]:
                continue
            group = 0.0
            gain = leaf_gain(lg, lh, l2) + leaf_gain(sum_g - lg, rh, l2)
            if best is None or gain > best[0]:
                walked = kept[:i + 1] if direction == 1 \
                    else kept[used - 1 - i:][::-1]
                best = (gain, list(walked), (lg, lh, lc))
    return best


def numerical_search(g, h, c, sum_g, sum_h, num_data, p):
    """The best x <= value split over one column's histogram by DISTINCT
    VALUE, ascending (no missing values): every value is a threshold, so
    this is at least what any binning of the column allows."""
    lg = np.cumsum(g)[:-1]
    lh = np.cumsum(h)[:-1] + K_EPSILON
    lc = np.cumsum(c)[:-1]
    rh = sum_h - lh
    ok = (lc >= p["min_data_in_leaf"]) \
        & (num_data - lc >= p["min_data_in_leaf"]) \
        & (lh >= p["min_sum_hessian_in_leaf"]) \
        & (rh >= p["min_sum_hessian_in_leaf"])
    if not ok.any():
        return None
    gain = np.where(ok, leaf_gain(lg, lh, p["lambda_l2"])
                    + leaf_gain(sum_g - lg, rh, p["lambda_l2"]), -np.inf)
    t = int(np.argmax(gain))
    return float(gain[t]), t, (lg[t], lh[t], lc[t])


def search_params(params):
    """The reference's defaults under the configuration's `params`."""
    return dict({"lambda_l2": 0.0, "min_data_in_leaf": 20,
                 "min_sum_hessian_in_leaf": 1e-3, "cat_smooth": 10.0,
                 "cat_l2": 10.0, "max_cat_threshold": 32,
                 "max_cat_to_onehot": 4, "min_data_per_group": 100,
                 "max_bin": 255}, **params)


def offered(counts, max_bin):
    """(values with a bin of their own, in the order of their bins;
    whether every value has one) for one categorical column, from
    `counts` {value: rows}, ascending by value, of ALL the rows.  The
    published rule: values in the order of their counts (a tie: the
    smaller value first) get bins until `max_bin` are used AND 99% of the
    rows are covered, or a value has under min_data_in_bin rows; where
    the column is not `full`, the last of them also takes every value
    without a bin and is not offered to the search.

    The program finds bins from a SAMPLE of BIN_SAMPLE rows.  Up to that
    many rows the sample is the data and this is exact; past it the
    answer holds only where the sizes leave no doubt which values the
    sample ranks first and which of them last, which is checked."""
    total = sum(counts.values())
    order = sorted(counts, key=lambda v: -counts[v])
    scale = min(1.0, BIN_SAMPLE / total)        # rows -> rows of the sample
    kept, used, cur = [], 0.0, 0
    while cur < len(order) and (used < int(total * 0.99)
                                or len(kept) < min(len(order), max_bin)):
        if counts[order[cur]] * scale < MIN_DATA_IN_BIN and cur > 1:
            break
        kept.append(order[cur])
        used += counts[order[cur]]
        cur += 1
    full = cur == len(order)
    if total > BIN_SAMPLE:
        sizes = [counts[v] * scale for v in order] + [0.0]
        n = len(kept)
        # every value with a bin holds 10 sampled rows or more; the last
        # of them stands clear of its neighbours on both sides
        settled = sizes[n - 1] >= 10 and (full or (
            sizes[n - 2] >= 3 * sizes[n - 1] and sizes[n - 1] >= 5 * sizes[n]))
        if not settled:
            raise ValueError("the sizes %s round bin %d do not settle which "
                             "categories a sample of %d rows gives bins"
                             % ([counts[v] for v in order[n - 3:n + 2]], n,
                                BIN_SAMPLE))
    return kept, full


def root_search(X, grad, hess, cfg):
    """The best split of ALL the rows by the plain search, column by
    column: {"gain" (the parent's taken off), "feature", "columns": the
    best gain of each column}."""
    p = search_params(cfg["params"])
    cats = set(cfg["categorical_feature"])
    sum_g, num_data = float(grad.sum()), float(len(grad))
    sum_h = float(hess.sum()) + 2 * K_EPSILON
    parent = leaf_gain(sum_g, sum_h, p["lambda_l2"])
    columns = {}
    for col in range(X.shape[1]):
        values, inverse = np.unique(X[:, col], return_inverse=True)
        g = np.bincount(inverse, weights=grad, minlength=len(values))
        h = np.bincount(inverse, weights=hess, minlength=len(values))
        c = np.bincount(inverse, minlength=len(values)).astype(np.float64)
        if col in cats:
            slot = {int(v): i for i, v in enumerate(values)}
            kept, full = offered({v: c[i] for v, i in slot.items()},
                                 p["max_bin"])
            idx = np.array([slot[v] for v in kept])
            last = np.ones(len(values), bool)
            last[idx[:len(idx) - (not full)]] = False
            bins = [a[idx] for a in (g, h, c)]
            if not full:                # the last bin takes what has none
                for a, b in zip((g, h, c), bins):
                    b[-1] = a[last].sum()
            found = categorical_search(*bins, sum_g, sum_h, num_data, p, full)
        else:
            found = numerical_search(g, h, c, sum_g, sum_h, num_data, p)
        columns[col] = None if found is None else found[0] - parent
    feature = max((c for c in columns if columns[c] is not None),
                  key=lambda c: columns[c], default=None)
    return {"gain": columns.get(feature), "feature": feature,
            "columns": columns, "parent": parent}


def split_gain(left, grad, hess, l2):
    """The gain in float64 of sending the rows `left` (a mask) left, the
    parent's taken off."""
    sum_g, sum_h = float(grad.sum()), float(hess.sum()) + 2 * K_EPSILON
    lg, lh = float(grad[left].sum()), float(hess[left].sum()) + K_EPSILON
    return leaf_gain(lg, lh, l2) + leaf_gain(sum_g - lg, sum_h - lh, l2) \
        - leaf_gain(sum_g, sum_h, l2)


# -- what the driver asks -----------------------------------------------------

def _parent_is_categorical(tree):
    """[leaves] whether the split that made each leaf was categorical."""
    nl = int(tree.num_leaves)
    out = np.zeros(nl, bool)
    for node in range(nl - 1):
        for child in (int(tree.left_child[node]), int(tree.right_child[node])):
            if child < 0:
                out[~child] = bool(int(tree.decision_type[node]) & 1)
    return out


def first_tree(tree, data, cfg):
    """Tree 0 of a binary log-loss model boosted from the average: rows
    walked through its thresholds and category sets on the raw columns,
    leaf counts exact, leaf values against float64 sums (two distinct
    gradients and one hessian, so the leaves follow from the labels
    alone; a leaf a categorical split made is regularised by lambda_l2 +
    cat_l2, as the reference does for the sorted search, and every
    categorical column here has more than max_cat_to_onehot values).
    And the root's split held to the plain search: its gain, recomputed
    in float64 from the rows it sends left, within ROOT_GAIN_RTOL of the
    best the search above finds in any column.  `counts_ok` is both; the
    root's gain is also an entry of the task's own `compared`.

    `max_value_diff` is NOT the largest difference over the leaves but
    their THIRD QUARTILE, or a `LONE_LEAF_ROOM`-th of the largest where
    that is more (as tasks/rank.py, and for the cell's own reason).  The
    root's gradient total is 0 but for float32 rounding (the model is
    boosted from the average), a few tenths here where column 0's twelve
    bins hold 830,000 rows each; a right child's sums are its parent's
    less its left sibling's, so the leaves down the right-hand spine
    inherit the whole of it, and the smallest of them (a thousand rows)
    read 1.1e-4 to 2.5e-4 off in sound runs at 20% positives and up to
    8.5e-5 at 21.5% (chip, PR 33), where the furthest leaf of a bfloat16
    histogram reads 2.2e-4 to 2.6e-4.  What tells the two apart is that
    bfloat16 moves EVERY leaf (PERF.md section 6, PR 33, has the
    readings).  The largest difference stays in the verdict at
    `LONE_LEAF_ROOM` times the limit, so one altered leaf still reads
    not correct."""
    p = search_params(cfg["params"])
    X, y = data["X"], data["y"]
    if len(X) >= 1 << 24:
        raise ValueError("float32 counts are exact under 2^24 rows only")
    nl = int(tree.num_leaves)
    leaf = leaf_index(tree, X)
    count = np.bincount(leaf, minlength=nl)
    n_pos = np.bincount(leaf, weights=(y > 0), minlength=nl)
    rate = float(np.mean(y > 0))
    init = float(np.log(rate / (1.0 - rate)))
    # y = 1: g = rate - 1; y = 0: g = rate; h = rate (1 - rate)
    grad = n_pos * (rate - 1.0) + (count - n_pos) * rate
    hess = count * rate * (1.0 - rate)
    l2 = p["lambda_l2"] + p["cat_l2"] * _parent_is_categorical(tree)
    expect = init - grad / (hess + l2) * p["learning_rate"]
    got = np.asarray(tree.leaf_value[:nl], np.float64)
    off = np.abs(count - np.asarray(tree.leaf_count[:nl], np.int64))
    diff = np.sort(np.abs(got - expect))[::-1]

    # the root: the rows it sends left are those of node 0's left subtree
    row_g = np.where(y > 0, rate - 1.0, rate)
    row_h = np.full(len(y), rate * (1.0 - rate))
    root_cat = bool(int(tree.decision_type[0]) & 1)
    feature = int(tree.split_feature[0])
    x = X[:, feature].astype(np.float64)
    sets = category_sets(tree)
    if root_cat:
        goes_left = np.isin(x.astype(np.int64), sets[0])
    else:
        goes_left = x <= float(tree.threshold[0])
    chosen = split_gain(goes_left, row_g, row_h,
                        p["lambda_l2"] + p["cat_l2"] * root_cat)
    plain = root_search(X, row_g, row_h, cfg)
    gain_off = abs(chosen - plain["gain"]) / abs(plain["gain"])
    root_ok = gain_off <= ROOT_GAIN_RTOL
    print("[bench] compared tree0_root_gain_rel_diff %.3e limit %.0e "
          "(chosen %.9g on column %d, plain search %.9g on column %d)"
          % (gain_off, ROOT_GAIN_RTOL, chosen, feature, plain["gain"],
             plain["feature"]), file=sys.stderr, flush=True)
    ni = nl - 1
    is_cat = (np.asarray(tree.decision_type[:ni], np.int64) & 1) != 0
    return {
        "leaves": nl, "rows": int(len(X)),
        "counts_ok": bool((off == 0).all() and root_ok),
        "max_count_diff": int(off.max()), "leaves_off": int((off > 0).sum()),
        "count_slack_max": 0,
        "max_value_diff": float(max(np.quantile(diff, 0.75),
                                    diff[0] / LONE_LEAF_ROOM)),
        "largest_value_diff": float(diff[0]),
        # the leaves' differences, largest first, and their quartiles
        "value_diffs_largest": [float(d) for d in diff[:8]],
        "value_diff_quartiles": [float(np.quantile(diff, q))
                                 for q in (0.25, 0.5, 0.75)],
        "positive_share": rate,
        "categorical_nodes": int(is_cat.sum()),
        "largest_left_set": max(map(len, sets.values()), default=0),
        # [value, limit] and what was compared
        "root_gain_rel_diff": [float(gain_off), ROOT_GAIN_RTOL],
        "root_gain_chosen": float(chosen), "root_feature": feature,
        "root_is_categorical": root_cat,
        "root_gain_plain": float(plain["gain"]),
        "root_feature_plain": int(plain["feature"]),
        "root_gain_by_column": {COLUMNS[c]: v
                                for c, v in plain["columns"].items()},
        "compared": {"tree0_root_gain_rel_diff": [float(gain_off),
                                                  ROOT_GAIN_RTOL]},
    }


def heldout(trees, data, cfg):
    raw = predict_raw(trees, data["X"])
    return float(quality.METRICS[cfg["quality_metric"]](data["y"], raw))
