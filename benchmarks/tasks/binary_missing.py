"""The learning task `binary_missing`: log-loss on a wide table of sensor
readings in which MOST CELLS ARE EMPTY and positives are rare: the shape
of the Bosch production-line data behind the reference's accelerator
experiment (docs/GPU-Performance.rst, row Bosch: 1,183,747 parts x 968
numeric columns, 0.58% failures).

A part moves down one of a few dozen ROUTES through 52 stations on 4
lines; a station measures its own group of columns, so the columns of a
station are present or NaN TOGETHER, routes on different lines share
next to nothing, and four cells in five are NaN.  Most stations see a
few parts in a hundred; the four of the FINAL TEST at the end of the
last line see two parts in three (every route but those that ship
without it), all four or none.  The empty cells are given to the trainer
as NaN, as the data set's CSV has them: every column gets a NaN bin,
every split scans both directions and carries a `default_left` that the
data decides.  The label is the 0.58% of the parts with the highest
latent: eight go / no-go readings of the final test that move the risk
by a large step each, a hundred and eighty-two more of its readings that
move it a little (some up, some down, so the best side for the NaN rows
is LEFT at some splits and RIGHT at others), which stations the part
visited (so missingness itself carries signal), and noise.

Why the plant is built so (PERF.md section 6, PR 37, has the numbers).
What a tree costs here is the rows its splits move, and a split moves
every row of its node that has NO value in the split's column along
with one side.  Where the risk hangs on which rare stations a part
visited, the model has learned it by iteration 40 (a real gain falls by
0.81 an iteration at a learning rate of 0.1), later splits are decided
by sampling noise on columns nine rows in ten lack, the million rows
that carry no hessian ride down a chain of such splits to a depth that
noise decides, and `train_s_per_iter` spreads by 2.5% between seeds.
Here the risk hangs on readings that two parts in three HAVE: a split
on one of them sends a third of its node's rows left, a third right and
the third without the value with one of them, whichever of the 190 it
takes (a saturating effect is best cut at the nominal value, in the
middle), trees of one iteration and of the next have one shape, and
190 small effects outlast the window's hundred trees.

The four functions drivers/train.py asks of a task (tasks/binary.py lists
them).  Tree 0 is held row for row and value for value by
`lib/reference.py`, whose walk routes NaN by `default_left`; and the
ROOT's split is held to a plain search written here, in float64 over
every column and every distinct value, both directions a threshold
(the missing rows right, the missing rows left).  Nothing here imports
the program's arithmetic.
"""
import json
import sys

import numpy as np

from benchmarks.lib import parallel, reference

#: rows per generation chunk; part of the data's definition
CHUNK_ROWS = 1 << 16
#: seed of the task itself (stations, routes, weights), fixed across
#: runs: every seed draws new parts of the SAME plant
TASK_SEED = 2016

FEATURES = 968
#: stations on each of the four lines, in the columns' order
LINE_STATIONS = (14, 14, 6, 18)
STATIONS = sum(LINE_STATIONS)
#: a station measures at least this many columns; the rest of the 968 are
#: dealt out by a log-normal draw, so the sizes are uneven (4 to ~60)
STATION_MIN_COLUMNS = 4
ROUTES = 36
#: the share of the cells that is present, and the bounds on any one
#: station's share of the parts (no column under 30% or over 99% empty)
PRESENT_SHARE = 0.19
STATION_SHARE = (0.012, 0.68)
#: the FINAL TEST: four stations of the last line (197 columns) that a
#: part visits all or none of: every part but those of the routes that
#: ship without it, the third line's and the rarest of the second's up to
#: FINAL_SKIP of the parts
FINAL_STATIONS = (43, 44, 46, 47)
FINAL_SKIP = 0.31
#: a reading is loc + scale * z, z standard normal cut at +-Z_CLIP and
#: rounded to 1/steps, steps the configuration's `gauge_steps` (GAUGE_STEPS
#: where it states none): at most 2 * Z_CLIP * 48 + 1 = 193 distinct
#: values a column, which 63 bins bin and do not enumerate.  The eight
#: go / no-go gauges read in STRONG_STEPS-ths, 49 values, which 63 bins
#: DO enumerate: tree 0's root is cut on one of them, and the plain
#: search, which tries every distinct value, then finds the cut the
#: program's bins allow and no better one between two bin edges.  The cut
#: at Z_CLIP gives the largest and the smallest value 2.3% of the
#: readings each, so that no threshold shaves a few hundred rows off a
#: column's end (cut at 2.5, one seed in seven found such a shave worth
#: 0.15% of the root's gain, which no binning can follow: chip, PR 37)
Z_CLIP = 2.0
GAUGE_STEPS = 48
STRONG_STEPS = 12
#: the latent.  SIGNAL_COLUMNS readings of the final test move the risk:
#: STRONG_COLUMNS of them by +-STRONG_WEIGHT as the reading lies over or
#: under its nominal value (a go / no-go dimension: one split holds it
#: whole, eight trees learn the eight, and the held-out AUC of iteration
#: 8 is quiet), the others by COLUMN_WEIGHT * tanh(STEEPNESS * z) (they
#: saturate a third of a standard deviation off nominal, so the best cut
#: is AT nominal for every one of them), signs alternating.  The visits
#: of SIGNAL_STATIONS stations seen by STATION_SEEN of the parts move it
#: by STATION_EFFECT falling by STATION_DECAY, a third of them down; and
#: noise
SIGNAL_COLUMNS = 190
STRONG_COLUMNS = 8
STRONG_WEIGHT = 3.0
COLUMN_WEIGHT = 0.65
STEEPNESS = 3.0
SIGNAL_STATIONS = 10
STATION_SEEN = (0.03, 0.3)
STATION_EFFECT = 1.0
STATION_DECAY = 0.75
NOISE_SD = 0.6
#: 0.58% of the parts fail (6,879 of 1,183,747): those whose latent is
#: highest (`failures`)
POSITIVE_SHARE = 0.0058
#: the limit on the LARGEST of tree 0's leaf-value differences where the
#: configuration states none (`first_tree`; `leaf_value_atol` holds their
#: third quartile)
LARGEST_VALUE_DIFF_ATOL = 3e-5
#: the root's gain, recomputed in float64 for the split the program chose,
#: against the best the plain search finds: relative
ROOT_GAIN_RTOL = 1e-4
K_EPSILON = 1e-15


# -- the plant ---------------------------------------------------------------

class Plant:
    """Everything TASK_SEED fixes: which columns a station measures, which
    stations a route visits and how likely it is, a column's scale, and
    what moves the risk."""

    def __init__(self):
        rng = np.random.default_rng(TASK_SEED)
        # columns of each station: STATION_MIN_COLUMNS and a log-normal deal
        spare = FEATURES - STATIONS * STATION_MIN_COLUMNS
        w = np.exp(0.9 * rng.standard_normal(STATIONS))
        extra = np.floor(w / w.sum() * spare).astype(np.int64)
        extra[np.argsort(-w)[:spare - extra.sum()]] += 1
        self.sizes = STATION_MIN_COLUMNS + extra
        self.first = np.concatenate([[0], np.cumsum(self.sizes)])
        self.station_of = np.repeat(np.arange(STATIONS), self.sizes)
        self.line_of = np.repeat(np.arange(len(LINE_STATIONS)),
                                 LINE_STATIONS)

        # routes: a main line among the first three, some of its stations,
        # some of the last line's (where every part is finished), and
        # hardly any of the others'
        main = rng.choice(3, ROUTES, p=(0.45, 0.38, 0.17))
        share = (np.arange(ROUTES) + 2.0) ** -1.1
        self.route_p = rng.permutation(share / share.sum())
        draws = rng.random((ROUTES, STATIONS))
        base = np.where(self.line_of[None, :] == main[:, None], 0.5,
                        np.where(self.line_of[None, :] == 3, 0.33, 0.02))
        self.final = np.isin(np.arange(STATIONS), FINAL_STATIONS)
        skips = main == 2
        for r in np.argsort(self.route_p):
            if self.route_p @ skips >= FINAL_SKIP:
                break
            skips |= (np.arange(ROUTES) == r) & (main == 1)
        self.final_routes = ~skips
        self.visits = self._settle(draws, base, main)
        self.cdf = np.cumsum(self.route_p)

        # a column's location and scale, to three decimals as a gauge reads
        self.loc = np.round(rng.uniform(-1.0, 1.0, FEATURES), 3)
        self.scale = np.round(np.exp(rng.uniform(-3.0, 0.5, FEATURES)), 3)

        # what moves the risk.  Stations: among those STATION_SEEN of the
        # parts visit, the final test's apart; columns: the final test's
        seen = self.route_p @ self.visits
        order = rng.permutation(np.flatnonzero(
            (seen > STATION_SEEN[0]) & (seen < STATION_SEEN[1])
            & ~self.final))
        self.signal_stations = order[:SIGNAL_STATIONS]
        sign = np.where(np.arange(SIGNAL_STATIONS) % 3 == 2, -1.0, 1.0)
        self.station_effect = np.zeros(STATIONS)
        self.station_effect[self.signal_stations] = STATION_EFFECT * sign \
            * STATION_DECAY ** np.arange(SIGNAL_STATIONS)
        self.route_effect = self.visits @ self.station_effect
        cols = rng.permutation(np.flatnonzero(self.final[self.station_of]))
        self.signal_columns = np.sort(cols[:SIGNAL_COLUMNS])
        rank = rng.permutation(SIGNAL_COLUMNS)
        #: the go / no-go gauges among the signal columns
        self.strong = rank < STRONG_COLUMNS
        self.signal_weight = np.where(self.strong, STRONG_WEIGHT,
                                      COLUMN_WEIGHT) \
            * np.where(rank % 2 == 0, 1.0, -1.0)
        #: [routes, signal columns]: whether the route's parts have it
        self.signal_seen = self.visits[:, self.station_of[self.signal_columns]]

    def _settle(self, draws, base, main):
        """[routes, stations] visits: `draws < q * base` at the q that
        brings the present share of the cells nearest PRESENT_SHARE, every
        station's share of the parts brought inside STATION_SHARE by the
        routes of its own line, likeliest first; the final test's stations
        are visited by `final_routes` and by no other."""
        lo, hi = STATION_SHARE
        by_p = np.argsort(-self.route_p)

        def at(q):
            v = draws < q * base
            v[:, self.final] = self.final_routes[:, None]
            for s in np.flatnonzero(~self.final):
                own = [r for r in by_p
                       if main[r] == self.line_of[s] or self.line_of[s] == 3]
                for r in own:                       # too rare: add routes
                    if self.route_p @ v[:, s] >= lo:
                        break
                    v[r, s] = True
                for r in by_p:                      # too common: drop them
                    if self.route_p @ v[:, s] <= hi:
                        break
                    v[r, s] = False
            return v

        def present(v):
            return float((self.route_p @ v) @ self.sizes) / FEATURES

        grid = np.linspace(0.05, 2.0, 391)
        return at(min(grid, key=lambda q: abs(present(at(q))
                                              - PRESENT_SHARE)))


_PLANT = None


def plant():
    global _PLANT
    if _PLANT is None:
        _PLANT = Plant()
    return _PLANT


def _quantised(z, steps):
    """Standard-normal draws as gauge steps, in place."""
    np.clip(z, -Z_CLIP, Z_CLIP, out=z)
    np.multiply(z, steps, out=z)
    np.rint(z, out=z)
    np.multiply(z, 1.0 / steps, out=z)
    return z


def _streams(seed, n_rows):
    """(bounds, [(main, bulk) generator seeds a chunk]): a chunk's route,
    signal readings and noise come from `main`, every other reading from
    `bulk`."""
    bounds = parallel.fixed_bounds(n_rows, CHUNK_ROWS)
    seeds = np.random.SeedSequence(seed).spawn(len(bounds) - 1)
    return bounds, [s.spawn(2) for s in seeds]


def _chunk_latent(pl, rng, n, steps):
    """(route [n], signal z [n, SIGNAL_COLUMNS] float32, latent [n])."""
    route = np.minimum(np.searchsorted(pl.cdf, rng.random(n) * pl.cdf[-1]),
                       ROUTES - 1)
    z = _quantised(rng.standard_normal((n, SIGNAL_COLUMNS),
                                       dtype=np.float32),
                   np.where(pl.strong, min(STRONG_STEPS, steps),
                            steps).astype(np.float32))
    moves = np.where(pl.strong, np.where(z > 0, 1.0, -1.0),
                     np.tanh(STEEPNESS * z))
    latent = pl.route_effect[route] \
        + (moves * pl.signal_seen[route]) @ pl.signal_weight \
        + NOISE_SD * rng.standard_normal(n)
    return route, z, latent


def parts_task(n_rows, seed, steps=GAUGE_STEPS):
    """(X [n_rows, 968] float32 with NaN, y [n_rows] float32): rows made
    in fixed chunks, chunk i from its own streams, so the data depends on
    the arguments and never on the number of threads."""
    pl = plant()
    X = np.empty((n_rows, FEATURES), np.float32)
    latent_all = np.empty(n_rows)
    bounds, streams = _streams(seed, n_rows)
    loc = pl.loc.astype(np.float32)
    scale = pl.scale.astype(np.float32)
    sig_station = pl.station_of[pl.signal_columns]

    def fill(i, lo, hi):
        main, bulk = (np.random.default_rng(s) for s in streams[i])
        n = hi - lo
        route, zsig, latent = _chunk_latent(pl, main, n, steps)
        latent_all[lo:hi] = latent
        Xc = X[lo:hi]
        Xc.fill(np.nan)
        seen = pl.visits[route]                     # [n, stations]
        for s in range(STATIONS):
            rows = np.flatnonzero(seen[:, s])
            if not len(rows):
                continue
            c0, c1 = pl.first[s], pl.first[s + 1]
            z = _quantised(bulk.standard_normal((len(rows), c1 - c0),
                                                dtype=np.float32), steps)
            for j in np.flatnonzero(sig_station == s):
                z[:, pl.signal_columns[j] - c0] = zsig[rows, j]
            z *= scale[c0:c1]
            z += loc[c0:c1]
            Xc[rows, c0:c1] = z

    parallel.for_chunks(bounds, fill)
    return X, failures(latent_all)


def failures(latent):
    """The labels: the POSITIVE_SHARE of the parts whose latent is highest
    fail, the same NUMBER in every table of one size (5,800 of a million,
    1,066 of the 183,747 held out), as a data set has one number of
    failures and not a draw of it.  (A cut fixed beforehand gave 5,800 +-
    76 from seed to seed; a tree's leaves follow the hessian the
    positives bring, and `train_s_per_iter` followed their number with a
    slope of 0.3: a third of its spread between seeds, PERF.md section 6,
    PR 37.)"""
    k = int(round(POSITIVE_SHARE * len(latent)))
    y = np.zeros(len(latent), np.float32)
    if k:
        y[np.argpartition(latent, len(latent) - k)[len(latent) - k:]] = 1.0
    return y


def make(cfg, seed, part):
    if cfg["features"] != FEATURES:
        raise ValueError("the task `binary_missing` makes %d columns, not %d"
                         % (FEATURES, cfg["features"]))
    rows = cfg["heldout_rows"] if part else cfg["rows"]
    X, y = parts_task(rows, (seed, part),
                      cfg.get("gauge_steps", GAUGE_STEPS))
    return {"X": X, "y": y}


def dataset_args(data):
    return {}


# -- the plain split search ---------------------------------------------------

def leaf_gain(sum_g, sum_h, l2):
    return sum_g * sum_g / (sum_h + l2)


def search_params(params):
    """The reference's defaults under the configuration's `params`."""
    return dict({"lambda_l2": 0.0, "min_data_in_leaf": 20,
                 "min_sum_hessian_in_leaf": 1e-3}, **params)


def column_search(x, grad, hess, sum_g, sum_h, p):
    """The best split of the rows by one column `x` (NaN: missing), over
    its DISTINCT VALUES ascending, both directions a value: the rows with
    x <= v left and the missing rows RIGHT, or the missing rows LEFT with
    them.  Every value is a threshold, so this is at least what any
    binning of the column allows.  (The missing rows ALONE on the left are
    the mirror image of every value left and the missing rows right: one
    split under two names, offered under the second, which a tree can
    hold: a threshold at the largest value, `default_left` false.)
    Returns (raw gain, value, missing rows go left) or None where no
    split stands; the parent's gain is not taken off."""
    there = ~np.isnan(x)
    values, inverse = np.unique(x[there], return_inverse=True)
    k = len(values)
    g = np.bincount(inverse, weights=grad[there], minlength=k)
    h = np.bincount(inverse, weights=hess[there], minlength=k)
    c = np.bincount(inverse, minlength=k).astype(np.float64)
    lg, lh, lc = (np.concatenate([[0.0], np.cumsum(a)]) for a in (g, h, c))
    n = float(len(x))
    miss = (sum_g - lg[-1], (sum_h - 2 * K_EPSILON) - lh[-1], n - lc[-1])
    best = None
    for left_missing in (False, True):
        if left_missing:
            if miss[2] == 0:
                continue
            Lg, Lh, Lc = lg + miss[0], lh + miss[1], lc + miss[2]
        else:
            Lg, Lh, Lc = lg, lh, lc
        Lh = Lh + K_EPSILON
        Rh = sum_h - Lh
        ok = (lc > 0) & (Lc >= max(p["min_data_in_leaf"], 1)) \
            & (n - Lc >= max(p["min_data_in_leaf"], 1)) \
            & (Lh >= p["min_sum_hessian_in_leaf"]) \
            & (Rh >= p["min_sum_hessian_in_leaf"])
        if not ok.any():
            continue
        with np.errstate(divide="ignore", invalid="ignore"):
            # (an empty side divides by zero; `ok` has ruled it out)
            gain = np.where(ok, leaf_gain(Lg, Lh, p["lambda_l2"])
                            + leaf_gain(sum_g - Lg, Rh, p["lambda_l2"]),
                            -np.inf)
        t = int(np.argmax(gain))
        if best is None or gain[t] > best[0]:
            best = (float(gain[t]), float(values[t - 1]), left_missing)
    return best


def plain_search(X, grad, hess, params, block=32):
    """The best split of ALL the rows of X by `column_search`, every
    column: {"gain" (the parent's taken off), "feature", "value",
    "default_left", "columns": [F] each column's best gain (NaN: none)}.
    Columns are read a block at a time (the table is row-major) and
    searched across threads."""
    p = search_params(params)
    grad = np.asarray(grad, np.float64)
    hess = np.asarray(hess, np.float64)
    sum_g = float(grad.sum())
    sum_h = float(hess.sum()) + 2 * K_EPSILON
    parent = leaf_gain(sum_g, sum_h, p["lambda_l2"])
    F = X.shape[1]
    found = [None] * F

    def search(_, lo, hi):
        cols = np.ascontiguousarray(X[:, lo:hi].T)
        for j in range(lo, hi):
            found[j] = column_search(cols[j - lo], grad, hess, sum_g, sum_h,
                                     p)

    parallel.for_chunks(parallel.fixed_bounds(F, block), search)
    gains = np.array([np.nan if f is None else f[0] - parent for f in found])
    if np.isnan(gains).all():
        return {"gain": None, "feature": None, "value": None,
                "default_left": None, "columns": gains, "parent": parent}
    feature = int(np.nanargmax(gains))
    return {"gain": float(gains[feature]), "feature": feature,
            "value": found[feature][1], "default_left": found[feature][2],
            "columns": gains, "parent": parent}


def split_gain(left, grad, hess, l2):
    """The gain in float64 of sending the rows `left` (a mask) left, the
    parent's taken off."""
    sum_g, sum_h = float(grad.sum()), float(hess.sum()) + 2 * K_EPSILON
    lg, lh = float(grad[left].sum()), float(hess[left].sum()) + K_EPSILON
    return leaf_gain(lg, lh, l2) + leaf_gain(sum_g - lg, sum_h - lh, l2) \
        - leaf_gain(sum_g, sum_h, l2)


def node_goes_left(tree, node, x):
    """The rows of column `x` that internal node `node` sends left, by the
    model file's own fields (threshold, missing type, default direction):
    `lib/reference.py`'s rule for one node."""
    dt = int(tree.decision_type[node])
    kind, default_left = (dt >> 2) & 3, bool(dt & 2)
    x = np.asarray(x, np.float64)
    nan = np.isnan(x)
    x = np.where(nan & (kind != reference.MISSING_NAN), 0.0, x)
    missing = ((kind == reference.MISSING_ZERO)
               & (np.abs(x) <= reference.K_ZERO)) \
        | ((kind == reference.MISSING_NAN) & nan)
    return np.where(missing, default_left, x <= float(tree.threshold[node]))


def auc(y, score):
    """Area under the ROC curve with TIES COUNTED AS HALVES (mid-ranks):
    eight trees of some fifty leaves over a table that is four fifths
    empty give many parts one and the same score, and a sort that broke
    the ties by row order would add its own noise."""
    _, inverse, counts = np.unique(score, return_inverse=True,
                                   return_counts=True)
    mid = np.cumsum(counts) - (counts - 1) / 2.0        # mean rank, from 1
    ranks = mid[inverse]
    pos = np.asarray(y) > 0
    npos = float(pos.sum())
    nneg = len(pos) - npos
    return (ranks[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg)


# -- what the driver asks -----------------------------------------------------

def leaf_value_diffs(tree, X, y, learning_rate, lambda_l2):
    """Tree 0's leaf values against float64 sums, leaf by leaf (what
    `reference.tree0_check` gives the largest of): the differences largest
    first with the leaves' rows and positives, and their quartiles."""
    nl = int(tree.num_leaves)
    leaf = reference.leaf_index(tree, X)
    count = np.bincount(leaf, minlength=nl)
    n_pos = np.bincount(leaf, weights=(y > 0), minlength=nl)
    rate = float(np.mean(y > 0))
    grad = n_pos * (rate - 1.0) + (count - n_pos) * rate
    hess = count * rate * (1.0 - rate)
    expect = float(np.log(rate / (1.0 - rate))) \
        - grad / (hess + lambda_l2) * learning_rate
    diff = np.abs(np.asarray(tree.leaf_value[:nl], np.float64) - expect)
    order = np.argsort(-diff)
    return {"value_diffs_largest": [
                [float(diff[k]), int(count[k]), int(n_pos[k])]
                for k in order[:6]],
            "value_diff_quartiles": [float(np.quantile(diff, q))
                                     for q in (0.25, 0.5, 0.75)]}


def first_tree(tree, data, cfg):
    """Tree 0 of a binary log-loss model boosted from the average, row
    for row and value for value by `reference.tree0_check` (its walk
    routes a NaN by the node's `default_left`), and the ROOT's split held
    to the plain search: its gain, recomputed in float64 from the rows its
    feature, threshold and direction send left, within ROOT_GAIN_RTOL of
    the best the search finds in any of the 968 columns in either
    direction.  `counts_ok` is both; the root's gain and direction are
    also entries of the task's own `compared`, beside their lines on
    standard error (the direction is shown, not held).

    Tree 0's values are held to TWO limits.  `max_value_diff`, which
    drivers/train.py holds to `leaf_value_atol`, is the THIRD QUARTILE of
    the leaves' differences (as tasks/rank.py and tasks/binary_cat.py): a
    bfloat16 histogram moves EVERY leaf (it rounds the negatives' gradient
    and the hessian, 0.0058 and 0.00577, to neighbouring or equal steps),
    so the quartile tells it from a sound run by the widest margin.  The
    LARGEST difference, which `reference.tree0_check` gives, is held here
    to the configuration's `leaf_value_largest_atol`, a `compared` entry
    and line, and folded into `counts_ok` as the root's gain is: the
    leaf that holds most of the positives reads several times the others
    in sound runs (its gradient sum is a difference of two large float32
    sums), so this limit is the wider of the two, and it is what catches
    ONE wrong leaf, which a quartile cannot.  PERF.md section 6 (PR 37)
    has both readings, sound and control."""
    p = search_params(cfg["params"])
    X, y = data["X"], data["y"]
    out = reference.tree0_check(tree, X, y, p["learning_rate"],
                                p["lambda_l2"])
    diffs = leaf_value_diffs(tree, X, y, p["learning_rate"], p["lambda_l2"])
    largest = float(out["max_value_diff"])
    largest_atol = float(cfg.get("leaf_value_largest_atol",
                                 LARGEST_VALUE_DIFF_ATOL))
    out.update(diffs, largest_value_diff=[largest, largest_atol],
               max_value_diff=float(diffs["value_diff_quartiles"][2]))
    print("[bench] compared tree0_largest_value_diff %.3e limit %.0e "
          "(third quartile %.3e)" % (largest, largest_atol,
                                     out["max_value_diff"]),
          file=sys.stderr, flush=True)
    rate = float(np.mean(y > 0))
    row_g = np.where(y > 0, rate - 1.0, rate)
    row_h = np.full(len(y), rate * (1.0 - rate))
    feature = int(tree.split_feature[0])
    goes_left = node_goes_left(tree, 0, X[:, feature])
    chosen = split_gain(goes_left, row_g, row_h, p["lambda_l2"])
    plain = plain_search(X, row_g, row_h, cfg["params"])
    gain_off = abs(chosen - plain["gain"]) / abs(plain["gain"])
    default_left = bool(int(tree.decision_type[0]) & 2)
    nan_rows = int(np.isnan(X[:, feature]).sum())
    print("[bench] compared tree0_root_gain_rel_diff %.3e limit %.0e "
          "(chosen %.9g on column %d at %.6g, plain search %.9g on column "
          "%d at %s)" % (gain_off, ROOT_GAIN_RTOL, chosen, feature,
                         float(tree.threshold[0]), plain["gain"],
                         plain["feature"], plain["value"]),
          file=sys.stderr, flush=True)
    print("[bench] compared tree0_root_default_left %s (plain search %s; "
          "%d of %d rows of the column are NaN)"
          % (json.dumps(default_left), json.dumps(plain["default_left"]),
             nan_rows, len(y)), file=sys.stderr, flush=True)
    ni = int(tree.num_leaves) - 1
    dt = np.asarray(tree.decision_type[:ni], np.int64)
    out.update({
        "counts_ok": bool(out["counts_ok"] and gain_off <= ROOT_GAIN_RTOL
                          and largest <= largest_atol),
        "positive_share": rate,
        "nan_aware_nodes": int(((dt >> 2) & 3 == reference.MISSING_NAN)
                               .sum()),
        "default_left_nodes": int(((dt & 2) != 0).sum()),
        # [value, limit] and what was compared
        "root_gain_rel_diff": [float(gain_off), ROOT_GAIN_RTOL],
        "root_gain_chosen": float(chosen), "root_feature": feature,
        "root_threshold": float(tree.threshold[0]),
        "root_default_left": default_left, "root_nan_rows": nan_rows,
        "root_gain_plain": float(plain["gain"]),
        "root_feature_plain": int(plain["feature"]),
        "root_value_plain": plain["value"],
        "root_default_left_plain": bool(plain["default_left"]),
        "compared": {
            "tree0_root_gain_rel_diff": [float(gain_off), ROOT_GAIN_RTOL],
            "tree0_largest_value_diff": [largest, largest_atol],
            "tree0_root_default_left": [default_left,
                                        bool(plain["default_left"])]},
    })
    return out


def heldout(trees, data, cfg):
    raw = reference.predict_raw(trees, data["X"])
    return float(auc(data["y"], raw))
