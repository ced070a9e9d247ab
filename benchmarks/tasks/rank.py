"""The learning task `rank`: LambdaRank over query groups on a dense
numerical table, graded labels 0-4, held out by NDCG@10.

The four functions drivers/train.py asks of a task (tasks/binary.py lists
them), and everything of the task they stand on: the generator, the
LambdaRank gradients in float64 numpy a query at a time, and NDCG.
Nothing here imports the program's arithmetic; the gradient formula is
written from the published description (Burges, "From RankNet to
LambdaRank to LambdaMART", and the reference's `rank_objective.hpp`):

    for a pair (i, j) of one query with label_i > label_j
        delta = (gain_i - gain_j) * |disc_i - disc_j| / maxDCG@k
        delta /= 0.01 + |s_i - s_j|      where the query's best and
                                         worst scores differ
        rho = 2 / (1 + exp(2 sigma (s_i - s_j)))
        lambda_i -= delta rho,  lambda_j += delta rho
        hess_i, hess_j += 2 delta rho (2 - rho)

with gain = 2^label - 1, disc = 1 / log2(2 + rank) for the rank in the
query's stable descending order of scores (ties in original order),
maxDCG@k the DCG of the labels sorted descending, cut at k =
`max_position` (20), and per-row weights multiplied in last.
"""
import numpy as np

from benchmarks.lib import parallel, reference

#: rows per generation chunk; part of the data's definition
CHUNK_ROWS = 1 << 19
#: seed of the task itself (the weight vector), fixed across runs: every
#: seed draws new rows and new queries of the SAME task
TASK_SEED = 4321
#: the linear term is scaled so that its variance is that of
#: `synth.binary_task` at any width
BASE_FEATURES = 28
#: column j's weight is WEIGHT_DECAY^j (sign drawn from TASK_SEED): a few
#: columns carry the relevance, as a few of MSLR-WEB30K's 136 (BM25, click
#: counts) do.  With weights of one size over all 137 columns, eight trees
#: read NDCG@10 0.43 and the 6,306 held-out queries spread it 1.4-1.7% over
#: seeds (chip, PR 31), three times what admits a cell; with these they
#: read 0.90 spreading 0.2%
WEIGHT_DECAY = 0.6
#: the last columns are constant within a query (query-level features,
#: as MSLR-WEB30K's query length or URL-independent statistics)
QUERY_LEVEL_FEATURES = 8
#: query sizes are exp(QUERY_SIGMA * normal), scaled to the mean and cut
#: to 1..longest
QUERY_SIGMA = 0.7
#: the latent relevance is cut into grades 0-4 at these values: the
#: 52 / 84 / 97 / 99% points of its distribution (read off 2M rows of
#: TASK_SEED's task at 137 columns; standard deviation 2.80), so the grades
#: hold about 52 / 32 / 13 / 2 / 1%
GRADE_CUTS = (0.504, 2.991, 5.181, 6.247)
LABEL_GAIN = 2.0 ** np.arange(31) - 1.0
#: tree 0's `max_value_diff` is the third quartile of the leaves'
#: differences, or a LONE_LEAF_ROOM-th of the largest (`first_tree`)
LONE_LEAF_ROOM = 128.0


# -- the generator -----------------------------------------------------------

def query_sizes(n_queries, n_rows, longest, seed):
    """[n_queries] int64 sizes in 1..longest that sum to n_rows, the
    largest exactly `longest`: a log-normal (QUERY_SIGMA) scaled by
    bisection until the rounded, cut sizes hold about n_rows, the
    remainder spread a row a query over the first queries that have
    room."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    raw = np.exp(QUERY_SIGMA * rng.standard_normal(n_queries))
    raw[np.argmax(raw)] = np.inf            # the longest query is the cut

    def sized(scale):
        return np.clip(np.rint(scale * raw), 1, longest).astype(np.int64)

    lo, hi = 0.0, 2.0 * n_rows / n_queries
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if sized(mid).sum() < n_rows:
            lo = mid
        else:
            hi = mid
    sizes = sized(lo)
    short = int(n_rows - sizes.sum())
    room = np.flatnonzero(sizes < longest)[:short]
    if short < 0 or len(room) < short:
        raise ValueError("%d queries of 1..%d rows cannot hold %d rows"
                         % (n_queries, longest, n_rows))
    sizes[room] += 1
    return sizes


def task_weights(n_features):
    """The linear term's weights: WEIGHT_DECAY^j with a sign from
    TASK_SEED, scaled to the variance `synth.binary_task`'s has."""
    sign = np.sign(np.random.default_rng(TASK_SEED)
                   .standard_normal(n_features))
    w = sign * WEIGHT_DECAY ** np.arange(n_features)
    return w * np.sqrt(0.25 * BASE_FEATURES / np.sum(w * w))


def ranking_task(n_rows, n_features, n_queries, longest, seed):
    """(X [n_rows, n_features] float64, y [n_rows] float32 grades 0-4,
    sizes [n_queries] int64): standard-normal columns, the last
    QUERY_LEVEL_FEATURES of them one value a query; a latent relevance
    from a linear term over every column (`task_weights`), two
    interactions and noise, as `synth.binary_task`, cut into grades.
    Rows are made in fixed chunks, chunk i from its own stream, so the
    data depends on the arguments and never on the number of threads."""
    w = task_weights(n_features)
    sizes = query_sizes(n_queries, n_rows, longest, (*seed, 1))
    q_of_row = np.repeat(np.arange(n_queries), sizes)
    q_values = np.random.default_rng(
        np.random.SeedSequence((*seed, 2))).standard_normal(
            (n_queries, QUERY_LEVEL_FEATURES))
    X = np.empty((n_rows, n_features), np.float64)
    y = np.empty(n_rows, np.float32)
    bounds = parallel.fixed_bounds(n_rows, CHUNK_ROWS)
    seeds = np.random.SeedSequence((*seed, 3)).spawn(len(bounds) - 1)

    def fill(i, lo, hi):
        rng = np.random.default_rng(seeds[i])
        Xc = X[lo:hi]
        rng.standard_normal(out=Xc)
        Xc[:, n_features - QUERY_LEVEL_FEATURES:] = q_values[q_of_row[lo:hi]]
        latent = Xc @ w
        latent += 0.4 * Xc[:, 0] * Xc[:, 1] + 0.3 * np.abs(Xc[:, 2])
        latent += 0.8 * rng.standard_normal(hi - lo)
        y[lo:hi] = np.searchsorted(GRADE_CUTS, latent)

    parallel.for_chunks(bounds, fill)
    return X, y, sizes


def make(cfg, seed, part):
    rows, queries = (("heldout_rows", "heldout_queries") if part
                     else ("rows", "queries"))
    X, y, sizes = ranking_task(cfg[rows], cfg["features"], cfg[queries],
                               cfg["longest_query"], (seed, part))
    return {"X": X, "y": y, "group": sizes}


def dataset_args(data):
    return {"group": data["group"]}


# -- LambdaRank in float64, a query at a time ---------------------------------

def _discounts(n):
    return 1.0 / np.log2(2.0 + np.arange(n))


def _query_lambdas(s, label, sigma, k):
    """(lambda, hessian) of one query's rows: an [n, n] block, row i the
    higher-labelled document of the pair, column j the lower."""
    n = len(s)
    lab = label.astype(np.int64)
    gain = LABEL_GAIN[lab]
    ideal = LABEL_GAIN[np.sort(lab)[::-1][:k]]
    max_dcg = float(np.sum(ideal * _discounts(len(ideal))))
    if n < 2 or max_dcg <= 0.0:
        return np.zeros(n), np.zeros(n)
    rank = np.empty(n, np.int64)
    rank[np.argsort(-s, kind="stable")] = np.arange(n)
    disc = _discounts(n)[rank]
    ds = s[:, None] - s[None, :]
    delta = (gain[:, None] - gain[None, :]) \
        * np.abs(disc[:, None] - disc[None, :]) / max_dcg
    if s.max() != s.min():
        delta = delta / (0.01 + np.abs(ds))
    with np.errstate(over="ignore"):
        rho = 2.0 / (1.0 + np.exp(2.0 * sigma * ds))
    pair = lab[:, None] > lab[None, :]
    lam = np.where(pair, -delta * rho, 0.0)
    hes = np.where(pair, 2.0 * delta * rho * (2.0 - rho), 0.0)
    return lam.sum(1) - lam.sum(0), hes.sum(1) + hes.sum(0)


def lambdarank(score, label, sizes, sigma=1.0, max_position=20,
               weight=None):
    """(gradient, hessian) [n] float64 of LambdaRank at `score`, queries
    of `sizes` rows one after another; chunks of queries of about equal
    pair counts across threads."""
    score = np.asarray(score, np.float64)
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    grad = np.zeros(len(score))
    hess = np.zeros(len(score))
    work = np.cumsum(sizes * sizes + 64)
    cuts = np.searchsorted(work, np.linspace(0, work[-1],
                                             8 * parallel.threads() + 1)[1:-1])
    bounds = sorted({0, len(sizes), *map(int, cuts)})

    def part(_, q_lo, q_hi):
        for q in range(q_lo, q_hi):
            lo, hi = ends[q] - sizes[q], ends[q]
            grad[lo:hi], hess[lo:hi] = _query_lambdas(
                score[lo:hi], label[lo:hi], sigma, max_position)

    parallel.for_chunks(bounds, part)
    if weight is not None:
        grad *= weight
        hess *= weight
    return grad, hess


def first_tree(tree, data, cfg):
    """Tree 0 under LambdaRank: rows walked through its thresholds on
    the raw columns, leaf counts exact, leaf values against
    -sum(g) / (sum(h) + lambda_l2) * learning_rate of the float64
    gradients at the initial score (0 for every row: the objective
    boosts from no average).

    `max_value_diff` is ABSOLUTE, as in the binary cells, though the
    values here are smaller (|value| <= learning_rate / 2, since a pair's
    hessian is twice its |lambda| at equal scores).  What rounding does
    to a leaf is an error in its gradient SUM over its hessian sum: it
    does not shrink with the value, and a leaf whose lambdas cancel has a
    value near 0 and the same error, so a relative measure would read
    noise there.

    It is NOT the largest difference over the leaves but their THIRD
    QUARTILE, or a `LONE_LEAF_ROOM`-th of the largest where that is more.
    The largest alone cannot tell float32 from the precision below it
    here: bfloat16 rounds thousands of DISTINCT gradients a leaf, the
    errors average out, and the furthest leaf of a bf16 histogram
    (5e-6 to 1.3e-5 on the chip) is no further off than the one or two
    leaves a sound run makes by subtraction under a large ancestor (up
    to 9.2e-6), where in the binary cells every row of a class has the
    same gradient and bf16's error adds up.  What tells them apart is
    that bf16 moves EVERY leaf (third quartile 1.5e-6 to 1.6e-6) and
    float32 only those few (3.2e-8 to 3.8e-8; PERF.md section 6, PR 31,
    has the readings).  The largest difference stays in the verdict at
    `LONE_LEAF_ROOM` times the limit, so one altered leaf still reads
    not correct."""
    p = cfg["params"]
    nl = int(tree.num_leaves)
    leaf = reference.leaf_index(tree, data["X"])
    count = np.bincount(leaf, minlength=nl)
    g, h = lambdarank(np.zeros(len(leaf)), data["y"], data["group"],
                      p.get("sigmoid", 1.0), p.get("max_position", 20))
    grad = np.bincount(leaf, weights=g, minlength=nl)
    hess = np.bincount(leaf, weights=h, minlength=nl)
    expect = -grad / (hess + p.get("lambda_l2", 0.0)) * p["learning_rate"]
    got = np.asarray(tree.leaf_value[:nl], np.float64)
    off = np.abs(count - np.asarray(tree.leaf_count[:nl], np.int64))
    slack = reference.count_slack(tree, count)
    diff = np.sort(np.abs(got - expect))[::-1]
    return {
        "leaves": nl, "rows": int(len(leaf)),
        "counts_ok": bool((off <= slack).all()),
        "max_count_diff": int(off.max()), "leaves_off": int((off > 0).sum()),
        "count_slack_max": int(slack.max()),
        "max_value_diff": float(max(np.quantile(diff, 0.75),
                                    diff[0] / LONE_LEAF_ROOM)),
        "largest_value_diff": float(diff[0]),
        "max_abs_value": float(np.abs(expect).max()),
        "smallest_leaf_rows": int(count.min()),
        # the leaves' differences, largest first, and their quartiles
        "value_diffs_largest": [float(d) for d in diff[:16]],
        "value_diff_quartiles": [float(np.quantile(diff, q))
                                 for q in (0.25, 0.5, 0.75)],
    }


# -- the held-out measure -----------------------------------------------------

def ndcg_at(k, label, score, sizes):
    """Mean NDCG@k over the queries: gain 2^label - 1, discount
    1 / log2(2 + i), ties in original order; a query with no relevant
    row counts as 1, as the reference's metric."""
    sizes = np.asarray(sizes, np.int64)
    q = np.repeat(np.arange(len(sizes)), sizes)
    start = np.repeat(np.cumsum(sizes) - sizes, sizes)
    gain = LABEL_GAIN[np.asarray(label, np.int64)]

    def dcg(key):
        order = np.lexsort((-key, q))           # stable within a query
        pos = np.arange(len(q)) - start
        top = pos < k
        return np.bincount(q[top], weights=gain[order][top]
                           / np.log2(2.0 + pos[top]), minlength=len(sizes))

    best = dcg(np.asarray(label, np.float64))
    got = dcg(np.asarray(score, np.float64))
    return float(np.mean(np.where(best > 0, got / np.where(best > 0, best, 1),
                                  1.0)))


def heldout(trees, data, cfg):
    metric, _, k = cfg["quality_metric"].partition("@")
    if metric != "ndcg":
        raise ValueError("the task `rank` measures ndcg@k, not %r"
                         % cfg["quality_metric"])
    raw = reference.predict_raw(trees, data["X"])
    return ndcg_at(int(k), data["y"], raw, data["group"])
