"""The categorical split search (`ops/split.py::_categorical_best`) against
the reference's sequential walk in float64 numpy (tests/plain_cat_search.py)
on random histograms, and against the `lax.scan` over the bins it was
before PR 33 (exp/cat_search_race.py keeps a copy), bit for bit."""
import inspect
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import split

import plain_cat_search as plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F = 6
MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2


def _params(max_cat_threshold, **over):
    return dict({"lambda_l2": 0.0, "min_data_in_leaf": 20,
                 "min_sum_hessian_in_leaf": 1e-3, "cat_smooth": 10.0,
                 "cat_l2": 10.0, "max_cat_threshold": max_cat_threshold,
                 "max_cat_to_onehot": 4, "min_data_per_group": 100}, **over)


def _histogram(rng, B, scenario):
    """(hist [F, B, 3] float64, num_bin [F], missing [F], totals): every
    column splits the same rows, so each sums to the same totals."""
    num_bin = rng.integers(max(B // 2, 5), B + 1, F)
    num_bin[0] = B
    missing = rng.integers(0, 3, F)
    missing[0] = MISSING_NONE
    if scenario == "onehot":
        num_bin[:] = rng.integers(2, 5, F)
    hist = np.zeros((F, B, 3))
    n = {"plain": 400 * B, "small_groups": 30 * B,
         "from_the_top": 400 * B, "onehot": 5000}.get(scenario)
    for f in range(F):
        nb = int(num_bin[f])
        if scenario == "starved":
            # three bins hold nearly every row, at one end of the sorted
            # order, and a few small ones the rest: a left side that has
            # the three leaves the right under min_data_per_group
            m = min(int(num_bin.min()), 12)
            at = rng.choice(nb, m, replace=False)
            c = np.zeros(nb)
            c[at] = [1300.0] * 3 + [12.0] * (m - 3)
            rate = np.full(nb, 0.3) + 0.05 * rng.standard_normal(nb)
            rate[at[:3]] = (0.05 if f % 2 else 0.9) \
                + 0.01 * rng.standard_normal(3)
        else:
            share = rng.dirichlet(np.full(nb, 0.8))
            c = np.floor(share * n)
            c[int(np.argmax(c))] += n - c.sum()
            rate = np.clip(0.3 + 0.15 * rng.standard_normal(nb), 0.01, 0.99)
        if scenario == "from_the_top":
            # two bins far above the rest: the best left set is the two
            # LAST of the sorted order, past the reach of the walk from
            # the bottom
            rate = np.clip(0.3 + 0.02 * rng.standard_normal(nb), 0.01, 0.99)
            big = np.argsort(-c)[1:3]
            rate[big] = 0.9
        hist[f, :nb, 0] = c * (rate - 0.3)      # gradient: mean - rate
        hist[f, :nb, 1] = c * 0.21
        hist[f, :nb, 2] = c
    # one total for every column: the leaf's
    hist[:, 0, 0] += hist[0, :, 0].sum() - hist[:, :, 0].sum(axis=1)
    totals = hist[0].sum(axis=0)
    return hist, num_bin, missing, totals


def _meta(num_bin, missing):
    n = len(num_bin)
    return split.FeatureMeta(
        num_bin=jnp.asarray(num_bin, jnp.int32),
        missing_type=jnp.asarray(missing, jnp.int32),
        default_bin=jnp.zeros(n, jnp.int32), is_trivial=jnp.zeros(n, bool),
        is_categorical=jnp.ones(n, bool), penalty=jnp.ones(n, jnp.float32),
        monotone=jnp.zeros(n, jnp.int32))


def _program_kwargs(p):
    return dict(l1=0.0, l2=p["lambda_l2"], max_delta_step=0.0,
                min_data_in_leaf=p["min_data_in_leaf"],
                min_sum_hessian_in_leaf=p["min_sum_hessian_in_leaf"],
                max_cat_threshold=p["max_cat_threshold"],
                cat_l2=p["cat_l2"], cat_smooth=p["cat_smooth"],
                max_cat_to_onehot=p["max_cat_to_onehot"],
                min_data_per_group=p["min_data_per_group"])


def _search(fn, hist, meta, totals, p):
    h32 = jnp.asarray(hist, jnp.float32)
    sum_h = jnp.float32(totals[1]) + 2 * split.K_EPSILON
    return jax.jit(lambda g, h, c: fn(
        g, h, c, jnp.float32(totals[0]), sum_h, jnp.float32(totals[2]),
        jnp.ones(hist.shape[0], bool), meta=meta, **_program_kwargs(p)))(
            h32[:, :, 0], h32[:, :, 1], h32[:, :, 2])


def _plain_by_column(hist, num_bin, missing, totals, p):
    out = []
    for f in range(hist.shape[0]):
        nb = int(num_bin[f])
        g, h, c = (hist[f, :nb, k] for k in range(3))
        out.append(plain.categorical_search(
            g, h, c, totals[0], totals[1] + 2 * plain.K_EPSILON, totals[2],
            p, full=missing[f] == MISSING_NONE))
    return out


def _walk_is_cut_off(hist_f, nb, missing_f, totals, p):
    """Whether a walk from either end of one column's sorted order meets
    a right side under min_data_per_group rows and stops."""
    used_bin = int(nb) - 1 + (missing_f == MISSING_NONE)
    kept = [t for t in range(used_bin) if hist_f[t, 2] >= p["cat_smooth"]]
    kept.sort(key=lambda t: hist_f[t, 0] / (hist_f[t, 1] + p["cat_smooth"]))
    for walk in (kept, kept[::-1]):
        lc = 0.0
        for t in walk[:min(p["max_cat_threshold"], (len(kept) + 1) // 2)]:
            lc += hist_f[t, 2]
            if lc >= p["min_data_in_leaf"] \
                    and totals[2] - lc < p["min_data_per_group"]:
                return True
    return False


SCENARIOS = ["plain", "small_groups", "starved", "from_the_top", "onehot"]


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("max_cat_threshold", [4, 32])
@pytest.mark.parametrize("B", [8, 64, 256])
def test_search_finds_what_the_sequential_walk_finds(B, max_cat_threshold,
                                                     scenario):
    """Column by column: the same left set, the same three left sums,
    the same gain; and over the columns, `find_best_split` takes the
    column the plain search ranks first."""
    rng = np.random.default_rng(1000 * B + 10 * max_cat_threshold
                                + SCENARIOS.index(scenario))
    p = _params(max_cat_threshold)
    hist, num_bin, missing, totals = _histogram(rng, B, scenario)
    meta = _meta(num_bin, missing)
    gain, bitset, lg, lh, lc, sorted_mode = map(
        np.asarray, _search(split._categorical_best, hist, meta, totals, p))
    want = _plain_by_column(hist, num_bin, missing, totals, p)
    assert any(w is not None for w in want)
    seen = {"none": 0, "top": 0, "cut_short": 0, "tied": 0}
    for f, w in enumerate(want):
        assert sorted_mode[f] == (num_bin[f] > p["max_cat_to_onehot"])
        if w is None:
            assert gain[f] == -np.inf
            seen["none"] += 1
            continue
        w_gain, w_bins, (w_lg, w_lh, w_lc) = w
        got_bins = sorted(np.flatnonzero(bitset[f]))
        if got_bins != sorted(w_bins):
            # a tie in exact arithmetic (one bin against the rest and the
            # rest against it; k bins up from the bottom and the others
            # down from the top), which float32 may break the other way:
            # the program's set gives the same gain
            l2 = p["lambda_l2"] + p["cat_l2"] * bool(sorted_mode[f])
            tg = hist[f, got_bins, 0].sum()
            th = hist[f, got_bins, 1].sum() + plain.K_EPSILON
            tied = plain.leaf_gain(tg, th, l2) + plain.leaf_gain(
                totals[0] - tg, totals[1] + 2 * plain.K_EPSILON - th, l2)
            assert abs(tied - w_gain) <= 1e-9 * abs(w_gain), \
                (f, got_bins, w_bins, tied, w_gain)
            seen["tied"] += 1
            continue
        assert lc[f] == w_lc
        np.testing.assert_allclose([lg[f], lh[f]], [w_lg, w_lh], rtol=2e-5)
        np.testing.assert_allclose(gain[f], w_gain, rtol=1e-4)
        if sorted_mode[f]:
            # which end the winner was walked from, and whether the walk
            # could have gone further
            ctr = hist[f, :, 0] / (hist[f, :, 1] + p["cat_smooth"])
            rest = [t for t in range(num_bin[f] - 1
                                     + (missing[f] == MISSING_NONE))
                    if hist[f, t, 2] >= p["cat_smooth"]
                    and t not in w_bins]
            if rest and min(ctr[w_bins]) > max(ctr[rest]):
                seen["top"] += 1
            if len(w_bins) == max_cat_threshold:
                seen["cut_short"] += 1
    if scenario == "from_the_top" and B > 8:
        assert seen["top"] >= F - 1
    if scenario == "starved":
        assert any(_walk_is_cut_off(hist[f], num_bin[f], missing[f], totals,
                                    p) for f in range(F))

    # over the columns
    shift = plain.leaf_gain(totals[0], totals[1] + 2 * plain.K_EPSILON,
                            p["lambda_l2"])
    best = max((f for f, w in enumerate(want) if w is not None),
               key=lambda f: want[f][0])
    res = jax.jit(lambda h: split.find_best_split(
        h, jnp.float32(totals[0]), jnp.float32(totals[1]),
        jnp.float32(totals[2]), jnp.ones(F, bool), meta=meta,
        min_gain_to_split=0.0, with_categorical=True,
        **_program_kwargs(p)))(jnp.asarray(hist, jnp.float32))
    if seen["tied"]:
        return
    if want[best][0] > shift:
        assert int(res.feature) == best and bool(res.is_cat)
        assert sorted(np.flatnonzero(np.asarray(res.cat_bitset))) \
            == sorted(want[best][1])
        np.testing.assert_allclose(float(res.gain), want[best][0] - shift,
                                   rtol=2e-4)
        assert float(res.left_count) == want[best][2][2]
    else:
        assert float(res.gain) == -np.inf


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("max_cat_threshold", [4, 32])
@pytest.mark.parametrize("B", [8, 64, 256])
def test_search_is_the_scan_it_replaced_bit_for_bit(B, max_cat_threshold,
                                                    scenario):
    sys.path.insert(0, os.path.join(ROOT, "exp"))
    try:
        import cat_search_race
    finally:
        sys.path.pop(0)
    rng = np.random.default_rng(7 + 1000 * B + 10 * max_cat_threshold
                                + SCENARIOS.index(scenario))
    p = _params(max_cat_threshold)
    hist, num_bin, missing, totals = _histogram(rng, B, scenario)
    meta = _meta(num_bin, missing)
    new = _search(split._categorical_best, hist, meta, totals, p)
    old = _search(cat_search_race.categorical_best_scan, hist, meta, totals,
                  p)
    new, old = ([np.asarray(a) for a in r] for r in (new, old))
    # a column whose best left set from the bottom and best from the top
    # cut the same rows apart (an odd number of bins, at the middle) has
    # two gains that are one number in exact arithmetic: which end wins
    # is the compiler's rounding, in the scan and in the walk alike
    tied = [f for f in range(F)
            if (new[1][f] != old[1][f]).any()
            and not (new[1][f] & old[1][f]).any()
            and abs(new[0][f] - old[0][f]) <= 1e-6 * abs(old[0][f])]
    # (five bins a column in `starved` at B = 8: ties by construction)
    assert len(tied) <= (F if scenario == "starved" else 1)
    same = np.array([f not in tied for f in range(F)])
    for a, b in zip(new, old):
        assert a[same].tobytes() == b[same].tobytes()


def test_no_column_is_offered_its_last_bin_unless_every_value_has_one():
    """Two columns of one histogram, the first `full`: only it may send
    its last bin left."""
    rng = np.random.default_rng(5)
    p = _params(32, min_data_per_group=1, cat_smooth=1.0)
    hist, num_bin, missing, _ = _histogram(rng, 16, "plain")
    # the last bin large and far below the rest: the first bin the walk
    # from the bottom takes, and the best left side with it
    hist[0, 15] = [-0.29 * 2000, 0.21 * 2000, 2000]
    hist[1:] = hist[0]
    num_bin[:] = 16
    missing[:] = [MISSING_NONE, MISSING_ZERO, MISSING_NAN] * 2
    totals = hist[0].sum(axis=0)
    _, bitset, *_ = _search(split._categorical_best, hist,
                            _meta(num_bin, missing), totals, p)
    bitset = np.asarray(bitset)
    assert (bitset[:, 15] == (missing == MISSING_NONE)).all()
    assert bitset[0, 15]
    want = _plain_by_column(hist, num_bin, missing, totals, p)
    for f in range(F):
        assert sorted(np.flatnonzero(bitset[f])) == sorted(want[f][1])


def test_the_plain_search_here_is_the_benchmark_task_s():
    from benchmarks.run import load_module
    task = load_module(os.path.join(ROOT, "benchmarks", "tasks",
                                    "binary_cat.py"))
    for name in ("leaf_gain", "categorical_search"):
        assert inspect.getsource(getattr(plain, name)) \
            == inspect.getsource(getattr(task, name))
    assert plain.K_EPSILON == task.K_EPSILON
