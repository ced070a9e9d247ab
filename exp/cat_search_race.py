#!/usr/bin/env python3
"""Time the categorical split search alone, at the `expo-cat` cell's
shape ([8, 256]: eight columns, six of them categorical, 256 bins): the
`lax.scan` over the 256 sorted positions that `ops/split.py` had before
PR 33 (`categorical_best_scan` below, a copy kept for this race and for
tests/test_cat_search.py, which holds the two to the same bits) beside
the unrolled walk over `max_cat_threshold` positions it has now.

    python3 exp/cat_search_race.py [--bins 256] [--reps 20] [--cpu]

Printed, a line a variant: milliseconds a call of `find_best_split` on
one histogram, and milliseconds a TREE: 254 splits one after another in
a `fori_loop`, each searching its two children through
`find_best_split_batched` (Q = 2) as the grower does, on histograms that
differ from split to split.  The numerical search alone (no categorical
column) stands beside them.  The readings are no speed of record; what
the search costs inside the step is `grower.cat_search_s_per_iter`.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import split


def categorical_best_scan(g, h, c, sum_g, sum_h, num_data, cat_mask, *, meta,
                      l1, l2, max_delta_step, min_data_in_leaf,
                      min_sum_hessian_in_leaf, max_cat_threshold, cat_l2,
                      cat_smooth, max_cat_to_onehot, min_data_per_group):
    """Best categorical split per feature (FindBestThresholdCategorical,
    feature_histogram.hpp:112-273).

    One-hot mode (num_bin <= max_cat_to_onehot) scans single-bin lefts as one
    [F, B] vector op.  Sorted-subset mode sorts bins by sum_g/(sum_h +
    cat_smooth) and scans bounded prefixes from both ends; the reference's
    sequential walk (min_data_per_group grouping, break-on-starved-right)
    becomes a batched `lax.scan` with [F] carries.

    Returns per-feature (raw_gain [F], bitset [F, B], left_g, left_h(+eps),
    left_c, used_sorted [F] bool).
    """
    F, B = g.shape
    eps = split.K_EPSILON
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]
    # used_bin = num_bin - 1 + (missing_type == None) (feature_histogram.hpp:125-126)
    used_bin = (meta.num_bin - 1 +
                (meta.missing_type == split.MISSING_NONE).astype(jnp.int32))[:, None]
    valid_t = (bins < used_bin) & cat_mask[:, None]

    def pair_gain(lg, lh, rg, rh, l2_eff):
        return split._leaf_split_gain(lg, lh, l1, l2_eff, max_delta_step) + \
               split._leaf_split_gain(rg, rh, l1, l2_eff, max_delta_step)

    # ---- one-hot: left = single bin t ------------------------------------
    other_g = sum_g - g
    other_h = sum_h - h - eps
    other_c = num_data - c
    ok_oh = valid_t & (c >= min_data_in_leaf) & (h >= min_sum_hessian_in_leaf) \
        & (other_c >= min_data_in_leaf) & (other_h >= min_sum_hessian_in_leaf)
    gain_oh = jnp.where(ok_oh, pair_gain(g, h + eps, other_g, other_h, l2),
                        split.K_MIN_SCORE)
    t_oh = jnp.argmax(gain_oh, axis=1).astype(jnp.int32)          # [F]
    best_oh = jnp.take_along_axis(gain_oh, t_oh[:, None], 1)[:, 0]

    # ---- sorted subset ----------------------------------------------------
    keep = valid_t & (c >= cat_smooth)
    ctr = jnp.where(keep, g / (h + cat_smooth), jnp.inf)
    order = jnp.argsort(ctr, axis=1).astype(jnp.int32)            # [F, B]
    used = jnp.sum(keep, axis=1).astype(jnp.int32)                # [F]
    max_cat = jnp.minimum(max_cat_threshold, (used + 1) // 2)     # [F]
    l2s = l2 + cat_l2
    gs = jnp.take_along_axis(g, order, 1)
    hs = jnp.take_along_axis(h, order, 1)
    cs = jnp.take_along_axis(c, order, 1)
    slot_valid = bins < used[:, None]
    gs = jnp.where(slot_valid, gs, 0.0)
    hs = jnp.where(slot_valid, hs, 0.0)
    cs = jnp.where(slot_valid, cs, 0.0)

    def scan_dir(flip: bool):
        if flip:
            # direction -1 walks sorted bins from the top (position used-1-i)
            pos = used[:, None] - 1 - bins
            posc = jnp.clip(pos, 0, B - 1)
            gd = jnp.take_along_axis(gs, posc, 1)
            hd = jnp.take_along_axis(hs, posc, 1)
            cd = jnp.take_along_axis(cs, posc, 1)
        else:
            gd, hd, cd = gs, hs, cs

        def step(carry, xs):
            lg, lh, lc, grp, stopped, bg, bi, blg, blh, blc = carry
            gi, hi, ci, i = xs
            stepping = (i < used) & (i < max_cat)
            lg = jnp.where(stepping, lg + gi, lg)
            lh = jnp.where(stepping, lh + hi, lh)
            lc = jnp.where(stepping, lc + ci, lc)
            grp = jnp.where(stepping, grp + ci, grp)
            cont1 = (lc < min_data_in_leaf) | (lh < min_sum_hessian_in_leaf)
            rc = num_data - lc
            rh = sum_h - lh
            brk = (rc < min_data_in_leaf) | (rc < min_data_per_group) | \
                  (rh < min_sum_hessian_in_leaf)
            # break only evaluated when the left side qualifies (reference
            # `continue`s before the break checks, :205-212)
            stopped_new = stopped | (stepping & ~cont1 & brk)
            candidate = stepping & ~stopped & ~cont1 & ~brk & \
                (grp >= min_data_per_group)
            grp = jnp.where(candidate, 0.0, grp)
            gain_i = pair_gain(lg, lh, sum_g - lg, rh, l2s)
            take = candidate & (gain_i > bg)
            bg = jnp.where(take, gain_i, bg)
            bi = jnp.where(take, i, bi)
            blg = jnp.where(take, lg, blg)
            blh = jnp.where(take, lh, blh)
            blc = jnp.where(take, lc, blc)
            return (lg, lh, lc, grp, stopped_new, bg, bi, blg, blh, blc), None

        zero = jnp.zeros(F, jnp.float32)
        carry0 = (zero, jnp.full(F, eps, jnp.float32), zero, zero,
                  jnp.zeros(F, bool), jnp.full(F, split.K_MIN_SCORE, jnp.float32),
                  jnp.full(F, -1, jnp.int32), zero, zero, zero)
        xs = (gd.T, hd.T, cd.T, jnp.arange(B, dtype=jnp.int32))
        carry, _ = jax.lax.scan(step, carry0, xs)
        _, _, _, _, _, bg, bi, blg, blh, blc = carry
        return bg, bi, blg, blh, blc

    bg1, bi1, blg1, blh1, blc1 = scan_dir(False)
    bg2, bi2, blg2, blh2, blc2 = scan_dir(True)
    use2 = bg2 > bg1
    bg_s = jnp.where(use2, bg2, bg1)
    bi_s = jnp.where(use2, bi2, bi1)
    blg_s = jnp.where(use2, blg2, blg1)
    blh_s = jnp.where(use2, blh2, blh1)
    blc_s = jnp.where(use2, blc2, blc1)
    # bitset: first bi+1 sorted bins (dir +1) or last bi+1 (dir -1) go left
    rank = jnp.argsort(order, axis=1)                             # position of bin b
    rank_dir = jnp.where(use2[:, None], used[:, None] - 1 - rank, rank)
    bitset_s = keep & (rank_dir <= bi_s[:, None]) & (rank_dir >= 0)

    # ---- choose one-hot vs sorted per feature ----------------------------
    use_onehot = (meta.num_bin <= max_cat_to_onehot)
    raw_gain = jnp.where(use_onehot, best_oh, bg_s)
    bitset = jnp.where(use_onehot[:, None], bins == t_oh[:, None], bitset_s)
    lg = jnp.where(use_onehot, jnp.take_along_axis(g, t_oh[:, None], 1)[:, 0], blg_s)
    lh = jnp.where(use_onehot,
                   jnp.take_along_axis(h, t_oh[:, None], 1)[:, 0] + eps, blh_s)
    lc = jnp.where(use_onehot, jnp.take_along_axis(c, t_oh[:, None], 1)[:, 0], blc_s)
    return raw_gain, bitset, lg, lh, lc, ~use_onehot


SPLITS_A_TREE = 254


def cell_inputs(bins, seed=0):
    """A [8, bins, 3] histogram of 10M rows and its metadata, shaped as
    the cell's: columns 0, 1, 2, 4 with 12, 31, 7 and 22 categories, 5 and
    6 with `bins` - 1 and a last bin that is not offered, 3 and 7
    numerical."""
    import numpy as np
    rng = np.random.default_rng(seed)
    num_bin = np.array([12, 31, 7, bins - 1, 22, bins - 1, bins - 1,
                        bins - 1], np.int32)
    num_bin = np.minimum(num_bin, bins)
    is_cat = np.array([1, 1, 1, 0, 1, 1, 1, 0], bool)
    missing = np.array([0, 0, 0, 0, 0, 1, 1, 0], np.int32)
    hist = np.zeros((8, bins, 3), np.float32)
    n = 10_000_000
    for f in range(8):
        share = rng.dirichlet(np.full(num_bin[f], 0.6))
        c = np.floor(share * n)
        c[0] += n - c.sum()
        rate = np.clip(0.2 + 0.08 * rng.standard_normal(num_bin[f]), 0.02, 0.9)
        hist[f, :num_bin[f], 0] = c * (0.2 - rate)
        hist[f, :num_bin[f], 1] = c * 0.16
        hist[f, :num_bin[f], 2] = c
    meta = split.FeatureMeta(
        num_bin=jnp.asarray(num_bin), missing_type=jnp.asarray(missing),
        default_bin=jnp.zeros(8, jnp.int32),
        is_trivial=jnp.zeros(8, bool), is_categorical=jnp.asarray(is_cat),
        penalty=jnp.ones(8, jnp.float32), monotone=jnp.zeros(8, jnp.int32))
    return jnp.asarray(hist), meta


def variants(meta):
    """{name: (patch for split._categorical_best or None, kwargs)}."""
    kw = dict(meta=meta, l1=0.0, l2=0.0, max_delta_step=0.0,
              min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3,
              min_gain_to_split=0.0)
    return {
        "numerical only": (None, dict(kw, with_categorical=False)),
        "scan over the bins (before PR 33)":
            (categorical_best_scan, dict(kw, with_categorical=True)),
        "unrolled walk (PR 33)":
            (split._categorical_best, dict(kw, with_categorical=True)),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import numpy as np
    if not args.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("a reading needs the chip (--cpu rehearses)")
    hist, meta = cell_inputs(args.bins)
    totals = jnp.sum(hist[0], axis=0)
    fmask = jnp.ones(8, bool)
    current = split._categorical_best
    out = {}
    for name, (search, kw) in variants(meta).items():
        # the search is looked up when the caller is traced
        split._categorical_best = search or current

        def one(hist, totals, fmask, kw=kw):
            return split.find_best_split(hist, totals[0], totals[1],
                                         totals[2], fmask, **kw)

        def tree(hist, totals, fmask, kw=kw):
            def body(i, acc):
                # another histogram a split: the left child keeps a share
                # of every bin that moves with i
                share = 0.3 + 0.4 * (i % 7) / 7.0
                pair = jnp.stack([hist * share, hist * (1.0 - share)])
                tot = jnp.stack([totals * share, totals * (1.0 - share)])
                r = split.find_best_split_batched(
                    pair, tot[:, 0], tot[:, 1], tot[:, 2], fmask, **kw)
                return acc + r.gain.sum() + r.cat_bitset.sum()
            return jax.lax.fori_loop(0, SPLITS_A_TREE, body, jnp.float32(0))

        ms = {}
        for what, fn in (("call", one), ("tree", tree)):
            f = jax.jit(fn)
            jax.block_until_ready(f(hist, totals, fmask))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(hist, totals, fmask))
                times.append(time.perf_counter() - t0)
            ms[what] = round(1e3 * float(np.median(times)), 4)
        split._categorical_best = current
        out[name] = ms
        print("%-36s %9.4f ms a call %10.3f ms a tree (%.1f us a child)"
              % (name, ms["call"], ms["tree"],
                 1e3 * ms["tree"] / (2 * SPLITS_A_TREE)), flush=True)
    report = {"bins": args.bins, "ms": out,
              "device": jax.devices()[0].device_kind}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "cat_search_race.json"),
              "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
