"""Best-split search over histograms as vectorized prefix scans.

Replaces the reference's per-feature threshold loops
(src/treelearner/feature_histogram.hpp: FindBestThresholdNumerical at :87-112,
FindBestThresholdSequence at :505-645, gain math ThresholdL1 /
CalculateSplittedLeafOutput / GetSplitGains at :442-503) with cumulative sums
and a single argmax over [features, directions, bins] — no per-feature control
flow, fully parallel on the VPU.

Semantics matched to the reference:
- two scan directions: dir=-1 routes missing left (default_left=True), dir=+1
  routes missing right; missing mass (NaN bin for MissingType::NaN, the
  zero/default bin for MissingType::Zero) is excluded from the scanned prefix
  so it always follows the default direction;
- for MissingType::None or num_bin<=2 only the dir=-1 scan runs
  (feature_histogram.hpp:99-106), with default_left forced off for the
  2-bin NaN case;
- candidate thresholds t ∈ [0, num_bin-2], skipping the default bin for
  MissingType::Zero;
- kEpsilon (1e-15) hessian seeding mirrors meta.h:38 so degenerate leaves
  divide safely;
- gain, L1 thresholding, max_delta_step clipping and min_gain_to_split follow
  the reference formulas exactly.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

#: the categorical search's operations carry this scope INSIDE the
#: grower's `lgbm.split_search` (grower2.PHASES); an operation's phase is
#: its innermost scope, so a trace reads the two apart
CAT_SEARCH_SCOPE = "lgbm.cat_search"


class FeatureMeta(NamedTuple):
    """Static per-feature arrays mirrored from the BinMappers
    (reference FeatureMetainfo, feature_histogram.hpp:15-26)."""
    num_bin: jax.Array       # [F] int32
    missing_type: jax.Array  # [F] int32
    default_bin: jax.Array   # [F] int32
    is_trivial: jax.Array    # [F] bool
    is_categorical: jax.Array  # [F] bool
    penalty: jax.Array       # [F] float32 feature_contrib penalty
    monotone: jax.Array      # [F] int32 in {-1, 0, +1}


class SplitResult(NamedTuple):
    gain: jax.Array          # scalar f32; -inf when no valid split
    feature: jax.Array       # scalar i32
    threshold_bin: jax.Array  # scalar i32
    default_left: jax.Array  # scalar bool
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_count: jax.Array    # f32
    is_cat: jax.Array        # scalar bool — categorical subset split
    cat_bitset: jax.Array    # [B] bool — bins routed left (categorical only)
    left_output: jax.Array   # child outputs computed with the split's own
    right_output: jax.Array  # regularization (cat_l2 for sorted-subset splits)



def pad_feature_meta(meta: "FeatureMeta", f_padded: int) -> "FeatureMeta":
    """Extend per-feature metadata with trivial (inert) entries for padded
    feature columns — shared by the feature- and data-parallel learners."""
    F = int(meta.num_bin.shape[0])
    pad = f_padded - F
    if pad <= 0:
        return meta

    def ext(a, fill):
        return jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])

    return FeatureMeta(
        num_bin=ext(meta.num_bin, 1),
        missing_type=ext(meta.missing_type, 0),
        default_bin=ext(meta.default_bin, 0),
        is_trivial=ext(meta.is_trivial, True),
        is_categorical=ext(meta.is_categorical, False),
        penalty=ext(meta.penalty, 1.0),
        monotone=ext(meta.monotone, 0),
    )

def dequantize_hist(hist: jax.Array, gscale, hscale) -> jax.Array:
    """f32 view of an integer quantized-gradient histogram.

    THE dequantize-at-the-boundary of the quantized training mode
    (`ops.quantize`): histograms accumulate int32 (exact, order-free —
    subtraction-trick siblings and cross-shard psums are bit-exact), and
    the f32 view is taken only here, immediately before the split search,
    so every gain formula below runs unchanged.  `hist` is [..., 3] with
    channels (sum_q_grad, sum_q_hess, count); gscale/hscale are the
    per-iteration per-class scale factors from `quantize.quantize_pair`
    (counts are never scaled)."""
    scale = jnp.stack([jnp.asarray(gscale, jnp.float32),
                       jnp.asarray(hscale, jnp.float32),
                       jnp.float32(1.0)])
    return hist.astype(jnp.float32) * scale


def threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_g, sum_h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:447-456)."""
    ret = -threshold_l1(sum_g, l1) / (sum_h + l2)
    if max_delta_step > 0.0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    return ret


def _leaf_split_gain(sum_g, sum_h, l1, l2, max_delta_step):
    """GetLeafSplitGain: gain of keeping (sum_g, sum_h) as one leaf."""
    out = leaf_output(sum_g, sum_h, l1, l2, max_delta_step)
    sg_l1 = threshold_l1(sum_g, l1)
    return -(2.0 * sg_l1 * out + (sum_h + l2) * out * out)


def _numerical_gain_tensor(g, h, c, sum_g, total_h, num_data, feature_mask, *,
                           meta, l1, l2, max_delta_step, min_data_in_leaf,
                           min_sum_hessian_in_leaf, min_gain_to_split,
                           apply_min_gain_filter: bool = True,
                           min_constraint=None, max_constraint=None):
    """Shifted+penalized numerical split gains [F, 2, B] (dir -1 first) plus
    the stacked left-side aggregates [F, 2, B] and min_gain_shift.  Shared by
    the global argmax (find_best_split) and the per-feature reduction used by
    the voting-parallel learner."""
    B = g.shape[1]
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]          # [1, B]
    nb = meta.num_bin[:, None]                               # [F, 1]
    valid_bin = bins < nb

    is_nan = (meta.missing_type == MISSING_NAN)[:, None]
    is_zero = (meta.missing_type == MISSING_ZERO)[:, None]
    two_scan = ((meta.num_bin > 2) & (meta.missing_type != MISSING_NONE))[:, None]

    # mass excluded from the scanned prefix: it follows the default direction
    excl = (is_nan & (bins == nb - 1)) | (is_zero & (bins == meta.default_bin[:, None]))
    excl = excl & two_scan  # the single-scan fallback scans everything

    gm = jnp.where(excl | ~valid_bin, 0.0, g)
    hm = jnp.where(excl | ~valid_bin, 0.0, h)
    cm = jnp.where(excl | ~valid_bin, 0.0, c)
    pg = jnp.cumsum(gm, axis=1)
    ph = jnp.cumsum(hm, axis=1)
    pc = jnp.cumsum(cm, axis=1)

    eps = K_EPSILON
    sum_g = jnp.asarray(sum_g)
    # dir = +1: left(t) = scanned prefix; missing mass implicitly right
    lg1, lh1, lc1 = pg, ph + eps, pc
    rg1, rh1, rc1 = sum_g - lg1, total_h - lh1, num_data - lc1
    # dir = -1: right(t) = scanned suffix; missing mass implicitly left
    sg_tot, sh_tot, sc_tot = pg[:, -1:], ph[:, -1:], pc[:, -1:]
    rg2, rh2, rc2 = sg_tot - pg, (sh_tot - ph) + eps, sc_tot - pc
    lg2, lh2, lc2 = sum_g - rg2, total_h - rh2, num_data - rc2

    # candidate thresholds: t <= num_bin-2, not the zero-skip bin, real feature
    tmask = (bins <= nb - 2) & valid_bin
    tmask &= ~(is_zero & (bins == meta.default_bin[:, None]) & two_scan)
    tmask &= (~meta.is_trivial & ~meta.is_categorical & feature_mask)[:, None]

    def direction(lg, lh, lc, rg, rh, rc, extra_mask):
        ok = (tmask & extra_mask
              & (lc >= min_data_in_leaf) & (rc >= min_data_in_leaf)
              & (lh >= min_sum_hessian_in_leaf) & (rh >= min_sum_hessian_in_leaf))
        lo = leaf_output(lg, lh, l1, l2, max_delta_step)
        ro = leaf_output(rg, rh, l1, l2, max_delta_step)
        if min_constraint is not None:
            # per-leaf value bounds (LeafSplits monotone constraints,
            # feature_histogram.hpp:478-489): candidate outputs are clamped
            # and the gain is evaluated AT the clamped outputs, which is
            # what makes monotonicity hold through whole subtrees
            lo = jnp.clip(lo, min_constraint, max_constraint)
            ro = jnp.clip(ro, min_constraint, max_constraint)
        mono = meta.monotone[:, None]
        mono_bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
        sgl = threshold_l1(lg, l1)
        sgr = threshold_l1(rg, l1)
        gain = -(2.0 * sgl * lo + (lh + l2) * lo * lo) \
               - (2.0 * sgr * ro + (rh + l2) * ro * ro)
        gain = jnp.where(mono_bad, 0.0, gain)
        return jnp.where(ok, gain, K_MIN_SCORE)

    gain_shift = _leaf_split_gain(sum_g, total_h, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split

    gain2 = direction(lg2, lh2, lc2, rg2, rh2, rc2, jnp.ones_like(tmask))  # dir -1 always runs
    gain1 = direction(lg1, lh1, lc1, rg1, rh1, rc1, two_scan)              # dir +1 only when two-scan
    gains = jnp.stack([gain2, gain1], axis=1)                              # [F, 2, B]; -1 first (tie-break)
    # shift by the no-split gain, then penalize (reference order:
    # FindBestThresholdNumerical subtracts, FindBestThreshold multiplies)
    if apply_min_gain_filter:
        gains = jnp.where(gains > min_gain_shift,
                          (gains - min_gain_shift) * meta.penalty[:, None, None],
                          K_MIN_SCORE)
    else:
        # forced-split path: constraint masks (already folded in as -inf)
        # still apply, but a below-min-gain split is NOT rejected
        gains = (gains - min_gain_shift) * meta.penalty[:, None, None]
    lgs = jnp.stack([lg2, lg1], axis=1)
    lhs = jnp.stack([lh2, lh1], axis=1)
    lcs = jnp.stack([lc2, lc1], axis=1)
    return gains, (lgs, lhs, lcs), min_gain_shift


def per_feature_best_gains(hist, sum_g, sum_h, num_data, feature_mask, *,
                           meta: FeatureMeta, l1, l2, max_delta_step,
                           min_data_in_leaf, min_sum_hessian_in_leaf,
                           min_gain_to_split, max_cat_threshold=32,
                           cat_l2=10.0, cat_smooth=10.0, max_cat_to_onehot=4,
                           min_data_per_group=100,
                           with_categorical: bool = False) -> jax.Array:
    """Best gain per feature [F] — the vote statistic of the voting-parallel
    learner (voting_parallel_tree_learner.cpp local FindBestSplits)."""
    g, h, c = hist[:, :, 0], hist[:, :, 1], hist[:, :, 2]
    total_h = sum_h + 2 * K_EPSILON
    gains, _, min_gain_shift = _numerical_gain_tensor(
        g, h, c, sum_g, total_h, num_data, feature_mask, meta=meta,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        min_gain_to_split=min_gain_to_split)
    best = jnp.max(gains, axis=(1, 2))
    if with_categorical:
        cat_mask = meta.is_categorical & ~meta.is_trivial & feature_mask
        raw_cat, _, _, _, _, _ = _categorical_best(
            g, h, c, sum_g, total_h, num_data, cat_mask, meta=meta,
            l1=l1, l2=l2, max_delta_step=max_delta_step,
            min_data_in_leaf=min_data_in_leaf,
            min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
            max_cat_threshold=max_cat_threshold, cat_l2=cat_l2,
            cat_smooth=cat_smooth, max_cat_to_onehot=max_cat_to_onehot,
            min_data_per_group=min_data_per_group)
        gain_cat = jnp.where(raw_cat > min_gain_shift,
                             (raw_cat - min_gain_shift) * meta.penalty,
                             K_MIN_SCORE)
        best = jnp.maximum(best, gain_cat)
    return best


@jax.named_scope(CAT_SEARCH_SCOPE)
def _categorical_best(g, h, c, sum_g, sum_h, num_data, cat_mask, *, meta,
                      l1, l2, max_delta_step, min_data_in_leaf,
                      min_sum_hessian_in_leaf, max_cat_threshold, cat_l2,
                      cat_smooth, max_cat_to_onehot, min_data_per_group):
    """Best categorical split per feature (FindBestThresholdCategorical,
    feature_histogram.hpp:112-273).

    One-hot mode (num_bin <= max_cat_to_onehot) scans single-bin lefts as one
    [F, B] vector op.  Sorted-subset mode sorts bins by sum_g/(sum_h +
    cat_smooth) and walks bounded prefixes from both ends.  The reference's
    sequential walk (min_data_per_group grouping, break-on-starved-right)
    visits at most `max_cat_threshold` sorted positions from either end
    whatever the bin count, so it is K = min(max_cat_threshold, B) unrolled
    steps on [2, F] vectors, both directions side by side: the histogram
    rides ONE stable sort as its payload, the walk from the top reads its K
    positions by a one-hot sum, and the winner's bins come back from the
    K positions it walked.  Nothing here loops, gathers or sorts a second
    time over the B bins.

    Returns per-feature (raw_gain [F], bitset [F, B], left_g, left_h(+eps),
    left_c, used_sorted [F] bool).
    """
    F, B = g.shape
    eps = K_EPSILON
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]
    # used_bin = num_bin - 1 + (missing_type == None) (feature_histogram.hpp:125-126)
    used_bin = (meta.num_bin - 1 +
                (meta.missing_type == MISSING_NONE).astype(jnp.int32))[:, None]
    valid_t = (bins < used_bin) & cat_mask[:, None]

    def pair_gain(lg, lh, rg, rh, l2_eff):
        return _leaf_split_gain(lg, lh, l1, l2_eff, max_delta_step) + \
               _leaf_split_gain(rg, rh, l1, l2_eff, max_delta_step)

    # ---- one-hot: left = single bin t ------------------------------------
    other_g = sum_g - g
    other_h = sum_h - h - eps
    other_c = num_data - c
    ok_oh = valid_t & (c >= min_data_in_leaf) & (h >= min_sum_hessian_in_leaf) \
        & (other_c >= min_data_in_leaf) & (other_h >= min_sum_hessian_in_leaf)
    gain_oh = jnp.where(ok_oh, pair_gain(g, h + eps, other_g, other_h, l2),
                        K_MIN_SCORE)
    t_oh = jnp.argmax(gain_oh, axis=1).astype(jnp.int32)          # [F]
    best_oh = jnp.take_along_axis(gain_oh, t_oh[:, None], 1)[:, 0]

    # ---- sorted subset ----------------------------------------------------
    K = min(int(max_cat_threshold), B)
    keep = valid_t & (c >= cat_smooth)
    ctr = jnp.where(keep, g / (h + cat_smooth), jnp.inf)
    # one stable sort by ctr; the bin ids and the histogram ride along
    _, order, gs, hs, cs = jax.lax.sort(
        (ctr, jnp.broadcast_to(bins, (F, B)), g, h, c), dimension=1,
        is_stable=True, num_keys=1)
    used = jnp.sum(keep, axis=1).astype(jnp.int32)                # [F]
    max_cat = jnp.minimum(max_cat_threshold, (used + 1) // 2)     # [F]
    l2s = l2 + cat_l2
    steps = jnp.arange(K, dtype=jnp.int32)
    # direction -1 walks the sorted bins from the top: step i reads sorted
    # position used-1-i.  A one-hot sum over B reads the K of them (exact:
    # every other term is a zero); steps past `used` read nothing the walk
    # adds (`stepping` below)
    top = (used[:, None] - 1 - steps[None, :])[:, :, None] == bins[None]

    def from_top(a):                                              # -> [F, K]
        return jnp.sum(jnp.where(top, a[:, None, :], jnp.zeros((), a.dtype)),
                       axis=2)

    # [K, 2, F]: a step's operands are one leading slice, +1 before -1
    gd, hd, cd = (jnp.stack([a[:, :K], from_top(a)]).transpose(2, 0, 1)
                  for a in (gs, hs, cs))
    order_d = jnp.stack([order[:, :K], from_top(order)])          # [2, F, K]

    lim = jnp.minimum(used, max_cat)[None, :]                     # [1, F]
    zero = jnp.zeros((2, F), jnp.float32)
    lg, lh, lc, grp = zero, jnp.full((2, F), eps, jnp.float32), zero, zero
    stopped = jnp.zeros((2, F), bool)
    bg = jnp.full((2, F), K_MIN_SCORE, jnp.float32)
    bi = jnp.full((2, F), -1, jnp.int32)
    blg, blh, blc = zero, zero, zero
    for i in range(K):
        stepping = i < lim
        lg = jnp.where(stepping, lg + gd[i], lg)
        lh = jnp.where(stepping, lh + hd[i], lh)
        lc = jnp.where(stepping, lc + cd[i], lc)
        grp = jnp.where(stepping, grp + cd[i], grp)
        cont1 = (lc < min_data_in_leaf) | (lh < min_sum_hessian_in_leaf)
        rc = num_data - lc
        rh = sum_h - lh
        brk = (rc < min_data_in_leaf) | (rc < min_data_per_group) | \
              (rh < min_sum_hessian_in_leaf)
        # break only evaluated when the left side qualifies (reference
        # `continue`s before the break checks, :205-212)
        candidate = stepping & ~stopped & ~cont1 & ~brk & \
            (grp >= min_data_per_group)
        stopped = stopped | (stepping & ~cont1 & brk)
        grp = jnp.where(candidate, 0.0, grp)
        gain_i = pair_gain(lg, lh, sum_g - lg, rh, l2s)
        take = candidate & (gain_i > bg)
        bg = jnp.where(take, gain_i, bg)
        bi = jnp.where(take, i, bi)
        blg = jnp.where(take, lg, blg)
        blh = jnp.where(take, lh, blh)
        blc = jnp.where(take, lc, blc)

    use2 = bg[1] > bg[0]

    def pick(a):
        return jnp.where(use2, a[1], a[0])

    bg_s, bi_s, blg_s, blh_s, blc_s = map(pick, (bg, bi, blg, blh, blc))
    # bitset: the bins at the first bi+1 steps of the winning direction
    walked = jnp.where(use2[:, None], order_d[1], order_d[0])     # [F, K]
    left_step = (steps[None, :] <= bi_s[:, None]) & \
        (steps[None, :] < used[:, None])
    bitset_s = jnp.any(left_step[:, :, None]
                       & (walked[:, :, None] == bins[None]), axis=1)

    # ---- choose one-hot vs sorted per feature ----------------------------
    use_onehot = (meta.num_bin <= max_cat_to_onehot)
    raw_gain = jnp.where(use_onehot, best_oh, bg_s)
    bitset = jnp.where(use_onehot[:, None], bins == t_oh[:, None], bitset_s)
    lg = jnp.where(use_onehot, jnp.take_along_axis(g, t_oh[:, None], 1)[:, 0], blg_s)
    lh = jnp.where(use_onehot,
                   jnp.take_along_axis(h, t_oh[:, None], 1)[:, 0] + eps, blh_s)
    lc = jnp.where(use_onehot, jnp.take_along_axis(c, t_oh[:, None], 1)[:, 0], blc_s)
    return raw_gain, bitset, lg, lh, lc, ~use_onehot


def find_best_split(hist, sum_g, sum_h, num_data, feature_mask, *,
                    meta: FeatureMeta, l1, l2, max_delta_step, min_data_in_leaf,
                    min_sum_hessian_in_leaf, min_gain_to_split,
                    max_cat_threshold=32, cat_l2=10.0, cat_smooth=10.0,
                    max_cat_to_onehot=4, min_data_per_group=100,
                    with_categorical: bool = False,
                    min_constraint=None, max_constraint=None) -> SplitResult:
    """Best split for one leaf given its histogram.

    hist: [F, B, 3] f32; sum_g/sum_h/num_data: leaf totals (scalars);
    feature_mask: [F] bool — feature_fraction sample for this tree.
    Regularization scalars are Python floats (static under jit).
    """
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    B = g.shape[1]
    eps = K_EPSILON
    total_h = sum_h + 2 * eps
    gains, (lgs, lhs, lcs), min_gain_shift = _numerical_gain_tensor(
        g, h, c, sum_g, total_h, num_data, feature_mask, meta=meta,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        min_gain_to_split=min_gain_to_split,
        min_constraint=min_constraint, max_constraint=max_constraint)

    flat = gains.reshape(-1)
    idx = jnp.argmax(flat)
    best_gain = flat[idx]
    f = idx // (2 * B)
    d = (idx // B) % 2
    t = idx % B

    # default_left = (dir == -1), except the 2-bin NaN fallback forces right
    force_right = (meta.num_bin[f] <= 2) & (meta.missing_type[f] == MISSING_NAN)
    default_left = (d == 0) & ~force_right

    left_g = lgs[f, d, t]
    left_h = lhs[f, d, t]  # includes the kEpsilon seed
    left_c = lcs[f, d, t]
    l2_eff = jnp.float32(l2)
    is_cat = jnp.bool_(False)
    cat_bitset = jnp.zeros(B, bool)

    if with_categorical:
        cat_mask = meta.is_categorical & ~meta.is_trivial & feature_mask
        raw_cat, bitset_cat, clg, clh, clc, sorted_mode = _categorical_best(
            g, h, c, sum_g, total_h, num_data, cat_mask, meta=meta,
            l1=l1, l2=l2, max_delta_step=max_delta_step,
            min_data_in_leaf=min_data_in_leaf,
            min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
            max_cat_threshold=max_cat_threshold, cat_l2=cat_l2,
            cat_smooth=cat_smooth, max_cat_to_onehot=max_cat_to_onehot,
            min_data_per_group=min_data_per_group)
        gain_cat = jnp.where(raw_cat > min_gain_shift,
                             (raw_cat - min_gain_shift) * meta.penalty,
                             K_MIN_SCORE)
        fc = jnp.argmax(gain_cat).astype(jnp.int32)
        best_cat = gain_cat[fc]
        cat_wins = best_cat > best_gain
        best_gain = jnp.where(cat_wins, best_cat, best_gain)
        f = jnp.where(cat_wins, fc, f)
        t = jnp.where(cat_wins, 0, t)
        default_left = jnp.where(cat_wins, False, default_left)
        left_g = jnp.where(cat_wins, clg[fc], left_g)
        left_h = jnp.where(cat_wins, clh[fc], left_h)
        left_c = jnp.where(cat_wins, clc[fc], left_c)
        is_cat = cat_wins
        cat_bitset = jnp.where(cat_wins, bitset_cat[fc], cat_bitset)
        # sorted-subset splits regularize child outputs with l2 + cat_l2
        l2_eff = jnp.where(cat_wins & sorted_mode[fc],
                           jnp.float32(l2 + cat_l2), l2_eff)

    right_g = sum_g - left_g
    right_h = total_h - left_h
    lo = leaf_output(left_g, left_h, l1, l2_eff, max_delta_step)
    ro = leaf_output(right_g, right_h, l1, l2_eff, max_delta_step)
    if min_constraint is not None:
        # numerical winners carry clamped outputs; categorical splits are
        # unclamped like the reference (feature_histogram.hpp:345-351)
        lo = jnp.where(is_cat, lo, jnp.clip(lo, min_constraint,
                                            max_constraint))
        ro = jnp.where(is_cat, ro, jnp.clip(ro, min_constraint,
                                            max_constraint))

    return SplitResult(
        gain=best_gain,
        feature=f.astype(jnp.int32),
        threshold_bin=t.astype(jnp.int32),
        default_left=default_left,
        left_sum_g=left_g, left_sum_h=left_h - eps, left_count=left_c,
        is_cat=is_cat, cat_bitset=cat_bitset,
        left_output=lo, right_output=ro)


def find_best_split_batched(hist, sum_g, sum_h, num_data, feature_mask, *,
                            meta: FeatureMeta, **kwargs) -> SplitResult:
    """`find_best_split` lifted to a LEAVES-LEADING axis.

    hist: [Q, F, B, 3] — one histogram per frontier child; sum_g / sum_h /
    num_data: [Q] leaf totals.  Returns a SplitResult whose every field
    carries the leading [Q] axis, so one XLA program replaces Q sequential
    scan+argmax programs (the frontier-batched grower's fused cross-leaf
    split search; the cross-leaf argmax itself happens over the per-leaf
    gains at commit time).

    Exactness contract: a row of the result is bit-identical to the same
    search run through this function at ANY other Q — which is why the
    sequential grower also routes its two-children evaluation through
    here (Q = 2) instead of calling `find_best_split` inline.  XLA
    compiles the gain arithmetic differently per surrounding program (fma
    contraction / duplicated-consumer fusion), and the resulting ~1e-5
    relative gain drift would break the frontier-batched grower's
    byte-identical-model guarantee; a `vmap` lift drifts the same way.
    Keeping every grower's search inside this one fori body is the
    measured fix: the body compiles identically at every Q, so the gains
    are the same bits everywhere (pinned by the byte-identity tests)."""
    fn = functools.partial(find_best_split, meta=meta, **kwargs)
    Q = hist.shape[0]
    B = hist.shape[2]
    out0 = SplitResult(
        gain=jnp.full(Q, K_MIN_SCORE, jnp.float32),
        feature=jnp.zeros(Q, jnp.int32),
        threshold_bin=jnp.zeros(Q, jnp.int32),
        default_left=jnp.zeros(Q, bool),
        left_sum_g=jnp.zeros(Q, jnp.float32),
        left_sum_h=jnp.zeros(Q, jnp.float32),
        left_count=jnp.zeros(Q, jnp.float32),
        is_cat=jnp.zeros(Q, bool),
        cat_bitset=jnp.zeros((Q, B), bool),
        left_output=jnp.zeros(Q, jnp.float32),
        right_output=jnp.zeros(Q, jnp.float32))

    def body(q, acc):
        r = fn(hist[q], sum_g[q], sum_h[q], num_data[q], feature_mask)
        return SplitResult(*[a.at[q].set(v) for a, v in zip(acc, r)])

    return jax.lax.fori_loop(0, Q, body, out0)


def evaluate_split_at(hist, sum_g, sum_h, num_data, feature, threshold_bin, *,
                      meta: FeatureMeta, l1, l2, max_delta_step,
                      min_data_in_leaf, min_sum_hessian_in_leaf,
                      min_constraint=None,
                      max_constraint=None) -> SplitResult:
    """SplitResult for a FORCED numerical split at (feature, threshold_bin).

    Role of the forced-split evaluation inside the reference's ForceSplits
    (serial_tree_learner.cpp:546-701): the threshold is imposed, but the
    missing-value default direction is still chosen by gain, and the
    min-data/min-hessian constraints still apply — an infeasible forced
    split comes back with gain = -inf so the caller can fall back to the
    leaf's gain-driven best.  feature/threshold_bin may be traced scalars.
    """
    f = jnp.asarray(feature, jnp.int32)
    t = jnp.asarray(threshold_bin, jnp.int32)
    B = hist.shape[1]
    eps = K_EPSILON
    total_h = sum_h + 2 * eps
    # slice everything down to the one forced feature before the scan —
    # this runs on every do_split when forcing is active, and the full
    # [F, 2, B] tensor would double the leaf's split-finding work
    hist_f = hist[f][None]                      # [1, B, 3]
    meta1 = FeatureMeta(*[a[f][None] for a in meta])
    gains, (lgs, lhs, lcs), _ = _numerical_gain_tensor(
        hist_f[:, :, 0], hist_f[:, :, 1], hist_f[:, :, 2], sum_g, total_h,
        num_data, jnp.ones(1, bool), meta=meta1,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        min_gain_to_split=0.0, apply_min_gain_filter=False,
        min_constraint=min_constraint, max_constraint=max_constraint)
    pair = gains[0, :, t]                       # [2] directions, -1 first
    d = jnp.argmax(pair)
    gain = pair[d]
    force_right = (meta1.num_bin[0] <= 2) & \
        (meta1.missing_type[0] == MISSING_NAN)
    default_left = (d == 0) & ~force_right
    left_g = lgs[0, d, t]
    left_h = lhs[0, d, t]
    left_c = lcs[0, d, t]
    right_g = sum_g - left_g
    right_h = total_h - left_h
    lo = leaf_output(left_g, left_h, l1, l2, max_delta_step)
    ro = leaf_output(right_g, right_h, l1, l2, max_delta_step)
    if min_constraint is not None:
        lo = jnp.clip(lo, min_constraint, max_constraint)
        ro = jnp.clip(ro, min_constraint, max_constraint)
    return SplitResult(
        gain=gain, feature=f, threshold_bin=t, default_left=default_left,
        left_sum_g=left_g, left_sum_h=left_h - eps, left_count=left_c,
        is_cat=jnp.bool_(False), cat_bitset=jnp.zeros(B, bool),
        left_output=lo, right_output=ro)
