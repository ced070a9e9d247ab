"""Leaf-wise (best-first) tree growth as one jitted fixed-trip-count loop.

Role parity with the reference SerialTreeLearner
(src/treelearner/serial_tree_learner.cpp: Train at :157-221, BeforeFindBestSplit
at :350-428, FindBestSplits at :430-445, Split at :703-777) redesigned for
XLA's compilation model:

- the leaf frontier is *data*, not control flow: a per-row leaf-id vector plus
  per-leaf state arrays sized [num_leaves], updated with masked scatters inside
  `lax.fori_loop` — no recompilation, no dynamic shapes;
- the reference's histogram-pool pointer juggling (feature_histogram.hpp:655+)
  becomes a dense [num_leaves, F, B, 3] histogram tensor in HBM;
- the one algorithmic trick that matters is preserved: per split, only the
  smaller child's histogram is built from rows; the sibling is parent - child
  (histogram subtraction, serial_tree_learner.cpp:475-544);
- rows excluded by bagging/padding carry zeroed (grad, hess, count) so they
  fall out of every sum while still being partitioned for score updates.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.bundle import decode_bin, expand_histogram
from ..ops.histogram import build_histogram
from ..ops.split import (FeatureMeta, K_MIN_SCORE, MISSING_NAN, MISSING_ZERO,
                         SplitResult, find_best_split,
                         find_best_split_batched, leaf_output,
                         pad_feature_meta, per_feature_best_gains)
from ..runtime import xla_obs


class GrowerConfig(NamedTuple):
    """Static scalars baked into the compiled grower."""
    num_leaves: int
    max_depth: int
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    row_chunk: int = 16384
    # categorical split knobs (feature_histogram.hpp:112-273)
    with_categorical: bool = False
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    # segment-engine implementation for the partitioned grower
    # (Config.tpu_histogram_impl): "auto" | "pallas" | "lax"
    hist_impl: str = "auto"
    # any feature carries a monotone constraint: per-leaf value bounds are
    # tracked and propagated through monotone splits (LeafSplits
    # min/max_constraint, serial_tree_learner.cpp:765-777)
    with_monotone: bool = False
    # histogram pool slots for the partitioned grower (reference
    # HistogramPool, feature_histogram.hpp:655-826, histogram_pool_size
    # param): 0 = one slot per leaf (unbounded); otherwise LRU-evicted
    # cache with recompute-on-miss over the leaf's row segment
    hist_pool_slots: int = 0
    # frontier-batch window (Config.tpu_frontier_batch): > 1 lets the
    # partitioned grower evaluate up to this many frontier leaves per
    # round (one batched histogram dispatch + one fused cross-leaf split
    # search) while committing splits in exact sequential argmax order —
    # byte-identical models, fewer sequential rounds per tree
    frontier_batch: int = 1


def propagate_monotone_bounds(blo, bro, is_num, mono_f, pmin, pmax):
    """Children's value bounds after a split (serial_tree_learner.cpp:
    765-777): inherit the parent's, and a numerical split on a monotone
    feature pins the shared boundary at the midpoint of the split outputs.
    Tightened (max/min), never replaced, so an out-of-bounds midpoint
    (possible for forced splits) cannot loosen a child's bounds."""
    mid = (blo + bro) * 0.5
    lmin = jnp.where(is_num & (mono_f < 0), jnp.maximum(mid, pmin), pmin)
    lmax = jnp.where(is_num & (mono_f > 0), jnp.minimum(mid, pmax), pmax)
    rmin = jnp.where(is_num & (mono_f > 0), jnp.maximum(mid, pmin), pmin)
    rmax = jnp.where(is_num & (mono_f < 0), jnp.minimum(mid, pmax), pmax)
    return lmin, lmax, rmin, rmax


def make_winner_sync(axis_name: str, my, f_offset):
    """SyncUpGlobalBestSplit (parallel_tree_learner.h:183-206): gain pmax +
    lowest-shard tie-break, then the whole SplitResult packed into ONE f32
    buffer for a single one-hot psum (the reference likewise ships a
    fixed-size SplitInfo blob).  Integer fields (feature, bin) are exact in
    f32 below 2^24.  Shared by the masked and partitioned mesh growers."""

    def bcast_from_winner(res):
        gain_max = lax.pmax(res.gain, axis_name)
        big = jnp.int32(1 << 30)
        winner = lax.pmin(jnp.where(res.gain == gain_max, my, big),
                          axis_name)
        is_w = my == winner
        payload = jnp.concatenate([
            jnp.stack([
                res.gain,
                (res.feature + f_offset).astype(jnp.float32),
                res.threshold_bin.astype(jnp.float32),
                res.default_left.astype(jnp.float32),
                res.left_sum_g, res.left_sum_h, res.left_count,
                res.is_cat.astype(jnp.float32),
                res.left_output, res.right_output,
            ]),
            res.cat_bitset.astype(jnp.float32)])
        payload = lax.psum(jnp.where(is_w, payload,
                                     jnp.zeros_like(payload)), axis_name)
        return SplitResult(
            gain=payload[0],
            feature=payload[1].astype(jnp.int32),
            threshold_bin=payload[2].astype(jnp.int32),
            default_left=payload[3] > 0,
            left_sum_g=payload[4],
            left_sum_h=payload[5],
            left_count=payload[6],
            is_cat=payload[7] > 0,
            cat_bitset=payload[10:] > 0,
            left_output=payload[8],
            right_output=payload[9])

    return bcast_from_winner


def make_tree_grower(meta: FeatureMeta, cfg: GrowerConfig, num_bins_max: int,
                     axis_name: str = None, jit: bool = True,
                     mode: str = "data", num_machines: int = 1,
                     top_k: int = 20, bundle_map=None, forced=None):
    """Returns grow(bins[F,N], vals[N,3], feature_mask[F]) -> tree arrays dict,
    jit-compiled once per (shape, config).

    axis_name: when set, the grower runs as a *parallel tree learner* inside
    shard_map over that mesh axis, in one of three modes mirroring the
    reference's parallel learners with XLA collectives in place of
    src/network/:

    - mode="data" (DataParallelTreeLearner, data_parallel_tree_learner.cpp:
      147-246): rows sharded, histograms `psum`ed over ICI, replicated split
      application.
    - mode="feature" (FeatureParallelTreeLearner, feature_parallel_tree_
      learner.cpp:21-69): features sharded, rows replicated; each shard finds
      the best split over its own features, the winner is chosen by a
      gain-keyed pmax/pmin pair (the SyncUpGlobalBestSplit allreduce-max) and
      its row partition is broadcast from the owning shard with one psum.
    - mode="voting" (VotingParallelTreeLearner, voting_parallel_tree_
      learner.cpp / PV-Tree): rows sharded but histograms stay LOCAL; each
      shard votes its top_k features by local split gain, the global top-2k
      vote winners' histograms alone are `psum`ed, and the best split is
      found on that subset — bounding the wire volume exactly like the
      reference's selective ReduceScatter.  Local vote constraints are
      scaled by 1/num_machines (:53-55).
    """
    L = cfg.num_leaves
    B = num_bins_max
    feature_mode = axis_name is not None and mode == "feature"
    voting_mode = axis_name is not None and mode == "voting"
    data_mode = axis_name is not None and mode == "data"
    bundled = bundle_map is not None
    assert not (bundled and axis_name is not None), \
        "EFB-bundled datasets train with the serial learner"
    assert not (forced is not None and axis_name is not None), \
        "forced splits run on the serial learners only"
    if forced is not None:
        from .forced import PRIORITY_UNIT, make_forced_machinery
        fc_lnext, fc_rnext, forced_override = \
            make_forced_machinery(forced, meta, cfg)
    # per-leaf bounds are replicated scalars every shard tracks identically
    # (all shards apply identical splits), so propagation runs on the
    # parallel learners too — each shard clamps its local candidates
    with_mono = cfg.with_monotone

    def hist_view(h):
        """[G, B, 3] bundle histogram -> [F, B, 3] split view (EFB)."""
        if not bundled:
            return h
        return expand_histogram(h, bundle_map, meta.num_bin,
                                meta.default_bin, B)

    find_kwargs = dict(
        l1=cfg.lambda_l1, l2=cfg.lambda_l2, max_delta_step=cfg.max_delta_step,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        min_gain_to_split=cfg.min_gain_to_split,
        max_cat_threshold=cfg.max_cat_threshold, cat_l2=cfg.cat_l2,
        cat_smooth=cfg.cat_smooth, max_cat_to_onehot=cfg.max_cat_to_onehot,
        min_data_per_group=cfg.min_data_per_group,
        with_categorical=cfg.with_categorical)
    find = functools.partial(find_best_split, meta=meta, **find_kwargs)

    out_fn = functools.partial(leaf_output, l1=cfg.lambda_l1, l2=cfg.lambda_l2,
                               max_delta_step=cfg.max_delta_step)

    _winner_sync = functools.partial(make_winner_sync, axis_name)

    def grow(bins: jax.Array, vals: jax.Array, feature_mask: jax.Array) -> Dict[str, jax.Array]:
        F, N = bins.shape

        reduce_hist = lambda h: h  # serial / feature / voting: local

        if feature_mode:
            my = lax.axis_index(axis_name)
            f_offset = my * F
            meta_local = FeatureMeta(*[lax.dynamic_slice_in_dim(a, f_offset, F)
                                       for a in meta])
            find_local = functools.partial(find_best_split, meta=meta_local,
                                           **find_kwargs)
            bcast_from_winner = _winner_sync(my, f_offset)

            def find_split(hist, sg, sh, cnt, fmask, **constraints):
                return bcast_from_winner(find_local(hist, sg, sh, cnt, fmask,
                                                    **constraints))

        elif data_mode:
            # DataParallelTreeLearner with the reference's actual wire
            # pattern (data_parallel_tree_learner.cpp:159-246): histograms
            # ReduceScatter over the feature axis so each shard owns F/n
            # features, split search runs only on owned features, and the
            # global winner is an allreduce-max of one SplitInfo blob —
            # psum_scatter + the shared winner sync, NOT a full psum with
            # replicated search.
            n = max(num_machines, 1)
            Fp = ((F + n - 1) // n) * n
            padf = Fp - F
            Floc = Fp // n
            if padf:
                bins_h = jnp.pad(bins, ((0, padf), (0, 0)))
                fmask_p = jnp.pad(feature_mask, (0, padf))
                meta_p = pad_feature_meta(meta, Fp)
            else:
                bins_h, fmask_p, meta_p = bins, feature_mask, meta
            my = lax.axis_index(axis_name)
            f_offset = my * Floc
            meta_local = FeatureMeta(
                *[lax.dynamic_slice_in_dim(a, f_offset, Floc)
                  for a in meta_p])
            find_local = functools.partial(find_best_split, meta=meta_local,
                                           **find_kwargs)
            bcast_from_winner = _winner_sync(my, f_offset)

            def reduce_hist(h):
                return lax.psum_scatter(h, axis_name, scatter_dimension=0,
                                        tiled=True)

            def find_split(hist_loc, sg, sh, cnt, fmask, **constraints):
                fmask_loc = lax.dynamic_slice_in_dim(fmask_p, f_offset, Floc)
                return bcast_from_winner(
                    find_local(hist_loc, sg, sh, cnt, fmask_loc,
                               **constraints))

        elif voting_mode:
            k_vote = min(top_k, F)
            S = min(2 * k_vote, F)
            vote_kwargs = dict(find_kwargs)
            vote_kwargs["min_data_in_leaf"] = cfg.min_data_in_leaf / max(num_machines, 1)
            vote_kwargs["min_sum_hessian_in_leaf"] = \
                cfg.min_sum_hessian_in_leaf / max(num_machines, 1)

            def find_split(hist_local, sg, sh, cnt, fmask, **constraints):
                # phase 1: vote top_k features by LOCAL split gain with
                # 1/num_machines-scaled constraints (:53-55, :322-342)
                local_tot = jnp.sum(hist_local[0], axis=0)
                local_gains = per_feature_best_gains(
                    hist_local, local_tot[0], local_tot[1], local_tot[2],
                    fmask, meta=meta, **vote_kwargs)
                top_vals, top_idx = lax.top_k(local_gains, k_vote)
                # a shard with no valid local split casts no votes (the
                # reference only votes splittable features)
                valid_vote = (top_vals > K_MIN_SCORE).astype(jnp.int32)
                all_top = lax.all_gather(top_idx, axis_name)
                all_valid = lax.all_gather(valid_vote, axis_name)
                votes = jnp.zeros(F, jnp.int32).at[all_top.reshape(-1)].add(
                    all_valid.reshape(-1))
                _, sel = lax.top_k(votes, S)
                # phase 2: reduce ONLY the winners' histograms, find on them
                hsel = lax.psum(hist_local[sel], axis_name)
                meta_sel = FeatureMeta(*[a[sel] for a in meta])
                res = find_best_split(hsel, sg, sh, cnt, fmask[sel],
                                      meta=meta_sel, **find_kwargs,
                                      **constraints)
                return res._replace(feature=sel[res.feature])

        else:
            def find_split(hist, sg, sh, cnt, fmask, **constraints):
                return find(hist_view(hist), sg, sh, cnt, fmask,
                            **constraints)

        if axis_name is None and not with_mono:
            # serial children evaluations run through the SAME stacked-fori
            # search as the partitioned growers (find_best_split_batched's
            # exactness note): the search compiles identically at every
            # batch size, so gains stay bit-comparable across engines
            def find_split2(hl, hr, lg, lh, lc, rg, rh, rc, fmask):
                hists = jnp.stack([hl, hr])
                if bundled:
                    hists = jax.vmap(hist_view)(hists)
                res2 = find_best_split_batched(
                    hists, jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                    jnp.stack([lc, rc]), fmask, meta=meta, **find_kwargs)
                return (jax.tree_util.tree_map(lambda a: a[0], res2),
                        jax.tree_util.tree_map(lambda a: a[1], res2))

        totals = jnp.sum(vals, axis=0)
        if axis_name and not feature_mode:
            totals = lax.psum(totals, axis_name)
        root_g, root_h, root_c = totals[0], totals[1], totals[2]
        hist_bins = bins_h if data_mode else bins   # padded F in data mode
        Fh = (bins_h.shape[0] // max(num_machines, 1)) if data_mode else F
        hist_root = reduce_hist(
            build_histogram(hist_bins, vals, num_bins=B,
                            row_chunk=cfg.row_chunk))
        if with_mono:
            res0 = find_split(hist_root, root_g, root_h, root_c,
                              feature_mask,
                              min_constraint=jnp.float32(-jnp.inf),
                              max_constraint=jnp.float32(jnp.inf))
        else:
            res0 = find_split(hist_root, root_g, root_h, root_c, feature_mask)

        real0 = res0.gain
        root_rank = jnp.int32(-1)
        if forced is not None:
            res0, real0, root_rank = forced_override(
                jnp.int32(0), hist_view(hist_root), root_g, root_h, root_c,
                res0)

        ni = max(L - 1, 1)
        leaf_id0 = jnp.zeros(N, jnp.int32)
        if axis_name and not feature_mode:
            # mark the per-row carry device-varying so shard_map's replication
            # checker tracks it correctly through the fori_loop (rows are
            # sharded; in feature mode rows are replicated instead)
            leaf_id0 = lax.pcast(leaf_id0, axis_name, to="varying")
        state = {
            "hist": jnp.zeros((L, Fh, B, 3), jnp.float32).at[0].set(hist_root),
            "leaf_id": leaf_id0,
            "sum_g": jnp.zeros(L, jnp.float32).at[0].set(root_g),
            "sum_h": jnp.zeros(L, jnp.float32).at[0].set(root_h),
            "cnt": jnp.zeros(L, jnp.float32).at[0].set(root_c),
            # value assigned to each leaf at creation (reference Tree keeps
            # leaf_value_, seeded 0 for the root, set by Split for children —
            # sorted-subset categorical children carry the cat_l2-regularized
            # output, so the value is bound at split time, not recomputed)
            "leaf_val": jnp.zeros(L, jnp.float32),
            "bgain": jnp.full(L, K_MIN_SCORE, jnp.float32).at[0].set(res0.gain),
            "bfeat": jnp.zeros(L, jnp.int32).at[0].set(res0.feature),
            "bbin": jnp.zeros(L, jnp.int32).at[0].set(res0.threshold_bin),
            "bdleft": jnp.zeros(L, jnp.bool_).at[0].set(res0.default_left),
            "blg": jnp.zeros(L, jnp.float32).at[0].set(res0.left_sum_g),
            "blh": jnp.zeros(L, jnp.float32).at[0].set(res0.left_sum_h),
            "blc": jnp.zeros(L, jnp.float32).at[0].set(res0.left_count),
            "bcat": jnp.zeros(L, jnp.bool_).at[0].set(res0.is_cat),
            "bbitset": jnp.zeros((L, B), jnp.bool_).at[0].set(res0.cat_bitset),
            "blo": jnp.zeros(L, jnp.float32).at[0].set(res0.left_output),
            "bro": jnp.zeros(L, jnp.float32).at[0].set(res0.right_output),
            "leaf_depth": jnp.zeros(L, jnp.int32),
            "leaf_parent": jnp.full(L, -1, jnp.int32),
            "split_feature": jnp.zeros(ni, jnp.int32),
            "split_bin": jnp.zeros(ni, jnp.int32),
            "split_gain": jnp.zeros(ni, jnp.float32),
            "default_left": jnp.zeros(ni, jnp.bool_),
            "split_is_cat": jnp.zeros(ni, jnp.bool_),
            "split_cat_bitset": jnp.zeros((ni, B), jnp.bool_),
            "left_child": jnp.zeros(ni, jnp.int32),
            "right_child": jnp.zeros(ni, jnp.int32),
            "internal_value": jnp.zeros(ni, jnp.float32),
            "internal_count": jnp.zeros(ni, jnp.float32),
            "num_leaves": jnp.int32(1),
            "done": jnp.bool_(False),
        }
        if forced is not None:
            state["fleaf"] = jnp.full(L, -1, jnp.int32).at[0].set(root_rank)
            state["breal"] = jnp.full(L, K_MIN_SCORE,
                                      jnp.float32).at[0].set(real0)
        if with_mono:
            state["mincon"] = jnp.full(L, -jnp.inf, jnp.float32)
            state["maxcon"] = jnp.full(L, jnp.inf, jnp.float32)

        def body(s, st):
            best_leaf = jnp.argmax(st["bgain"]).astype(jnp.int32)
            gain = st["bgain"][best_leaf]
            do = jnp.logical_and(~st["done"], gain > 0.0)
            node = s - 1

            f = st["bfeat"][best_leaf]
            t = st["bbin"][best_leaf]
            dl = st["bdleft"][best_leaf]
            cat = st["bcat"][best_leaf]
            bitset = st["bbitset"][best_leaf]

            # -- partition rows of the split leaf (DataPartition::Split /
            #    Bin::Split[Categorical], dense_bin.hpp:190-283) -------------
            if feature_mode:
                # only the shard owning the winning feature has its bin
                # column; it computes the row routing and broadcasts it (the
                # reference needs no exchange because every rank holds full
                # data — here the one-psum broadcast replaces that copy)
                owner = (f // F) == my
                f_loc = jnp.clip(f - f_offset, 0, F - 1)
                fbin = bins[f_loc].astype(jnp.int32)
            elif bundled:
                raw = bins[bundle_map.f_group[f]]
                fbin = decode_bin(raw, bundle_map.f_identity[f],
                                  bundle_map.f_offset[f], meta.num_bin[f],
                                  meta.default_bin[f])
            else:
                fbin = bins[f].astype(jnp.int32)
            mt = meta.missing_type[f]
            is_missing_bin = ((mt == MISSING_NAN) & (fbin == meta.num_bin[f] - 1)) | \
                             ((mt == MISSING_ZERO) & (fbin == meta.default_bin[f]))
            go_left_num = jnp.where(is_missing_bin, dl, fbin <= t)
            go_left = jnp.where(cat, bitset[fbin], go_left_num)
            if feature_mode:
                go_left = lax.psum(jnp.where(owner, go_left.astype(jnp.int32), 0),
                                   axis_name) > 0
            in_leaf = st["leaf_id"] == best_leaf
            leaf_id = jnp.where(do & in_leaf & ~go_left, s, st["leaf_id"])

            # -- child aggregates: left from the stored split, right by diff --
            lg, lh, lcnt = st["blg"][best_leaf], st["blh"][best_leaf], st["blc"][best_leaf]
            pg, ph, pc = st["sum_g"][best_leaf], st["sum_h"][best_leaf], st["cnt"][best_leaf]
            rg, rh, rcnt = pg - lg, ph - lh, pc - lcnt

            # -- histograms: build only the smaller child, subtract for sibling
            left_smaller = lcnt <= rcnt
            small_slot = jnp.where(left_smaller, best_leaf, s)
            mask = ((leaf_id == small_slot) & do).astype(jnp.float32)
            hist_small = reduce_hist(
                build_histogram(hist_bins, vals * mask[:, None],
                                num_bins=B, row_chunk=cfg.row_chunk))
            hist_parent = st["hist"][best_leaf]
            hist_big = hist_parent - hist_small
            new_left = jnp.where(left_smaller, hist_small, hist_big)
            new_right = jnp.where(left_smaller, hist_big, hist_small)
            hist = st["hist"]
            hist = hist.at[best_leaf].set(jnp.where(do, new_left, hist_parent))
            hist = hist.at[s].set(jnp.where(do, new_right, hist[s]))

            # -- best splits of the two children ------------------------------
            child_depth = st["leaf_depth"][best_leaf] + 1
            if with_mono:
                lmin, lmax, rmin, rmax = propagate_monotone_bounds(
                    st["blo"][best_leaf], st["bro"][best_leaf], ~cat,
                    meta.monotone[f], st["mincon"][best_leaf],
                    st["maxcon"][best_leaf])
                res_l = find_split(new_left, lg, lh, lcnt, feature_mask,
                                   min_constraint=lmin, max_constraint=lmax)
                res_r = find_split(new_right, rg, rh, rcnt, feature_mask,
                                   min_constraint=rmin, max_constraint=rmax)
            elif axis_name is None:
                lmin = lmax = rmin = rmax = None
                res_l, res_r = find_split2(new_left, new_right, lg, lh,
                                           lcnt, rg, rh, rcnt, feature_mask)
            else:
                lmin = lmax = rmin = rmax = None
                res_l = find_split(new_left, lg, lh, lcnt, feature_mask)
                res_r = find_split(new_right, rg, rh, rcnt, feature_mask)
            real_l, real_r = res_l.gain, res_r.gain
            if forced is not None:
                jp = st["fleaf"][best_leaf]
                applied = (jp >= 0) & \
                    (st["bgain"][best_leaf] >= 0.5 * PRIORITY_UNIT)
                jp0 = jnp.maximum(jp, 0)
                jl = jnp.where(applied, fc_lnext[jp0], -1)
                jr = jnp.where(applied, fc_rnext[jp0], -1)
                res_l, real_l, jl = forced_override(
                    jl, hist_view(new_left), lg, lh, lcnt, res_l,
                    min_constraint=lmin, max_constraint=lmax)
                res_r, real_r, jr = forced_override(
                    jr, hist_view(new_right), rg, rh, rcnt, res_r,
                    min_constraint=rmin, max_constraint=rmax)
            if cfg.max_depth > 0:
                depth_ok = child_depth < cfg.max_depth
            else:
                depth_ok = jnp.bool_(True)
            gain_l = jnp.where(depth_ok, res_l.gain, K_MIN_SCORE)
            gain_r = jnp.where(depth_ok, res_r.gain, K_MIN_SCORE)

            def set2(arr, vl, vr):
                arr = arr.at[best_leaf].set(jnp.where(do, vl, arr[best_leaf]))
                return arr.at[s].set(jnp.where(do, vr, arr[s]))

            st_new = dict(st)
            st_new["hist"] = hist
            st_new["leaf_id"] = leaf_id
            st_new["sum_g"] = set2(st["sum_g"], lg, rg)
            st_new["sum_h"] = set2(st["sum_h"], lh, rh)
            st_new["cnt"] = set2(st["cnt"], lcnt, rcnt)
            st_new["bgain"] = set2(st["bgain"], gain_l, gain_r)
            st_new["bfeat"] = set2(st["bfeat"], res_l.feature, res_r.feature)
            st_new["bbin"] = set2(st["bbin"], res_l.threshold_bin, res_r.threshold_bin)
            st_new["bdleft"] = set2(st["bdleft"], res_l.default_left, res_r.default_left)
            st_new["blg"] = set2(st["blg"], res_l.left_sum_g, res_r.left_sum_g)
            st_new["blh"] = set2(st["blh"], res_l.left_sum_h, res_r.left_sum_h)
            st_new["blc"] = set2(st["blc"], res_l.left_count, res_r.left_count)
            st_new["bcat"] = set2(st["bcat"], res_l.is_cat, res_r.is_cat)
            bs = st["bbitset"]
            bs = bs.at[best_leaf].set(jnp.where(do, res_l.cat_bitset, bs[best_leaf]))
            st_new["bbitset"] = bs.at[s].set(jnp.where(do, res_r.cat_bitset, bs[s]))
            st_new["blo"] = set2(st["blo"], res_l.left_output, res_r.left_output)
            st_new["bro"] = set2(st["bro"], res_l.right_output, res_r.right_output)
            # children take the value their creating split computed
            st_new["leaf_val"] = set2(st["leaf_val"], st["blo"][best_leaf],
                                      st["bro"][best_leaf])
            st_new["leaf_depth"] = set2(st["leaf_depth"], child_depth, child_depth)
            if forced is not None:
                st_new["fleaf"] = set2(st["fleaf"], jl, jr)
                st_new["breal"] = set2(st["breal"], real_l, real_r)
            if with_mono:
                st_new["mincon"] = set2(st["mincon"], lmin, rmin)
                st_new["maxcon"] = set2(st["maxcon"], lmax, rmax)

            # -- record the internal node (Tree::Split, tree.h:404-448) -------
            def setn(arr, v):
                return arr.at[node].set(jnp.where(do, v, arr[node]))

            gain_rec = st["breal"][best_leaf] if forced is not None else gain
            st_new["split_feature"] = setn(st["split_feature"], f)
            st_new["split_bin"] = setn(st["split_bin"], t)
            st_new["split_gain"] = setn(st["split_gain"], gain_rec)
            st_new["default_left"] = setn(st["default_left"], dl)
            st_new["split_is_cat"] = setn(st["split_is_cat"], cat)
            st_new["split_cat_bitset"] = st["split_cat_bitset"].at[node].set(
                jnp.where(do, bitset, st["split_cat_bitset"][node]))
            # internal_value = the split leaf's creation value (tree.cpp:419)
            st_new["internal_value"] = setn(st["internal_value"],
                                            st["leaf_val"][best_leaf])
            st_new["internal_count"] = setn(st["internal_count"], pc)
            left_child = setn(st["left_child"], ~best_leaf)
            right_child = setn(st["right_child"], ~s)
            # re-point the grandparent's child slot from ~best_leaf to node
            parent_node = st["leaf_parent"][best_leaf]
            has_par = (parent_node >= 0) & do
            pn = jnp.maximum(parent_node, 0)
            was_left = left_child[pn] == ~best_leaf
            left_child = left_child.at[pn].set(
                jnp.where(has_par & was_left, node, left_child[pn]))
            right_child = right_child.at[pn].set(
                jnp.where(has_par & ~was_left, node, right_child[pn]))
            st_new["left_child"] = left_child
            st_new["right_child"] = right_child
            st_new["leaf_parent"] = set2(st["leaf_parent"], node, node)

            st_new["num_leaves"] = st["num_leaves"] + do.astype(jnp.int32)
            st_new["done"] = st["done"] | (gain <= 0.0)
            return st_new

        st = lax.fori_loop(1, L, body, state) if L > 1 else state

        # leaves keep the value bound at their creating split; an unsplit root
        # (stump) falls back to its own Newton step
        leaf_value = jnp.where(
            (jnp.arange(L) == 0) & (st["num_leaves"] == 1),
            out_fn(st["sum_g"], st["sum_h"]), st["leaf_val"])
        return {
            "num_leaves": st["num_leaves"],
            "leaf_id": st["leaf_id"],
            "leaf_value": leaf_value,
            "leaf_count": st["cnt"],
            "leaf_sum_g": st["sum_g"],
            "leaf_sum_h": st["sum_h"],
            "split_feature": st["split_feature"],
            "split_bin": st["split_bin"],
            "split_gain": st["split_gain"],
            "default_left": st["default_left"],
            "split_is_cat": st["split_is_cat"],
            "split_cat_bitset": st["split_cat_bitset"],
            "left_child": st["left_child"],
            "right_child": st["right_child"],
            "internal_value": st["internal_value"],
            "internal_count": st["internal_count"],
        }

    return xla_obs.jit(grow, site="grower.serial") if jit else grow
