"""Partitioned leaf-wise tree grower — O(rows-touched) histogram work.

Same split semantics as `grower.make_tree_grower` (reference
SerialTreeLearner, src/treelearner/serial_tree_learner.cpp:157-221) but with
the reference's actual cost model restored: rows of every leaf are kept
physically contiguous in a payload matrix (DataPartition,
src/treelearner/data_partition.hpp), each split stably partitions only the
split leaf's rows, and only the smaller child's histogram is built from rows
(serial_tree_learner.cpp:447-544) — the sibling comes from subtraction.

Histogram + partition run on the segment engine (`ops.segment`), whose TPU
hot paths are Pallas kernels; everything here is shape-static and jitted
once per (shape, config).

Differences from the masked grower (grower.py):
- no per-row leaf-id vector; leaf locations are (start, count) segments;
- the payload is both input and output: the caller owns extra columns
  (label / weight / scores) that ride along through every partition, so
  training state can stay partition-ordered across trees;
- per-row leaf outputs are written into a payload column at split time,
  making the score update an elementwise add instead of a gather.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime import xla_obs
from ..utils.log import Log

from ..ops.bundle import BundleMap, expand_histogram, identity_bundle_map
from ..ops.split import (FeatureMeta, K_MIN_SCORE, MISSING_NAN, MISSING_NONE,
                         SplitResult, dequantize_hist, find_best_split,
                         find_best_split_batched, leaf_output,
                         pad_feature_meta, per_feature_best_gains)
from ..ops import segment as seg
from ..ops.segment import SplitPredicate
from .forced import PRIORITY_UNIT, ForcedSchedule
from .grower import GrowerConfig, make_winner_sync


#: the phases of the fused step (`gbdt.step`: gradients -> grow -> score
#: add), by which its device time is told apart in a profiler trace.
#: `grad_pairs` and `grad_permute` are entered inside `grad` by an
#: objective that couples rows (objective/rank.py): the per-query
#: pairwise program, and every move between partition order, original
#: order and query slots.  `cat_search` is entered inside `split_search`
#: by ops/split.py (`CAT_SEARCH_SCOPE`) round the categorical search
PHASES = ("grad", "root_hist", "partition", "hist", "subtract",
          "split_search", "tree_update", "score", "allreduce",
          "grad_pairs", "grad_permute", "cat_search")


def phase(name: str):
    """`jax.named_scope("lgbm.<name>")` round one phase of the fused
    step.  Trace-time only: it goes into the `op_name` metadata of the
    operations traced inside (what a profiler trace calls `tf_op`),
    changes no instruction's name and costs nothing at run time.
    Scopes nest and the innermost names the phase.  They go ROUND a
    Pallas call, never into its `name=`: the trace names the kernel's
    custom call after its jitted wrapper and the benchmark matches
    that."""
    if name not in PHASES:
        raise ValueError("no phase %r of the fused step (has: %s)"
                         % (name, ", ".join(PHASES)))
    return jax.named_scope("lgbm." + name)


def partition_engine(hist_impl: str, payload_width: int,
                     num_bins: int) -> str:
    """The partition implementation for a [N, payload_width] payload,
    chosen from the platform and the shape: the accumulator kernel in one
    pass where its VMEM plan fits (to 512 lanes); past that, for a
    lane-padded payload, the accumulator kernel a 512-lane column block
    at a time; else the portable lax partition.  The read-modify-write
    kernel (`pallas_segment.partition_segment`) is chosen for no shape
    since the band of 640 to 1,664 lanes, where its plan and the block
    plan both fit, was raced on the chip (PERF.md section 6, PR 37): the
    block kernel takes 25 to 51 ns a row there where the read-modify-write
    kernel takes 157 to 255, 0.21 s a tree against 1.57 at the Bosch
    cell's 1,024 lanes, and at 1,664 lanes Mosaic refuses the
    read-modify-write kernel for want of VMEM though its plan admits it.
    Gated separately from the histogram: the partition is exact at any
    bin count but spans the full payload width, so a wide payload can
    overflow it while the histogram kernel still fits."""
    if hist_impl == "lax" or jax.default_backend() != "tpu":
        return "lax"
    from ..ops import pallas_segment as pseg
    if pseg.partition_acc_fits_vmem(payload_width, num_bins):
        return "pallas-acc"
    if (payload_width % 128 == 0
            and pseg.partition_blocks_fits_vmem(payload_width, num_bins)):
        return "pallas-blocks"
    return "lax"


#: a tree's row counters (`rows_partitioned`, `rows_staged`,
#: `rows_missing`) are sums of
#: up to num_leaves - 1 segment lengths: past int32 on a deep tree over
#: 10^7 rows, and the tree's fetch carries float32, exact to 2^24.  They
#: ride as [2] int32, (count >> WIDE_BITS, count & (2^WIDE_BITS - 1)),
#: a pair a device block on a mesh; `wide_count` sums them on the host.
WIDE_BITS = 20


def _wide_add(acc, n):
    """acc + n for a (high, low) pair and an int32 n."""
    low = acc[1] + n
    return jnp.stack([acc[0] + (low >> WIDE_BITS),
                      low & ((1 << WIDE_BITS) - 1)])


def wide_count(pairs) -> int:
    """The Python int a fetched row counter holds: its (high, low) pairs,
    one a device block, summed."""
    values = [int(v) for v in pairs]
    return (sum(values[0::2]) << WIDE_BITS) + sum(values[1::2])


class PayloadCols(NamedTuple):
    """Static column indices of the value columns inside the payload
    (bin columns occupy [0, F))."""
    grad: int
    hess: int
    cnt: int       # 0/1 count-mask (valid & bagged)
    value: int     # per-row current-tree leaf output


#: grower-output fields forming the DEVICE HALF of a finished tree —
#: everything a bin-level traversal replay (gbdt._traverse_update: valid
#: scores, DART/RF replay, rollback) consumes.  The fused boosting
#: window slices these [j, k] planes out of its stacked [J, K, ...]
#: record emission, so the tuple is the gbdt<->grower2 contract for
#: scan-composed growth: grow() is pure and shape-static (jit=False
#: composes under lax.scan through the __wrapped__ seam), and every
#: field below must stay present in the returned tree dict.
TREE_DEVICE_FIELDS = ("split_feature", "split_bin", "default_left",
                      "split_is_cat", "split_cat_bitset", "left_child",
                      "right_child")


def make_partitioned_grower(meta: FeatureMeta, cfg: GrowerConfig,
                            num_bins_max: int, cols: PayloadCols,
                            num_features: int, jit: bool = True,
                            bundle_map: BundleMap = None,
                            num_columns: int = None,
                            forced: ForcedSchedule = None,
                            axis_name: str = None, mode: str = "data",
                            num_machines: int = 1, top_k: int = 20,
                            payload_width: int = None,
                            quantized: bool = False, qmax: int = 0):
    """Returns grow(payload, aux, feature_mask[, qscale]) ->
    (tree arrays dict, payload, aux).

    payload/aux: [N_pad + GUARD, P] f32 with a GUARD-row tail whose
    count-mask is 0.  Valid rows are [0, N_pad); the root segment covers all
    of them regardless of the ordering left behind by previous trees.

    With EFB (bundle_map set), the payload holds num_columns < num_features
    bundled bin columns; histograms are built bundled (state stays [L, G,
    B, 3] — the memory win) and expanded to per-feature views only for
    split finding.

    axis_name: when set, the grower is one shard of a row-sharded parallel
    tree learner inside shard_map over that mesh axis — the reference's
    DataParallel / VotingParallel learners ARE its serial learner plus a
    network boundary (data_parallel_tree_learner.cpp:147-246 inherits
    SerialTreeLearner), and this grower keeps the same shape: per-device
    payload segments partition locally, and only histograms cross the wire:

    - mode="data": local per-leaf histograms are ReduceScattered over the
      storage-column axis (`psum_scatter`), split search runs on owned
      columns only, and one SyncUpGlobalBestSplit allreduce broadcasts the
      winner (data_parallel_tree_learner.cpp:159-246).  When the dataset is
      EFB-bundled or forced splits are active, the learner switches to a
      full `psum` with replicated search: bundling already shrank G (so the
      full blob is small on the wire) and both features need the whole
      histogram on every shard.
    - mode="voting": histograms stay local; shards vote top_k features by
      local gain, only the vote winners' histograms are `psum`ed (PV-Tree,
      voting_parallel_tree_learner.cpp), constraints scaled 1/num_machines.
    - mode="feature": FULL rows per shard with the payload's storage
      columns permuted OWNED-FIRST (shard r's columns [r*Gloc, (r+1)*Gloc)
      lead its payload); histograms/search cover only the owned leading
      columns — the O(rows-touched) cost model with 1/n of the column
      work — the winner crosses the wire as one SyncUpGlobalBestSplit
      blob, and each shard partitions its full rows locally with the
      winner's column translated into its own layout.  This mirrors
      FeatureParallelTreeLearner (feature_parallel_tree_learner.cpp:21-69:
      full data per rank, feature-sliced search, no row movement).
      Unbundled/unforced only; the caller builds the permuted payload.

    quantized (gradient_quantization mode, ops.quantize): the payload's
    grad/hess columns hold integer-valued quantized gradients with grid
    half-range `qmax`; histograms accumulate int32 (exact — subtraction
    siblings and cross-shard psum/psum_scatter are bit-exact and every
    engine agrees to the bit), and `grow` takes a fourth argument, the
    [2] f32 (gradient, hessian) scale vector, dequantizing with
    `ops.split.dequantize_hist` exactly at the split-search boundary so
    the gain arithmetic is the f32 code unchanged.  Serial + mesh modes;
    forced splits are f32-only.
    """
    L = cfg.num_leaves
    B = num_bins_max
    F = num_features
    G = num_columns if num_columns is not None else F
    bundled = bundle_map is not None
    bmap = bundle_map if bundled else identity_bundle_map(F)
    meshed = axis_name is not None
    # full-psum + replicated search when scatter/vote can't see whole
    # features (EFB) or need the whole histogram everywhere (forced splits)
    replicated = meshed and (bundled or forced is not None)
    scatter_mode = meshed and not replicated and mode == "data"
    voting_mode = meshed and not replicated and mode == "voting"
    feature_mode = meshed and mode == "feature"
    if meshed:
        assert mode in ("data", "voting", "feature"), \
            "partitioned mesh grower supports data|voting|feature"
    if feature_mode:
        # feature-parallel keeps full rows per shard with an OWNED-FIRST
        # column permutation (the caller lays the payload out that way),
        # so the histogram walk covers only the shard's own columns; EFB
        # and forced splits need whole-histogram views and stay on the
        # replicated/legacy paths (gbdt falls back before reaching here)
        assert not bundled and forced is None, \
            "feature-parallel partitioned engine is unbundled/unforced only"
    n_mach = max(num_machines, 1)
    if scatter_mode or feature_mode:
        Gp = -(-G // n_mach) * n_mach
        padg = Gp - G
        Gloc = Gp // n_mach
    # width of a pooled histogram: the owned slice in data/feature mode,
    # the full (local or replicated) blob otherwise
    Gh = Gloc if (scatter_mode or feature_mode) else G

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

    # feature mode's payload columns are permuted owned-first, so the
    # histogram (and its engine/VMEM-fit choice) covers Gloc columns only
    Ghist = Gloc if feature_mode else G
    hist_kwargs = dict(num_features=Ghist, num_bins=B, grad_col=cols.grad,
                       hess_col=cols.hess, cnt_col=cols.cnt)
    if quantized:
        # f32-only machinery stays off the quantized path: forced splits
        # read raw f32 hist views in their override (gbdt gates
        # eligibility before building a quantized grower, so these are
        # invariants)
        assert forced is None, "quantized grower is unforced-only"
        assert qmax >= 2, "quantized grower needs the derive_qmax grid"
    # the real payload width reaches the VMEM gate: the kernel DMAs full
    # rows even when it histograms only the owned leading columns
    # (feature-parallel), so the num_features-based estimate under-budgeted
    # exactly where Ghist << payload_width
    hist_engine = seg.resolve_impl(cfg.hist_impl, Ghist, B, payload_width)
    if quantized:
        # the Pallas kernel sums f32 parts: int32 histograms of quantized
        # gradients, like widths past its VMEM plan, take the lax engine
        hist_engine = "lax"
    if hist_engine == "pallas":
        from ..ops import pallas_segment as pseg
        hist_fn = functools.partial(pseg.segment_histogram, **hist_kwargs)
    else:
        hist_fn = functools.partial(seg.segment_histogram,
                                    quantized=quantized, **hist_kwargs)

    def part_fn(payload, aux, start, count, pred, lv, rv, right_first):
        # the width is static at trace time; a caller that did not pass
        # payload_width gets its engine resolved (and reported) here
        part = partition_engine(cfg.hist_impl, payload.shape[1], B)
        engines["partition"] = part
        if part == "lax":
            return seg.partition_segment(payload, aux, start, count, pred,
                                         lv, rv, cols.value, right_first)
        from ..ops import pallas_segment as pseg
        # ("pallas-rmw": no shape resolves to it; the scripts under exp/
        # that race it name it themselves)
        kernel = {"pallas-acc": pseg.partition_segment_acc,
                  "pallas-rmw": pseg.partition_segment,
                  "pallas-blocks": pseg.partition_segment_acc_blocks}[part]
        return kernel(payload, aux, start, count, pred, lv, rv, cols.value, B,
                      right_first)

    #: what this build resolved, readable as ``grower.engines`` — the
    #: choice is made from platform and shape, never invisibly
    engines = {"histogram": hist_engine,
               "partition": (partition_engine(cfg.hist_impl, payload_width, B)
                             if payload_width is not None else None)}
    Log.info("partitioned grower engines: histogram=%s partition=%s "
             "(%d columns x %d bins, payload width %s)",
             engines["histogram"], engines["partition"] or "at first trace",
             Ghist, B, payload_width)

    def hist_view(hist_g):
        """[G, B, 3] bundle histogram -> [F, B, 3] per-feature split view."""
        if not bundled:
            return hist_g
        return expand_histogram(hist_g, bmap, meta.num_bin, meta.default_bin,
                                B)

    def missing_rows(hist_leaf, f, column):
        """Rows of a leaf that its split on feature `f` routes by the
        default direction and not by the threshold: the count channel of
        the leaf's own histogram at the feature's missing bin (the NaN bin
        or, with zero as missing, the default bin; none where the mapper
        has neither; a categorical split routes by its set alone and
        counts none), so in-bag rows under bagging.  `column` is where the
        feature's storage column lies in `hist_leaf`; a bundle's default
        bin is its total less the member's own bins, as
        `expand_histogram` has it.  int32."""
        kind, nb, d = (meta.missing_type[f], meta.num_bin[f],
                       meta.default_bin[f])
        m = jnp.where(kind == MISSING_NAN, nb - 1, d)
        if not bundled:
            at = hist_leaf[column, m, 2].astype(jnp.float32)
        else:
            counts = hist_leaf[column, :, 2].astype(jnp.float32)
            off = bmap.f_offset[f]
            b = jnp.arange(counts.shape[0], dtype=jnp.int32)
            own = jnp.sum(jnp.where((b >= off) & (b < off + nb - 1), counts,
                                    0.0))
            stored = jnp.where(bmap.f_identity[f], m, off + m - (m > d))
            at = counts[jnp.clip(stored, 0, counts.shape[0] - 1)]
            at = jnp.where(bmap.f_identity[f] | (m != d), at,
                           jnp.sum(counts) - own)
        return jnp.where(kind != MISSING_NONE, jnp.round(at),
                         0.0).astype(jnp.int32)

    # histogram pool (reference HistogramPool, feature_histogram.hpp:655-826):
    # POOL < L caches per-leaf histograms with LRU eviction; a split whose
    # parent was evicted recomputes it by walking the (still contiguous)
    # parent segment — cheap under the O(rows-touched) engine
    POOL = cfg.hist_pool_slots if 0 < cfg.hist_pool_slots < L else L
    pooled = POOL < L
    assert POOL >= 2, "histogram pool needs at least 2 slots"

    # ---- frontier batching (Config.tpu_frontier_batch > 1) --------------
    # A gain-ordered window of up to K frontier leaves is EVALUATED per
    # round (K partitions of disjoint segments, ONE batched histogram
    # dispatch for the K smaller children, ONE fused cross-leaf split
    # search over the 2K children), then splits COMMIT by replaying the
    # sequential grower's argmax order against the cached evaluations — a
    # pop outside the evaluated window ends the round.  Leaf-wise
    # semantics are exact (byte-identical models): splitting one leaf
    # never changes another frontier leaf's rows, histogram or best split
    # (segments are disjoint and the partition is stable), so an
    # evaluation is the same bits whenever it runs, and the commit replay
    # IS the sequential order.  Serial unforced/unpooled/non-monotone
    # configs only; everything else keeps the sequential loop.
    fb_req = max(int(getattr(cfg, "frontier_batch", 1) or 1), 1)
    # every serial unforced non-monotone grower evaluates children through
    # the SAME stacked-fori search (find_best_split_batched), whatever its
    # window size — XLA compiles a find embedded directly in the do_split
    # body differently than one in a fori body (duplicated-consumer fma
    # contraction), and the ~1e-5 gain drift would break the batched
    # grower's byte-identical-model guarantee against the K = 1 grower
    stacked_find = not meshed and forced is None and not cfg.with_monotone
    # on the lax engine only: the Pallas kernel takes one segment a call
    # (2.17 us beyond its rows on the chip, PERF.md §5: nothing to batch)
    frontier_batched = (fb_req > 1 and L > 2 and stacked_find
                        and not pooled and hist_engine == "lax")
    frontier_k = min(fb_req, L - 1) if frontier_batched else 1
    if frontier_batched:
        hist_batched_fn = functools.partial(
            seg.segment_histogram_batched, quantized=quantized,
            **hist_kwargs)

    if forced is not None:
        from .forced import make_forced_machinery
        fc_lnext, fc_rnext, forced_override = \
            make_forced_machinery(forced, meta, cfg)

    def grow(payload: jax.Array, aux: jax.Array,
             feature_mask: jax.Array, qscale: jax.Array = None):
        # what no narrower phase below names is the tree's own
        # book-keeping: selection, the state writes, the records
        with phase("tree_update"):
            return grow_tree(payload, aux, feature_mask, qscale)

    def grow_tree(payload, aux, feature_mask, qscale):
        n_rows = jnp.int32(payload.shape[0] - seg.GUARD)

        # dequantize-at-the-boundary: int32 histograms become f32 views
        # exactly where the split search consumes them; identity in f32
        # mode so the default path's trace is unchanged
        if quantized:
            assert qscale is not None, "quantized grow needs the scale pair"
            deq = functools.partial(dequantize_hist, gscale=qscale[0],
                                    hscale=qscale[1])
        else:
            def deq(h):
                return h

        # mesh-mode machinery is built at trace time (axis_index exists only
        # inside shard_map); find_split closes over the feature mask so the
        # split loop below is mode-agnostic
        localize_col = None
        if scatter_mode or feature_mode:
            # shared owned-column search: shard `my` owns global storage
            # columns [my*Gloc, (my+1)*Gloc) — in data mode as its
            # psum_scatter slice of the reduced histogram, in feature mode
            # as the leading columns of its permuted payload — and the
            # winner is broadcast with the SyncUpGlobalBestSplit allreduce
            # (parallel_tree_learner.h:183-206)
            my = lax.axis_index(axis_name)
            f_offset = my * Gloc
            meta_p = pad_feature_meta(meta, Gp) if padg else meta
            meta_local = FeatureMeta(
                *[lax.dynamic_slice_in_dim(a, f_offset, Gloc)
                  for a in meta_p])
            find_local = functools.partial(find_best_split, meta=meta_local,
                                           **find_kwargs)
            bcast_from_winner = make_winner_sync(axis_name, my, f_offset)
            fmask_p = (jnp.pad(feature_mask, (0, padg)) if padg
                       else feature_mask)
            fmask_loc = lax.dynamic_slice_in_dim(fmask_p, f_offset, Gloc)

            if scatter_mode:
                def reduce_hist(h):
                    with phase("allreduce"):
                        if padg:
                            h = jnp.pad(h, ((0, padg), (0, 0), (0, 0)))
                        return lax.psum_scatter(h, axis_name,
                                                scatter_dimension=0,
                                                tiled=True)
            else:
                # feature mode: hist_fn already produced the owned slice
                # over the full rows — nothing crosses the wire
                # (FeatureParallelTreeLearner holds full data per rank,
                # feature_parallel_tree_learner.cpp:21-69)
                def reduce_hist(h):
                    return h

                def localize_col(g):
                    # inverse of the owned-first column permutation:
                    # [owned block | columns before it | columns after it]
                    return jnp.where(
                        g < f_offset, Gloc + g,
                        jnp.where(g < f_offset + Gloc, g - f_offset, g))

            def find_split(hist_loc, sg, sh, cnt, **constraints):
                res = find_local(deq(hist_loc), sg, sh, cnt, fmask_loc,
                                 **constraints)
                with phase("allreduce"):
                    return bcast_from_winner(res)

        elif voting_mode:
            k_vote = min(top_k, F)
            S = min(2 * k_vote, F)
            vote_kwargs = dict(find_kwargs)
            vote_kwargs["min_data_in_leaf"] = cfg.min_data_in_leaf / n_mach
            vote_kwargs["min_sum_hessian_in_leaf"] = \
                cfg.min_sum_hessian_in_leaf / n_mach

            def reduce_hist(h):
                return h

            def find_split(hist_local, sg, sh, cnt, **constraints):
                # phase 1: vote top_k features by LOCAL split gain with
                # 1/num_machines-scaled constraints; phase 2: reduce ONLY
                # the vote winners' histograms and find on them (PV-Tree)
                hist_local_f = deq(hist_local)
                local_tot = jnp.sum(hist_local_f[0], axis=0)
                local_gains = per_feature_best_gains(
                    hist_local_f, local_tot[0], local_tot[1], local_tot[2],
                    feature_mask, meta=meta, **vote_kwargs)
                top_vals, top_idx = lax.top_k(local_gains, k_vote)
                valid_vote = (top_vals > K_MIN_SCORE).astype(jnp.int32)
                with phase("allreduce"):
                    all_top = lax.all_gather(top_idx, axis_name)
                    all_valid = lax.all_gather(valid_vote, axis_name)
                votes = jnp.zeros(F, jnp.int32).at[all_top.reshape(-1)].add(
                    all_valid.reshape(-1))
                _, sel = lax.top_k(votes, S)
                # the vote winners' histograms cross the wire as integers
                # in quantized mode (exact psum, 0 ulp shard-order drift)
                with phase("allreduce"):
                    hsel = lax.psum(hist_local[sel], axis_name)
                hsel = deq(hsel)
                meta_sel = FeatureMeta(*[a[sel] for a in meta])
                res = find_best_split(hsel, sg, sh, cnt, feature_mask[sel],
                                      meta=meta_sel, **find_kwargs,
                                      **constraints)
                return res._replace(feature=sel[res.feature])

        else:
            def reduce_hist(h):
                if not replicated:
                    return h
                with phase("allreduce"):
                    return lax.psum(h, axis_name)

            def find_split(h, sg, sh, cnt, **constraints):
                return find(hist_view(deq(h)), sg, sh, cnt, feature_mask,
                            **constraints)

        # a split's missing rows, counted once over a mesh: by the shard
        # that owns the column's (global) histogram, by every shard from
        # its local one (voting), by shard 0 where all hold the same
        if scatter_mode or feature_mode:
            def own_missing(hist_leaf, f, gcol):
                local = gcol - f_offset if scatter_mode else gcol
                owned = (local >= 0) & (local < Gloc)
                return jnp.where(owned, missing_rows(
                    hist_leaf, f, jnp.clip(local, 0, Gloc - 1)), 0)
        elif replicated:
            def own_missing(hist_leaf, f, gcol):
                return jnp.where(lax.axis_index(axis_name) == 0,
                                 missing_rows(hist_leaf, f, gcol), 0)
        else:
            own_missing = missing_rows

        if stacked_find:
            def find_split_batched(hists, sgs, shs, cnts):
                """Fused search over a [Q, Gh, B, 3] stack of children."""
                hists = deq(hists)
                if bundled:
                    hists = jax.vmap(hist_view)(hists)
                return find_best_split_batched(hists, sgs, shs, cnts,
                                               feature_mask, meta=meta,
                                               **find_kwargs)

        with phase("root_hist"):
            hist_root_local = hist_fn(payload, jnp.int32(0), n_rows)
            # every row lands in exactly one bin of storage column 0, so
            # the root totals fall out of the histogram — no separate
            # full-data pass
            totals = jnp.sum(hist_root_local[0], axis=0)
        if meshed and not feature_mode:
            with phase("allreduce"):
                totals = lax.psum(totals, axis_name)
        elif feature_mode:
            # every shard sees FULL rows, so its local column-0 totals are
            # already global IN VALUE — but fp summation order differs per
            # column at ulp level, and the winner's split outputs are
            # computed against these totals by whichever shard owns it.
            # Pin global column 0's totals (shard 0's, the exact sums the
            # serial engine uses) onto every shard so all shards — and the
            # serial learner — agree bit-for-bit.
            with phase("allreduce"):
                totals = lax.psum(jnp.where(my == 0, totals,
                                            jnp.zeros_like(totals)),
                                  axis_name)
        hist_root = reduce_hist(hist_root_local)
        # quantized mode: totals crossed the wire as exact integers; the
        # f32 leaf aggregates exist only from this boundary on
        totals = deq(totals)
        root_g, root_h, root_c = totals[0], totals[1], totals[2]
        with phase("split_search"):
            if cfg.with_monotone:
                res0 = find_split(hist_root, root_g, root_h, root_c,
                                  min_constraint=jnp.float32(-jnp.inf),
                                  max_constraint=jnp.float32(jnp.inf))
            else:
                res0 = find_split(hist_root, root_g, root_h, root_c)
            real0 = res0.gain
            root_rank = jnp.int32(-1)
            if forced is not None:
                res0, real0, root_rank = forced_override(
                    jnp.int32(0), hist_view(hist_root), root_g, root_h,
                    root_c, res0)

        # rows start as one root segment.  The value column is NOT set to
        # the root's Newton step: every reader of it is behind
        # `num_leaves > 1` (a stump moves no score, gbdt.py), and a tree
        # that splits writes its children's values over every row of the
        # root segment in its first partition.  The write was a select
        # over the whole payload, 16 ms a tree at 10.5M rows x 128 lanes
        # (PERF.md section 6, PR 32).

        ni = max(L - 1, 1)
        state = {
            "payload": payload,
            "aux": aux,
            "seg_start": jnp.zeros(L, jnp.int32),
            "seg_cnt": jnp.zeros(L, jnp.int32).at[0].set(n_rows),
            "sum_g": jnp.zeros(L, jnp.float32).at[0].set(root_g),
            "sum_h": jnp.zeros(L, jnp.float32).at[0].set(root_h),
            "cnt": jnp.zeros(L, jnp.float32).at[0].set(root_c),
            # creation value: 0 for the root (it has no creating split), set
            # by do_split for children — matches grower.py / Tree semantics
            # so internal_value of the first split agrees with the reference
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
            # rows the tree's partitions took in, and of them the rows of
            # the children that lay second (staged and moved once more by
            # the Pallas kernels): raw counts, as the kernels returned them
            "rows_partitioned": jnp.zeros(2, jnp.int32),
            "rows_staged": jnp.zeros(2, jnp.int32),
            # of the rows partitioned, those their split's column had no
            # value for: routed by `default_left`, not by the threshold
            "rows_missing": jnp.zeros(2, jnp.int32),
        }
        # per-leaf (or pooled) histogram state for the subtraction trick.
        # int32 in quantized mode (the narrow-dtype plumbing: LRU slots,
        # subtraction and the frontier-batch dispatch all carry the
        # integer histograms)
        state["hist"] = jnp.zeros((POOL, Gh, B, 3),
                                  hist_root.dtype).at[0].set(hist_root)
        if forced is not None:
            # pending forced rank per leaf, and the REAL (not priority) gain
            # of each leaf's stored best split, for honest split_gain records
            state["fleaf"] = jnp.full(L, -1, jnp.int32).at[0].set(root_rank)
            state["breal"] = jnp.full(L, K_MIN_SCORE,
                                      jnp.float32).at[0].set(real0)
        if cfg.with_monotone:
            state["mincon"] = jnp.full(L, -jnp.inf, jnp.float32)
            state["maxcon"] = jnp.full(L, jnp.inf, jnp.float32)
        if pooled:
            state["slot_of_leaf"] = jnp.full(L, -1, jnp.int32).at[0].set(0)
            state["leaf_of_slot"] = jnp.full(POOL, -1, jnp.int32).at[0].set(0)
            state["slot_use"] = jnp.zeros(POOL, jnp.int32)
        if frontier_batched:
            state["rounds"] = jnp.int32(0)

        def do_split(s, st, best_leaf):
            """Partition the split leaf and evaluate its children; runs only
            when a positive-gain split exists (under lax.cond)."""
            node = s - 1
            f = st["bfeat"][best_leaf]
            gcol = bmap.f_group[f]
            if localize_col is not None:
                # feature mode: the winner carries the GLOBAL feature id;
                # this shard's payload stores that column at its permuted
                # position
                gcol = localize_col(gcol)
            pred = SplitPredicate(
                col=gcol,
                threshold=st["bbin"][best_leaf],
                default_left=st["bdleft"][best_leaf],
                is_cat=st["bcat"][best_leaf],
                bitset=st["bbitset"][best_leaf],
                missing_type=meta.missing_type[f],
                num_bin=meta.num_bin[f],
                default_bin=meta.default_bin[f],
                offset=bmap.f_offset[f],
                identity=bmap.f_identity[f])

            start = st["seg_start"][best_leaf]
            count = st["seg_cnt"][best_leaf]

            # child aggregates: left from the stored split, right by diff
            lg, lh, lcnt = (st["blg"][best_leaf], st["blh"][best_leaf],
                            st["blc"][best_leaf])
            pg, ph, pc = (st["sum_g"][best_leaf], st["sum_h"][best_leaf],
                          st["cnt"][best_leaf])
            rg, rh, rcnt = pg - lg, ph - lh, pc - lcnt

            # parent histogram: read the pool slot, or rebuild it from the
            # (still contiguous) parent segment if it was evicted
            def rebuild_parent():
                with phase("hist"):
                    h = hist_fn(st["payload"], start, count)
                return reduce_hist(h)

            with phase("subtract"):
                if pooled:
                    # NOTE: the rebuild branch runs a collective in mesh
                    # modes; the pool bookkeeping is replicated-in-value,
                    # so every shard takes the same branch and the psum
                    # pairs up
                    pslot = st["slot_of_leaf"][best_leaf]
                    hist_parent = lax.cond(
                        pslot >= 0,
                        lambda: st["hist"][jnp.maximum(pslot, 0)],
                        rebuild_parent)
                else:
                    # read out before the pool is written: left for the
                    # compiler to fuse, this slice is re-read from the old
                    # pool inside the children's slot writes, which then
                    # cannot happen in place, and the whole pool is copied
                    # twice a split (0.39 GB at 2,000 columns x 63 bins:
                    # 4.6 ms a split, 1.18 s a tree; PERF.md §6, PR 26)
                    hist_parent = lax.optimization_barrier(
                        st["hist"][best_leaf])

            # histograms: build only the smaller child, derive the sibling
            # by subtraction.  The choice uses masked counts (like grower.py
            # and the reference's num_data comparison) so both growers build
            # the direct histogram on the same child and stay bit-comparable.
            # That child lies SECOND in the parent's range: the kernels move
            # the second child's rows twice, and no reader needs the left
            # child first.  On a mesh the counts are all-reduced, so every
            # shard makes the same choice.
            left_smaller = lcnt <= rcnt
            right_first = left_smaller
            with phase("partition"):
                payload, aux, nl_raw = part_fn(
                    st["payload"], st["aux"], start, count, pred,
                    st["blo"][best_leaf], st["bro"][best_leaf], right_first)
            nr_raw = count - nl_raw
            h_count = jnp.where(right_first, nl_raw, nr_raw)
            h_start = start + count - h_count
            l_start, r_start = seg.first_second(right_first, start, h_start)
            with phase("hist"):
                hist_small = hist_fn(payload, h_start, h_count)
            hist_small = reduce_hist(hist_small)
            with phase("subtract"):
                hist_big = hist_parent - hist_small
                new_left = jnp.where(left_smaller, hist_small, hist_big)
                new_right = jnp.where(left_smaller, hist_big, hist_small)
            # the histogram pool's book-keeping and the two slot writes
            with phase("subtract"):
                if pooled:
                    slot_of_leaf = st["slot_of_leaf"]
                    leaf_of_slot = st["leaf_of_slot"]
                    use = st["slot_use"]
                    iota_pool = jnp.arange(POOL, dtype=jnp.int32)

                    def evict(slot_of_leaf, leaf_of_slot, victim):
                        old = leaf_of_slot[victim]
                        oldc = jnp.maximum(old, 0)
                        slot_of_leaf = slot_of_leaf.at[oldc].set(
                            jnp.where(old >= 0, -1, slot_of_leaf[oldc]))
                        return slot_of_leaf

                    # left child: reuse the parent's slot, else evict the LRU
                    victim_l = jnp.argmin(use).astype(jnp.int32)
                    lslot = jnp.where(pslot >= 0, pslot, victim_l)
                    slot_of_leaf = jnp.where(
                        pslot >= 0, slot_of_leaf,
                        evict(slot_of_leaf, leaf_of_slot, victim_l))
                    leaf_of_slot = leaf_of_slot.at[lslot].set(best_leaf)
                    use = use.at[lslot].set(s)
                    # right child: evict the LRU among the remaining slots
                    prio = jnp.where(iota_pool == lslot,
                                     jnp.int32(1 << 30), use)
                    rslot = jnp.argmin(prio).astype(jnp.int32)
                    slot_of_leaf = evict(slot_of_leaf, leaf_of_slot, rslot)
                    leaf_of_slot = leaf_of_slot.at[rslot].set(s)
                    use = use.at[rslot].set(s)
                    slot_of_leaf = slot_of_leaf.at[best_leaf].set(lslot)
                    slot_of_leaf = slot_of_leaf.at[s].set(rslot)
                    hist = st["hist"].at[lslot].set(new_left)
                    hist = hist.at[rslot].set(new_right)
                else:
                    hist = st["hist"].at[best_leaf].set(new_left)
                    hist = hist.at[s].set(new_right)

            child_depth = st["leaf_depth"][best_leaf] + 1
            with phase("split_search"):
                if cfg.with_monotone:
                    from .grower import propagate_monotone_bounds
                    lmin, lmax, rmin, rmax = propagate_monotone_bounds(
                        st["blo"][best_leaf], st["bro"][best_leaf],
                        ~st["bcat"][best_leaf], meta.monotone[f],
                        st["mincon"][best_leaf], st["maxcon"][best_leaf])
                    res_l = find_split(new_left, lg, lh, lcnt,
                                       min_constraint=lmin,
                                       max_constraint=lmax)
                    res_r = find_split(new_right, rg, rh, rcnt,
                                       min_constraint=rmin,
                                       max_constraint=rmax)
                elif stacked_find:
                    # the sequential loop must stay bit-comparable with the
                    # frontier-batched grower: evaluate the two children
                    # through the SAME stacked-fori search the batched rounds
                    # use (see find_best_split_batched's exactness note),
                    # then split the [2] rows back out
                    lmin = lmax = rmin = rmax = None
                    res2_ = find_split_batched(
                        jnp.stack([new_left, new_right]),
                        jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                        jnp.stack([lcnt, rcnt]))
                    res_l = jax.tree_util.tree_map(lambda a: a[0], res2_)
                    res_r = jax.tree_util.tree_map(lambda a: a[1], res2_)
                else:
                    lmin = lmax = rmin = rmax = None
                    res_l = find_split(new_left, lg, lh, lcnt)
                    res_r = find_split(new_right, rg, rh, rcnt)
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
                return arr.at[best_leaf].set(vl).at[s].set(vr)

            st_new = dict(st)
            st_new["payload"] = payload
            st_new["aux"] = aux
            st_new["hist"] = hist
            if pooled:
                st_new["slot_of_leaf"] = slot_of_leaf
                st_new["leaf_of_slot"] = leaf_of_slot
                st_new["slot_use"] = use
            st_new["seg_start"] = set2(st["seg_start"], l_start, r_start)
            st_new["seg_cnt"] = set2(st["seg_cnt"], nl_raw, nr_raw)
            st_new["rows_partitioned"] = _wide_add(st["rows_partitioned"],
                                                   count)
            st_new["rows_staged"] = _wide_add(st["rows_staged"], h_count)
            st_new["rows_missing"] = _wide_add(
                st["rows_missing"],
                jnp.where(st["bcat"][best_leaf], 0,
                          own_missing(hist_parent, f, gcol)))
            st_new["sum_g"] = set2(st["sum_g"], lg, rg)
            st_new["sum_h"] = set2(st["sum_h"], lh, rh)
            st_new["cnt"] = set2(st["cnt"], lcnt, rcnt)
            st_new["bgain"] = set2(st["bgain"], gain_l, gain_r)
            st_new["bfeat"] = set2(st["bfeat"], res_l.feature, res_r.feature)
            st_new["bbin"] = set2(st["bbin"], res_l.threshold_bin,
                                  res_r.threshold_bin)
            st_new["bdleft"] = set2(st["bdleft"], res_l.default_left,
                                    res_r.default_left)
            st_new["blg"] = set2(st["blg"], res_l.left_sum_g, res_r.left_sum_g)
            st_new["blh"] = set2(st["blh"], res_l.left_sum_h, res_r.left_sum_h)
            st_new["blc"] = set2(st["blc"], res_l.left_count, res_r.left_count)
            st_new["bcat"] = set2(st["bcat"], res_l.is_cat, res_r.is_cat)
            st_new["bbitset"] = set2(st["bbitset"], res_l.cat_bitset,
                                     res_r.cat_bitset)
            st_new["blo"] = set2(st["blo"], res_l.left_output,
                                 res_r.left_output)
            st_new["bro"] = set2(st["bro"], res_l.right_output,
                                 res_r.right_output)
            st_new["leaf_val"] = set2(st["leaf_val"], st["blo"][best_leaf],
                                      st["bro"][best_leaf])
            st_new["leaf_depth"] = set2(st["leaf_depth"], child_depth,
                                        child_depth)
            if forced is not None:
                st_new["fleaf"] = set2(st["fleaf"], jl, jr)
                st_new["breal"] = set2(st["breal"], real_l, real_r)
            if cfg.with_monotone:
                st_new["mincon"] = set2(st["mincon"], lmin, rmin)
                st_new["maxcon"] = set2(st["maxcon"], lmax, rmax)

            # record the internal node (Tree::Split, tree.h:404-448)
            gain = (st["breal"] if forced is not None
                    else st["bgain"])[best_leaf]
            st_new["split_feature"] = st["split_feature"].at[node].set(f)
            st_new["split_bin"] = st["split_bin"].at[node].set(
                st["bbin"][best_leaf])
            st_new["split_gain"] = st["split_gain"].at[node].set(gain)
            st_new["default_left"] = st["default_left"].at[node].set(
                st["bdleft"][best_leaf])
            st_new["split_is_cat"] = st["split_is_cat"].at[node].set(
                st["bcat"][best_leaf])
            st_new["split_cat_bitset"] = st["split_cat_bitset"].at[node].set(
                st["bbitset"][best_leaf])
            st_new["internal_value"] = st["internal_value"].at[node].set(
                st["leaf_val"][best_leaf])
            st_new["internal_count"] = st["internal_count"].at[node].set(pc)
            left_child = st["left_child"].at[node].set(~best_leaf)
            right_child = st["right_child"].at[node].set(~s)
            parent_node = st["leaf_parent"][best_leaf]
            has_par = parent_node >= 0
            pn = jnp.maximum(parent_node, 0)
            was_left = left_child[pn] == ~best_leaf
            left_child = left_child.at[pn].set(
                jnp.where(has_par & was_left, node, left_child[pn]))
            right_child = right_child.at[pn].set(
                jnp.where(has_par & ~was_left, node, right_child[pn]))
            st_new["left_child"] = left_child
            st_new["right_child"] = right_child
            st_new["leaf_parent"] = set2(st["leaf_parent"], node, node)
            st_new["num_leaves"] = st["num_leaves"] + 1
            return st_new

        # while-loop, not fori+cond: a cond with an identity pass-through
        # branch makes XLA copy the whole carried state — payload and aux
        # included, ~1 GB per split at Higgs scale — every iteration.  The
        # while body always splits; "no positive gain" simply ends the loop,
        # which also gives early exit for free.
        def loop_cond(st):
            return (st["num_leaves"] < L) & (jnp.max(st["bgain"]) > 0.0)

        def body(st):
            best_leaf = jnp.argmax(st["bgain"]).astype(jnp.int32)
            return do_split(st["num_leaves"], st, best_leaf)

        # ---- frontier-batched rounds (see the gate comment above) -------
        KB = frontier_k

        def round_body(st):
            # selection: the gain-ordered window.  lax.top_k is stable
            # (ties prefer the lower index), so slot 0 is exactly the
            # argmax the sequential grower would pop next — the first
            # commit of a round always succeeds and rounds always progress.
            top_gain, cand = lax.top_k(st["bgain"], KB)
            active = top_gain > 0.0
            start_c = st["seg_start"][cand]
            cnt_c = jnp.where(active, st["seg_cnt"][cand], 0)
            feat_c = st["bfeat"][cand]
            bbin_c = st["bbin"][cand]
            bdleft_c = st["bdleft"][cand]
            bcat_c = st["bcat"][cand]
            bbitset_c = st["bbitset"][cand]
            blo_c, bro_c = st["blo"][cand], st["bro"][cand]
            lg_c, lh_c, lc_c = (st["blg"][cand], st["blh"][cand],
                                st["blc"][cand])
            pg_c, ph_c, pc_c = (st["sum_g"][cand], st["sum_h"][cand],
                                st["cnt"][cand])
            rg_c, rh_c, rc_c = pg_c - lg_c, ph_c - lh_c, pc_c - lc_c
            # the sequential loop's rule: the smaller child (masked
            # counts) is histogrammed and lies second
            left_smaller = lc_c <= rc_c
            right_first = left_smaller

            # eval phase A: STAGE every candidate's partition into the aux
            # scratch (passes A+B; payload is only read, so an evaluated
            # candidate that never commits leaves its rows — and every
            # later tree's accumulation order — exactly as the sequential
            # grower would).  Segments are disjoint; ascending start order
            # keeps each stage's one-chunk aux overrun inside regions
            # staged afterwards.  Inactive window slots run with count 0
            # (zero-trip loops) instead of lax.cond, which would copy aux.
            order = jnp.argsort(start_c)

            def eval_part(i, carry):
                aux, nfs = carry
                k = order[i]
                f = feat_c[k]
                pred = SplitPredicate(
                    col=bmap.f_group[f],
                    threshold=bbin_c[k],
                    default_left=bdleft_c[k],
                    is_cat=bcat_c[k],
                    bitset=bbitset_c[k],
                    missing_type=meta.missing_type[f],
                    num_bin=meta.num_bin[f],
                    default_bin=meta.default_bin[f],
                    offset=bmap.f_offset[f],
                    identity=bmap.f_identity[f])
                aux, nf = seg.partition_segment_stage(
                    st["payload"], aux, start_c[k], cnt_c[k], pred,
                    right_first[k])
                return aux, nfs.at[k].set(nf)

            with phase("partition"):
                aux, nf_c = lax.fori_loop(
                    0, KB, eval_part, (st["aux"], jnp.zeros(KB, jnp.int32)))
            payload = st["payload"]
            h_count = cnt_c - nf_c
            h_start = start_c + nf_c
            nl_c = jnp.where(right_first, h_count, nf_c)
            lstart_c, rstart_c = seg.first_second(right_first, start_c,
                                                  h_start)

            # eval phase B: ONE batched histogram dispatch over the K
            # smaller children, read from the STAGED aux rows — compacted
            # at the same offsets pass C will copy them back to, so the
            # chunk layout (and every f32 accumulation) is bit-identical
            # to the sequential grower's post-partition build.  Siblings
            # by batched subtraction, same masked-count smaller-child
            # choice as the sequential path.
            with phase("hist"):
                hist_small = hist_batched_fn(aux, h_start, h_count)
            with phase("subtract"):
                hist_big = st["hist"][cand] - hist_small
                ls4 = left_smaller[:, None, None, None]
                new_left = jnp.where(ls4, hist_small, hist_big)
                new_right = jnp.where(ls4, hist_big, hist_small)

            # eval phase C: ONE fused split search over the 2K children
            with phase("split_search"):
                res2 = find_split_batched(
                    jnp.concatenate([new_left, new_right]),
                    jnp.concatenate([lg_c, rg_c]),
                    jnp.concatenate([lh_c, rh_c]),
                    jnp.concatenate([lc_c, rc_c]))
            child_depth = st["leaf_depth"][cand] + 1
            if cfg.max_depth > 0:
                depth_ok = child_depth < cfg.max_depth
            else:
                depth_ok = jnp.ones(KB, jnp.bool_)
            gain_l = jnp.where(depth_ok, res2.gain[:KB], K_MIN_SCORE)
            gain_r = jnp.where(depth_ok, res2.gain[KB:], K_MIN_SCORE)
            lval_c = st["leaf_val"][cand]
            gain_stored = st["bgain"][cand]
            miss_c = jnp.where(bcat_c, 0, jax.vmap(missing_rows)(
                st["hist"][cand], feat_c, bmap.f_group[feat_c]))

            # commit phase: replay the sequential argmax order against the
            # evaluated window.  Small-state bookkeeping only (payload and
            # aux stay out of the carry); a pop outside the window — a
            # child created this round, an unevaluated leaf, exhausted
            # gain, or the leaf budget — ends the round.  `used` guards
            # against a committed candidate's id (now its LEFT child)
            # being popped again and replayed from the stale evaluation.
            st2 = {k_: v for k_, v in st.items()
                   if k_ not in ("payload", "aux")}

            def commit_body(k, carry):
                st2, used, stopped = carry
                best = jnp.argmax(st2["bgain"]).astype(jnp.int32)
                is_c = (cand == best) & active & ~used
                j = jnp.argmax(is_c).astype(jnp.int32)
                do = (is_c[j] & ~stopped & (st2["num_leaves"] < L)
                      & (st2["bgain"][best] > 0.0))
                stopped = stopped | ~do
                used = used.at[j].set(used[j] | do)
                s = st2["num_leaves"]
                s_c = jnp.minimum(s, L - 1)     # clamp no-op writes
                node = jnp.maximum(s - 1, 0)

                def set2(arr, vl, vr):
                    arr = arr.at[best].set(jnp.where(do, vl, arr[best]))
                    return arr.at[s_c].set(jnp.where(do, vr, arr[s_c]))

                def setn(arr, v):
                    return arr.at[node].set(jnp.where(do, v, arr[node]))

                nl = nl_c[j]
                st2["seg_start"] = set2(st2["seg_start"], lstart_c[j],
                                        rstart_c[j])
                st2["seg_cnt"] = set2(st2["seg_cnt"], nl, cnt_c[j] - nl)
                done = do.astype(jnp.int32)
                st2["rows_partitioned"] = _wide_add(
                    st2["rows_partitioned"], done * cnt_c[j])
                st2["rows_staged"] = _wide_add(
                    st2["rows_staged"], done * h_count[j])
                st2["rows_missing"] = _wide_add(
                    st2["rows_missing"], done * miss_c[j])
                st2["sum_g"] = set2(st2["sum_g"], lg_c[j], rg_c[j])
                st2["sum_h"] = set2(st2["sum_h"], lh_c[j], rh_c[j])
                st2["cnt"] = set2(st2["cnt"], lc_c[j], rc_c[j])
                st2["bgain"] = set2(st2["bgain"], gain_l[j], gain_r[j])
                st2["bfeat"] = set2(st2["bfeat"], res2.feature[j],
                                    res2.feature[KB + j])
                st2["bbin"] = set2(st2["bbin"], res2.threshold_bin[j],
                                   res2.threshold_bin[KB + j])
                st2["bdleft"] = set2(st2["bdleft"], res2.default_left[j],
                                     res2.default_left[KB + j])
                st2["blg"] = set2(st2["blg"], res2.left_sum_g[j],
                                  res2.left_sum_g[KB + j])
                st2["blh"] = set2(st2["blh"], res2.left_sum_h[j],
                                  res2.left_sum_h[KB + j])
                st2["blc"] = set2(st2["blc"], res2.left_count[j],
                                  res2.left_count[KB + j])
                st2["bcat"] = set2(st2["bcat"], res2.is_cat[j],
                                   res2.is_cat[KB + j])
                st2["bbitset"] = set2(st2["bbitset"], res2.cat_bitset[j],
                                      res2.cat_bitset[KB + j])
                st2["blo"] = set2(st2["blo"], res2.left_output[j],
                                  res2.left_output[KB + j])
                st2["bro"] = set2(st2["bro"], res2.right_output[j],
                                  res2.right_output[KB + j])
                st2["leaf_val"] = set2(st2["leaf_val"], blo_c[j], bro_c[j])
                st2["leaf_depth"] = set2(st2["leaf_depth"], child_depth[j],
                                         child_depth[j])
                st2["hist"] = st2["hist"].at[best].set(
                    jnp.where(do, new_left[j], st2["hist"][best]))
                st2["hist"] = st2["hist"].at[s_c].set(
                    jnp.where(do, new_right[j], st2["hist"][s_c]))

                # record the internal node (do_split's bookkeeping, with
                # the same round-start reads the sequential grower makes)
                st2["split_feature"] = setn(st2["split_feature"], feat_c[j])
                st2["split_bin"] = setn(st2["split_bin"], bbin_c[j])
                st2["split_gain"] = setn(st2["split_gain"], gain_stored[j])
                st2["default_left"] = setn(st2["default_left"], bdleft_c[j])
                st2["split_is_cat"] = setn(st2["split_is_cat"], bcat_c[j])
                st2["split_cat_bitset"] = setn(st2["split_cat_bitset"],
                                               bbitset_c[j])
                st2["internal_value"] = setn(st2["internal_value"], lval_c[j])
                st2["internal_count"] = setn(st2["internal_count"], pc_c[j])
                left_child = setn(st2["left_child"], ~best)
                right_child = setn(st2["right_child"], ~s)
                parent_node = st2["leaf_parent"][best]
                has_par = parent_node >= 0
                pn = jnp.maximum(parent_node, 0)
                was_left = left_child[pn] == ~best
                left_child = left_child.at[pn].set(
                    jnp.where(do & has_par & was_left, node, left_child[pn]))
                right_child = right_child.at[pn].set(
                    jnp.where(do & has_par & ~was_left, node,
                              right_child[pn]))
                st2["left_child"] = left_child
                st2["right_child"] = right_child
                st2["leaf_parent"] = set2(st2["leaf_parent"], node, node)
                st2["num_leaves"] = st2["num_leaves"] + do.astype(jnp.int32)
                return st2, used, stopped

            st2, committed, _ = lax.fori_loop(
                0, KB, commit_body,
                (st2, jnp.zeros(KB, jnp.bool_), jnp.bool_(False)))

            # commit pass C: copy the staged rows back for exactly the
            # splits that committed (count 0 skips the rest — their
            # payload rows were never touched).  Disjoint segments, so
            # slot order is free.
            def commit_part(j, pay):
                cnt = jnp.where(committed[j], cnt_c[j], 0)
                return seg.partition_segment_commit(
                    pay, aux, start_c[j], cnt, nf_c[j],
                    *seg.first_second(right_first[j], blo_c[j], bro_c[j]),
                    cols.value)

            with phase("partition"):
                payload = lax.fori_loop(0, KB, commit_part, payload)

            st2["rounds"] = st2["rounds"] + 1
            st2["payload"] = payload
            st2["aux"] = aux
            return st2

        if frontier_batched:
            st = lax.while_loop(loop_cond, round_body, state)
            split_rounds = st["rounds"]
        elif L > 1:
            st = lax.while_loop(loop_cond, body, state)
            split_rounds = st["num_leaves"] - 1
        else:
            st = state
            split_rounds = jnp.int32(0)

        leaf_value = jnp.where(
            (jnp.arange(L) == 0) & (st["num_leaves"] == 1),
            out_fn(st["sum_g"], st["sum_h"]), st["leaf_val"])
        tree = {
            "num_leaves": st["num_leaves"],
            # sequential device rounds this tree paid (== splits for the
            # sequential grower; < splits once frontier batching commits
            # more than one split per round) — bench telemetry
            "split_rounds": split_rounds.astype(jnp.int32),
            "leaf_value": leaf_value,
            "leaf_count": st["cnt"],
            "leaf_sum_g": st["sum_g"],
            "leaf_sum_h": st["sum_h"],
            "seg_start": st["seg_start"],
            "seg_cnt": st["seg_cnt"],
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
            "rows_partitioned": st["rows_partitioned"],
            "rows_staged": st["rows_staged"],
            "rows_missing": st["rows_missing"],
        }
        return tree, st["payload"], st["aux"]

    # payload/aux are donated: the training state is updated in place across
    # trees, never copied (HistogramPool-style buffer discipline without the
    # pointer juggling of feature_histogram.hpp:655-826)
    grower = xla_obs.jit(grow, site="grower2.partitioned",
                         donate_argnums=(0, 1)) if jit else grow
    grower.engines = engines
    return grower
