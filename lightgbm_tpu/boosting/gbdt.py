"""GBDT boosting driver.

Role parity with the reference src/boosting/gbdt.cpp: Init (:64-169),
TrainOneIter (:387-482), Bagging (:213-295), BoostFromAverage (:363-385),
UpdateScore / ScoreUpdater (src/boosting/score_updater.hpp), RollbackOneIter
(:484-500).

TPU-first: raw scores live on device for the whole run; one boosting
iteration is (jitted gradient) → (jitted tree grower) → (jitted score
gather-update per dataset).  Only the finished tree's small arrays come back
to the host, where the reference-format model is assembled.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..io.binning import BIN_TYPE_CATEGORICAL
from ..io.dataset import BinnedDataset
from ..models.gbdt_model import GBDTModel
from ..models.tree import Tree
from ..ops.split import FeatureMeta, MISSING_NONE
from ..runtime import resilience, syncs, telemetry, tracing, xla_obs
from ..utils.log import Log
from ..utils.random import Random, partition_seed
from ..utils.timer import PhaseTimer
from ..ops import segment as seg
from ..ops import state_columns
from ..ops.bundle import (BundleMap, bundle_map_from_info, decode_bin,
                          identity_bundle_map)
from .grower import GrowerConfig, make_tree_grower
from .grower2 import (PayloadCols, TREE_DEVICE_FIELDS, phase,
                      make_partitioned_grower, wide_count)
from .pipeline import TreeAssembler

K_EPSILON = 1e-15


def _construct_bitset(vals) -> list:
    """Common::ConstructBitset — uint32 words spanning [0, max(vals)]."""
    if not vals:
        return []
    words = [0] * (max(vals) // 32 + 1)
    for v in vals:
        words[v // 32] |= 1 << (v % 32)
    return words

# Reuse compiled growers across boosters: jax.jit caches per wrapper object,
# so two boosters with identical feature metadata + config would otherwise
# recompile the identical program (slow on every lgb.train call).
_GROWER_CACHE: Dict = {}


def _bundle_key(ds: BinnedDataset):
    info = ds.bundle_info
    if info is None:
        return None
    return (info.f_group.tobytes(), info.f_offset.tobytes(),
            info.f_identity.tobytes())


def _cached_grower(meta_dev: FeatureMeta, cfg, max_num_bin: int, ds: BinnedDataset,
                   bundle_map=None, forced=None):
    key = (cfg, max_num_bin, ds.bins.shape, _bundle_key(ds), forced,
           tuple((m.num_bin, m.missing_type, m.default_bin, m.is_trivial, m.bin_type)
                 for m in ds.bin_mappers),
           ds.monotone_constraints.tobytes(), ds.feature_penalty.tobytes())
    grower = _GROWER_CACHE.get(key)
    if grower is None:
        xla_obs.cache_event("gbdt.grower_cache", "miss")
        grower = make_tree_grower(meta_dev, cfg, max_num_bin,
                                  bundle_map=bundle_map, forced=forced)
        _GROWER_CACHE[key] = grower
    else:
        xla_obs.cache_event("gbdt.grower_cache", "hit")
    return grower


_PGROWER_CACHE: Dict = {}

#: row count past which the fast path's f32 index column splits into
#: radix-4096 (hi, lo) halves (f32 integers are exact below 2^24; tests
#: lower this to exercise the wide layout at small N)
_IDX_WIDE_THRESHOLD = 1 << 24

#: radix of the split index
_IDX_RADIX = 4096.0

#: packed-fetch program cache, bounded so long-lived serving/training
#: processes cycling through many output specs (different num_leaves,
#: grower variants, eval-round shapes) cannot grow it without limit;
#: LRU eviction — steady-state training uses one or two specs
_PACK_CACHE: "OrderedDict" = OrderedDict()
_PACK_CACHE_MAX = 64


def _pack_cache_put(cache: "OrderedDict", key, entry,
                    site: str = "gbdt.pack_cache") -> None:
    cache[key] = entry
    while len(cache) > _PACK_CACHE_MAX:
        cache.popitem(last=False)
        xla_obs.cache_event(site, "evict")


def _fetch_packed(out: Dict, label: str = "tree_fetch") -> Dict[str, np.ndarray]:
    """device_get of the grower's (small) outputs in ONE transfer.

    Every fetched array is its own device-to-host transfer, and the tree
    dict has ~17 entries against ~2 ms of actual host assembly (what one
    transfer costs on this machine is not measured).  All values are exact in
    f32 (counts/ids < 2^24, flags 0/1), so flatten+concat on device, fetch
    once, and split on host.  The big per-row leaf_id array (legacy grower)
    is excluded and fetched only by the paths that need it."""
    spec = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in out.items() if k != "leaf_id"))
    entry = _PACK_CACHE.get(spec)
    if entry is None:
        xla_obs.cache_event("gbdt.pack_cache", "miss")
        keys = [k for k, _, _ in spec]
        shapes = {k: s for k, s, _ in spec}
        dtypes = {k: d for k, _, d in spec}
        sizes = [int(np.prod(shapes[k], dtype=np.int64)) for k in keys]
        offs = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)

        @functools.partial(xla_obs.jit, site="gbdt.pack_fetch")
        def pack(o):
            return jnp.concatenate(
                [o[k].astype(jnp.float32).reshape(-1) for k in keys])

        entry = (keys, shapes, dtypes, offs, pack)
        _pack_cache_put(_PACK_CACHE, spec, entry)
    else:
        xla_obs.cache_event("gbdt.pack_cache", "hit")
        _PACK_CACHE.move_to_end(spec)
    keys, shapes, dtypes, offs, pack = entry
    flat = np.asarray(syncs.device_get(pack(out), label=label))
    host = {}
    for i, k in enumerate(keys):
        a = flat[offs[i]:offs[i + 1]].reshape(shapes[k])
        host[k] = a if dtypes[k] == "float32" else a.astype(dtypes[k])
    return host


#: eval-round pack cache (same pattern/bound as _PACK_CACHE): one jitted
#: flatten+concat program per tuple-of-shapes of the round's score arrays
_EVAL_PACK_CACHE: "OrderedDict" = OrderedDict()


#: grower2 tree-dict fields that are replicated in value across a mesh
#: (everything except the per-device row-segment bookkeeping)
_PTREE_REPLICATED = (
    "num_leaves", "split_rounds", "leaf_value", "leaf_count", "leaf_sum_g",
    "leaf_sum_h", "split_feature", "split_bin", "split_gain", "default_left",
    "split_is_cat", "split_cat_bitset", "left_child", "right_child",
    "internal_value", "internal_count")


def _cached_pgrower(meta_dev: FeatureMeta, cfg, max_num_bin: int,
                    ds: BinnedDataset, cols: PayloadCols, payload_width: int,
                    bundle_map=None, forced=None, mesh=None, mesh_axis=None,
                    mode="data", top_k=20, quantized=False, qmax=0):
    key = (cfg, max_num_bin, ds.bins.shape, cols, payload_width,
           _bundle_key(ds), forced, mesh, mesh_axis, mode, top_k,
           quantized, qmax,
           tuple((m.num_bin, m.missing_type, m.default_bin, m.is_trivial, m.bin_type)
                 for m in ds.bin_mappers),
           ds.monotone_constraints.tobytes(), ds.feature_penalty.tobytes())
    grower = _PGROWER_CACHE.get(key)
    if grower is None:
        xla_obs.cache_event("gbdt.pgrower_cache", "miss")
        if mesh is None:
            grower = make_partitioned_grower(
                meta_dev, cfg, max_num_bin, cols, ds.num_features,
                bundle_map=bundle_map, num_columns=ds.bins.shape[0],
                forced=forced, payload_width=payload_width,
                quantized=quantized, qmax=qmax)
        else:
            # the mesh fast path: the SAME partitioned engine per shard
            # (local row blocks partition locally), collectives at the
            # histogram boundary only — the reference's learner inheritance
            # (data_parallel_tree_learner.cpp:147 IS SerialTreeLearner +
            # network), kept structurally
            from jax.sharding import PartitionSpec as P
            ax = mesh_axis
            grow = make_partitioned_grower(
                meta_dev, cfg, max_num_bin, cols, ds.num_features,
                jit=False, bundle_map=bundle_map,
                num_columns=ds.bins.shape[0], forced=forced,
                axis_name=ax, mode=mode,
                num_machines=int(mesh.shape[ax]), top_k=top_k,
                payload_width=payload_width,
                quantized=quantized, qmax=qmax)
            tree_specs = dict.fromkeys(_PTREE_REPLICATED, P())
            # per-device row segments come back stacked [ndev * L]
            tree_specs["seg_start"] = P(ax)
            tree_specs["seg_cnt"] = P(ax)
            # and so do each block's row counters, a (high, low) pair each
            tree_specs["rows_partitioned"] = P(ax)
            tree_specs["rows_staged"] = P(ax)
            tree_specs["rows_missing"] = P(ax)
            # quantized growers take the replicated [2] scale pair as a
            # fourth argument (scales are global maxima, so every shard
            # holds the same values)
            in_specs = (P(ax, None), P(ax, None), P(None))
            if quantized:
                in_specs = in_specs + (P(),)
            grower = xla_obs.jit(jax.shard_map(
                grow, mesh=mesh,
                in_specs=in_specs,
                out_specs=(tree_specs, P(ax, None), P(ax, None)),
                check_vma=False), donate_argnums=(0, 1),
                site="gbdt.pgrower_mesh")
            grower.engines = grow.engines
        _PGROWER_CACHE[key] = grower
    else:
        xla_obs.cache_event("gbdt.pgrower_cache", "hit")
    return grower


class _FastState:
    """Partition-ordered training state for the serial fast path.

    The whole of training state — bin columns, label/weight, per-class raw
    scores, per-iteration grad/hess and the current tree's per-row output —
    lives in ONE row-major payload matrix that the partitioned grower
    reorders in place (rows of each leaf contiguous).  Everything downstream
    of the grower becomes elementwise: gradients, score updates, and the
    count-mask column (which doubles as the bagging mask, refreshed through
    the index column on resample).  Original row order is recovered through the index column
    only when a consumer needs it (metrics, sync back to the legacy path).
    """

    def __init__(self, gbdt: "GBDT"):
        ds = gbdt.train_set
        G = ds.bins.shape[0]   # storage columns (EFB bundles, G <= F)
        K = gbdt.num_tree_per_iteration
        n_pad = ds.num_data_padded
        # mesh fast path: rows live in ndev device blocks of n_loc real rows
        # + a GUARD-row tail EACH (the partition kernels overrun into the
        # guard, so it must sit at the end of every LOCAL block, not just
        # the global tail).  Guard rows carry idx == n_pad — a dead slot
        # that every original-order consumer (bag refresh, score sync)
        # filters or routes to a zero entry.  Serial is the ndev == 1 case.
        #
        # feature-parallel: every block is the FULL row set (the reference
        # learner holds full data per rank) with the storage columns
        # permuted owned-first; original-order consumers work unchanged
        # because their idx-routed scatters are idempotent across the
        # duplicate blocks.
        mesh = gbdt.mesh if gbdt.parallel_mode in ("data", "voting",
                                                   "feature") else None
        feature_par = mesh is not None and gbdt.parallel_mode == "feature"
        self.feature_par = feature_par
        if feature_par:
            # the padded feature axis (shard multiple) IS the storage width
            G = G + gbdt._fmask_pad
        self.G, self.K, self.n_pad = G, K, n_pad
        self.mesh = mesh
        ndev = int(mesh.shape[gbdt.mesh_axis]) if mesh is not None else 1
        self.ndev = ndev
        n_loc = n_pad if feature_par else n_pad // ndev
        self.n_loc = n_loc
        n_rows = (n_loc + seg.GUARD) * ndev
        self.n_rows = n_rows
        self.label_col = G
        self.weight_col = G + 1
        self.cnt_col = G + 2
        self.idx_col = G + 3
        self.score0 = G + 4
        # multiclass trains K trees per iteration, all from the SAME
        # pre-iteration scores (gbdt.cpp Boosting computes every class's
        # gradients before any tree), but each tree reorders the rows — so
        # the pre-iteration scores are snapshotted into columns that ride
        # the partition, and each class's gradients are recomputed from the
        # snapshot in whatever order the rows currently sit
        self.snap0 = G + 4 + K if K > 1 else self.score0
        self.grad_col = self.snap0 + (K if K > 1 else 1)
        self.hess_col = self.grad_col + 1
        self.value_col = self.grad_col + 2
        # pristine valid mask: the cnt column is a WORKING mask (bagging /
        # GOSS selection overwrite it per iteration).  The gradient-weight
        # column carries the sampling amplification so multiclass can draw
        # one selection per iteration that RIDES the per-tree partitions.
        self.bvalid_col = self.value_col + 1
        self.gweight_col = self.bvalid_col + 1
        # past ~2^24 rows an f32 index column loses exactness; split the
        # index into radix-4096 (hi, lo) halves — both remain exact through
        # the one-hot permutation matmuls (each output is a single-term sum)
        self.wide_idx = (n_pad + 1) >= _IDX_WIDE_THRESHOLD
        self.idxhi_col = self.gweight_col + 1 if self.wide_idx else None
        last_col = self.idxhi_col if self.wide_idx else self.gweight_col
        self.P = last_col + 1
        if jax.default_backend() == "tpu":
            # Mosaic DMA slices must span whole 128-lane tiles; a [N, P]
            # f32 array is physically padded to 128 lanes on TPU anyway,
            # so declaring the pad costs no extra HBM
            self.P = -(-self.P // 128) * 128
        self.cols = PayloadCols(grad=self.grad_col, hess=self.hess_col,
                                cnt=self.cnt_col, value=self.value_col)
        payload_gb = self.n_rows * self.P * 4 / 2**30
        Log.info("fast path payload: %d rows x %d cols, %.2f GB "
                 "(+%.2f GB partition scratch)%s", self.n_rows, self.P,
                 payload_gb, payload_gb,
                 " sharded over %d devices" % ndev if ndev > 1 else "")

        P, score0, idx_col = self.P, self.score0, self.idx_col
        cnt_col_, bvalid_col_ = self.cnt_col, self.bvalid_col

        wide_idx, idxhi_col = self.wide_idx, self.idxhi_col

        def write_idx(pay, rows, idx):
            """Store integer row indices into the index column(s)."""
            if wide_idx:
                pay = pay.at[rows, idxhi_col].set(
                    jnp.floor_divide(idx, jnp.int32(_IDX_RADIX))
                    .astype(jnp.float32))
                idx = jnp.remainder(idx, jnp.int32(_IDX_RADIX))
            return pay.at[rows, idx_col].set(idx.astype(jnp.float32))

        def decode_idx(lo, hi=None):
            """Integer row indices from the index column(s)' values."""
            idx = lo.astype(jnp.int32)
            if wide_idx:
                idx = idx + hi.astype(jnp.int32) * jnp.int32(_IDX_RADIX)
            return idx

        def read_idx(payload):
            return decode_idx(payload[:, idx_col],
                              payload[:, idxhi_col] if wide_idx else None)

        def build_block(bins, label, weight, vmask, score, idx0):
            """One device block: n_loc_b real rows + the GUARD-row tail,
            guard idx pinned to the dead slot."""
            n_loc_b = label.shape[0]
            pay = jnp.zeros((n_loc_b + seg.GUARD, P), jnp.float32)
            pay = pay.at[:n_loc_b, :G].set(bins.T.astype(jnp.float32))
            pay = pay.at[:n_loc_b, G].set(label)
            pay = pay.at[:n_loc_b, G + 1].set(weight)
            pay = pay.at[:n_loc_b, cnt_col_].set(vmask)
            pay = pay.at[:n_loc_b, bvalid_col_].set(vmask)
            pay = write_idx(pay, slice(None),
                            jnp.full(pay.shape[0], n_pad, jnp.int32))
            pay = write_idx(pay, slice(None, n_loc_b),
                            idx0 + jnp.arange(n_loc_b, dtype=jnp.int32))
            pay = pay.at[:n_loc_b, score0:score0 + K].set(score.T)
            return pay

        if mesh is None:
            build = xla_obs.jit(functools.partial(build_block,
                                                 idx0=jnp.int32(0)),
                                site="gbdt.payload_build")
        elif feature_par:
            from jax.sharding import PartitionSpec as PS
            ax = gbdt.mesh_axis
            Gloc_f = G // ndev

            def build_local_feat(bins_l, label_f, weight_f, vmask_f,
                                 score_f):
                # bins arrive feature-sharded [Gloc, N]; gather the full
                # matrix once and lay this shard's columns first — the
                # partitioned grower's histogram then walks only the
                # leading Gloc columns
                my = lax.axis_index(ax)
                bins_all = lax.all_gather(bins_l, ax, axis=0, tiled=True)
                off = my * Gloc_f
                l_ = jnp.arange(G, dtype=jnp.int32)
                perm = jnp.where(l_ < Gloc_f, off + l_,
                                 jnp.where(l_ - Gloc_f < off,
                                           l_ - Gloc_f, l_))
                return build_block(bins_all[perm], label_f, weight_f,
                                   vmask_f, score_f, jnp.int32(0))

            build = xla_obs.jit(jax.shard_map(
                build_local_feat, mesh=mesh,
                in_specs=(PS(ax, None), PS(), PS(), PS(), PS(None, None)),
                out_specs=PS(ax, None), check_vma=False),
                site="gbdt.payload_build_feature_mesh")
        else:
            from jax.sharding import PartitionSpec as PS
            ax = gbdt.mesh_axis

            def build_local(bins_l, label_l, weight_l, vmask_l, score_l):
                my = lax.axis_index(ax)
                return build_block(bins_l, label_l, weight_l, vmask_l,
                                   score_l, my * n_loc)

            build = xla_obs.jit(jax.shard_map(
                build_local, mesh=mesh,
                in_specs=(PS(None, ax), PS(ax), PS(ax), PS(ax),
                          PS(None, ax)),
                out_specs=PS(ax, None), check_vma=False),
                site="gbdt.payload_build_mesh")

        self._build = build
        self.reset(gbdt)
        # quantized-gradient mode (ops.quantize): integer grad/hess
        # columns, int32 histograms, dequantize at the split boundary
        self.quant_on = bool(getattr(gbdt, "_quant_enabled", False))
        self.qmax = int(getattr(gbdt, "_qmax", 0))
        self.grower = _cached_pgrower(gbdt.meta_dev, gbdt.grower_cfg,
                                      ds.max_num_bin, ds, self.cols, self.P,
                                      bundle_map=gbdt.bundle_map
                                      if ds.bundle_info is not None else None,
                                      forced=gbdt.forced_schedule,
                                      mesh=mesh, mesh_axis=gbdt.mesh_axis,
                                      mode=gbdt.parallel_mode or "data",
                                      top_k=int(getattr(gbdt.config, "top_k",
                                                        20) or 20),
                                      quantized=self.quant_on,
                                      qmax=self.qmax)

        obj = gbdt.objective
        snap0, cnt_col = self.snap0, self.cnt_col
        grad_col, hess_col = self.grad_col, self.hess_col
        value_col = self.value_col

        #: how the fused step reads and writes the state columns
        #: (ops.state_columns): "pallas" on a TPU where they sit inside
        #: two lane tiles, one pass over those tiles an update; else "lax"
        self.state_form = form = state_columns.resolve_form(
            self.P, (self.label_col, last_col))
        Log.info("fast path state columns: lanes %d-%d of %d, form=%s",
                 self.label_col, last_col, self.P, form)
        if mesh is not None and form != "lax":
            # GSPMD partitions the lax form's elementwise code; a Pallas
            # call it does not: each device's block of rows (its own
            # GUARD tail included) takes the call on its own
            from jax.sharding import PartitionSpec as PS
            by_rows, by_lanes, replicated = PS(gbdt.mesh_axis, None), \
                PS(None, gbdt.mesh_axis), PS()

            def per_block(fn, in_specs, out_specs):
                return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False)
        else:
            by_rows = by_lanes = replicated = None

            def per_block(fn, in_specs, out_specs):
                return fn

        def read_state(payload, cols):
            """[len(cols), N]: the state columns `cols`, rows in lanes."""
            return per_block(
                lambda pay: state_columns.read_cols(pay, cols, form),
                (by_rows,), by_lanes)(payload)

        def write_state(payload, cols, vecs):
            """payload[:, cols[i]] = vecs[i], one pass for all of them."""
            return per_block(
                lambda pay, vals: state_columns.write_cols(pay, cols, vals,
                                                           form),
                (by_rows, by_lanes), by_rows)(payload, jnp.stack(vecs))

        def add_value(payload, k, lr, moved):
            """score[:, k] += value * lr where `moved`, inside the tile."""
            return per_block(
                lambda pay, dst, scale, on: state_columns.add_scaled(
                    pay, dst, (score0, score0 + K - 1), value_col, scale,
                    on, form),
                (by_rows, replicated, replicated, replicated), by_rows)(
                    payload, score0 + k, lr, moved)

        @functools.partial(xla_obs.jit, site="gbdt.snap_scores",
                           donate_argnums=(0,))
        def snap_scores(payload):
            # K lane-masked selects, not a slice DUS — see
            # seg.payload_col_write (whether the K share a pass is the
            # compiler's; no cell trains K > 1, so no trace says)
            for kk in range(K):
                payload = seg.payload_col_write(payload, snap0 + kk,
                                                payload[:, score0 + kk])
            return payload

        idx_col = self.idx_col

        @functools.partial(xla_obs.jit, site="gbdt.set_bag",
                           donate_argnums=(0,))
        def set_bag(payload, combined):
            """Refresh the count-mask column from an ORIGINAL-order
            valid*bag vector — rows sit in partition order, so the index
            column routes the gather (Bagging, gbdt.cpp:213-295).  Guard
            rows route to the appended dead slot and stay masked out."""
            combined = jnp.concatenate([combined, jnp.zeros(1, jnp.float32)])
            return seg.payload_col_write(payload, cnt_col,
                                         combined[read_idx(payload)])

        rowwise = getattr(obj, "is_rowwise", True) if obj is not None else True

        snap_cols = tuple(range(snap0, snap0 + K))
        idx_cols = (idx_col, idxhi_col) if wide_idx else (idx_col,)

        def _all_grads(payload, *more):
            """Every class's (gradient, hessian) from the snapshot scores,
            and the further state columns `more` as vectors, out of ONE
            read of the state columns."""
            state = read_state(payload, snap_cols + (G, G + 1) + more)
            g, h = obj.get_gradients_multi(state[:K], state[K],
                                           state[K + 1])
            return (g, h) + tuple(state[K + 2 + i]
                                  for i in range(len(more)))

        if rowwise:
            def _class_grads(payload, k):
                """Class k's masked (gradient, hessian) vectors in the
                payload's current row order — shared by the f32 fill and
                the quantized fill."""
                g, h, valid = _all_grads(payload, cnt_col)
                return (jnp.take(g, k, axis=0) * valid,
                        jnp.take(h, k, axis=0) * valid)
        else:
            def _class_grads(payload, k):
                """Objectives that couple rows within a query (lambdarank):
                the objective is handed the snapshot scores as they sit,
                in partition order, with the index column that says which
                original row each is, and answers in the same order
                (`gradients_in_order`).  It owns the one map from
                original rows to its query slots (and such an objective
                trains one tree an iteration, K = 1), so the scores take ONE
                permutation in and the gradients one out; nothing passes
                through original row order.  On `msltr-train` the two
                read 0.041 s on the chip, 13% of the iteration, where
                three permutations round a padded [Q, S] layout read 0.64
                (PERF.md section 6, PR 31)."""
                score, valid, *idx = read_state(
                    payload, (snap0, cnt_col) + idx_cols)
                g, h = obj.gradients_in_order(score, decode_idx(*idx))
                return g * valid, h * valid

        def _fill_body(payload, k):
            """Write class k's gradients into the grad/hess columns —
            shared by the piecewise (profiled) and fused paths."""
            with phase("grad"):
                return write_state(payload, (grad_col, hess_col),
                                   _class_grads(payload, k))

        @functools.partial(xla_obs.jit, site="gbdt.fill_class",
                           donate_argnums=(0,), static_argnames=("k",))
        def fill_class(payload, k):
            return _fill_body(payload, k)

        if self.quant_on:
            from ..ops.quantize import quantize_pair
            qmax_f = float(self.qmax)

            def _fill_body_quant(payload, k, qseed):
                """Quantized fill: class k's masked gradients are scaled
                to the integer grid and stochastically rounded; the
                integer-valued columns feed the int32 histogram engine
                and the [2] scale pair rides to the grower's dequantize
                boundary."""
                with phase("grad"):
                    gk, hk = _class_grads(payload, k)
                    qg, qh, qscale = quantize_pair(gk, hk, qseed, qmax_f)
                    return write_state(payload, (grad_col, hess_col),
                                       (qg, qh)), qscale

            @functools.partial(xla_obs.jit,
                               site="gbdt.fill_class_quant",
                               donate_argnums=(0,),
                               static_argnames=("k",))
            def fill_class_quant(payload, k, qseed):
                return _fill_body_quant(payload, k, qseed)

        @functools.partial(xla_obs.jit, site="gbdt.apply_score",
                           donate_argnums=(0,), static_argnames=("k",))
        def apply_score(payload, lr, k):
            return add_value(payload, k, lr, True)

        grower = self.grower
        bvalid_col = self.bvalid_col
        sample_hook = getattr(gbdt, "_fast_sample_hook", None)

        def _grow_and_score(payload, aux, fmask, lr, k, qscale=None):
            args = (payload, aux, fmask) if qscale is None \
                else (payload, aux, fmask, qscale)
            out, payload, aux = grower.__wrapped__(*args) \
                if hasattr(grower, "__wrapped__") else grower(*args)
            # stumps must not move the scores (gbdt.cpp stops instead)
            with phase("score"):
                payload = add_value(payload, k, lr, out["num_leaves"] > 1)
            return out, payload, aux

        @functools.partial(xla_obs.jit, site="gbdt.step",
                           donate_argnums=(0, 1))
        def step(payload, aux, fmask, lr, k):
            """One fused tree: gradients -> grow -> conditional score add.
            Fusing the per-tree chain into one program leaves a single
            launch plus the packed result fetch.  k is traced (one compile
            serves every class)."""
            payload = _fill_body(payload, k)
            return _grow_and_score(payload, aux, fmask, lr, k)

        if self.quant_on:
            @functools.partial(xla_obs.jit, site="gbdt.step_quant",
                               donate_argnums=(0, 1))
            def step_quant(payload, aux, fmask, lr, k, qseed):
                """Quantized fused tree: the scale pair never leaves the
                program — quantize, int32-histogram growth and the score
                add are one dispatch, like the f32 step."""
                payload, qscale = _fill_body_quant(payload, k, qseed)
                return _grow_and_score(payload, aux, fmask, lr, k, qscale)

        def _write_sampled(payload, g, h, k, gw, cm=None):
            """Class k's weighted gradients, and the selection's count
            mask where it is new, in one write."""
            cols, vecs = (grad_col, hess_col), (jnp.take(g, k, axis=0) * gw,
                                                jnp.take(h, k, axis=0) * gw)
            if cm is not None:
                cols, vecs = cols + (cnt_col,), vecs + (cm,)
            return write_state(payload, cols, vecs)

        @functools.partial(xla_obs.jit, site="gbdt.step_sampled",
                           donate_argnums=(0, 1))
        def step_sampled(payload, aux, fmask, lr, k, key, enabled):
            """Fused tree with a per-iteration row-sampling hook (GOSS):
            gradients for ALL classes come from the snapshot, the hook
            derives (gradient-weight, count-mask) from them off the
            pristine valid column, and class k's weighted gradients plus
            the selection mask land in the working columns."""
            with phase("grad"):
                g, h, valid = _all_grads(payload, bvalid_col)
                gw, cm = sample_hook(g * valid, h * valid, valid, key,
                                     enabled)
                payload = _write_sampled(payload, g, h, k, gw, cm)
            return _grow_and_score(payload, aux, fmask, lr, k)

        gweight_col = self.gweight_col

        @functools.partial(xla_obs.jit, site="gbdt.apply_sample_masks",
                           donate_argnums=(0,))
        def apply_sample_masks(payload, key, enabled):
            """Multiclass prelude: the selection is identical for every
            class tree of an iteration, so it is drawn ONCE and written
            into payload COLUMNS (gweight + cnt) — each class tree
            repartitions the rows, and columns ride the partition while
            standalone mask arrays would go stale after the first tree."""
            g, h, valid = _all_grads(payload, bvalid_col)
            gw, cm = sample_hook(g * valid, h * valid, valid, key, enabled)
            return write_state(payload, (gweight_col, cnt_col), (gw, cm))

        @functools.partial(xla_obs.jit, site="gbdt.step_masked",
                           donate_argnums=(0, 1))
        def step_masked(payload, aux, fmask, lr, k):
            with phase("grad"):
                # (the count mask is the prelude's: it rides the partition)
                g, h, gw = _all_grads(payload, gweight_col)
                payload = _write_sampled(payload, g, h, k, gw)
            return _grow_and_score(payload, aux, fmask, lr, k)

        bmap_fs = gbdt.bundle_map
        meta_fs = gbdt.meta_dev
        depth_iters_fs = max(gbdt.grower_cfg.num_leaves - 1, 1)

        def _tree_add_body(payload, tree_dev, leaf_scaled, k, col_of):
            """score[:, k] += leaf_scaled[leaf(x)] routed by the payload's
            OWN bin columns — rows sit in partition order and the bins ride
            along, so DART's drop/normalize score edits (and any other
            tree replay) never need the original row order.  col_of maps a
            per-row global storage-column array to this payload's layout
            (identity everywhere except feature-parallel's owned-first
            permutation)."""
            bins_cols = payload[:, :G]
            body = _make_decision_body(
                tree_dev, meta_fs, bmap_fs,
                lambda f: jnp.take_along_axis(
                    bins_cols, col_of(bmap_fs.f_group[f])[:, None],
                    axis=1)[:, 0].astype(jnp.int32))
            nd = lax.fori_loop(0, depth_iters_fs, body,
                               jnp.zeros(payload.shape[0], jnp.int32))
            return seg.payload_col_write(payload, score0 + k,
                                         leaf_scaled[~nd], "add")

        if feature_par:
            from jax.sharding import PartitionSpec as PS
            ax_f = gbdt.mesh_axis
            Gloc_pta = G // ndev

            def _pta_local(payload_l, tree_dev, leaf_scaled, k):
                my = lax.axis_index(ax_f)
                off = my * Gloc_pta

                def col_of(g):
                    return jnp.where(g < off, Gloc_pta + g,
                                     jnp.where(g < off + Gloc_pta,
                                               g - off, g))

                return _tree_add_body(payload_l, tree_dev, leaf_scaled, k,
                                      col_of)

            payload_tree_add = xla_obs.jit(jax.shard_map(
                _pta_local, mesh=mesh,
                in_specs=(PS(ax_f, None), PS(), PS(), PS()),
                out_specs=PS(ax_f, None), check_vma=False),
                donate_argnums=(0,),
                site="gbdt.payload_tree_add_mesh")
        else:
            @functools.partial(xla_obs.jit,
                               site="gbdt.payload_tree_add",
                               donate_argnums=(0,))
            def payload_tree_add(payload, tree_dev, leaf_scaled, k):
                return _tree_add_body(payload, tree_dev, leaf_scaled, k,
                                      lambda g: g)

        @functools.partial(xla_obs.jit, site="gbdt.apply_const_score",
                           donate_argnums=(0,))
        def apply_const_score(payload, delta, k):
            return seg.payload_col_write(payload, score0 + k, delta, "add")

        @functools.partial(xla_obs.jit, site="gbdt.scale_score",
                           donate_argnums=(0,))
        def scale_score(payload, factor, k):
            return seg.payload_col_write(payload, score0 + k, factor, "mul")

        @functools.partial(xla_obs.jit, site="gbdt.step_rf",
                           donate_argnums=(0, 1))
        def step_rf(payload, aux, fmask):
            """RF's fused tree (rf.hpp Boosting): gradients of the ZERO
            score masked by the bagged count column, then growth — one
            dispatch, like the base fast path's _step.  Scoring is the
            caller's job (running average, not an additive update)."""
            zeros = jnp.zeros((K, n_rows), jnp.float32)
            g, h = obj.get_gradients_multi(zeros, payload[:, G],
                                           payload[:, G + 1])
            valid = payload[:, cnt_col]
            payload = seg.payload_col_write(payload, grad_col, g[0] * valid)
            payload = seg.payload_col_write(payload, hess_col, h[0] * valid)
            return grower.__wrapped__(payload, aux, fmask) \
                if hasattr(grower, "__wrapped__") else grower(payload, aux,
                                                              fmask)

        @functools.partial(xla_obs.jit, site="gbdt.rf_score_update",
                           donate_argnums=(0,))
        def rf_score_update(payload, tree_dev, leaf_scaled, m):
            """score = (score*m + tree)/(m+1) in one dispatch."""
            payload = seg.payload_col_write(payload, score0,
                                            m / (m + 1.0), "mul")
            return payload_tree_add.__wrapped__(
                payload, tree_dev, leaf_scaled / (m + 1.0), jnp.int32(0))

        self._payload_tree_add = payload_tree_add
        self._apply_const_score = apply_const_score
        self._scale_score = scale_score
        self._step_rf = step_rf
        self._rf_score_update = rf_score_update
        self._snap_scores = snap_scores
        self._fill_class = fill_class
        self._apply_score = apply_score
        self._step = step
        self._fill_class_quant = fill_class_quant if self.quant_on else None
        self._step_quant = step_quant if self.quant_on else None
        self._step_sampled = step_sampled if sample_hook is not None else None
        self._apply_sample_masks = apply_sample_masks \
            if sample_hook is not None else None
        self._step_masked = step_masked if sample_hook is not None else None
        self._set_bag = set_bag
        #: fused boosting-window programs keyed by (J, with_bag) — built
        #: lazily by window_program(); survive sync-backs like the other
        #: jitted closures
        self._window_cache: Dict = {}
        #: what the trees grown on this state split on and what their
        #: partitions moved (`rows_staged`: the rows of the children that
        #: lay second in their parents' ranges, which the Pallas kernels
        #: stage and move once more), a tree an entry in the order they
        #: were finished: read off the tree's own fetch by
        #: `_finish_tree_host`, so it costs no dispatch and no transfer.
        #: `missing_splits`: the numerical splits on a column whose mapper
        #: has a NaN or zero-as-missing bin; `default_left_splits`: those
        #: of them that send the missing rows left; `rows_missing`: the
        #: rows such splits routed by that direction and not by the
        #: threshold (the split leaf's histogram count at the missing bin)
        self.counters: Dict[str, List[int]] = {
            "splits": [], "categorical_splits": [],
            "missing_splits": [], "default_left_splits": [],
            "rows_partitioned": [], "rows_staged": [], "rows_missing": []}

    def window_program(self, J: int, with_bag: bool):
        """One jitted, donated device program for a whole boosting window:
        a lax.scan over J iterations whose body is EXACTLY the sequential
        fast path's per-iteration programs inlined (`_set_bag` ->
        `_snap_scores` -> K x `_step` through their ``__wrapped__`` seam),
        so every scan step computes the same graph the per-tree dispatch
        loop would — the byte-identity contract of boost_window.  Inputs:
        payload, aux (donated), the per-step feature masks [J, F], the
        per-step ORIGINAL-order bag masks [J, n_pad] (a dummy [J, 1] when
        bagging is off), and the shrinkage scalar.  Outputs: the stacked
        packed split records [J, K, ...] plus the carried payload/aux —
        the records come back to the host in ONE `_fetch_packed`
        transfer."""
        key = (int(J), bool(with_bag))
        prog = self._window_cache.get(key)
        if prog is not None:
            xla_obs.cache_event("gbdt.window_cache", "hit")
            return prog
        xla_obs.cache_event("gbdt.window_cache", "miss")
        K = self.K
        step_fn = self._step.__wrapped__
        snap_fn = self._snap_scores.__wrapped__
        bag_fn = self._set_bag.__wrapped__

        def window(payload, aux, fmasks, bags, lr):
            def step(carry, xs):
                payload, aux = carry
                if with_bag:
                    payload = bag_fn(payload, xs["bag"])
                if K > 1:
                    payload = snap_fn(payload)
                outs = []
                for k in range(K):
                    out, payload, aux = step_fn(payload, aux, xs["fmask"],
                                                lr, jnp.int32(k))
                    outs.append(out)
                stacked = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *outs)
                return (payload, aux), stacked

            xs = {"fmask": fmasks}
            if with_bag:
                xs["bag"] = bags
            (payload, aux), recs = lax.scan(step, (payload, aux), xs,
                                            length=J)
            return recs, payload, aux

        prog = xla_obs.jit(window, site="gbdt.window",
                           donate_argnums=(0, 1))
        self._window_cache[key] = prog
        return prog

    def reset(self, gbdt: "GBDT") -> None:
        """(Re)build the payload from the legacy-order state — used on first
        entry and when re-entering the fast path after a sync back (the
        jitted closures and the grower survive, so no retracing)."""
        with tracing.span("booster/payload", rows=self.n_rows,
                          lanes=self.P, devices=self.ndev):
            self.payload = self._build(gbdt.bins_dev, gbdt.label_dev,
                                       gbdt.weight_dev, gbdt.valid_mask,
                                       gbdt.score)
            self.aux = jnp.zeros_like(self.payload)
        self._bag_dirty = True  # cnt col holds the plain valid mask

    def host_idx(self) -> np.ndarray:
        """Integer original-row indices of every payload row (host)."""
        idx = np.asarray(syncs.device_get(
            self.payload[:, self.idx_col], label="score_fetch")) \
            .astype(np.int64)
        if self.wide_idx:
            hi = np.asarray(syncs.device_get(
                self.payload[:, self.idxhi_col],
                label="score_fetch")).astype(np.int64)
            idx = idx + hi * int(_IDX_RADIX)
        return idx

    def score_cols_device(self) -> List[jax.Array]:
        """Device views whose host fetch reconstructs the original-order
        scores: the contiguous [idx | score_0..score_{K-1}] column block,
        plus the radix-hi index column on the wide layout.  Exposed so an
        eval round can fold them into ONE packed transfer."""
        cols = [self.payload[:, self.idx_col:self.score0 + self.K]]
        if self.wide_idx:
            cols.append(self.payload[:, self.idxhi_col])
        return cols

    def scores_from_host(self, h: np.ndarray,
                         hi: Optional[np.ndarray] = None) -> np.ndarray:
        """[K, n_pad] ORIGINAL-order scores from the fetched column block
        (and radix-hi column on the wide layout).  Guard rows carry the
        dead-slot index and are dropped."""
        idx = h[:, 0].astype(np.int64)
        if self.wide_idx:
            idx = idx + hi.astype(np.int64) * int(_IDX_RADIX)
        keep = idx < self.n_pad
        out = np.zeros((self.K, self.n_pad), np.float32)
        out[:, idx[keep]] = h[keep, 1:1 + self.K].T
        return out

    def raw_scores(self) -> np.ndarray:
        """[K, n_pad] scores in ORIGINAL row order (host)."""
        host = syncs.device_get(self.score_cols_device(),
                                label="score_fetch")
        h = np.asarray(host[0])
        hi = np.asarray(host[1]) if self.wide_idx else None
        return self.scores_from_host(h, hi)


def _feature_meta_device(ds: BinnedDataset) -> FeatureMeta:
    m = ds.bin_mappers
    return FeatureMeta(
        num_bin=jnp.asarray([mm.num_bin for mm in m], jnp.int32),
        missing_type=jnp.asarray([mm.missing_type for mm in m], jnp.int32),
        default_bin=jnp.asarray([mm.default_bin for mm in m], jnp.int32),
        is_trivial=jnp.asarray([mm.is_trivial for mm in m], jnp.bool_),
        is_categorical=jnp.asarray([mm.bin_type == BIN_TYPE_CATEGORICAL for mm in m], jnp.bool_),
        penalty=jnp.asarray(ds.feature_penalty, jnp.float32),
        monotone=jnp.asarray(ds.monotone_constraints, jnp.int32),
    )


@functools.partial(xla_obs.jit, site="gbdt.make_vals",
                   static_argnames=("k",))
def _make_vals(grads, hesss, gmask, cmask, k):
    """Per-row (grad, hess, count) columns for the histogram kernel.  gmask
    scales gradient/hessian mass (bagging zeroes, GOSS amplifies), cmask is
    the 0/1 row-count weight (min_data_in_leaf, leaf counts)."""
    return jnp.stack([grads[k] * gmask, hesss[k] * gmask, cmask], axis=1)


@functools.partial(xla_obs.jit, site="gbdt.update_score_k",
                   static_argnames=("k",))
def _update_score_k(score, leaf_id, leaf_out, k):
    return score.at[k].add(leaf_out[leaf_id])


def _make_decision_body(tree_dev, meta: FeatureMeta, bmap: BundleMap,
                        gather_raw):
    """One traversal step over per-row node ids (Tree::DecisionInner
    semantics, tree.h:234-249 / 288-295), shared by the column-major
    score replay and the payload-order replay — only the raw-bin gather
    differs between the two layouts."""
    sf, sb, dl, lc, rc = (tree_dev["split_feature"], tree_dev["split_bin"],
                          tree_dev["default_left"], tree_dev["left_child"],
                          tree_dev["right_child"])
    is_cat = tree_dev["split_is_cat"]
    cat_bitset = tree_dev["split_cat_bitset"]

    def body(_, nd):
        is_leaf = nd < 0
        ndc = jnp.maximum(nd, 0)
        f = sf[ndc]
        raw = gather_raw(f)
        fbin = decode_bin(raw, bmap.f_identity[f], bmap.f_offset[f],
                          meta.num_bin[f], meta.default_bin[f])
        mt = meta.missing_type[f]
        is_missing = ((mt == 2) & (fbin == meta.num_bin[f] - 1)) | \
                     ((mt == 1) & (fbin == meta.default_bin[f]))
        go_left_num = jnp.where(is_missing, dl[ndc], fbin <= sb[ndc])
        go_left = jnp.where(is_cat[ndc], cat_bitset[ndc, fbin], go_left_num)
        child = jnp.where(go_left, lc[ndc], rc[ndc])
        return jnp.where(is_leaf, nd, child)

    return body


def _mark_critical_path(fn):
    """Run `fn` under the sync-audit's tree->tree critical-path marker:
    any blocking host fetch inside it is a pipeline stall and counts
    against the `host_syncs_per_iter.critical_path` pin."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with syncs.critical_path():
            return fn(*args, **kwargs)
    return wrapped


@functools.partial(xla_obs.jit, site="gbdt.traverse_update",
                   static_argnames=("depth_iters", "k"))
def _traverse_update(bins_v, score_kv, leaf_out, tree_dev, meta: FeatureMeta,
                     bmap: BundleMap, depth_iters: int, k: int):
    """Add one tree's (shrunk) outputs to row k of a [K, M] score matrix by
    vectorized bin-level traversal."""
    M = bins_v.shape[1]
    rows = jnp.arange(M)
    body = _make_decision_body(
        tree_dev, meta, bmap,
        lambda f: bins_v[bmap.f_group[f], rows].astype(jnp.int32))
    nd = jax.lax.fori_loop(0, depth_iters, body, jnp.zeros(M, jnp.int32))
    return score_kv.at[k].add(leaf_out[~nd])


class GBDT:
    """The boosting engine behind Booster."""

    def __init__(self, config, train_set: BinnedDataset, objective,
                 metrics: List, init_model: Optional[GBDTModel] = None):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.train_metrics = metrics
        self.iter = 0
        self.timer = PhaseTimer(bool(getattr(config, "tpu_profile_phases",
                                             False)))
        # frontier-batch telemetry: sequential device rounds the growers
        # paid, accumulated per finished tree (bench split_rounds_per_tree;
        # == num_leaves-1 per tree unless tpu_frontier_batch > 1 engaged)
        self.split_rounds_total = 0
        self.trees_finished = 0
        self.shrinkage_rate = float(config.learning_rate)
        self.num_class = int(config.num_class)
        self.num_tree_per_iteration = objective.num_model_per_iteration \
            if objective is not None else self.num_class

        self.model = init_model if init_model is not None else GBDTModel()
        self.model.num_class = self.num_class
        self.model.num_tree_per_iteration = self.num_tree_per_iteration
        self.model.max_feature_idx = train_set.num_features - 1
        self.model.feature_names = list(train_set.feature_names)
        self.model.feature_infos = train_set.feature_infos()
        if objective is not None:
            self.model.objective_str = objective.to_string()
        self.num_init_iteration = self.model.current_iteration

        # -- parallel learner selection (tree_learner factory parity,
        #    src/treelearner/tree_learner.cpp:9-33: the requested mode times
        #    the visible device count decides the learner) ------------------
        self.parallel_mode: Optional[str] = None
        self.mesh = None
        self.mesh_axis = "workers"
        self._fmask_pad = 0
        tl = str(getattr(config, "tree_learner", "serial") or "serial")
        if tl != "serial":
            devices = jax.devices()
            # num_machines semantics DIFFER from the reference on purpose:
            # there it counts socket/MPI HOSTS; here the parallel unit is a
            # mesh DEVICE (jax.devices() already spans all hosts under
            # jax.distributed), so num_machines caps the devices used.
            # Reference configs that set num_machines=<hosts> get at least
            # that much parallelism.  See docs/DISTRIBUTED.md.
            nm = int(getattr(config, "num_machines", 1) or 1)
            ndev = len(devices) if nm <= 1 else min(nm, len(devices))
            n_pad_ = train_set.num_data_padded
            if ndev <= 1:
                Log.warning(
                    "tree_learner=%s requested but only one device is "
                    "visible; training with the serial learner", tl)
            elif tl in ("data", "voting") and n_pad_ % ndev != 0:
                Log.warning(
                    "tree_learner=%s: padded row count %d is not divisible "
                    "by %d devices; training with the serial learner",
                    tl, n_pad_, ndev)
            else:
                from jax.sharding import Mesh
                self.parallel_mode = tl
                self.mesh = Mesh(np.array(devices[:ndev]), (self.mesh_axis,))
                Log.info("Using %s-parallel tree learner over %d devices",
                         tl, ndev)

        # forced splits: compile the JSON into a static BFS schedule for the
        # partitioned grower (serial_tree_learner.cpp:546-701)
        self.forced_schedule = None
        fs_path = str(getattr(config, "forcedsplits_filename", "") or "")
        if fs_path:
            from .forced import build_forced_schedule, load_forced_json
            self.forced_schedule = build_forced_schedule(
                load_forced_json(fs_path), train_set.bin_mappers,
                int(config.num_leaves))
            if self.forced_schedule is not None:
                Log.info("Loaded forced splits from %s (%d nodes)",
                         fs_path, len(self.forced_schedule.feat))

        # quantized-gradient training (gradient_quantization, ops.quantize):
        # per-iteration int gradient/hessian columns + int32 histograms on
        # the partition-ordered fast path.  Plain gbdt boosting only (GOSS
        # amplifies gradients inside its fused step, DART/RF replay trees
        # through their own steps) and unforced (the forced override reads
        # raw f32 hist views); anything else trains f32 with a warning.
        self._quant_enabled = False
        self._qmax = 0
        self.quant_report = None
        if bool(getattr(config, "gradient_quantization", False)):
            if type(self) is not GBDT or self.forced_schedule is not None:
                Log.warning(
                    "gradient_quantization supports plain gbdt boosting "
                    "without forced splits; training with f32 gradients")
            else:
                from ..ops.quantize import (F32_GH_BYTES, QUANT_GH_BYTES,
                                            derive_qmax)
                qdtype = str(getattr(config, "gradient_quant_dtype",
                                     "int16") or "int16")
                # trace-time int32 overflow guard: rows-per-leaf x max|q|
                # must stay below 2^31 (raises when it cannot)
                self._qmax = derive_qmax(train_set.num_data_padded, qdtype)
                self._quant_enabled = True
                gh_bytes = QUANT_GH_BYTES[qdtype]
                self.quant_report = {
                    "dtype": qdtype, "qmax": self._qmax,
                    "hist_gh_bytes_per_row": gh_bytes,
                    "hist_bytes_reduction_vs_f32": F32_GH_BYTES / gh_bytes,
                }
                Log.info(
                    "gradient quantization on: %s grid (qmax=%d, %.1fx "
                    "fewer grad/hess bytes per histogram dispatch)",
                    qdtype, self._qmax, F32_GH_BYTES / gh_bytes)

        # EFB bundle decode map (identity when the dataset is unbundled).
        # Bundled + data/voting parallel trains on the MESH FAST PATH
        # (partitioned engine per shard, full-psum of the small bundled
        # histogram, replicated search — grower2 mesh modes); the masked
        # legacy mesh grower cannot decode bundles, so feature-parallel or
        # a fast-ineligible config falls back to the serial learner.
        self._mesh_fast_only = False
        if train_set.bundle_info is not None:
            self.bundle_map = bundle_map_from_info(train_set.bundle_info)
            if self.parallel_mode == "feature":
                Log.warning("EFB-bundled dataset: feature-parallel is not "
                            "supported with bundling; training with the "
                            "serial learner")
                self.parallel_mode = None
                self.mesh = None
            elif self.parallel_mode is not None:
                self._mesh_fast_only = True
        else:
            self.bundle_map = identity_bundle_map(train_set.num_features)

        # -- device state ----------------------------------------------------
        if self.parallel_mode == "feature":
            # uploaded padded + feature-sharded in _setup_parallel_learner;
            # avoid a second full-matrix host->device transfer here
            self.bins_dev = None
        else:
            from ..io.nbits import get_packed, should_pack, \
                unpack_nibbles_device
            if should_pack(train_set):
                # dense_nbits_bin parity at the transfer boundary: ship the
                # nibble-packed matrix (half the H2D bytes), unpack on chip
                self.bins_dev = unpack_nibbles_device(
                    get_packed(train_set), train_set.bins.shape[0])
            else:
                self.bins_dev = jnp.asarray(train_set.bins)
        self.meta_dev = _feature_meta_device(train_set)
        self.valid_mask = jnp.asarray(train_set.valid_row_mask())
        md = train_set.metadata
        self.label_dev = jnp.asarray(train_set.padded(md.label))
        self.weight_dev = jnp.asarray(train_set.padded(
            md.weight if md.weight is not None else np.ones(train_set.num_data, np.float32)))
        n_pad = train_set.num_data_padded

        row_chunk = 16384 if n_pad % 16384 == 0 else n_pad
        has_cat = any(m.bin_type == BIN_TYPE_CATEGORICAL and not m.is_trivial
                      for m in train_set.bin_mappers)
        self.grower_cfg = GrowerConfig(
            num_leaves=int(config.num_leaves),
            max_depth=int(config.max_depth),
            lambda_l1=float(config.lambda_l1),
            lambda_l2=float(config.lambda_l2),
            max_delta_step=float(config.max_delta_step),
            min_data_in_leaf=int(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            row_chunk=row_chunk,
            with_categorical=has_cat,
            max_cat_threshold=int(config.max_cat_threshold),
            cat_l2=float(config.cat_l2),
            cat_smooth=float(config.cat_smooth),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=int(config.min_data_per_group),
            hist_impl=str(getattr(config, "tpu_histogram_impl", "auto")
                          or "auto"),
            hist_pool_slots=self._hist_pool_slots(config, train_set),
            with_monotone=bool(np.any(train_set.monotone_constraints)),
            frontier_batch=max(1, int(getattr(config, "tpu_frontier_batch",
                                              1) or 1)))
        self.grower = _cached_grower(self.meta_dev, self.grower_cfg,
                                     train_set.max_num_bin, train_set,
                                     bundle_map=self.bundle_map
                                     if train_set.bundle_info is not None
                                     else None,
                                     forced=self.forced_schedule
                                     if self.parallel_mode is None else None)
        # partition-ordered fast path (built lazily on first eligible iter;
        # the state object survives sync-backs so re-entry never retraces)
        self._fast: Optional[_FastState] = None
        self._fast_active = False

        # scores: [K, N_pad] on device
        K = self.num_tree_per_iteration
        self.score = jnp.zeros((K, n_pad), jnp.float32)
        self.init_score_value = 0.0
        if md.init_score is not None:
            init = train_set.padded(md.init_score.astype(np.float32))
            self.score = jnp.broadcast_to(init, (K, n_pad)).astype(jnp.float32)
        if objective is not None:
            objective.init(md.label, md.weight, md.query_boundaries)

        # validation sets
        self.valid_sets: List[Tuple[str, BinnedDataset, jax.Array, jax.Array, List]] = []

        # non-finite sentinel (runtime/resilience.py): screen every
        # iteration's fetched tree outputs for NaN/inf under the
        # configurable abort-vs-rollback policy.  'off' costs nothing.
        self._sentinel_policy = str(getattr(config, "sentinel_nonfinite",
                                            "off") or "off").lower()
        if self._sentinel_policy not in ("off", "abort", "rollback"):
            Log.warning("sentinel_nonfinite=%s is not off|abort|rollback; "
                        "using abort", self._sentinel_policy)
            self._sentinel_policy = "abort"

        # async boosting pipeline (pipeline_depth, ISSUE 5): how many trees
        # the device may run ahead of host Tree assembly on the fused fast
        # path.  0 = synchronous classic loop; 1 (default) overlaps tree
        # t's packed D2H fetch + host assembly with tree t+1's device
        # compute; 2 runs two trees ahead.  The legacy/profiled/renew/RF
        # paths always run synchronously (honest fallback), and an armed
        # non-finite sentinel disables the pipeline — its abort/rollback
        # contract screens every iteration's outputs before the next one
        # is dispatched.
        self._pipeline_depth = max(0, min(
            int(getattr(config, "pipeline_depth", 1) or 0), 8))
        if self._sentinel_policy != "off" and self._pipeline_depth > 0:
            Log.info("sentinel_nonfinite=%s: the dispatch pipeline is "
                     "disabled so each iteration's tree outputs are "
                     "screened before the next dispatch",
                     self._sentinel_policy)
        self._assembler: Optional[TreeAssembler] = None
        #: engine-run iteration whose trees ALL failed to split, observed
        #: by the assembler thread after later iterations were already
        #: dispatched; flush() rolls the over-dispatch back
        self._pipe_stop_iter: Optional[int] = None
        self._pipe_k_seen = 0
        self._pipe_any_split = False
        self._in_flush = False

        # fused boosting window (boost_window=J, ISSUE 13): one donated
        # lax.scan program trains J iterations per dispatch; the driver
        # below consumes the window one update() at a time (parked host
        # trees + lazy valid-score replay), truncating to the reported
        # iteration by exact snapshot replay when an observation point
        # (eval, snapshot, rollback, reset_parameter) lands mid-window.
        self._boost_window = max(1, int(getattr(config, "boost_window", 1)
                                        or 1))
        #: the open (still-consuming) window, or None
        self._win: Optional[Dict] = None
        #: fully-consumed windows whose parked trees have not all been
        #: appended yet (drain still in flight) — strictly ordered
        self._win_unappended: List[Dict] = []
        #: adaptive effective window length: shrinks to the observed
        #: truncation point when observations land mid-window, grows back
        #: toward boost_window after consecutive clean windows
        self._win_adapt = self._boost_window
        self._win_clean = 0
        #: engine.train's look-ahead hint: iterations until the next
        #: observation point (None = unknown; adaptive length governs)
        self._win_horizon: Optional[int] = None

        # deterministic per-subsystem RNG (bagging / feature sampling)
        seed = int(getattr(config, "seed", 0) or 0)
        self.bagging_rng = Random(partition_seed(seed + int(config.bagging_seed), 1))
        self.feature_rng = Random(partition_seed(seed + int(config.feature_fraction_seed), 2))
        self.bag_mask_host = np.ones(n_pad, dtype=np.float32)
        self.bag_mask_host[train_set.num_data:] = 0.0

        self._boosted_from_average = False
        self._grad_fn = None
        self._leaf_transform = None
        self._bag_cmask = jnp.asarray(self.bag_mask_host)
        # RF evaluates metrics with objective=None: scores already hold
        # converted outputs (rf.hpp EvalOneMetric)
        self._metric_objective = objective

        if self.parallel_mode is not None:
            self._setup_parallel_learner()

        # continued training (input_model / init_model, gbdt.cpp:64-169 with
        # num_init_iteration_ > 0): map the loaded trees' double thresholds
        # back onto this dataset's bins, then replay them onto the score
        # entirely on device
        if self.num_init_iteration > 0:
            K = self.num_tree_per_iteration
            for idx, tree in enumerate(self.model.trees):
                tree.set_bin_thresholds(train_set.bin_mappers)
                self._add_tree_to_train_score(tree, idx % K, 1.0)

    @property
    def engines(self) -> Optional[Dict[str, str]]:
        """Histogram and partition implementations the fast path's grower
        resolved (``{"histogram": ..., "partition": ...}``); None until the
        fast path has been built."""
        return self._fast.grower.engines if self._fast is not None else None

    @staticmethod
    def _hist_pool_slots(config, train_set: BinnedDataset) -> int:
        """histogram_pool_size (MB, reference HistogramPool semantics) ->
        pool slot count for the partitioned grower.  -1 keeps one slot per
        leaf unless that alone would exceed a 4 GB HBM budget, in which
        case the pool auto-caps with a warning."""
        L = int(config.num_leaves)
        slot_bytes = (train_set.bins.shape[0] * train_set.max_num_bin
                      * 3 * 4)
        pool_mb = float(getattr(config, "histogram_pool_size", -1.0) or -1.0)
        if pool_mb > 0:
            return max(2, min(L, int(pool_mb * 1024 * 1024 // max(slot_bytes, 1))))
        budget = 4 << 30
        if L * slot_bytes > budget:
            slots = max(2, int(budget // max(slot_bytes, 1)))
            Log.warning(
                "histogram memory for %d leaves would be %.1f GB; capping "
                "the histogram pool at %d slots (set histogram_pool_size "
                "to control this)", L, L * slot_bytes / 2**30, slots)
            return slots
        return 0

    def _setup_parallel_learner(self) -> None:
        """Build the shard_map'd grower and place training state on the mesh.

        data/voting: rows sharded (bins [F, N] over N, per-row vectors over
        N, scores [K, N] over N); feature: features sharded (bins/fmask over
        F, rows replicated).  The grower output tree is replicated except
        the per-row leaf ids, which follow the row sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.feature_parallel import pad_features, pad_feature_meta

        mode = self.parallel_mode
        ax = self.mesh_axis
        n = self.mesh.shape[ax]
        meta = self.meta_dev
        if mode == "feature":
            bins_h, _, f_padded = pad_features(
                self.train_set.bins, np.ones(self.train_set.num_features,
                                             bool), n)
            self._fmask_pad = f_padded - self.train_set.num_features
            meta = pad_feature_meta(meta, f_padded)
            self.bins_dev = jax.device_put(
                jnp.asarray(bins_h), NamedSharding(self.mesh, P(ax, None)))
            row_spec, vals_spec, score_spec = P(), P(), P()
            bins_spec, fmask_spec = P(ax, None), P(ax)
            leaf_id_spec = P()
        else:
            self.bins_dev = jax.device_put(
                self.bins_dev, NamedSharding(self.mesh, P(None, ax)))
            row_spec, vals_spec, score_spec = P(ax), P(ax, None), P(None, ax)
            bins_spec, fmask_spec = P(None, ax), P()
            leaf_id_spec = P(ax)
        self._row_sharding = NamedSharding(self.mesh, row_spec)
        self._score_sharding = NamedSharding(self.mesh, score_spec)

        for attr in ("valid_mask", "label_dev", "weight_dev", "_bag_cmask"):
            setattr(self, attr, jax.device_put(
                getattr(self, attr), self._row_sharding))
        self.score = jax.device_put(self.score, self._score_sharding)

        if self._mesh_fast_only:
            # bundled dataset: only the partitioned mesh fast path can
            # decode EFB columns — the masked mesh grower is not built, and
            # a fast-ineligible config falls back to the serial learner
            return

        cfg = self.grower_cfg
        if mode in ("data", "voting"):
            # inside shard_map the histogram kernel sees only the local
            # shard's rows; its chunking invariant must hold for N/n
            local_n = self.train_set.num_data_padded // n
            cfg = cfg._replace(
                row_chunk=16384 if local_n % 16384 == 0 else local_n)
        grow_core = make_tree_grower(
            meta, cfg, self.train_set.max_num_bin,
            axis_name=ax, jit=False, mode=mode, num_machines=n,
            top_k=int(getattr(self.config, "top_k", 20)))
        out_specs = dict.fromkeys((
            "num_leaves", "leaf_value", "leaf_count", "leaf_sum_g",
            "leaf_sum_h", "split_feature", "split_bin", "split_gain",
            "default_left", "split_is_cat", "split_cat_bitset", "left_child",
            "right_child", "internal_value", "internal_count"), P())
        out_specs["leaf_id"] = leaf_id_spec
        # check_vma off: every shard carries the replicated winner through
        # the fori_loop, which the varying-axes tracker cannot prove
        self.grower = xla_obs.jit(jax.shard_map(
            grow_core, mesh=self.mesh,
            in_specs=(bins_spec, vals_spec, fmask_spec),
            out_specs=out_specs, check_vma=False),
            site="gbdt.mesh_grower")

    # -- validation ----------------------------------------------------------
    def add_valid(self, name: str, valid: BinnedDataset, metrics: List) -> None:
        bins_v = jnp.asarray(valid.bins)
        K = self.num_tree_per_iteration
        score_v = jnp.zeros((K, valid.num_data_padded), jnp.float32)
        if valid.metadata.init_score is not None:
            init = valid.padded(valid.metadata.init_score.astype(np.float32))
            score_v = jnp.broadcast_to(init, score_v.shape).astype(jnp.float32)
        # replay every existing tree (loaded model and/or earlier iterations)
        # onto the new validation score
        for idx, tree in enumerate(self.model.trees):
            if tree.num_leaves <= 1:
                score_v = score_v.at[idx % K].add(jnp.float32(tree.leaf_value[0]))
                continue
            tree_dev, leaf_out = self._tree_to_device(tree)
            score_v = _traverse_update(bins_v, score_v, leaf_out, tree_dev,
                                       self.meta_dev, self.bundle_map, self._depth_iters(tree),
                                       idx % K)
        for m in metrics:
            m.init(valid.metadata.label, valid.metadata.weight,
                   valid.metadata.query_boundaries)
        self.valid_sets.append([name, valid, bins_v, score_v, metrics])

    # -- one boosting iteration (gbdt.cpp:387-482) ---------------------------
    def _fast_eligible(self) -> bool:
        """The partition-ordered fast path covers the serial GBDT (with or
        without bagging), ALL THREE mesh learners (tree_learner=
        data|voting run the partitioned engine per row shard with
        collectives at the histogram boundary; tree_learner=feature runs
        it per feature shard over replicated rows with owned-first column
        permutation — except under forced splits or GOSS, which keep the
        legacy masked engine), ranking objectives (the objective is handed
        the scores in partition order with the index column and answers
        in that order, `gradients_in_order`; not under GOSS, whose fused
        sampling step computes gradients row-wise from payload columns
        and has no such call, so rank + GOSS trains on the legacy
        engine), leaf-output renewal (except under
        GOSS), and row counts up to 2^31 (radix-split index columns past
        2^24)."""
        cfg = self.config
        return ((type(self) is GBDT
                 or getattr(self, "_fast_sample_hook", None) is not None
                 or getattr(self, "_fast_variant_ok", False))
                and (self.mesh is None
                     or self.parallel_mode in ("data", "voting")
                     or (self.parallel_mode == "feature"
                         and self.forced_schedule is None
                         and getattr(self, "_fast_sample_hook", None)
                         is None))
                and self.objective is not None
                # objectives that couple rows (ranking) ride the fast path
                # through `gradients_in_order`; GOSS's fused sampling step
                # makes no such call, so rank+GOSS keeps the legacy path
                and (getattr(self.objective, "is_rowwise", True)
                     or getattr(self, "_fast_sample_hook", None) is None)
                # leaf renewal runs on the fast path (per-segment leaf
                # membership + idx-column original-order mapping) except
                # under GOSS, whose fused sampling step is incompatible
                # with the pre-update-score renewal ordering
                and (not self.objective.renew_tree_output_required()
                     or getattr(self, "_fast_sample_hook", None) is None)
                # int32 row positions in the segment engine; past 2^24 the
                # payload's index column switches to the radix-split layout
                and self.train_set.num_data_padded < (1 << 31))

    # -- async pipeline drain ------------------------------------------------
    def flush(self, sync_scores: bool = False) -> None:
        """Drain the dispatch pipeline: after this returns, model.trees
        holds every REPORTED iteration's trees in dispatch order and any
        deferred assembly error has been re-raised.  Every point that
        observes the model calls this — metric eval, early-stop
        callbacks, snapshot writes / PreemptionGuard, rollback_one_iter,
        save_model, _fast_sync_back, and the train() exit path.

        `sync_scores=True` additionally settles the DEVICE training state
        at the reported iteration: an open boosting window (boost_window
        >= 2 ran the device ahead of the reported iteration) is truncated
        by exact snapshot replay.  Score observers (eval rounds,
        raw_train_score, snapshot capture, sync-back) pass True; pure
        model-view reads (current_iteration, save_model of the trees so
        far) keep the cheap default and never pay a truncation.

        If a drained iteration turned out to have no splittable leaves,
        the iterations dispatched past it are rolled back here — the
        synchronous loop would have stopped before training them."""
        if self._assembler is not None:
            self._assembler.flush()
        self._window_append_ready()
        if sync_scores:
            self._window_truncate()
        if self._in_flush:
            return
        stop = self._pipe_stop_iter
        if stop is not None and self.iter > stop + 1:
            # over-reported iterations exist; settle any open window at
            # its consumed boundary first so the payload scores the
            # rollback edits match the reported iteration exactly
            self._window_truncate()
            self._in_flush = True
            try:
                # rollback IN PLACE (payload score replay on the fast
                # path) rather than via rollback_one_iter, which would
                # sync the engine off the fast path — a state change the
                # synchronous loop never makes on a no-split stop
                K = self.num_tree_per_iteration
                for _ in range(self.iter - (stop + 1)):
                    for k in reversed(range(K)):
                        tree = self.model.trees.pop()
                        if tree.num_leaves <= 1:
                            continue
                        self._add_tree_to_train_score(tree, k, -1.0)
                        self._add_tree_to_valid_scores(tree, k, -1.0)
                    self.iter -= 1
            finally:
                self._in_flush = False

    def _note_tree_drained(self, num_leaves: int, it: int) -> None:
        """Assembler-thread bookkeeping, strictly in tree order: when a
        full iteration's trees have drained and none found a split, the
        run should have stopped at that iteration."""
        self._pipe_k_seen += 1
        if num_leaves > 1:
            self._pipe_any_split = True
        if self._pipe_k_seen >= self.num_tree_per_iteration:
            if not self._pipe_any_split and self._pipe_stop_iter is None:
                self._pipe_stop_iter = it
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
            self._pipe_k_seen = 0
            self._pipe_any_split = False

    # -- fused boosting window (boost_window=J, ISSUE 13) --------------------
    def _window_len(self) -> int:
        """Effective boosting-window length for the next dispatch: the
        configured boost_window clamped by the adaptive truncation
        history and engine.train's observation horizon; 1 (the sequential
        per-tree loop) whenever the config sits outside the validated
        window envelope."""
        J = self._boost_window
        if J <= 1 or type(self) is not GBDT or self.mesh is not None:
            return 1
        if (self.objective is None
                or self.objective.renew_tree_output_required()
                or self._quant_enabled
                or self.forced_schedule is not None
                or getattr(self, "_fast_sample_hook", None) is not None
                or self.timer.enabled
                or self._sentinel_policy != "off"):
            return 1
        J = min(J, max(1, self._win_adapt))
        if self._win_horizon is not None:
            J = min(J, max(1, int(self._win_horizon)))
        return J

    def _window_dispatch(self, J: int) -> bool:
        """Train J boosting iterations in ONE device dispatch: pre-draw
        the J per-iteration host RNG decisions (feature masks, bagging
        re-draws — the same stream positions the sequential loop would
        consume), snapshot the window-start device state for exact
        truncation, run the donated scan program, and hand the stacked
        [J*K] split records to the assembler as ONE drain unit.  Only
        iteration 0 is reported to the caller; the rest are consumed by
        the following update() calls with zero device work."""
        init_score = self._boost_from_average()
        fs = self._fast_enter()
        cfg = self.config
        K = self.num_tree_per_iteration
        bag_on = cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
        it0 = self.iter
        import copy as _copy
        rng0 = (_copy.deepcopy(self.bagging_rng._rng.bit_generator.state),
                _copy.deepcopy(self.feature_rng._rng.bit_generator.state),
                self.bag_mask_host.copy(), fs._bag_dirty)
        fmasks = np.empty((J, self.train_set.num_features
                           + self._fmask_pad), bool)
        bag_rows = (np.empty((J, self.train_set.num_data_padded),
                             np.float32) if bag_on else None)
        for j in range(J):
            fmasks[j] = self._feature_sample_host()
            if bag_on:
                bag_rows[j] = self._bagging_host(it0 + j)
        lr = self.shrinkage_rate
        # explicit window-start copies: the scan program donates its
        # payload/aux inputs, and truncation needs the exact start bits
        snap = (jnp.copy(fs.payload), jnp.copy(fs.aux))
        prog = fs.window_program(J, bag_on)
        bag_dev = (jnp.asarray(bag_rows) if bag_on
                   else jnp.zeros((J, 1), jnp.float32))
        # the window dispatch as a named span (ISSUE 14): the J stays in
        # the series name (telemetry.SPAN_KEEP_KEYS) — J=2 and J=4
        # windows are different stages, and the trace slice shows which
        # iteration paid this dispatch
        with telemetry.span("window dispatch J=%d" % J), \
                syncs.critical_path():
            recs, fs.payload, fs.aux = prog(fs.payload, fs.aux,
                                            jnp.asarray(fmasks), bag_dev,
                                            jnp.float32(lr))
        if bag_on:
            fs._bag_dirty = False
        w = {"iter0": it0, "total": J, "consumed": 0, "appended": 0,
             "recs": recs, "lr": lr, "snap": snap, "rng0": rng0,
             "trees": [], "drained": threading.Event()}
        self._win = w
        telemetry.counter("lgbm_window_iterations_total").inc(J)
        t_dispatch = time.monotonic()

        def host_half():
            host = _fetch_packed(recs, label="window_drain")
            trees = []
            stop_at = None
            for j in range(J):
                any_split = False
                for k in range(K):
                    one = {key: val[j, k] for key, val in host.items()}
                    tree = self._finish_tree_host(
                        one, init_score if j == 0 else 0.0, lr)
                    trees.append(tree)
                    if tree.num_leaves > 1:
                        any_split = True
                if not any_split and stop_at is None:
                    stop_at = it0 + j
            w["trees"] = trees
            if stop_at is not None and self._pipe_stop_iter is None:
                self._pipe_stop_iter = stop_at
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
            w["drained"].set()
            telemetry.histogram("lgbm_pipeline_drain_seconds").observe(
                time.monotonic() - t_dispatch)

        if self._pipeline_depth > 0:
            if self._assembler is None:
                self._assembler = TreeAssembler(self._pipeline_depth)
            self._assembler.submit(host_half, trees=J * K)
        else:
            host_half()
        return self._window_consume_one()

    #: grower-output fields whose [j, k] slices form the device half a
    #: valid-set replay needs (matches _tree_device_half's tree_dev;
    #: the tuple itself is the gbdt<->grower2 stacked-record contract)
    _WINDOW_TREE_DEV = TREE_DEVICE_FIELDS

    def _window_consume_one(self) -> bool:
        """Report one already-trained window iteration: replay its trees
        onto the valid scores from the stacked device records (lazily, so
        valid state never runs ahead of the reported iteration), append
        its parked host trees when the drain has landed, and surface the
        sequential loop's no-split stop."""
        w = self._win
        j = w["consumed"]
        K = self.num_tree_per_iteration
        recs, lr = w["recs"], w["lr"]
        if self.valid_sets:
            depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
            with syncs.critical_path():
                for k in range(K):
                    tree_dev = {f: recs[f][j, k]
                                for f in self._WINDOW_TREE_DEV}
                    leaf_out = jnp.where(
                        recs["num_leaves"][j, k] > 1,
                        recs["leaf_value"][j, k] * jnp.float32(lr),
                        jnp.float32(0.0))
                    for vs in self.valid_sets:
                        vs[3] = _traverse_update(
                            vs[2], vs[3], leaf_out, tree_dev,
                            self.meta_dev, self.bundle_map, depth_iters, k)
        w["consumed"] = j + 1
        self.iter += 1
        finished = False
        if w["drained"].is_set() and w["trees"]:
            finished = all(t.num_leaves <= 1
                           for t in w["trees"][j * K:(j + 1) * K])
        self._window_append_ready()
        if w["consumed"] >= w["total"]:
            # fully consumed: the window can never truncate again — free
            # the start snapshot now, and keep the parked trees around
            # only until their drain lands
            self._win = None
            w["snap"] = None
            if w["appended"] < w["total"] * K:
                self._win_unappended.append(w)
            self._win_clean += 1
            if self._win_clean >= 2 and self._win_adapt < self._boost_window:
                self._win_clean = 0
                self._win_adapt = min(self._boost_window,
                                      max(2, self._win_adapt * 2))
        if finished and self._pipe_stop_iter is not None \
                and self.iter > self._pipe_stop_iter:
            self._pipe_stop_iter = None
        return finished

    def _window_append_ready(self) -> None:
        """Append parked window trees to the model, strictly in dispatch
        order, up to the reported (consumed) iteration.  Trees whose
        drain has not landed stay parked — flush()'s assembler barrier
        guarantees completeness for every observer."""
        K = self.num_tree_per_iteration
        while self._win_unappended:
            w0 = self._win_unappended[0]
            if not w0["drained"].is_set():
                return    # strict order: later windows must wait too
            while w0["appended"] < w0["total"] * K:
                self.model.trees.append(w0["trees"][w0["appended"]])
                w0["appended"] += 1
            self._win_unappended.pop(0)
        w = self._win
        if w is None or not w["drained"].is_set():
            return
        while w["appended"] < w["consumed"] * K:
            self.model.trees.append(w["trees"][w["appended"]])
            w["appended"] += 1

    def _window_truncate(self) -> None:
        """Settle an open window at its consumed boundary: drop the
        unreported parked trees, restore the window-start device payload
        and host RNG/bag state, and replay the consumed iterations
        through the sequential fused steps — bit-identical to a run that
        never windowed (the scan step and `_step` trace the same graph).
        Costs `consumed` sequential re-dispatches; the adaptive window
        length shrinks to the observed truncation point so repeated
        mid-window observations stop paying it."""
        w = self._win
        if w is None:
            return
        if self._assembler is not None:
            self._assembler.flush()
        self._window_append_ready()
        self._win = None
        c = w["consumed"]
        self._win_adapt = max(1, min(self._win_adapt, c))
        self._win_clean = 0
        telemetry.counter("lgbm_window_truncations_total").inc()
        fs = self._fast
        fs.payload, fs.aux = w["snap"]
        w["snap"] = None
        bag_state, feat_state, bag_mask0, bag_dirty0 = w["rng0"]
        self.bagging_rng._rng.bit_generator.state = bag_state
        self.feature_rng._rng.bit_generator.state = feat_state
        self.bag_mask_host = bag_mask0
        fs._bag_dirty = bag_dirty0
        it_end = self.iter
        self.iter = w["iter0"]
        lr_now = self.shrinkage_rate
        self.shrinkage_rate = w["lr"]
        try:
            for _ in range(c):
                fmask = self._feature_sample()
                self._fast_refresh_bag(fs)
                if fs.K > 1:
                    fs.payload = fs._snap_scores(fs.payload)
                for k in range(fs.K):
                    _, fs.payload, fs.aux = fs._step(
                        fs.payload, fs.aux, fmask, jnp.float32(w["lr"]),
                        jnp.int32(k))
                self.iter += 1
        finally:
            self.shrinkage_rate = lr_now
            self.iter = it_end
        # a stop discovered in the truncated (never-reported) region
        # never happened; the continued run rediscovers it if real
        if self._pipe_stop_iter is not None \
                and self._pipe_stop_iter > self.iter - 1:
            self._pipe_stop_iter = None

    def _tree_device_half(self, out: Dict, lr: float, masked: bool = False):
        """The half of _finish_tree the NEXT device step may depend on,
        derived from the grower output without any host fetch: the
        traversal arrays plus the shrunk leaf outputs.  With masked=True
        a stump's outputs are zeroed so deferred consumers (valid-set
        _traverse_update, DART/RF replay) add +0.0 instead of needing the
        host-side num_leaves gate."""
        tree_dev = {
            "split_feature": out["split_feature"],
            "split_bin": out["split_bin"],
            "default_left": out["default_left"],
            "split_is_cat": out["split_is_cat"],
            "split_cat_bitset": out["split_cat_bitset"],
            "left_child": out["left_child"],
            "right_child": out["right_child"],
        }
        leaf_out = out["leaf_value"] * jnp.float32(lr)
        if masked:
            leaf_out = jnp.where(out["num_leaves"] > 1, leaf_out,
                                 jnp.float32(0.0))
        return tree_dev, leaf_out

    def _defer_finish(self, out: Dict, init_score: float, lr: float,
                      k: int) -> None:
        """Pipeline one tree's host half: the packed fetch + Tree assembly
        + model append run on the assembler thread (bounded at
        pipeline_depth in flight, strict dispatch order), while this
        thread goes on to dispatch the next tree.  The valid-set score
        replay runs NOW from the device half, so it never waits on the
        fetch either."""
        if self.valid_sets:
            tree_dev, leaf_out = self._tree_device_half(out, lr, masked=True)
            depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
            for vs in self.valid_sets:
                vs[3] = _traverse_update(vs[2], vs[3], leaf_out, tree_dev,
                                         self.meta_dev, self.bundle_map,
                                         depth_iters, k)
        if self._assembler is None:
            self._assembler = TreeAssembler(self._pipeline_depth)
        it = self.iter
        t_dispatch = time.monotonic()

        def host_half():
            host = _fetch_packed(out, label="pipeline_drain")
            tree = self._finish_tree_host(host, init_score, lr)
            self.model.trees.append(tree)
            self._note_tree_drained(tree.num_leaves, it)
            # dispatch-to-append latency of this tree's deferred host
            # half: queue wait + packed fetch + Tree assembly (ISSUE 9)
            telemetry.histogram("lgbm_pipeline_drain_seconds").observe(
                time.monotonic() - t_dispatch)

        self._assembler.submit(host_half)

    def _fast_sync_back(self) -> None:
        """Leave the fast path: restore original-order scores into the
        legacy score matrix.  The state object is kept for cheap re-entry."""
        self.flush(sync_scores=True)
        if not self._fast_active:
            return
        self.score = jnp.asarray(self._fast.raw_scores())
        if getattr(self, "_score_sharding", None) is not None:
            self.score = jax.device_put(self.score, self._score_sharding)
        self._fast_active = False

    def _fast_enter(self) -> "_FastState":
        if self._fast is None:
            self._fast = _FastState(self)
            self._fast_active = True
        elif not self._fast_active:
            self._fast.reset(self)
            self._fast_active = True
        return self._fast

    def _fast_refresh_bag(self, fs) -> None:
        cfg = self.config
        if not (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0):
            return
        # same RNG stream as the masked path, so both paths draw
        # identical bags (equality-testable).  The cnt column rides
        # the partition, so only an actual resample (or a rebuilt
        # payload) needs the gather+scatter refresh.
        resampled = self.iter % cfg.bagging_freq == 0
        with self.timer.phase("bagging"):
            bag = self._bagging()    # advances the RNG on resample
            if resampled or fs._bag_dirty:
                # bag_mask_host is already zero on padded rows
                fs.payload = fs._set_bag(fs.payload,
                                         bag.astype(jnp.float32))
                fs._bag_dirty = False
            self.timer.sync(fs.payload)

    def _train_one_iter_fast(self) -> bool:
        if self._pipe_stop_iter is not None and \
                self.iter > self._pipe_stop_iter:
            # a drained host half found an iteration with no splittable
            # leaves; flush() rolls back anything dispatched past it and
            # this update reports finished (one-to-two updates later than
            # the synchronous loop, with an identical final model).  The
            # flag clears once reported so a caller that keeps driving
            # update() manually trains again, like the synchronous loop.
            # (A boosting window can discover the stop AHEAD of the
            # reported iteration — the guard keeps consuming up to it.)
            self.flush()
            self._pipe_stop_iter = None
            return True
        if self._win is not None:
            # an open boosting window already trained this iteration on
            # device; reporting it is pure host bookkeeping
            return self._window_consume_one()
        J = self._window_len()
        if J >= 2:
            return self._window_dispatch(J)
        init_score = self._boost_from_average()
        fs = self._fast_enter()
        fmask = self._feature_sample()
        if fs.feature_par and self._fmask_pad:
            # the partitioned grower pads the mask to the shard multiple
            # itself; _feature_sample's padding serves the legacy masked
            # engine only
            fmask = fmask[:self.train_set.num_features]
        self._fast_refresh_bag(fs)
        if fs.K > 1:
            fs.payload = fs._snap_scores(fs.payload)

        lr = self.shrinkage_rate
        should_continue = False
        renew = (self.objective is not None
                 and self.objective.renew_tree_output_required())
        # pipelined iterations cover exactly the fused steps (_step /
        # _step_quant / _step_sampled / _step_masked); the piecewise
        # profiled path and leaf renewal observe per-tree host state by
        # construction and stay synchronous
        use_pipe = (self._pipeline_depth > 0 and not renew
                    and not self.timer.enabled
                    and self._sentinel_policy == "off")
        if not use_pipe:
            # deferred appends from earlier pipelined iterations must land
            # before this iteration's inline appends
            self.flush()
        return self._run_iter_trees(fs, fmask, init_score, lr, renew,
                                    use_pipe, should_continue)

    @_mark_critical_path
    def _run_iter_trees(self, fs, fmask, init_score, lr, renew, use_pipe,
                        should_continue) -> bool:
        for k in range(self.num_tree_per_iteration):
            if renew:
                # leaf-output renewal (RenewTreeOutput, serial_tree_learner
                # .cpp:780-818): grow WITHOUT the fused score add — the
                # robust per-leaf statistic needs the pre-update scores —
                # then renew on host and replay the renewed outputs through
                # the payload's bin-traversal score add.
                with self.timer.phase("boosting (gradients)"):
                    if fs.quant_on:
                        fs.payload, qsc = fs._fill_class_quant(
                            fs.payload, k=k, qseed=self._quant_seed(k))
                    else:
                        fs.payload = fs._fill_class(fs.payload, k=k)
                with self.timer.phase("tree (hist+split+partition)"):
                    gargs = (fs.payload, fs.aux, fmask) if not fs.quant_on \
                        else (fs.payload, fs.aux, fmask, qsc)
                    out, fs.payload, fs.aux = fs.grower(*gargs)
                    self.timer.sync(fs.payload)
                with self.timer.phase("leaf renewal (host)"):
                    renewed = self._renew_leaf_values_fast(fs, out, k)
                with self.timer.phase("tree assemble (host)"):
                    tree, tree_dev, leaf_out = self._finish_tree(
                        out, init_score, renewed)
                if tree.num_leaves > 1:
                    should_continue = True
                    with self.timer.phase("train score update"):
                        fs.payload = fs._payload_tree_add(
                            fs.payload, tree_dev, leaf_out, jnp.int32(k))
                        self.timer.sync(fs.payload)
                    depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
                    with self.timer.phase("valid score update"):
                        for vs in self.valid_sets:
                            vs[3] = _traverse_update(
                                vs[2], vs[3], leaf_out, tree_dev,
                                self.meta_dev, self.bundle_map, depth_iters,
                                k)
                self.model.trees.append(tree)
                continue
            if fs._step_sampled is not None:
                # row-sampling boosting (GOSS): always the fused path —
                # the hook needs all-class gradients in one program.
                # Multiclass draws the (identical) selection once per
                # iteration and reuses it for every class tree.
                key, enabled = self._fast_sample_args()
                with self.timer.phase("tree (hist+split+partition)"):
                    if fs.K == 1:
                        out, fs.payload, fs.aux = fs._step_sampled(
                            fs.payload, fs.aux, fmask, jnp.float32(lr),
                            jnp.int32(k), key, enabled)
                    else:
                        if k == 0:
                            fs.payload = fs._apply_sample_masks(
                                fs.payload, key, enabled)
                        out, fs.payload, fs.aux = fs._step_masked(
                            fs.payload, fs.aux, fmask, jnp.float32(lr),
                            jnp.int32(k))
                    self.timer.sync(fs.payload)
            elif not self.timer.enabled:
                # one dispatch for the whole tree (gradients + growth +
                # score add); profiling uses the piecewise path below
                if fs.quant_on:
                    out, fs.payload, fs.aux = fs._step_quant(
                        fs.payload, fs.aux, fmask, jnp.float32(lr),
                        jnp.int32(k), self._quant_seed(k))
                else:
                    out, fs.payload, fs.aux = fs._step(
                        fs.payload, fs.aux, fmask, jnp.float32(lr),
                        jnp.int32(k))
            else:
                with self.timer.phase("boosting (gradients)"):
                    if fs.quant_on:
                        fs.payload, qsc = fs._fill_class_quant(
                            fs.payload, k=k, qseed=self._quant_seed(k))
                    else:
                        fs.payload = fs._fill_class(fs.payload, k=k)
                    self.timer.sync(fs.payload)
                with self.timer.phase("tree (hist+split+partition)"):
                    gargs = (fs.payload, fs.aux, fmask) if not fs.quant_on \
                        else (fs.payload, fs.aux, fmask, qsc)
                    out, fs.payload, fs.aux = fs.grower(*gargs)
                    self.timer.sync(fs.payload)
            if use_pipe:
                # the host half (packed fetch -> Tree assembly -> append)
                # drains off-path; the device already applied the masked
                # score add inside the fused step, and _defer_finish
                # replays the valid sets from the device half.  The
                # no-split stop is signaled by the drain (see
                # _note_tree_drained) — report continue optimistically.
                self._defer_finish(out, init_score, lr, k)
                should_continue = True
                continue
            with self.timer.phase("tree assemble (host)"):
                tree, tree_dev, leaf_out = self._finish_tree(out, init_score)
            if tree.num_leaves > 1:
                should_continue = True
                # the fused steps already applied the score add on device
                if self.timer.enabled and fs._step_sampled is None:
                    with self.timer.phase("train score update"):
                        fs.payload = fs._apply_score(fs.payload,
                                                     jnp.float32(lr), k=k)
                        self.timer.sync(fs.payload)
                depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
                with self.timer.phase("valid score update"):
                    for vs in self.valid_sets:
                        vs[3] = _traverse_update(vs[2], vs[3], leaf_out,
                                                 tree_dev, self.meta_dev,
                                                 self.bundle_map,
                                                 depth_iters, k)
                    if self.valid_sets:
                        self.timer.sync(self.valid_sets[-1][3])
            self.model.trees.append(tree)
        self.iter += 1
        if not should_continue:
            Log.warning("Stopped training because there are no more leaves that meet the split requirements")
        return not should_continue

    def _quant_seed(self, k: int) -> jax.Array:
        """Deterministic stochastic-rounding seed per (iteration, class):
        reruns of the same config quantize identically, and no two trees
        share a rounding draw."""
        base = int(getattr(self.config, "seed", 0) or 0)
        return jnp.int32((base + self.iter * self.num_tree_per_iteration
                          + k) & 0x7FFFFFFF)

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        if grad is None and hess is None and self._fast_eligible():
            return self._train_one_iter_fast()
        self._fast_sync_back()
        if self._quant_enabled and not getattr(self, "_warned_quant_legacy",
                                               False):
            Log.warning("gradient_quantization rides the fast path only; "
                        "this iteration trains with f32 gradients")
            self._warned_quant_legacy = True
        if self.forced_schedule is not None and self.parallel_mode is not None \
                and not getattr(self, "_warned_forced_legacy", False):
            Log.warning("forcedsplits_filename is honored by the serial "
                        "learners only; the parallel tree learners train "
                        "WITHOUT forced splits")
            self._warned_forced_legacy = True
        if self._mesh_fast_only and not getattr(self, "_warned_mesh_fast",
                                                False):
            Log.warning("EFB-bundled parallel training rides the fast path "
                        "only; this configuration trains with the serial "
                        "learner")
            self._warned_mesh_fast = True
        init_score = 0.0
        with self.timer.phase("boosting (gradients)"):
            if grad is None or hess is None:
                init_score = self._boost_from_average()
                grads, hesss = self._gradients()
            else:
                grads, hesss = self._pad_custom_gradients(grad, hess)
            self.timer.sync(grads)

        with self.timer.phase("bagging"):
            gmask, cmask = self._bagging_masks(grads, hesss)
            self.timer.sync(gmask)
        self._bag_cmask = cmask
        fmask = self._feature_sample()

        renew = self.objective is not None and self.objective.renew_tree_output_required()
        should_continue = False
        for k in range(self.num_tree_per_iteration):
            vals = _make_vals(grads, hesss, gmask, cmask, k)
            with self.timer.phase("tree (hist+split+partition)"):
                out = self.grower(self.bins_dev, vals, fmask)
                self.timer.sync(out)
            renewed = None
            if renew:
                renewed = self._renew_leaf_values(out, k)
            with self.timer.phase("tree assemble (host)"):
                tree, tree_dev, leaf_out = self._finish_tree(out, init_score,
                                                             renewed)
            if tree.num_leaves > 1:
                should_continue = True
                with self.timer.phase("train score update"):
                    self.score = _update_score_k(self.score, out["leaf_id"],
                                                 leaf_out, k)
                    self.timer.sync(self.score)
                # fixed trip count (num_leaves-1 covers any depth) so the
                # traversal compiles exactly once per config
                depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
                with self.timer.phase("valid score update"):
                    for vs in self.valid_sets:
                        vs[3] = _traverse_update(vs[2], vs[3], leaf_out,
                                                 tree_dev, self.meta_dev,
                                                 self.bundle_map,
                                                 depth_iters, k)
                    if self.valid_sets:
                        self.timer.sync(self.valid_sets[-1][3])
            self.model.trees.append(tree)
        self.iter += 1
        if not should_continue:
            Log.warning("Stopped training because there are no more leaves that meet the split requirements")
        return not should_continue

    def reset_config(self, new_params: Dict) -> None:
        """Booster::ResetConfig: live-apply parameter changes into the
        engine config so they take effect on the next iteration (shared by
        Booster.reset_parameter and the reset_parameter callback)."""
        from ..config import Config
        if self._win is not None:
            # parameter changes are observation points: iterations the
            # open window trained past the reported one used the OLD
            # parameters — settle at the boundary before applying
            self.flush(sync_scores=True)
        self.config.set(new_params)
        if any(Config.resolve_alias(k) == "learning_rate"
               for k in new_params):
            self.shrinkage_rate = float(self.config.learning_rate)

    def rollback_one_iter(self) -> None:
        """RollbackOneIter (gbdt.cpp:484-500): drop the last iteration's trees
        and subtract their contribution from every score vector by re-running
        the bin-level traversal with negated leaf outputs."""
        if self.iter <= 0:
            return
        self._fast_sync_back()
        K = self.num_tree_per_iteration
        for k in reversed(range(K)):
            tree = self.model.trees.pop()
            if tree.num_leaves <= 1:
                continue
            tree_dev, neg_out = self._tree_to_device(tree, negate=True)
            depth_iters = max(self.grower_cfg.num_leaves - 1, 1)
            self.score = _traverse_update(self.bins_dev, self.score, neg_out,
                                          tree_dev, self.meta_dev, self.bundle_map, depth_iters, k)
            for vs in self.valid_sets:
                vs[3] = _traverse_update(vs[2], vs[3], neg_out, tree_dev,
                                         self.meta_dev, self.bundle_map, depth_iters, k)
        self.iter -= 1

    def _depth_iters(self, tree: Tree) -> int:
        """Traversal trip count covering this run's grower and any loaded
        tree (which may be larger than the current num_leaves)."""
        return max(self.grower_cfg.num_leaves - 1, tree.num_leaves - 1, 1)

    def _add_tree_to_train_score(self, tree: Tree, k: int, scale: float) -> None:
        """score[k] += scale * tree(x) over the training bins (DART drop /
        normalize, RF running average, continued-training replay).  On the
        fast path the edit lands in the partition-ordered payload score
        column, routed by the payload's own bin columns."""
        if self._fast_active and tree.num_leaves > self.grower_cfg.num_leaves:
            # the payload traversal's trip count covers only trees this
            # run's grower can produce; replay oversized loaded trees
            # through the legacy path (it sizes the traversal per tree)
            self._fast_sync_back()
        if self._fast_active:
            fs = self._fast
            if tree.num_leaves <= 1:
                fs.payload = fs._apply_const_score(
                    fs.payload, jnp.float32(scale * tree.leaf_value[0]),
                    jnp.int32(k))
                return
            tree_dev, leaf_out = self._tree_to_device(tree)
            fs.payload = fs._payload_tree_add(
                fs.payload, tree_dev, leaf_out * jnp.float32(scale),
                jnp.int32(k))
            return
        if tree.num_leaves <= 1:
            self.score = self.score.at[k].add(jnp.float32(scale * tree.leaf_value[0]))
            return
        tree_dev, leaf_out = self._tree_to_device(tree)
        self.score = _traverse_update(self.bins_dev, self.score,
                                      leaf_out * jnp.float32(scale), tree_dev,
                                      self.meta_dev, self.bundle_map, self._depth_iters(tree), k)

    def _add_tree_to_valid_scores(self, tree: Tree, k: int, scale: float) -> None:
        if tree.num_leaves <= 1:
            for vs in self.valid_sets:
                vs[3] = vs[3].at[k].add(jnp.float32(scale * tree.leaf_value[0]))
            return
        depth_iters = self._depth_iters(tree)
        tree_dev, leaf_out = self._tree_to_device(tree)
        leaf_out = leaf_out * jnp.float32(scale)
        for vs in self.valid_sets:
            vs[3] = _traverse_update(vs[2], vs[3], leaf_out, tree_dev,
                                     self.meta_dev, self.bundle_map, depth_iters, k)

    def _multiply_scores(self, k: int, factor: float) -> None:
        """ScoreUpdater::MultiplyScore on plane k, train + valid (rf.hpp)."""
        self.score = self.score.at[k].multiply(jnp.float32(factor))
        for vs in self.valid_sets:
            vs[3] = vs[3].at[k].multiply(jnp.float32(factor))

    def _tree_to_device(self, tree: Tree, negate: bool = False):
        """Device arrays for bin-level traversal of a host tree (trees built
        this run carry bin thresholds + inner categorical bitsets)."""
        ni = max(tree.num_leaves - 1, 1)
        B = self.train_set.max_num_bin
        is_cat = (tree.decision_type[:ni] & 1) != 0
        bitset = np.zeros((ni, B), dtype=bool)
        for node in np.nonzero(is_cat)[0]:
            ci = int(tree.threshold_in_bin[node])
            lo, hi = tree.cat_boundaries_inner[ci], tree.cat_boundaries_inner[ci + 1]
            for wi in range(lo, hi):
                word = tree.cat_threshold_inner[wi]
                for bit in range(32):
                    b = (wi - lo) * 32 + bit
                    if b < B and (word >> bit) & 1:
                        bitset[node, b] = True
        tree_dev = {
            "split_feature": jnp.asarray(tree.split_feature[:ni], jnp.int32),
            "split_bin": jnp.asarray(np.where(is_cat, 0, tree.threshold_in_bin[:ni]),
                                     jnp.int32),
            "default_left": jnp.asarray((tree.decision_type[:ni] & 2) != 0),
            "split_is_cat": jnp.asarray(is_cat),
            "split_cat_bitset": jnp.asarray(bitset),
            "left_child": jnp.asarray(tree.left_child[:ni], jnp.int32),
            "right_child": jnp.asarray(tree.right_child[:ni], jnp.int32),
        }
        lv = tree.leaf_value[: max(tree.num_leaves, 1)].astype(np.float32)
        leaf_out = jnp.asarray(-lv if negate else lv)
        return tree_dev, leaf_out

    # -- internals -----------------------------------------------------------
    def _pad_custom_gradients(self, grad, hess):
        """Reshape caller-supplied fobj gradients to the padded [K, N] layout."""
        K, n = self.num_tree_per_iteration, self.train_set.num_data
        grads = jnp.asarray(np.asarray(grad, np.float32).reshape(K, n))
        hesss = jnp.asarray(np.asarray(hess, np.float32).reshape(K, n))
        pad = self.train_set.num_data_padded - n
        if pad:
            grads = jnp.pad(grads, ((0, 0), (0, pad)))
            hesss = jnp.pad(hesss, ((0, 0), (0, pad)))
        return grads, hesss

    def _gradients(self):
        if self._grad_fn is None:
            obj = self.objective

            def gradfn(score, label, weight):
                return obj.get_gradients_multi(score, label, weight)

            self._grad_fn = xla_obs.jit(gradfn, site="gbdt.gradients")
        return self._grad_fn(self.score, self.label_dev, self.weight_dev)

    def _boost_from_average(self) -> float:
        if self._boosted_from_average or self.model.current_iteration > 0 \
                or self.train_set.metadata.init_score is not None \
                or self.num_class > 1 or self.objective is None:
            return 0.0
        self._boosted_from_average = True
        if not bool(self.config.boost_from_average):
            return 0.0
        init = self.objective.boost_from_score()
        if abs(init) > K_EPSILON:
            self.score = self.score + jnp.float32(init)
            for vs in self.valid_sets:
                vs[3] = vs[3] + jnp.float32(init)
            Log.info("Start training from score %f", init)
            self.init_score_value = init
            return init
        return 0.0

    def _bagging_host(self, it: int) -> np.ndarray:
        """Host half of _bagging: advance the bagging stream to iteration
        `it` (resample when it lands on the bagging_freq grid) and return
        the current host mask.  The window dispatcher pre-draws J steps
        through this, so the stream position stays identical to the
        sequential loop's."""
        cfg = self.config
        n = self.train_set.num_data
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
            if it % cfg.bagging_freq == 0:
                bag_cnt = int(n * cfg.bagging_fraction)
                idx = self.bagging_rng.sample(n, bag_cnt)
                mask = np.zeros(self.train_set.num_data_padded, dtype=np.float32)
                mask[idx] = 1.0
                self.bag_mask_host = mask
        return self.bag_mask_host

    def _bagging(self) -> jax.Array:
        mask = self._bagging_host(self.iter)
        if self.mesh is not None:
            return jax.device_put(mask, self._row_sharding)
        return jnp.asarray(mask)

    def _bagging_masks(self, grads, hesss):
        """(gradient-scale mask, count mask) per row.  Plain bagging uses the
        same 0/1 mask for both; GOSS overrides with an amplified gradient mask
        (goss.hpp BaggingHelper)."""
        m = self._bagging()
        return m, m

    def _feature_sample_host(self) -> np.ndarray:
        """Host half of _feature_sample (one per-iteration draw); the
        window dispatcher stacks J of these into one device upload."""
        cfg = self.config
        f = self.train_set.num_features
        mask = np.zeros(f, dtype=bool)
        if cfg.feature_fraction < 1.0:
            used = max(1, int(f * cfg.feature_fraction))
            mask[self.feature_rng.sample(f, used)] = True
        else:
            mask[:] = True
        if self._fmask_pad:
            # feature-parallel pads the feature axis to a shard multiple;
            # padded columns never enter split search
            mask = np.concatenate([mask, np.zeros(self._fmask_pad, bool)])
        return mask

    def _feature_sample(self) -> jax.Array:
        return jnp.asarray(self._feature_sample_host())

    def _renew_leaf_values_fast(self, fs: "_FastState", out: Dict,
                                k: int) -> Optional[np.ndarray]:
        """RenewTreeOutput on the partitioned fast path: leaf membership
        falls out of the row segments (every leaf's rows are contiguous per
        device block), and the payload's index column maps the
        partition-ordered scores/bag back to original row order so the
        objective's renewal code runs UNCHANGED — bit-identical to the
        legacy path."""
        nl = int(syncs.device_get(out["num_leaves"], label="renew_fetch"))
        if nl <= 1:
            return None
        # one round of transfers: the contiguous column block (cnt/bag,
        # idx, per-class scores) plus the segment tables and leaf values
        h, ss, sc, lv = syncs.device_get(
            (fs.payload[:, fs.cnt_col:fs.score0 + fs.K],
             out["seg_start"], out["seg_cnt"], out["leaf_value"]),
            label="renew_fetch")
        h = np.asarray(h)
        cnt = h[:, 0]
        idx = fs.host_idx() if fs.wide_idx else h[:, 1].astype(np.int64)
        score_k = h[:, 2 + k].astype(np.float64)
        ss = np.asarray(ss).astype(np.int64)
        sc = np.asarray(sc).astype(np.int64)
        L = ss.size // fs.ndev
        R = fs.n_rows // fs.ndev
        lid_part = np.full(fs.n_rows, nl, np.int64)
        for d in range(fs.ndev):
            off = d * R
            for leaf in range(nl):
                s = off + ss[d * L + leaf]
                lid_part[s:s + sc[d * L + leaf]] = leaf
        keep = idx < fs.n_pad
        lid = np.full(fs.n_pad, nl, np.int64)
        lid[idx[keep]] = lid_part[keep]
        pred = np.zeros(fs.n_pad, np.float64)
        pred[idx[keep]] = score_k[keep]
        in_bag = np.zeros(fs.n_pad, bool)
        in_bag[idx[keep]] = cnt[keep] > 0
        lv = np.asarray(lv, dtype=np.float64)
        return self.objective.renew_leaf_values(lv[:nl], lid, pred, in_bag)

    def _renew_leaf_values(self, out: Dict, k: int) -> Optional[np.ndarray]:
        """RenewTreeOutput wiring (gbdt.cpp:441-448 →
        serial_tree_learner.cpp:780-818): replace leaf outputs with the
        objective's robust statistic (e.g. L1 median of residuals) computed
        over the bagged rows of each leaf, before shrinkage."""
        nl = int(syncs.device_get(out["num_leaves"], label="renew_fetch"))
        if nl <= 1:
            return None
        leaf_id, pred_k, lv, in_bag = syncs.device_get(
            (out["leaf_id"], self.score[k], out["leaf_value"],
             self._bag_cmask), label="renew_fetch")
        leaf_id = np.asarray(leaf_id)
        pred_k = np.asarray(pred_k, dtype=np.float64)
        lv = np.asarray(lv, dtype=np.float64)
        in_bag = np.asarray(in_bag) > 0
        return self.objective.renew_leaf_values(lv[:nl], leaf_id, pred_k, in_bag)

    def _finish_tree(self, out: Dict, init_score: float,
                     renewed: Optional[np.ndarray] = None):
        """Fetch grower output, assemble the host Tree (reference numbering),
        apply shrinkage and first-tree bias (gbdt.cpp:450-456) — the
        synchronous form; the pipelined fast path defers the host half
        through _defer_finish instead."""
        host = _fetch_packed(out)
        # the outputs are on host anyway — the non-finite sentinel rides
        # this fetch for free (raises NonFiniteDetected under
        # sentinel_nonfinite=abort|rollback; Booster.update arbitrates)
        resilience.sentinel_check(self, host)
        lr = self.shrinkage_rate
        tree = self._finish_tree_host(host, init_score, lr, renewed)
        if renewed is not None or self._leaf_transform is not None:
            leaf_value_dev_f = jnp.asarray(
                (host["leaf_value"] * lr).astype(np.float32))
            tree_dev, _ = self._tree_device_half(out, lr)
        else:
            tree_dev, leaf_value_dev_f = self._tree_device_half(out, lr)
        return tree, tree_dev, leaf_value_dev_f

    def _finish_tree_host(self, host: Dict[str, np.ndarray],
                          init_score: float, lr: float,
                          renewed: Optional[np.ndarray] = None) -> Tree:
        """The pure-host half of _finish_tree: fetched outputs -> reference
        Tree.  Runs inline (classic loop) or on the assembler thread
        (pipelined loop); `lr` is the shrinkage captured AT DISPATCH —
        DART and reset_parameter may have moved self.shrinkage_rate by
        drain time."""
        nl = int(host["num_leaves"])
        # legacy masked grower reports no round counter: its loop is one
        # round per split by construction
        self.split_rounds_total += int(host.get("split_rounds",
                                                max(nl - 1, 0)))
        self.trees_finished += 1
        if self._fast is not None:
            self._fast.counters["splits"].append(nl - 1)
            self._fast.counters["categorical_splits"].append(
                int(host["split_is_cat"][:nl - 1].sum()))
            for name in ("rows_partitioned", "rows_staged", "rows_missing"):
                self._fast.counters[name].append(wide_count(host[name]))
            ni = max(nl - 1, 0)
            mappers = self.train_set.bin_mappers
            aware = np.asarray(
                [mappers[int(f)].missing_type != MISSING_NONE
                 for f in host["split_feature"][:ni]], bool) \
                & ~host["split_is_cat"][:ni].astype(bool)
            self._fast.counters["missing_splits"].append(int(aware.sum()))
            self._fast.counters["default_left_splits"].append(
                int((aware & host["default_left"][:ni].astype(bool)).sum()))
        L = self.grower_cfg.num_leaves
        tree = Tree(max(L, 2))
        tree.num_leaves = nl
        host_lv = host["leaf_value"]
        if renewed is not None:
            host_lv = host_lv.copy()
            host_lv[: len(renewed)] = renewed
        if self._leaf_transform is not None:
            # RF converts leaf outputs through the objective before scoring
            # (rf.hpp ConvertTreeOutput)
            host_lv = self._leaf_transform(np.asarray(host_lv, np.float64))
        if renewed is not None or self._leaf_transform is not None:
            host["leaf_value"] = host_lv

        if nl > 1:
            ni = nl - 1
            ds = self.train_set
            tree.split_feature[:ni] = host["split_feature"][:ni]
            is_cat_nodes = host["split_is_cat"][:ni].astype(bool)
            tree.split_gain[:ni] = host["split_gain"][:ni]
            dt = np.zeros(ni, dtype=np.int8)
            dt |= np.where(is_cat_nodes, 0,
                           host["default_left"][:ni].astype(np.int8) << 1)
            dt |= np.where(is_cat_nodes, 1, 0).astype(np.int8)
            miss = np.asarray([ds.bin_mappers[int(f)].missing_type
                               for f in host["split_feature"][:ni]], dtype=np.int8)
            dt |= (miss << 2)
            tree.decision_type[:ni] = dt
            for node in range(ni):
                f = int(host["split_feature"][node])
                if is_cat_nodes[node]:
                    # categorical: threshold slots hold the cat index; bitsets
                    # over bins (training traversal) and over category values
                    # (raw prediction + model file), tree.cpp SplitCategorical
                    chosen = np.nonzero(host["split_cat_bitset"][node])[0]
                    cat_idx = tree.num_cat
                    tree.threshold_in_bin[node] = cat_idx
                    tree.threshold[node] = float(cat_idx)
                    tree.num_cat += 1
                    mapper = ds.bin_mappers[f]
                    vals = [int(mapper.bin_2_categorical[int(b)]) for b in chosen
                            if int(b) < len(mapper.bin_2_categorical)]
                    tree.cat_threshold.extend(_construct_bitset(vals))
                    tree.cat_boundaries.append(len(tree.cat_threshold))
                    tree.cat_threshold_inner.extend(
                        _construct_bitset([int(b) for b in chosen]))
                    tree.cat_boundaries_inner.append(len(tree.cat_threshold_inner))
                else:
                    b = int(host["split_bin"][node])
                    tree.threshold_in_bin[node] = b
                    tree.threshold[node] = ds.real_threshold(f, b)
            tree.left_child[:ni] = host["left_child"][:ni]
            tree.right_child[:ni] = host["right_child"][:ni]
            tree.internal_value[:ni] = host["internal_value"][:ni] * lr
            tree.internal_count[:ni] = host["internal_count"][:ni].astype(np.int64)
            tree.leaf_value[:nl] = host["leaf_value"][:nl].astype(np.float64) * lr
            tree.leaf_count[:nl] = host["leaf_count"][:nl].astype(np.int64)
            tree.leaf_parent[:] = -1
            for node in range(ni):
                for child in (tree.left_child[node], tree.right_child[node]):
                    if child < 0:
                        tree.leaf_parent[~child] = node
            tree.shrinkage = lr
            if abs(init_score) > K_EPSILON:
                tree.leaf_value[:nl] += init_score
                tree.shrinkage = 1.0
        else:
            tree.leaf_value[0] = float(host["leaf_value"][0]) * lr + init_score
            tree.shrinkage = 1.0
        return tree

    def split_rounds_per_tree(self) -> Optional[float]:
        """Mean sequential grower rounds per finished tree (telemetry for
        the frontier-batch fixed-cost claim: < num_leaves - 1 means the
        batched grower committed more than one split per round)."""
        if self.trees_finished == 0:
            return None
        return self.split_rounds_total / self.trees_finished

    # -- evaluation ----------------------------------------------------------
    def raw_train_score(self) -> np.ndarray:
        self.flush(sync_scores=True)
        if self._fast_active:
            return self._fast.raw_scores()[:, : self.train_set.num_data]
        return syncs.device_get(
            self.score, label="score_fetch")[:, : self.train_set.num_data]

    def raw_valid_score(self, i: int) -> np.ndarray:
        name, valid, _, score_v, _ = self.valid_sets[i]
        return syncs.device_get(score_v,
                                label="score_fetch")[:, : valid.num_data]

    def _packed_eval_fetch(self, arrays: List[jax.Array]) -> List[np.ndarray]:
        """ONE blocking D2H for a whole eval round (the _fetch_packed
        pattern on the f32 score arrays): flatten+concat on device, fetch
        once, split on host — metric_freq=1 must not serialize one
        round trip per dataset.  Mesh runs fetch the list as one pytree
        device_get instead (a cross-sharding concat would insert
        collectives); jax still overlaps every leaf's transfer."""
        if not arrays:
            return []
        if self.mesh is not None or len(arrays) == 1:
            return [np.asarray(a) for a in
                    syncs.device_get(arrays, label="eval_fetch")]
        spec = tuple(tuple(a.shape) for a in arrays)
        entry = _EVAL_PACK_CACHE.get(spec)
        if entry is None:
            xla_obs.cache_event("gbdt.eval_pack_cache", "miss")
            sizes = [int(np.prod(s, dtype=np.int64)) for s in spec]
            offs = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)

            @functools.partial(xla_obs.jit, site="gbdt.eval_pack")
            def pack(xs):
                return jnp.concatenate([x.reshape(-1) for x in xs])

            entry = (offs, pack)
            _pack_cache_put(_EVAL_PACK_CACHE, spec, entry,
                            site="gbdt.eval_pack_cache")
        else:
            xla_obs.cache_event("gbdt.eval_pack_cache", "hit")
            _EVAL_PACK_CACHE.move_to_end(spec)
        offs, pack = entry
        flat = np.asarray(syncs.device_get(pack(arrays), label="eval_fetch"))
        return [flat[offs[i]:offs[i + 1]].reshape(s)
                for i, s in enumerate(spec)]

    def _eval_raws(self, want_train: bool, want_valid: bool):
        """(train raw, [valid raws]) for an eval round, off one packed
        transfer.  Flushing here makes every eval a pipeline barrier —
        callbacks that observe the model (early stopping bookkeeping,
        snapshot schedules) run against a fully-assembled tree list —
        and settles any open boosting window at the reported iteration
        (score observation)."""
        self.flush(sync_scores=True)
        fs = self._fast if self._fast_active else None
        arrays: List[jax.Array] = []
        if want_train:
            arrays.extend(fs.score_cols_device() if fs is not None
                          else [self.score])
        if want_valid:
            arrays.extend(vs[3] for vs in self.valid_sets)
        host = self._packed_eval_fetch(arrays)
        i = 0
        train_raw = None
        if want_train:
            if fs is not None:
                cols = host[i]
                i += 1
                hi = None
                if fs.wide_idx:
                    hi = host[i]
                    i += 1
                train_raw = fs.scores_from_host(cols, hi)
            else:
                train_raw = host[i]
                i += 1
            train_raw = train_raw[:, : self.train_set.num_data]
        valid_raws = []
        if want_valid:
            for (_name, valid, _b, _s, _m) in self.valid_sets:
                valid_raws.append(host[i][:, : valid.num_data])
                i += 1
        return train_raw, valid_raws

    @staticmethod
    def _metric_input(raw: np.ndarray, m) -> np.ndarray:
        """Metrics see the 1D score plane, except multiclass metrics which
        consume the full [K, N] matrix (multiclass_metric.hpp Eval)."""
        return raw if getattr(m, "multiclass", False) else raw[0]

    def _eval_train_results(self, raw) -> List[Tuple[str, str, float, bool]]:
        return [("training", m.name,
                 m.eval(self._metric_input(raw, m), self._metric_objective),
                 m.is_higher_better)
                for m in self.train_metrics]

    def _eval_valid_results(self, raws) -> List[Tuple[str, str, float, bool]]:
        out = []
        for (name, _valid, _b, _s, metrics), raw in zip(self.valid_sets,
                                                        raws):
            for m in metrics:
                out.append((name, m.name,
                            m.eval(self._metric_input(raw, m),
                                   self._metric_objective),
                            m.is_higher_better))
        return out

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        raw, _ = self._eval_raws(True, False)
        return self._eval_train_results(raw)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        _, raws = self._eval_raws(False, True)
        return self._eval_valid_results(raws)

    def eval_all(self, include_train: bool):
        """One eval round — train metrics (optional) plus every valid set
        — off a single packed device_get (see _packed_eval_fetch)."""
        raw, raws = self._eval_raws(include_train, True)
        train_res = self._eval_train_results(raw) if include_train else []
        return train_res, self._eval_valid_results(raws)
