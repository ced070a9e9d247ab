"""Feature-parallel training step over a jax.sharding.Mesh.

TPU-native equivalent of the reference FeatureParallelTreeLearner
(src/treelearner/feature_parallel_tree_learner.cpp:21-69): every shard holds
the full rows but only its slice of the feature columns; split search is
sharded over features, the global best is chosen with a gain-keyed
pmax/pmin (the SyncUpGlobalBestSplit fixed-size allreduce-max,
parallel_tree_learner.h:183-206), and the winning feature's row routing is
broadcast from its owner with one psum — the reference needs no data movement
there because all ranks hold full data; here the single psum replaces it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..boosting.grower import GrowerConfig, make_tree_grower
from ..ops.split import FeatureMeta, pad_feature_meta  # noqa: F401  (re-export)
from ..runtime import xla_obs
from ._common import make_step, resolve_objective

FEATURE_AXIS = "feature"


def pad_features(bins: np.ndarray, feature_mask: np.ndarray, num_shards: int):
    """Pad the feature axis to a shard multiple; padded columns are all-bin-0
    and masked out of split search."""
    F = bins.shape[0]
    pad = -F % num_shards
    if pad:
        bins = np.concatenate([bins, np.zeros((pad, bins.shape[1]), bins.dtype)])
        feature_mask = np.concatenate([feature_mask, np.zeros(pad, bool)])
    return bins, feature_mask, F + pad


def make_feature_parallel_train_step(meta: FeatureMeta, cfg: GrowerConfig,
                                     num_bins_max: int, mesh: Mesh,
                                     learning_rate: float, objective=None):
    """One boosting step with features sharded over mesh axis 'feature'.

    Global shapes: bins [F, N] sharded over features, score/label/weight/mask
    [N] replicated, feature_mask [F] sharded.  meta must cover the padded
    feature count (pad_feature_meta).
    """
    objective = resolve_objective(objective)
    grow = make_tree_grower(meta, cfg, num_bins_max, axis_name=FEATURE_AXIS,
                            jit=False, mode="feature")
    step = make_step(grow, objective, learning_rate)
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(FEATURE_AXIS, None), P(), P(), P(), P(), P(FEATURE_AXIS)),
        out_specs=(P(), P()))
    return xla_obs.jit(sharded, site="parallel.feature_step")


def shard_features(mesh: Mesh, bins, feature_mask, *replicated):
    """Place bins/feature_mask sharded over features, the rest replicated."""
    out = [jax.device_put(bins, NamedSharding(mesh, P(FEATURE_AXIS, None))),
           jax.device_put(feature_mask, NamedSharding(mesh, P(FEATURE_AXIS)))]
    for a in replicated:
        out.append(jax.device_put(a, NamedSharding(mesh, P())))
    return tuple(out)
