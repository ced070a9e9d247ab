"""Data-parallel training step over a jax.sharding.Mesh.

TPU-native equivalent of the reference DataParallelTreeLearner
(src/treelearner/data_parallel_tree_learner.cpp) + Network collectives
(src/network/network.cpp): rows are sharded over the mesh 'data' axis, local
histograms are ReduceScattered over the feature dimension with
`lax.psum_scatter` so each shard owns F/n features' reduced histograms,
split search runs only on owned features, and the global winner is one
SyncUpGlobalBestSplit allreduce (gain pmax + packed SplitInfo psum) — the
same wire pattern as the reference's network boundary at
data_parallel_tree_learner.cpp:159-246, with XLA collectives over ICI in
place of src/network/ sockets.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..boosting.grower import GrowerConfig, make_tree_grower
from ..runtime import xla_obs
from ..ops.split import FeatureMeta
from ._common import make_step, resolve_objective

DATA_AXIS = "data"


def make_data_parallel_train_step(meta: FeatureMeta, cfg: GrowerConfig,
                                  num_bins_max: int, mesh: Mesh,
                                  learning_rate: float, objective=None):
    """One full boosting step, sharded: gradients → tree → score update.

    Inputs (global shapes):  bins [F, N] sharded over rows, score [N] sharded,
    label/weight/mask [N] sharded, feature_mask [F] replicated.
    Returns (new_score, tree_arrays) with per-row outputs sharded and tree
    arrays replicated.  `objective` is an ObjectiveFunction whose
    get_gradients runs shard-locally (gradients are row-local in every
    objective except ranking, which is query-sharded); defaults to binary
    logloss.
    """
    objective = resolve_objective(objective)
    grow = make_tree_grower(meta, cfg, num_bins_max, axis_name=DATA_AXIS,
                            jit=False, mode="data",
                            num_machines=mesh.shape[DATA_AXIS])
    step = make_step(grow, objective, learning_rate)
    # check_vma off: the owned-feature winner is broadcast to every shard by
    # the SyncUpGlobalBestSplit psum, so the carried split state is
    # replicated in value, but the varying-axes tracker cannot prove it
    # through the fori_loop carry
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(None)),
        out_specs=(P(DATA_AXIS), P()),
        check_vma=False)
    return xla_obs.jit(sharded, site="parallel.data_step")


def shard_rows(mesh: Mesh, *arrays):
    """Place per-row arrays (last axis = rows for 2-D) on the mesh."""
    out = []
    for a in arrays:
        spec = P(None, DATA_AXIS) if a.ndim == 2 else P(DATA_AXIS)
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)
