"""Voting-parallel (PV-Tree) training step over a jax.sharding.Mesh.

TPU-native equivalent of the reference VotingParallelTreeLearner
(src/treelearner/voting_parallel_tree_learner.cpp): rows are sharded like the
data-parallel learner, but per-leaf histograms stay shard-local; each shard
votes its top_k features by local split gain (constraints scaled by
1/num_machines, :53-55), the vote winners (top 2k globally, GlobalVoting
:190-195) alone have their histograms `psum`ed over ICI, and the best split
is found on that reduced subset — bounding communication volume exactly like
the reference's selective ReduceScatter (:362-366).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..boosting.grower import GrowerConfig, make_tree_grower
from ..runtime import xla_obs
from ..ops.split import FeatureMeta
from ._common import make_step, resolve_objective

DATA_AXIS = "data"


def make_voting_parallel_train_step(meta: FeatureMeta, cfg: GrowerConfig,
                                    num_bins_max: int, mesh: Mesh,
                                    learning_rate: float, objective=None,
                                    top_k: int = 20):
    """One boosting step, rows sharded, histogram exchange bounded by voting.

    Same input/output contract as make_data_parallel_train_step."""
    objective = resolve_objective(objective)
    num_machines = mesh.shape[DATA_AXIS]
    grow = make_tree_grower(meta, cfg, num_bins_max, axis_name=DATA_AXIS,
                            jit=False, mode="voting",
                            num_machines=num_machines, top_k=top_k)
    step = make_step(grow, objective, learning_rate)
    # check_vma off: the vote (all_gather -> identical top-2k set on every
    # shard) and the psum'ed subset histograms are replicated in value, but
    # the varying-axes tracker cannot prove it through the scan carry
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(None)),
        out_specs=(P(DATA_AXIS), P()),
        check_vma=False)
    return xla_obs.jit(sharded, site="parallel.voting_step")
