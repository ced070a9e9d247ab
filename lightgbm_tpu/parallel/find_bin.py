"""Mesh-distributed find-bin (dataset_loader.cpp:842-924 role).

The reference's distributed loader splits FEATURES across machines: each
rank runs find-bin on its slice of the sample and the BinMappers are
allgathered so every rank ends with the full mapper set.  The TPU-native
counterpart keeps the same shape over a `jax.sharding.Mesh`: the sample
matrix is row-sharded (each device sees its data shard, the multi-host
reality), each device computes weighted quantile boundaries for EVERY
feature from its shard, and one `all_gather` + deterministic merge gives
identical boundaries on all devices — one collective, like the reference's
single mapper allgather.

This is the device-resident path for data already sharded across hosts
(pre_partition).  Single-host construction keeps the exact host-side
GreedyFindBin (io/binning.py), which this quantile merge approximates but
does not replicate bit-for-bit (distinct-value counting does not
distribute); the reference's distributed mappers equally differ from its
single-machine ones.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime import xla_obs

DATA_AXIS = "find_bin_rows"


def _local_quantile_sketch(x: jax.Array,
                           n_sketch: int) -> Tuple[jax.Array, jax.Array]:
    """[n_local] -> (sorted [n_sketch] evenly-spaced order statistics,
    valid count); NaNs pushed to the end and excluded by the count."""
    finite = jnp.isfinite(x)
    cnt = jnp.sum(finite)
    xs = jnp.sort(jnp.where(finite, x, jnp.inf))
    # positions over the valid prefix only
    pos = (jnp.arange(n_sketch) + 0.5) / n_sketch * jnp.maximum(cnt, 1) - 0.5
    idx = jnp.clip(pos.astype(jnp.int32), 0, jnp.maximum(cnt - 1, 0))
    return xs[idx], cnt


def make_distributed_find_bin(mesh: Mesh, max_bin: int,
                              n_sketch: int = 1024):
    """Returns find(sample [N, F]) -> bounds [F, max_bin] f64-ish bounds.

    bounds[f] are ascending bin upper bounds, last = +inf, replicated on
    every device.  N must divide by the mesh size.
    """
    ndev = mesh.devices.size

    def per_shard(sample):                      # [N/ndev, F]
        sk, cnt = jax.vmap(functools.partial(
            _local_quantile_sketch, n_sketch=n_sketch),
            in_axes=1, out_axes=0)(sample)      # [F, n_sketch], [F]
        # one collective: every device gets every shard's sketch + count
        all_sk = jax.lax.all_gather(sk, DATA_AXIS)      # [ndev, F, S]
        all_cnt = jax.lax.all_gather(cnt, DATA_AXIS)    # [ndev, F]
        # weight each shard's sketch points by its valid count and take
        # global evenly-spaced quantiles of the merged, sorted sketch
        F = sk.shape[0]
        merged = jnp.transpose(all_sk, (1, 0, 2)).reshape(F, -1)
        weights = jnp.repeat(all_cnt.T / n_sketch, n_sketch, axis=1)
        order = jnp.argsort(merged, axis=1)
        msort = jnp.take_along_axis(merged, order, axis=1)
        wsort = jnp.take_along_axis(weights, order, axis=1)
        cum = jnp.cumsum(wsort, axis=1)
        total = cum[:, -1:]
        targets = (jnp.arange(1, max_bin) / max_bin)[None, :] * total
        pos = jax.vmap(jnp.searchsorted)(cum, targets)  # [F, max_bin-1]
        pos = jnp.clip(pos, 0, msort.shape[1] - 1)
        bounds = jnp.take_along_axis(msort, pos, axis=1)
        # STRICTLY ascending (duplicated quantile values would create
        # unreachable bins downstream, the case GreedyFindBin's
        # distinct-value dedup handles): each bound is bumped to at least
        # one ulp above its predecessor
        def bump(prev, b):
            # a relative epsilon, floored inside the NORMAL f32 range —
            # nextafter from 0 is subnormal and XLA flushes subnormals
            eps = jnp.maximum(jnp.abs(prev) * 1e-6, 1e-30)
            nb = jnp.maximum(b, jnp.where(jnp.isfinite(prev),
                                          prev + eps, b))
            return nb, nb

        _, strict = jax.lax.scan(
            bump, jnp.full((F,), -jnp.inf, bounds.dtype), bounds.T)
        bounds = strict.T
        return jnp.concatenate(
            [bounds, jnp.full((F, 1), jnp.inf, bounds.dtype)], axis=1)

    # the post-all_gather computation is device-identical, but the static
    # replication checker cannot see through vmap(searchsorted); the
    # replication tests assert it dynamically instead
    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=P(DATA_AXIS, None),
                       out_specs=P(), check_vma=False)
    return xla_obs.jit(fn, site="parallel.find_bin")


def shard_sample(mesh: Mesh, sample: np.ndarray) -> jax.Array:
    n = sample.shape[0]
    ndev = mesh.devices.size
    assert n % ndev == 0, "sample rows must divide the mesh size"
    return jax.device_put(
        sample, NamedSharding(mesh, P(DATA_AXIS, None)))
