"""Updates of the payload's state columns outside the segment kernels.

The state columns (label, weight, count mask, index, score, gradient,
hessian, value, ...) sit side by side after the bin columns of the [N, P]
payload: inside ONE 128-lane tile in every cell the benchmark has.  A
column of an f32 [N, P] array cannot be touched for less than its
(8, 128) tile, so the floor of one update is one read-modify-write of
that tile's column of the payload, not of the payload.
`segment.payload_col_write` is a select over all P lanes, and on the chip
each call is a pass of its own (its docstring); here an update is ONE
pass over the lane tile(s) that hold the columns:

- `read_cols`: the named columns out as a compact [S, N] array with rows
  in lanes (what the objective's elementwise arithmetic wants), one read
  of the tile(s);
- `write_cols`: an [S, N] array back into the named columns, in place;
- `add_scaled`: `payload[:, dst] += scale * payload[:, src]` inside the
  tile, no vector leaves the kernel.

Each has two forms, `"pallas"` and `"lax"` (`resolve_form`, by platform
and shape, the rule `grower2.partition_engine` / `segment.resolve_impl`
follow; tests run the first under the interpreter as
`"pallas-interpret"`): on a TPU a Pallas call whose `BlockSpec` picks the lane tile, a grid step a
`_BLOCK_ROWS`-row block of it (two steps where the columns straddle a
tile edge); elsewhere, and for columns over more than two tiles, the
`lax` form: slices and `payload_col_write`.  The two agree bit for bit:
a column moves between lanes and sublanes through the XLU's transpose,
which rounds nothing.  A Pallas call is not partitioned by GSPMD: on a
mesh the caller wraps these in `jax.shard_map` over the row axis.

The jitted wrappers are named `_state_cols_*`: the profiler names a
kernel's custom call after its wrapper, and the benchmark's kernel
readers match `_partition_segment*` and `_segment_histogram*`, which
these must not.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import xla_obs
from .segment import payload_col_write

LANES = 128
#: rows of a lane tile a grid step takes: 1 MiB a buffer, so the in and
#: out double buffers, the transposed block and the staging scratch stay
#: under half of the 16 MiB of VMEM a kernel may plan for; a multiple of
#: 128, which the transpose wants
_BLOCK_ROWS = 2048


def _tiles(cols: Sequence[int]) -> Tuple[int, int]:
    """(first lane tile, number of tiles) the columns span."""
    first = min(cols) // LANES
    return first, max(cols) // LANES - first + 1


def resolve_form(payload_width: int, cols: Sequence[int]) -> str:
    """The form of every state-column update of a [N, payload_width]
    payload whose state columns are `cols`: the kernels on a TPU where
    the columns sit inside a window of two lane tiles, else `lax`."""
    if jax.default_backend() != "tpu" or payload_width % LANES:
        return "lax"
    return "pallas" if _tiles(cols)[1] <= 2 else "lax"


def _block_rows(n_rows: int) -> int:
    return min(_BLOCK_ROWS, -(-n_rows // LANES) * LANES)


def _picks(cols: Sequence[int], first_tile: int, tiles: int):
    """For each tile of the window, the (row of the compact array, lane
    inside the tile) pairs of the columns it holds."""
    return tuple(tuple((row, c % LANES) for row, c in enumerate(cols)
                       if c // LANES - first_tile == j)
                 for j in range(tiles))


def _per_tile(statics, body):
    """Run `body(statics[j])` in the grid step of the window's j-th tile:
    the tile is the grid's minor index, what is known of it is static."""
    j = pl.program_id(1)
    for jj, static in enumerate(statics):
        pl.when(j == jj)(functools.partial(body, static))


def _read_kernel(x_ref, out_ref, *, picks):
    def tile(tile_picks):
        rows_in_lanes = x_ref[...].T                      # [128, R]
        for row, lane in tile_picks:
            out_ref[row:row + 1, :] = rows_in_lanes[lane:lane + 1, :]

    _per_tile(picks, tile)


def _write_kernel(v_ref, x_ref, out_ref, staged, *, picks):
    lane_of = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def tile(tile_picks):
        # the columns' rows go where their lanes will be; what the other
        # rows of the scratch hold lands in lanes the mask leaves alone
        mask = None
        for row, lane in tile_picks:
            staged[lane:lane + 1, :] = v_ref[row:row + 1, :]
            mask = lane_of == lane if mask is None \
                else mask | (lane_of == lane)
        out_ref[...] = jnp.where(mask, staged[...].T, x_ref[...])

    _per_tile(picks, tile)


def _axpy_kernel(ints, floats, x_ref, out_ref, src_rows, *, tile_order,
                 src_lane):
    """out[:, dst] = x[:, dst] + (on ? x[:, src] * scale : 0).  The
    source's tile comes first in `tile_order`; its column, broadcast over
    the lanes, waits in `src_rows` for a destination in the second tile."""
    dst, on, scale = ints[0], ints[1], floats[0]
    lane_of = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        src_rows[...] = jnp.broadcast_to(
            x_ref[:, src_lane:src_lane + 1], src_rows.shape)

    # the product outside the branch that adds it: under the interpreter a
    # branch is an XLA computation of its own, and inside one the CPU's
    # compiler contracts multiply and add into an FMA, which rounds once
    # where the lax form (and the chip, which has none) rounds twice
    upd = jnp.where(on > 0, src_rows[...] * scale, 0.0)

    def add(tile):
        x = x_ref[...]
        out_ref[...] = jnp.where(lane_of + tile * LANES == dst, x + upd, x)

    _per_tile(tile_order, add)


_ROWS_THEN_TILES = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


@functools.partial(xla_obs.jit, site="pallas.state_cols_read",
                   static_argnames=("cols", "interpret"))
def _state_cols_read(payload, *, cols, interpret=False):
    n, s_pad = payload.shape[0], -(-len(cols) // 8) * 8
    first, tiles = _tiles(cols)
    r = _block_rows(n)
    out = pl.pallas_call(
        functools.partial(_read_kernel, picks=_picks(cols, first, tiles)),
        grid=(pl.cdiv(n, r), tiles),
        in_specs=[pl.BlockSpec((r, LANES), lambda i, j: (i, first + j))],
        out_specs=pl.BlockSpec((s_pad, r), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s_pad, n), payload.dtype),
        compiler_params=_ROWS_THEN_TILES,
        interpret=interpret,
    )(payload)
    return out[:len(cols)]


@functools.partial(xla_obs.jit, site="pallas.state_cols_write",
                   static_argnames=("cols", "interpret"))
def _state_cols_write(payload, vals, *, cols, interpret=False):
    n, s = payload.shape[0], vals.shape[0]
    first, tiles = _tiles(cols)
    r = _block_rows(n)
    tile_spec = pl.BlockSpec((r, LANES), lambda i, j: (i, first + j))
    return pl.pallas_call(
        functools.partial(_write_kernel, picks=_picks(cols, first, tiles)),
        grid=(pl.cdiv(n, r), tiles),
        in_specs=[pl.BlockSpec((s, r), lambda i, j: (0, i)), tile_spec],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct(payload.shape, payload.dtype),
        scratch_shapes=[pltpu.VMEM((LANES, r), payload.dtype)],
        input_output_aliases={1: 0},
        compiler_params=_ROWS_THEN_TILES,
        interpret=interpret,
    )(vals, payload)


@functools.partial(xla_obs.jit, site="pallas.state_cols_axpy",
                   static_argnames=("src", "dst_range", "interpret"))
def _state_cols_axpy(payload, dst, scale, on, *, src, dst_range,
                     interpret=False):
    n = payload.shape[0]
    first, tiles = _tiles((src,) + dst_range)
    # the source's tile, then the other (a window holds at most two)
    src_tile = src // LANES
    other = first if src_tile != first else first + tiles - 1
    r = _block_rows(n)
    tile_spec = pl.BlockSpec(
        (r, LANES), lambda i, j, *_: (i, src_tile + j * (other - src_tile)))
    ints = jnp.stack([dst, on.astype(jnp.int32)])
    floats = jnp.reshape(scale, (1,)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_axpy_kernel,
                          tile_order=(src_tile, other)[:tiles],
                          src_lane=src % LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n, r), tiles),
            in_specs=[tile_spec],
            out_specs=tile_spec,
            scratch_shapes=[pltpu.VMEM((r, LANES), payload.dtype)]),
        out_shape=jax.ShapeDtypeStruct(payload.shape, payload.dtype),
        input_output_aliases={2: 0},
        compiler_params=_ROWS_THEN_TILES,
        interpret=interpret,
    )(ints, floats, payload)


def read_cols(payload: jax.Array, cols: Sequence[int],
              form: str) -> jax.Array:
    """[len(cols), N]: the payload's columns `cols` (static), rows in
    lanes."""
    cols = tuple(int(c) for c in cols)
    if form == "lax":
        return jnp.stack([payload[:, c] for c in cols])
    return _state_cols_read(payload, cols=cols,
                            interpret=form == "pallas-interpret")


def write_cols(payload: jax.Array, cols: Sequence[int], vals: jax.Array,
               form: str) -> jax.Array:
    """payload[:, cols[i]] = vals[i] for every i, in place where the
    payload is donated; `cols` static, `vals` [len(cols), N]."""
    cols = tuple(int(c) for c in cols)
    if form == "lax":
        for i, c in enumerate(cols):
            payload = payload_col_write(payload, c, vals[i])
        return payload
    return _state_cols_write(payload, vals, cols=cols,
                             interpret=form == "pallas-interpret")


def add_scaled(payload: jax.Array, dst, dst_range: Tuple[int, int],
               src: int, scale, on, form: str) -> jax.Array:
    """payload[:, dst] += where(on, payload[:, src] * scale, 0).  `dst`
    may be traced, anywhere in the static `dst_range` (first, last);
    `src` is static.  The score add of the fused step: `on` is "the tree
    split", so a stump moves no score."""
    if form == "lax":
        upd = jnp.where(on, payload[:, src] * scale, 0.0)
        return payload_col_write(payload, dst, upd, "add")
    return _state_cols_axpy(
        payload, jnp.asarray(dst, jnp.int32), jnp.asarray(scale),
        jnp.asarray(on), src=int(src), dst_range=tuple(dst_range),
        interpret=form == "pallas-interpret")
