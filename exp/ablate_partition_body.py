"""In-kernel ablation of the accumulator partition's pass A: what each
piece of the loop body costs a chunk on the chip.

The profiler's trace ends at the kernel's edge (PERF.md §7: "time inside a
Pallas kernel" is not seen), so the body is timed by leaving pieces out.
`_pass_a_kernel` below is pass A of `pallas_segment._acc_kernel` (ring
read, index arithmetic with rows in lanes, one destination one-hot, three
part matmuls, the placement into the two accumulators, the flushes),
without pass B and the final blend read, in two bodies that differ in the
placement alone:

    new   as the kernel stands since PR 36: the sub-tile part of each
          cursor rides in the one-hot's destination, the [C + 24, P] block
          is stored once to a scratch and each side is ONE masked store
          into the tile-aligned [C + 8, P] window of its accumulator
    old   as it stood from PR 29 to PR 35: a [C, P] block doubled to
          [2C, P], rotated by a dynamic amount once a side and selected
          into the whole [2C, P] accumulator, the value column's select
          over [2C, P] twice; kept runnable so that both tables come from
          one instrument

and with a static set of stubs.  Both bodies:

    matmul2  one part matmul of the three (the two others' cost)
    parts    no bf16 hi/mid/lo split (the chunk stands in for each part)
    flush    no write of a full accumulator window to HBM
    body     nothing but the ring read and one add of the chunk (the DMA
             floor)
    rank     the lefts' ranks are the row number (no product)
    onehot   the one-hot is a hoisted triangle (none built a chunk)
    route    the routing is a parity of the row number; split further:
               colselect  the split column's bins are the row number (no
                          masked NT product)
               mask       the window's other lanes are not zeroed before
                          the product
               predicate  the Bin::Split arithmetic is a parity of the bin
               catword    no word select chain of the categorical bitset

the placement's own, by body:

    place       (new) the accumulators take 8 rows of the block, not a
                masked store of an aligned [C + 8, P] window a side
    blockstore  (new) the block is not stored to its scratch (the value
                column's select goes with it)
    rotate      (old) no dynamic rotate of the doubled block
    blend       (old) the accumulators take 8 rows, not a [2C, P] select

the choices the new body was made from, by race (`--race`):

    place=where   each side a select of the block's window against the
                  accumulator's window read back, not a masked store
    onehot=sides  a [C + 8, C] one-hot a side, each applied to the three
                  parts (six products a chunk), no scratch and no dynamic
                  slice of the block
    above=roll    the lefts of a trip's earlier chunks by static sublane
                  rotates of the lane-reduced counts, not by a second lane
                  sum over the earlier chunks' rows broadcast down
    rank=roll  the exclusive prefix count by log-step roll-and-add along
               lanes, not `[8, C] x tri_t` on the MXU
    col=xpose  the split column by one XLU transposition of its window
               and a row load at a dynamic sublane, not an NT product of a
               one-hot row against the masked window; `col=nt` the product
               with the window not masked (a NaN in another lane would
               reach it), `col=nthigh` at the precision bins past 256 need
    nl=scalar  the lefts' count reaches the destination through a scalar,
               not as a lane-reduced [8, 1] vector

and the loop's shape, `group2` / `group4`: that many chunks a loop trip on
a ring twice as deep.  The product takes 2 at 128 lanes (`_pass_a_group`)
and 1 in a 512-lane block; the stubs are read at one chunk a trip, where a
piece's cost is not hidden behind another chunk's, and the placement's
also at the shipped trip.

The argument `512` (beside `128`, the default being both) times one
512-lane column block: the kernel moves a 512-lane payload and routes from
a [N, 128] copy of the split window, read into a ring of its own, as
`partition_segment_acc_blocks`' passes do.

A stubbed kernel computes nonsense; only its time is read.  With no stub
the lefts it writes are checked against the portable partition, for a
numerical and a categorical predicate.  Times are wall clock round a call
whose payload is donated (no copy in the program) and whose scalar result
is fetched; the cost of a piece is full minus stubbed, per chunk of CHUNK
rows.  Pieces overlap in the kernel's schedule, so the costs need not add
up to the body.

On the chip:   python exp/ablate_partition_body.py [--race] [128] [512]
               [--match A,B]   (only `full` and the labels that hold A or B)
CPU rehearsal: JAX_PLATFORMS=cpu python exp/ablate_partition_body.py --interpret
"""
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import pallas_segment as pseg

CHUNK, C2, WIN, BLOCK_ROWS = pseg.CHUNK, pseg.C2, pseg.WIN, pseg.BLOCK_ROWS
SHARED_STUBS = ("rank", "onehot", "matmul2", "parts", "flush", "body",
                "route", "colselect", "mask", "predicate", "catword")
#: the stubs of a body's placement, together its ceiling
PLACEMENT = {"old": ("rotate", "blend"), "new": ("place", "blockstore")}
RACES = ("place=where", "onehot=sides", "above=roll", "rank=roll",
         "col=xpose", "col=nt", "col=nthigh", "nl=scalar")


def _pass_a_kernel(scalars, fvals, payload_hbm, aux_hbm, *rest,
                   P, B, value_col, stubs, group, body, blocks):
    if blocks:
        route_hbm, *rest = rest
    payload_out, aux_out, nl_out, *rest = rest
    (ring, lacc, racc, stage, rbuf, blk, win_t, sem_ring, sem_w, sem_r,
     *rest) = rest
    if blocks:
        route_ring, sem_route = rest
    old, sides_race = body == "old", "onehot=sides" in stubs
    start, count = scalars[0], scalars[1]
    left_value, right_value = fvals[0], fvals[1]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    iota_c2 = lax.broadcasted_iota(jnp.int32, (C2, 1), 0)[:, 0]
    iota_win = lax.broadcasted_iota(jnp.int32, (WIN, 1), 0)
    iota_p = lax.broadcasted_iota(jnp.int32, (1, P), 1)
    # the lanes of the split window: the 128-lane chunk's own, or those of
    # a column block's copy (this script times no other width)
    iota_route = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    iota_ci = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    iota_cj = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    tri_t = (iota_ci < iota_cj).astype(jnp.float32)
    # the one-hot's rows: the block's (new), a side's window, the chunk's
    hot_rows = CHUNK if old else WIN if sides_race else BLOCK_ROWS
    iota_hot = iota_ci if old else lax.broadcasted_iota(
        jnp.int32, (hot_rows, CHUNK), 0)
    iota_b = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, 1), 0)
    tri_hot = (iota_hot < lax.broadcasted_iota(
        jnp.int32, (hot_rows, CHUNK), 1)).astype(jnp.float32)
    chunk_of_row = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 0)
    row_of_lane = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 1)
    R, G = ring.shape[0], group
    route_col = scalars[2]
    route_sel = (lax.broadcasted_iota(jnp.int32, (8, 128), 1) ==
                 route_col).astype(jnp.float32)

    def read_a(k, slot):
        rows = pl.ds(pl.multiple_of(base + k * CHUNK, 8), CHUNK)
        dmas = [pltpu.make_async_copy(payload_out.at[rows, :], ring.at[slot],
                                      sem_ring.at[slot])]
        if blocks:
            dmas.append(pltpu.make_async_copy(
                route_hbm.at[rows, :], route_ring.at[slot],
                sem_route.at[slot]))
        return dmas

    def drain(dst_ref, stage_buf, sem, pend):
        @pl.when(pend > 0)
        def _():
            pltpu.make_async_copy(
                stage_buf, dst_ref.at[pl.ds(0, CHUNK), :], sem).wait()

    def flush(acc, dst_ref, wbase, stage_buf, sem, pend):
        drain(dst_ref, stage_buf, sem, pend)
        stage_buf[:] = acc[0:CHUNK]
        pltpu.make_async_copy(
            stage_buf, dst_ref.at[pl.ds(pl.multiple_of(wbase, 8), CHUNK), :],
            sem).start()
        acc[0:CHUNK] = acc[CHUNK:C2]

    @pl.when(nch > 0)
    def _prefetch_first():
        for i in range(G if G > 1 else R - 1):
            @pl.when(i < nch)
            def _start(i=i):
                for dma in read_a(i, i):
                    dma.start()

    # ---- the index arithmetic, rows in lanes: both bodies' ------------------
    def go_left_lanes(raw):
        """`pseg._go_left_lanes` with its pieces stubbable."""
        if "predicate" in stubs:
            return raw & 1
        if "catword" not in stubs:
            return pseg._go_left_lanes(scalars, raw, B)
        return pseg._go_left_lanes(scalars, raw, 0)   # no word, gl_cat 0

    def above(x):
        """[8, C]: under each chunk of a trip, the sum of `x`'s rows of
        the trip's earlier chunks (a sublane broadcast each; none at
        G = 1)."""
        out = jnp.zeros_like(x)
        for g in range(G - 1):
            out = out + jnp.where(chunk_of_row > g, x[g:g + 1, :], 0)
        return out

    def routed(k0, windows, lo_, ro_):
        """(gl, dest, dest_r): the routing and the one-hot's destination,
        the parent's (lefts from row 0, rights behind them) or the new
        body's (each side from its cursor's part under 8); `dest_r` the
        second one-hot's under `onehot=sides`."""
        if "colselect" in stubs:
            raw = row_of_lane
        elif "col=xpose" in stubs:
            for g, window in enumerate(windows):
                win_t[g] = window.T
            raw = jnp.broadcast_to(win_t[0, pl.ds(route_col, 1), :],
                                   (8, CHUNK))
            for g in range(1, G):
                raw = jnp.where(
                    chunk_of_row == g,
                    jnp.broadcast_to(win_t[g, pl.ds(route_col, 1), :],
                                     (8, CHUNK)), raw)
            raw = raw.astype(jnp.int32)
        else:
            raw = None
            for g, window in enumerate(windows):
                if "mask" not in stubs and "col=nt" not in stubs:
                    window = jnp.where(iota_route == route_col, window, 0.0)
                column = lax.dot_general(
                    route_sel, window, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=(lax.Precision.HIGHEST
                               if "col=nthigh" in stubs else None))  # [8, C]
                raw = column if raw is None \
                    else jnp.where(chunk_of_row == g, column, raw)
            raw = raw.astype(jnp.int32)
        first = shift - (k0 + chunk_of_row) * CHUNK
        valid = ((row_of_lane >= first) &
                 (row_of_lane < first + count)).astype(jnp.int32)
        if "route" in stubs:
            gl = (row_of_lane & 1) * valid
        else:
            gl = go_left_lanes(raw) * valid
        if "rank" in stubs:
            rank_l = row_of_lane
        elif "rank=roll" in stubs:
            incl = gl
            for step in (1, 2, 4, 8, 16, 32, 64, 128):
                incl = incl + jnp.where(row_of_lane >= step,
                                        pltpu.roll(incl, step, axis=1), 0)
            rank_l = incl - gl
        else:
            rank_l = jnp.dot(gl.astype(jnp.float32), tri_t,
                             preferred_element_type=jnp.float32
                             ).astype(jnp.int32)
        rank_r = jnp.maximum(row_of_lane - jnp.maximum(first, 0), 0) - rank_l
        if "nl=scalar" in stubs:
            nl = jnp.zeros_like(gl)
            for g in range(G):
                nl = jnp.where(chunk_of_row == g,
                               jnp.sum(jnp.where(chunk_of_row == g, gl, 0)),
                               nl)
        else:
            nl = jnp.sum(gl, axis=1, keepdims=True)
        if old:
            dest = jnp.where(gl > 0, rank_l, nl + rank_r)
            return gl, jnp.where(valid > 0, dest, -1), None
        # the trip's earlier chunks: their lefts by a second lane sum,
        # their rows from where the segment lies in the stream
        if "above=roll" in stubs:
            nl_above = sum(
                jnp.where(chunk_of_row >= d, pltpu.roll(
                    jnp.broadcast_to(nl, gl.shape), d, axis=0), 0)
                for d in range(1, G)) if G > 1 else 0
        else:
            nl_above = jnp.sum(above(gl), axis=1, keepdims=True)
        span = chunk_of_row * CHUNK
        n_above = (jnp.clip(shift + count - k0 * CHUNK, 0, span) -
                   jnp.clip(shift - k0 * CHUNK, 0, span))
        r_l = (lo_ + nl_above) & 7
        r_r = (ro_ + n_above - nl_above) & 7
        if sides_race:
            return (gl, jnp.where(gl > 0, r_l + rank_l, -1),
                    jnp.where(valid > gl, r_r + rank_r, -1))
        dest = jnp.where(gl > 0, r_l + rank_l,
                         ((r_l + nl + 7) & -8) + r_r + rank_r)
        return gl, jnp.where(valid > 0, dest, -1), None

    def product(mat, data):
        hi, mid, lo = (data, data, data) if "parts" in stubs \
            else pseg._bf16_parts(data)
        perm = jnp.dot(mat, hi, preferred_element_type=jnp.float32)
        if "matmul2" in stubs:
            rest = mid + lo
            if perm.shape[0] > CHUNK:
                rest = jnp.pad(rest, ((0, perm.shape[0] - CHUNK), (0, 0)))
            return perm + rest
        return (perm + jnp.dot(mat, mid, preferred_element_type=jnp.float32)
                + jnp.dot(mat, lo, preferred_element_type=jnp.float32))

    def permuted(g, k, data, gl, dest, dest_r, lo_):
        """(nlk, nrk, block) of chunk k: the parent's doubled block, the
        two sides' windows, or None with the block in its scratch."""
        nlk = jnp.sum(jnp.where(chunk_of_row == g, gl, 0))
        lo = jnp.maximum(shift - k * CHUNK, 0)
        hi = jnp.minimum(shift + count - k * CHUNK, CHUNK)
        nrk = jnp.maximum(hi - lo, 0) - nlk

        def hot(dest):
            return tri_hot if "onehot" in stubs \
                else (iota_hot == dest[g:g + 1, :]).astype(jnp.float32)

        if old:
            perm = product(hot(dest), data)
            return nlk, nrk, jnp.concatenate([perm, perm], axis=0)
        if sides_race:
            return nlk, nrk, tuple(
                jnp.where(iota_p == value_col, value, product(hot(d), data))
                for d, value in ((dest, left_value), (dest_r, right_value)))
        perm = product(hot(dest), data)
        if "blockstore" not in stubs:
            blk[g, 0:BLOCK_ROWS] = jnp.where(
                iota_p == value_col,
                jnp.where(iota_b < (lo_ & 7) + nlk, left_value, right_value),
                perm)
        else:
            blk[g, 0:8] = perm[0:8]
        return nlk, nrk, None

    # ---- the parent's placement: rotate the doubled block, blend ------------
    def blend(acc, placed, cnt, off, value):
        if "blend" in stubs:
            acc[0:8] = placed[0:8]
            return
        placed = jnp.where(iota_p == value_col, value, placed)
        region = ((iota_c2 >= off) & (iota_c2 < off + cnt))[:, None]
        acc[:] = jnp.where(region, placed, acc[:])

    # ---- the new placement: a tile-aligned window a side --------------------
    def put(acc, cursor, cnt, rows):
        if "place" in stubs:
            acc[0:8] = rows[0:8]
            return
        r = cursor & 7
        win = pl.ds(pl.multiple_of(cursor - r, 8), WIN)
        region = (iota_win >= r) & (iota_win < r + cnt)
        if "place=where" in stubs:
            acc[win] = jnp.where(region, rows, acc[win])
        else:
            pltpu.store(acc.at[win], rows,
                        mask=jnp.broadcast_to(region, rows.shape))

    def place(g, nlk, nrk, block, carry):
        """Each side placed and, where its window filled, flushed: the
        first side, then the staged one, as the kernel orders them."""
        nl, nr, lo_, ro_, lfl, rfl, pl_, pr_ = carry
        if old:
            if "rotate" in stubs:
                placed_l = placed_r = block
            else:
                placed_l = pltpu.roll(block, lo_, axis=0)
                placed_r = pltpu.roll(block, ro_ - nlk + C2, axis=0)
            sides = (lambda: blend(lacc, placed_l, nlk, lo_, left_value),
                     lambda: blend(racc, placed_r, nrk, ro_, right_value))
        elif sides_race:
            sides = (lambda: put(lacc, lo_, nlk, block[0]),
                     lambda: put(racc, ro_, nrk, block[1]))
        else:
            tile_r = pl.multiple_of(((lo_ & 7) + nlk + 7) & -8, 8)
            sides = (lambda: put(lacc, lo_, nlk, blk[g, 0:WIN]),
                     lambda: put(racc, ro_, nrk, blk[g, pl.ds(tile_r, WIN)]))
        fl = ((lo_ + nlk) >= CHUNK).astype(jnp.int32)
        fr = ((ro_ + nrk) >= CHUNK).astype(jnp.int32)
        sides[0]()
        if "flush" not in stubs:
            @pl.when(fl > 0)
            def _flush_l():
                flush(lacc, payload_out, base + lfl * CHUNK, stage, sem_w,
                      pl_)
        sides[1]()
        if "flush" in stubs:
            return (nl + nlk, nr + nrk, lo_ + nlk - fl * CHUNK,
                    ro_ + nrk - fr * CHUNK, lfl + fl, rfl + fr, pl_, pr_)

        @pl.when(fr > 0)
        def _flush_r():
            flush(racc, aux_out, base + rfl * CHUNK, rbuf, sem_r, pr_)

        return (nl + nlk, nr + nrk, lo_ + nlk - fl * CHUNK,
                ro_ + nrk - fr * CHUNK, lfl + fl, rfl + fr,
                jnp.maximum(pl_, fl), jnp.maximum(pr_, fr))

    def body_trip(t, carry):
        """Chunks G t .. G t + G - 1 (the caller's segment has a multiple
        of G chunks).  Every wait and load comes before any chunk's
        arithmetic: a DMA wait is a barrier the scheduler moves nothing
        across."""
        k0 = G * t
        ahead = G if G > 1 else R - 1
        for i in range(G):
            @pl.when(k0 + ahead + i < nch)
            def _prefetch(i=i):
                for dma in read_a(k0 + ahead + i,
                                  lax.rem(k0 + ahead + i, R)):
                    dma.start()

        slots = [lax.rem(k0 + i, R) for i in range(G)]
        for i in range(G):
            for dma in read_a(k0 + i, slots[i]):
                dma.wait()
        datas = [ring[slot] for slot in slots]

        if "body" in stubs:
            for data in datas:
                lacc[0:CHUNK] += data
            return (carry[0] + G,) + carry[1:]

        windows = [route_ring[slot] for slot in slots] if blocks else datas

        @pl.when(t == 0)
        def _seed():
            lacc[0:CHUNK] = datas[0]

        lo_, ro_ = carry[2], carry[3]
        gl, dest, dest_r = routed(k0, windows, lo_, ro_)
        chunks = []
        for i in range(G):
            chunks.append(permuted(i, k0 + i, datas[i], gl, dest, dest_r,
                                   lo_))
            lo_ = lo_ + chunks[i][0]
        for i in range(G):
            carry = place(i, *chunks[i], carry)
        return carry

    out = lax.fori_loop(
        0, nch // G, body_trip,
        (jnp.int32(0), jnp.int32(0), shift, shift,
         jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    nl_out[0] = out[0]
    drain(payload_out, stage, sem_w, out[6])
    drain(aux_out, rbuf, sem_r, out[7])


@functools.partial(jax.jit, static_argnames=("value_col", "num_bins",
                                             "interpret", "stubs", "group",
                                             "body"),
                   donate_argnums=(0, 1))
def pass_a(payload, aux, route, start, count, pred, left_value, right_value,
           value_col, num_bins, interpret, stubs, group=1, body="new"):
    """Pass A over `payload`; with `route` ([N, 128], the split window's
    copy) as one column block's pass, else routed from the rows."""
    P, B = payload.shape[1], num_bins
    blocks = route is not None
    win_lo = (pred.col // 128) * 128 if blocks else 0
    scalars = pseg._acc_scalars(start, count, pred, pred.col - win_lo,
                                win_lo, B, False)
    fvals = jnp.stack([left_value, right_value]).astype(jnp.float32)
    kern = functools.partial(_pass_a_kernel, P=P, B=B, value_col=value_col,
                             stubs=stubs, group=group, body=body,
                             blocks=blocks)
    depth = 2 * group
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    payload_new, aux_new, nl = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[hbm, hbm] + [hbm] * blocks,
            out_specs=(hbm, hbm, pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[
                pltpu.VMEM((depth, CHUNK, P), jnp.float32),
                pltpu.VMEM((C2, P), jnp.float32),
                pltpu.VMEM((C2, P), jnp.float32),
                pltpu.VMEM((CHUNK, P), jnp.float32),
                pltpu.VMEM((CHUNK, P), jnp.float32),
                pltpu.VMEM((group, pseg.BLOCK_SCRATCH_ROWS, P),
                           jnp.float32),
                pltpu.VMEM((group, 128, CHUNK), jnp.float32),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ] + [pltpu.VMEM((depth, CHUNK, 128), jnp.float32),
                 pltpu.SemaphoreType.DMA((depth,))] * blocks),
        out_shape=(jax.ShapeDtypeStruct(payload.shape, payload.dtype),
                   jax.ShapeDtypeStruct(aux.shape, aux.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pseg._SIDE_EFFECTS,
        interpret=interpret,
    )(scalars, fvals, payload, aux, *([route] * blocks))
    return payload_new, aux_new, nl[0]


def stub_sets(body, race):
    """(label, stubs, group) of every kernel timed for `body`: each stub
    at one chunk a trip, the placement's also at two and four, and the
    loop's shapes."""
    placement = PLACEMENT[body]
    sets = [("full", (), 1)] + [(s, (s,), 1)
                                for s in SHARED_STUBS + placement] + [
        ("matmul2+onehot+rank", ("matmul2", "onehot", "rank"), 1),
        ("placement", placement, 1),
        ("index chain", ("onehot", "rank", "route"), 1),
        ("group2", (), 2), ("group4", (), 4)]
    for g in (2, 4):
        sets += [("group%d+%s" % (g, label), stubs, g) for label, stubs in (
            ("placement", placement), ("flush", ("flush",)),
            ("index chain", ("onehot", "rank", "route")),
            ("body", ("body",)))]
    if body == "new" and race:
        for choice in RACES:
            sets += [(choice, (choice,), 1), ("group2+" + choice, (choice,), 2)]
    return sets


def ablate(P, interpret, race, times, match=None):
    assert P in (128, 512), P
    n = 2048 if interpret else (1 << 22 if P == 128 else 1 << 20)
    F, B = 28, 256
    rng = np.random.default_rng(0)
    host = np.zeros((n + seg.GUARD, P), np.float32)
    host[:n, :F] = rng.integers(0, B, (n, F))
    host[:n, F] = rng.standard_normal(n)
    host[:n, F + 1] = rng.random(n) + 0.1
    host[:n, F + 2] = 1.0
    payload = jnp.asarray(host)
    # one column block routes from a copy of the split window
    route = payload[:, :128] + 0.0 if P > 128 else None
    lv, rv = jnp.float32(1.5), jnp.float32(-2.5)
    start, count = jnp.int32(0), jnp.int32(n)
    fresh = jax.jit(lambda x: x + 0.0)

    def pred(**kw):
        base = dict(
            col=jnp.int32(2), threshold=jnp.int32(100),
            default_left=jnp.bool_(True), is_cat=jnp.bool_(False),
            missing_type=jnp.int32(0), num_bin=jnp.int32(B),
            default_bin=jnp.int32(0), offset=jnp.int32(0),
            identity=jnp.bool_(True), bitset=jnp.zeros(B, jnp.int32))
        base.update(kw)
        return seg.SplitPredicate(**base)

    def call(pr, body, stubs, group):
        """Seconds of one donated call, its scalar fetched."""
        p_ = fresh(payload)
        a_ = jnp.zeros_like(p_)
        jax.block_until_ready((p_, a_))
        t0 = time.perf_counter()
        out = pass_a(p_, a_, route, start, count, pr, lv, rv, F + 3, B,
                     interpret, stubs, group, body)
        nl = int(out[2])
        return time.perf_counter() - t0, out, nl

    # the unstubbed copies write the lefts the portable partition writes
    cat = pred(is_cat=jnp.bool_(True), bitset=jnp.asarray(
        np.isin(np.arange(B), (0, 31, 32, 63, 64, 100, B - 1)), jnp.int32))
    for pr in (pred(), cat):
        ref, _, ref_nl = seg.partition_segment(
            payload, jnp.zeros_like(payload), start, count, pr, lv, rv, F + 3)
        for body in ("old", "new"):
            variants = [((), g) for g in ((1,) if P > 128 else (1, 2, 4))]
            if body == "new" and race:
                variants += [((c,), 1) for c in RACES]
            for stubs, group in variants:
                _, out, nl = call(pr, body, stubs, group)
                full = nl // CHUNK * CHUNK
                assert nl == int(ref_nl), (body, stubs, group, nl, int(ref_nl))
                assert bool(jnp.array_equal(out[0][:full], ref[:full])), \
                    "lefts differ (%s, %s, group=%d)" % (body, stubs, group)
        del out, ref

    chunks = n // CHUNK
    for body in ("old", "new"):
        for label, stubs, group in stub_sets(body, race):
            if P > 128 and group > 2:
                continue        # four chunks of 512 lanes pass the VMEM plan
            if match and label != "full" and not any(
                    m in label for m in match.split(",")):
                continue
            name = "%d %s %s" % (P, body, label)
            if interpret:
                call(pred(), body, stubs, group)
                times[name] = None
                continue
            call(pred(), body, stubs, group)
            ts = sorted(call(pred(), body, stubs, group)[0] for _ in range(5))
            times[name] = ts[2]
            full = times["%d %s %s" % (P, body, "full")]
            print("%-44s %8.3f ms  %7.1f ns/chunk  (full - this: %7.1f)"
                  % (name, ts[2] * 1e3, ts[2] / chunks * 1e9,
                     (full - ts[2]) / chunks * 1e9), flush=True)
    return n


def main():
    argv = sys.argv[1:]
    interpret = "--interpret" in argv
    race = "--race" in argv
    if not interpret and jax.default_backend() != "tpu":
        sys.exit("ablate_partition_body: platform is %r, not tpu"
                 % jax.default_backend())
    lanes = [int(a) for a in argv if a.isdigit()] or [128, 512]
    match = argv[argv.index("--match") + 1] if "--match" in argv else None
    times, rows = {}, {}
    for P in lanes:
        rows[P] = ablate(P, interpret, race, times, match)
    line = json.dumps({"rows": rows, "chunk": CHUNK, "interpret": interpret,
                       "seconds": times})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "ablate_partition_body.json"),
              "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
