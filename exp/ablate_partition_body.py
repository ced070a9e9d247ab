"""In-kernel ablation of the accumulator partition's pass A: what each
piece of the loop body costs a chunk on the chip.

The profiler's trace ends at the kernel's edge (PERF.md §7: "time inside a
Pallas kernel" is not seen), so the body is timed by leaving pieces out.
`_pass_a_kernel` below is pass A of `pallas_segment._acc_kernel` (ring
read, index arithmetic with rows in lanes, one destination one-hot, three
part matmuls, the placement into the two accumulators, the flushes),
without pass B and the final blend read, in two bodies that differ in how
a full window of an accumulator reaches HBM, and in nothing else:

    new   as the kernel stands since PR 38: each accumulator a ring of
          `_ACC_WINDOWS` windows and a chunk's tail; a flush is ONE DMA
          out of the ring's own window, the cursor runs on and wraps, the
          put that fills a window first waits for the flush of the window
          it runs on into, and a put that crosses the ring's end is two
          aligned masked stores
    old   as it stood from PR 36 to PR 37: a [2C, P] accumulator whose
          first window is copied to a stage, sent from there, and whose
          second half is then slid onto the first; each flush first waits
          for the side's last one (the stage is one buffer); kept runnable
          so that both tables come from one instrument

and with a static set of stubs.  Both bodies:

    matmul2  one part matmul of the three (the two others' cost)
    parts    no bf16 hi/mid/lo split (the chunk stands in for each part)
    flush    no write of a full accumulator window to HBM; its parts:
               wait       (both) no wait inside the loop: every flush's
                          wait is paid at the kernel's end instead, so
                          the semaphores end balanced
               stage      (old) no copy of the window to the stage
               slide      (old) no slide of the accumulator
               wrapstore  (new) no second store where a put crosses the
                          ring's end
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
    place       the accumulators take 8 rows of the block, not a masked
                store of an aligned [C + 8, P] window a side
    blockstore  the block is not stored to its scratch (the value
                column's select goes with it)

the choices the new body was made from, by race (`--race`):

    windows=2    a ring of two windows: the put that fills one waits for
                 the flush one chunk of rows earlier, as the stage did
    wrap=always  the second store of a put unconditional, its mask empty
                 where the put does not cross the ring's end: a store
                 more a put for a region less
    wrap=flush   the second store inside the flush's own region (a put
                 that crosses the ring's end fills a window): a region
                 less a put and no store more
    above=roll  the lefts of a trip's earlier chunks by static sublane
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
piece's cost is not hidden behind another chunk's, and the flush's and
the placement's also at the shipped trip.

`--first S` sends S% of the rows to the first side (50, 90, 100; the
bins are uniform, so it is a threshold): a tree's splits are lopsided,
the larger child lies first, and the side that takes nearly every row
flushes nearly every chunk, so the flush is read at 90 and 100, not at
the even split the other pieces are read at (39% where nothing is asked
for, as the tables of PR 29 and PR 36 were).

The argument `512` (beside `128`, the default being both) times one
512-lane column block: the kernel moves a 512-lane payload and routes from
a [N, 128] copy of the split window, read into a ring of its own, as
`partition_segment_acc_blocks`' passes do.

A stubbed kernel computes nonsense; only its time is read.  With no stub
the lefts it writes are checked against the portable partition, for a
numerical and a categorical predicate and under the timed one.  Times are
wall clock round a call whose payload is donated (no copy in the program)
and whose scalar result is fetched; the cost of a piece is full minus
stubbed, per chunk of CHUNK rows.  Pieces overlap in the kernel's
schedule, so the costs need not add up to the body.

On the chip:   python exp/ablate_partition_body.py [--race] [128] [512]
               [--first 50|90|100] [--body old|new]
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
SHARED_STUBS = ("rank", "onehot", "matmul2", "parts", "flush", "wait", "body",
                "route", "colselect", "mask", "predicate", "catword",
                "place", "blockstore")
#: the placement's stubs, together what is left of it
PLACEMENT = ("place", "blockstore")
#: the parts of a body's flush beside the wait
FLUSH_PARTS = {"old": ("stage", "slide"), "new": ("wrapstore",)}
RACES = ("windows=2", "wrap=always", "wrap=flush", "above=roll", "rank=roll",
         "col=xpose", "col=nt", "col=nthigh", "nl=scalar")


def _pass_a_kernel(scalars, fvals, payload_hbm, aux_hbm, *rest,
                   P, B, value_col, stubs, group, body, blocks):
    if blocks:
        route_hbm, *rest = rest
    payload_out, aux_out, nl_out, *rest = rest
    old = body == "old"
    if old:
        ring, lacc, racc, stage, rbuf, blk, win_t, *rest = rest
    else:
        ring, lacc, racc, blk, win_t, *rest = rest
    sem_ring, sem_w, sem_r, *rest = rest
    if blocks:
        route_ring, sem_route = rest
    start, count = scalars[0], scalars[1]
    left_value, right_value = fvals[0], fvals[1]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    iota_win = lax.broadcasted_iota(jnp.int32, (WIN, 1), 0)
    iota_p = lax.broadcasted_iota(jnp.int32, (1, P), 1)
    # the lanes of the split window: the 128-lane chunk's own, or those of
    # a column block's copy (this script times no other width)
    iota_route = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    iota_ci = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    iota_cj = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    tri_t = (iota_ci < iota_cj).astype(jnp.float32)
    iota_hot = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, CHUNK), 0)
    iota_b = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, 1), 0)
    tri_hot = (iota_hot < lax.broadcasted_iota(
        jnp.int32, (BLOCK_ROWS, CHUNK), 1)).astype(jnp.float32)
    chunk_of_row = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 0)
    row_of_lane = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 1)
    R, G = ring.shape[0], group
    # the new body's ring: its rows and windows (the old accumulator is
    # [2C, P], a window and the room a put overruns it by)
    RS = lacc.shape[0] - CHUNK
    NW = RS // CHUNK
    route_col = scalars[2]
    route_sel = (lax.broadcasted_iota(jnp.int32, (8, 128), 1) ==
                 route_col).astype(jnp.float32)

    def hbm_window(dst_ref, row0):
        return dst_ref.at[pl.ds(row0, CHUNK), :]

    def read_a(k, slot):
        rows = pl.ds(pl.multiple_of(base + k * CHUNK, 8), CHUNK)
        dmas = [pltpu.make_async_copy(payload_out.at[rows, :], ring.at[slot],
                                      sem_ring.at[slot])]
        if blocks:
            dmas.append(pltpu.make_async_copy(
                route_hbm.at[rows, :], route_ring.at[slot],
                sem_route.at[slot]))
        return dmas

    # ---- the old flush: wait, copy to a stage, send, slide ------------------
    def old_landed(dst_ref, stage_buf, sem):
        pltpu.make_async_copy(stage_buf, hbm_window(dst_ref, 0), sem).wait()

    def old_flush(acc, dst_ref, wbase, stage_buf, sem, pend):
        if "wait" not in stubs:
            @pl.when(pend > 0)
            def _():
                old_landed(dst_ref, stage_buf, sem)
        if "stage" not in stubs:
            stage_buf[:] = acc[0:CHUNK]
        pltpu.make_async_copy(
            stage_buf, hbm_window(dst_ref, pl.multiple_of(wbase, 8)),
            sem).start()
        if "slide" not in stubs:
            acc[0:CHUNK] = acc[CHUNK:C2]

    # ---- the new flush: reserve, (put), one DMA out of the ring -------------
    def landed(acc, dst_ref, sem, f):
        pltpu.make_async_copy(acc.at[pl.ds(0, CHUNK)], hbm_window(dst_ref, 0),
                              sem.at[lax.rem(f, NW)]).wait()

    def reserve(acc, dst_ref, sem, f, fl):
        if "wait" in stubs or "flush" in stubs:
            return

        @pl.when((fl > 0) & (f >= NW - 1))
        def _():
            landed(acc, dst_ref, sem, f + 1 - NW)

    def flush(acc, dst_ref, sem, f):
        h = lax.rem(f, NW)
        pltpu.make_async_copy(
            acc.at[pl.ds(pl.multiple_of(h * CHUNK, CHUNK), CHUNK)],
            hbm_window(dst_ref, pl.multiple_of(base + f * CHUNK, 8)),
            sem.at[h]).start()

    @pl.when(nch > 0)
    def _prefetch_first():
        for i in range(G if G > 1 else R - 1):
            @pl.when(i < nch)
            def _start(i=i):
                for dma in read_a(i, i):
                    dma.start()

    # ---- the index arithmetic, rows in lanes --------------------------------
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
        """(gl, dest): the routing and the one-hot's destination, each
        side from its cursor's part under 8."""
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
        dest = jnp.where(gl > 0, r_l + rank_l,
                         ((r_l + nl + 7) & -8) + r_r + rank_r)
        return gl, jnp.where(valid > 0, dest, -1)

    def product(mat, data):
        hi, mid, lo = (data, data, data) if "parts" in stubs \
            else pseg._bf16_parts(data)
        perm = jnp.dot(mat, hi, preferred_element_type=jnp.float32)
        if "matmul2" in stubs:
            rest = mid + lo
            return perm + jnp.pad(rest, ((0, perm.shape[0] - CHUNK), (0, 0)))
        return (perm + jnp.dot(mat, mid, preferred_element_type=jnp.float32)
                + jnp.dot(mat, lo, preferred_element_type=jnp.float32))

    def permuted(g, k, data, gl, dest, lo_):
        """(nlk, nrk) of chunk k, its block in its scratch."""
        nlk = jnp.sum(jnp.where(chunk_of_row == g, gl, 0))
        lo = jnp.maximum(shift - k * CHUNK, 0)
        hi = jnp.minimum(shift + count - k * CHUNK, CHUNK)
        nrk = jnp.maximum(hi - lo, 0) - nlk
        hot = tri_hot if "onehot" in stubs \
            else (iota_hot == dest[g:g + 1, :]).astype(jnp.float32)
        perm = product(hot, data)
        if "blockstore" not in stubs:
            blk[g, 0:BLOCK_ROWS] = jnp.where(
                iota_p == value_col,
                jnp.where(iota_b < (lo_ & 7) + nlk, left_value, right_value),
                perm)
        else:
            blk[g, 0:8] = perm[0:8]
        return nlk, nrk

    # ---- the placement: a tile-aligned window a side ------------------------
    def put(acc, cursor, cnt, g, tile_src, second=False):
        """The block's window from `tile_src` to the accumulator's window
        at the cursor's tile; the new body's second store where the rows
        cross the ring's end (with `wrap=flush` the caller asks for it
        apart, `second`, from inside the flush's region)."""
        if "place" in stubs:
            if not second:
                acc[0:8] = blk[g, 0:8]
            return
        r = cursor & 7
        tile = pl.multiple_of(cursor - r, 8)
        rows = blk[g, pl.ds(tile_src, WIN)]
        if not second:
            region = (iota_win >= r) & (iota_win < r + cnt)
            pltpu.store(acc.at[pl.ds(tile, WIN)], rows,
                        mask=jnp.broadcast_to(region, rows.shape))
        if old or "wrapstore" in stubs or (
                "wrap=flush" in stubs and not second):
            return
        wraps = cursor + cnt > RS
        if "wrap=always" in stubs:
            over = pl.multiple_of(jnp.where(wraps, RS - tile, 0), 8)
            spill = blk[g, pl.ds(pl.multiple_of(tile_src + over, 8), WIN)]
            pltpu.store(acc.at[pl.ds(0, WIN)], spill, mask=jnp.broadcast_to(
                iota_win + over < jnp.where(wraps, r + cnt, 0), rows.shape))
            return

        @pl.when(wraps)
        def _wrap():
            over = pl.multiple_of(RS - tile, 8)
            spill = blk[g, pl.ds(pl.multiple_of(tile_src + over, 8), WIN)]
            pltpu.store(acc.at[pl.ds(0, WIN)], spill, mask=jnp.broadcast_to(
                iota_win + over < r + cnt, rows.shape))

    def fills(cursor, cnt):
        return (lax.rem(cursor, CHUNK) + cnt >= CHUNK).astype(jnp.int32)

    def moved(cursor, cnt):
        return jnp.where(cursor + cnt >= RS, cursor + cnt - RS, cursor + cnt)

    def place(g, nlk, nrk, carry):
        """Each side placed and, where its window filled, flushed: the
        first side, then the staged one, as the kernel orders them."""
        nl, nr, lo_, ro_, lfl, rfl, pl_, pr_ = carry
        tile_r = pl.multiple_of(((lo_ & 7) + nlk + 7) & -8, 8)
        if old:
            fl = ((lo_ + nlk) >= CHUNK).astype(jnp.int32)
            fr = ((ro_ + nrk) >= CHUNK).astype(jnp.int32)
            put(lacc, lo_, nlk, g, 0)
            if "flush" not in stubs:
                @pl.when(fl > 0)
                def _flush_l():
                    old_flush(lacc, payload_out, base + lfl * CHUNK, stage,
                              sem_w, pl_)
            put(racc, ro_, nrk, g, tile_r)
            if "flush" not in stubs:
                @pl.when(fr > 0)
                def _flush_r():
                    old_flush(racc, aux_out, base + rfl * CHUNK, rbuf, sem_r,
                              pr_)
                pl_, pr_ = jnp.maximum(pl_, fl), jnp.maximum(pr_, fr)
            return (nl + nlk, nr + nrk, lo_ + nlk - fl * CHUNK,
                    ro_ + nrk - fr * CHUNK, lfl + fl, rfl + fr, pl_, pr_)

        fl, fr = fills(lo_, nlk), fills(ro_, nrk)
        reserve(lacc, payload_out, sem_w, lfl, fl)
        put(lacc, lo_, nlk, g, 0)
        if "flush" not in stubs:
            @pl.when(fl > 0)
            def _flush_l():
                if "wrap=flush" in stubs:
                    put(lacc, lo_, nlk, g, 0, second=True)
                flush(lacc, payload_out, sem_w, lfl)
        reserve(racc, aux_out, sem_r, rfl, fr)
        put(racc, ro_, nrk, g, tile_r)
        if "flush" not in stubs:
            @pl.when(fr > 0)
            def _flush_r():
                if "wrap=flush" in stubs:
                    put(racc, ro_, nrk, g, tile_r, second=True)
                flush(racc, aux_out, sem_r, rfl)
        return (nl + nlk, nr + nrk, moved(lo_, nlk), moved(ro_, nrk),
                lfl + fl, rfl + fr, pl_, pr_)

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
            if old:
                lacc[0:CHUNK] = datas[0]
            else:
                lacc[0:8] = ring[slots[0], 0:8]

        lo_, ro_ = carry[2], carry[3]
        gl, dest = routed(k0, windows, lo_, ro_)
        chunks = []
        for i in range(G):
            chunks.append(permuted(i, k0 + i, datas[i], gl, dest, lo_))
            lo_ = lo_ + chunks[i][0]
        for i in range(G):
            carry = place(i, *chunks[i], carry)
        return carry

    out = lax.fori_loop(
        0, nch // G, body_trip,
        (jnp.int32(0), jnp.int32(0), shift, shift,
         jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    nl_out[0] = out[0]
    if "flush" in stubs or "body" in stubs:
        return
    def settle(acc, dst_ref, sem, stage_buf, flushes, pend):
        """What the loop has not waited for: with `wait` stubbed every
        flush, else the old body's last one, the new body's last NW - 1."""
        if "wait" in stubs:
            def one(f, c):
                if old:
                    old_landed(dst_ref, stage_buf, sem)
                else:
                    landed(acc, dst_ref, sem, f)
                return c
            lax.fori_loop(0, flushes, one, 0)
        elif old:
            @pl.when(pend > 0)
            def _():
                old_landed(dst_ref, stage_buf, sem)
        else:
            for j in range(1, NW):
                @pl.when(flushes >= j)
                def _(j=j):
                    landed(acc, dst_ref, sem, flushes - j)

    settle(lacc, payload_out, sem_w, stage if old else None, out[4], out[6])
    settle(racc, aux_out, sem_r, rbuf if old else None, out[5], out[7])


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
    windows = 2 if "windows=2" in stubs else pseg._ACC_WINDOWS
    f32 = jnp.float32
    if body == "old":
        accs = [pltpu.VMEM((C2, P), f32), pltpu.VMEM((C2, P), f32),
                pltpu.VMEM((CHUNK, P), f32), pltpu.VMEM((CHUNK, P), f32)]
        flush_sems = [pltpu.SemaphoreType.DMA(())] * 2
    else:
        accs = [pltpu.VMEM(((windows + 1) * CHUNK, P), f32)] * 2
        flush_sems = [pltpu.SemaphoreType.DMA((windows,))] * 2
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    payload_new, aux_new, nl = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[hbm, hbm] + [hbm] * blocks,
            out_specs=(hbm, hbm, pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[pltpu.VMEM((depth, CHUNK, P), f32)] + accs + [
                pltpu.VMEM((group, pseg.BLOCK_SCRATCH_ROWS, P), f32),
                pltpu.VMEM((group, 128, CHUNK), f32),
                pltpu.SemaphoreType.DMA((depth,))] + flush_sems + [
                pltpu.VMEM((depth, CHUNK, 128), f32),
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
    at one chunk a trip, the flush's and the placement's also at two and
    four, and the loop's shapes."""
    parts = FLUSH_PARTS[body]
    sets = [("full", (), 1)] + [(s, (s,), 1)
                                for s in SHARED_STUBS + parts] + [
        ("matmul2+onehot+rank", ("matmul2", "onehot", "rank"), 1),
        ("placement", PLACEMENT, 1),
        ("index chain", ("onehot", "rank", "route"), 1),
        ("group2", (), 2), ("group4", (), 4)]
    for g in (2, 4):
        sets += [("group%d+%s" % (g, label), stubs, g) for label, stubs in (
            ("placement", PLACEMENT), ("flush", ("flush",)),
            ("wait", ("wait",)), *((part, (part,)) for part in parts),
            ("wait+" + "+".join(parts), ("wait",) + parts),
            ("index chain", ("onehot", "rank", "route")),
            ("body", ("body",)))]
    if body == "new" and race:
        for choice in RACES:
            sets += [(choice, (choice,), 1), ("group2+" + choice, (choice,), 2)]
    return sets


def ablate(P, interpret, race, times, match=None, first=None,
           bodies=("old", "new")):
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

    # the share of the rows the timed predicate sends to the first side
    # (bins are uniform over 256): 100 of 256 where none is asked for
    timed_threshold = 100 if first is None else round(B * first / 100) - 1

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
    timed = pred(threshold=jnp.int32(timed_threshold))
    for pr in (pred(), cat) + ((timed,) if first is not None else ()):
        ref, _, ref_nl = seg.partition_segment(
            payload, jnp.zeros_like(payload), start, count, pr, lv, rv, F + 3)
        for body in bodies:
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
    for body in bodies:
        for label, stubs, group in stub_sets(body, race):
            if P > 128 and group > 2:
                continue        # four chunks of 512 lanes pass the VMEM plan
            if match and label not in ("full", "group2") and not any(
                    m in label for m in match.split(",")):
                continue
            name = "%d %s %s" % (P, body, label)
            if interpret:
                call(timed, body, stubs, group)
                times[name] = None
                continue
            call(timed, body, stubs, group)
            ts = sorted(call(timed, body, stubs, group)[0] for _ in range(5))
            times[name] = ts[2]
            # a piece costs what its own trip's unstubbed body takes more
            whole = label.split("+")[0] if label.startswith("group") \
                else "full"
            full = times.get("%d %s %s" % (P, body, whole), ts[2])
            print("%-44s %8.3f ms  %7.1f ns/chunk  (%s - this: %7.1f)"
                  % (name, ts[2] * 1e3, ts[2] / chunks * 1e9, whole,
                     (full - ts[2]) / chunks * 1e9), flush=True)
    return n


def main():
    argv = sys.argv[1:]
    interpret = "--interpret" in argv
    race = "--race" in argv
    if not interpret and jax.default_backend() != "tpu":
        sys.exit("ablate_partition_body: platform is %r, not tpu"
                 % jax.default_backend())
    lanes = [int(a) for a in argv if a in ("128", "512")] or [128, 512]
    match = argv[argv.index("--match") + 1] if "--match" in argv else None
    first = int(argv[argv.index("--first") + 1]) if "--first" in argv else None
    bodies = (argv[argv.index("--body") + 1],) if "--body" in argv \
        else ("old", "new")
    times, rows = {}, {}
    for P in lanes:
        rows[P] = ablate(P, interpret, race, times, match, first, bodies)
    line = json.dumps({"rows": rows, "chunk": CHUNK, "interpret": interpret,
                       "first": first, "seconds": times})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "ablate_partition_body%s.json"
                           % ("" if first is None else "_first%d" % first)),
              "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
