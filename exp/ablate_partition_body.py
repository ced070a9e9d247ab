"""In-kernel ablation of the accumulator partition's pass A: what each
piece of the loop body costs a chunk on the chip.

The profiler's trace ends at the kernel's edge (PERF.md §7: "time inside a
Pallas kernel" is not seen), so the body is timed by leaving pieces out.
`_pass_a_kernel` below is pass A of `pallas_segment._acc_kernel` as it
stands (ring read, routing, rank mat-vec, one destination one-hot, three
part matmuls, two rotates of the doubled block, two blends, the flushes),
without pass B and the final blend read, and with a static set of stubs:

    route    the routing is a parity of the row number (no lane reduction,
             no [C, B] bitset one-hot)
    rank     the lefts' ranks are the row number (no tri mat-vec)
    onehot   the [C, C] one-hot is the hoisted `tri` (none built a chunk)
    matmul2  one part matmul of the three (the two others' cost)
    parts    no bf16 hi/mid/lo split (the chunk stands in for each part)
    rotate   no dynamic rotate of the doubled block
    blend    the accumulators take 8 rows, not a [2C, P] select
    flush    no write of a full accumulator window to HBM
    body     nothing but the ring read and one add of the chunk (the DMA
             floor)

and the loop's shape, `group2` / `group4`: that many chunks a loop trip on
a ring twice as deep, every wait and load first, then every chunk's
permuted block, then the placements in order, so that independent chains
(routing, rank, one-hot, matmuls) lie in one basic block for the scheduler
to interleave.  The product takes 2 at this width (`_pass_a_group`); the
stubs above are read on one chunk a trip, where a piece's cost is not
hidden behind another chunk's.

A stubbed kernel computes nonsense; only its time is read.  With no stub
the lefts it writes are checked against the portable partition, so the
copy is the product's body.  Times are wall clock round a call whose
payload is donated (no copy in the program) and whose scalar result is
fetched; the cost of a piece is full minus stubbed, per chunk of CHUNK
rows.  Pieces overlap in the kernel's schedule, so the costs need not add
up to the body.

On the chip:   python exp/ablate_partition_body.py
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

CHUNK, C2 = pseg.CHUNK, pseg.C2
STUBS = ("route", "rank", "onehot", "matmul2", "parts", "rotate", "blend",
         "flush", "body")


def _pass_a_kernel(scalars, fvals, bitset_ref, payload_hbm, aux_hbm,
                   payload_out, aux_out, nl_out,
                   ring, lacc, racc, stage, rbuf, sem_ring, sem_w, sem_r, *,
                   P, B, value_col, stubs, group):
    start, count = scalars[0], scalars[1]
    left_value, right_value = fvals[0], fvals[1]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    iota_rows = pseg._row_iota()
    iota_c2 = lax.broadcasted_iota(jnp.int32, (C2, 1), 0)[:, 0]
    iota_p = lax.broadcasted_iota(jnp.int32, (1, P), 1)
    iota_ci = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    tri = (lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1) <
           iota_ci).astype(jnp.float32)
    R = ring.shape[0]

    def ring_dma(k, slot):
        return pltpu.make_async_copy(
            payload_out.at[pl.ds(pl.multiple_of(base + k * CHUNK, 8),
                                 CHUNK), :],
            ring.at[slot], sem_ring.at[slot])

    def blend(acc, placed, cnt, off, value):
        if "blend" in stubs:
            acc[0:8] = placed[0:8]
            return
        placed = jnp.where(iota_p == value_col, value, placed)
        region = ((iota_c2 >= off) & (iota_c2 < off + cnt))[:, None]
        acc[:] = jnp.where(region, placed, acc[:])

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
        for i in range(group if group > 1 else R - 1):
            @pl.when(i < nch)
            def _start(i=i):
                ring_dma(i, i).start()

    def permuted(k, data):
        """The chunk's doubled permuted block and its two counts: no
        accumulator, cursor or DMA is touched."""
        valid = ((iota_rows >= shift - k * CHUNK) &
                 (iota_rows < shift + count - k * CHUNK)).astype(jnp.int32)
        if "route" in stubs:
            gl = (iota_rows & 1) * valid
        else:
            gl = pseg._go_left_rows(scalars, bitset_ref, data, B,
                                    iota_p) * valid
        keep_r = valid - gl
        nlk = jnp.sum(gl)
        nrk = jnp.sum(keep_r)
        if "rank" in stubs:
            rank_l = iota_rows
        else:
            rank_l = jnp.dot(tri, gl.astype(jnp.float32)[:, None],
                             preferred_element_type=jnp.float32)[:, 0] \
                .astype(jnp.int32)
        rank_r = jnp.maximum(
            iota_rows - jnp.maximum(shift - k * CHUNK, 0), 0) - rank_l
        dest = jnp.where(gl > 0, rank_l, nlk + rank_r)
        if "onehot" in stubs:
            mat = tri
        else:
            mat = ((iota_ci == dest[None, :]) &
                   (valid[None, :] > 0)).astype(jnp.float32)
        hi, mid, lo = (data, data, data) if "parts" in stubs \
            else pseg._bf16_parts(data)
        perm = jnp.dot(mat, hi, preferred_element_type=jnp.float32)
        if "matmul2" in stubs:
            perm = perm + mid + lo
        else:
            perm = (perm +
                    jnp.dot(mat, mid, preferred_element_type=jnp.float32) +
                    jnp.dot(mat, lo, preferred_element_type=jnp.float32))
        return jnp.concatenate([perm, perm], axis=0), nlk, nrk

    def place(both, nlk, nrk, carry):
        """Rotate the block to each accumulator's cursor, blend, flush."""
        nl, nr, lo_, ro_, lfl, rfl, pl_, pr_ = carry
        if "rotate" in stubs:
            placed_l = placed_r = both
        else:
            placed_l = pltpu.roll(both, lo_, axis=0)
            placed_r = pltpu.roll(both, ro_ - nlk + C2, axis=0)
        blend(lacc, placed_l, nlk, lo_, left_value)
        fl = ((lo_ + nlk) >= CHUNK).astype(jnp.int32)
        blend(racc, placed_r, nrk, ro_, right_value)
        fr = ((ro_ + nrk) >= CHUNK).astype(jnp.int32)
        if "flush" in stubs:
            return (nl + nlk, nr + nrk, lo_ + nlk - fl * CHUNK,
                    ro_ + nrk - fr * CHUNK, lfl + fl, rfl + fr, pl_, pr_)

        @pl.when(fl > 0)
        def _flush_l():
            flush(lacc, payload_out, base + lfl * CHUNK, stage, sem_w, pl_)

        @pl.when(fr > 0)
        def _flush_r():
            flush(racc, aux_out, base + rfl * CHUNK, rbuf, sem_r, pr_)

        return (nl + nlk, nr + nrk, lo_ + nlk - fl * CHUNK,
                ro_ + nrk - fr * CHUNK, lfl + fl, rfl + fr,
                jnp.maximum(pl_, fl), jnp.maximum(pr_, fr))

    def body_a(k, carry):
        slot = lax.rem(k, R)

        @pl.when(k + R - 1 < nch)
        def _prefetch_next():
            ring_dma(k + R - 1, lax.rem(k + R - 1, R)).start()

        ring_dma(k, slot).wait()
        data = ring[slot]

        if "body" in stubs:
            lacc[0:CHUNK] += data
            return (carry[0] + 1,) + carry[1:]

        @pl.when(k == 0)
        def _seed():
            lacc[0:CHUNK] = data

        return place(*permuted(k, data), carry)

    def body_group(t, carry):
        """Chunks group*t .. group*t + group - 1 (the caller's segment
        has a multiple of `group` chunks); the trip before started their
        reads.  Every wait and load comes before any chunk's arithmetic:
        a DMA wait is a barrier the scheduler moves nothing across."""
        k0 = group * t
        for k in range(group):
            @pl.when(k0 + group + k < nch)
            def _prefetch(k=k):
                ring_dma(k0 + group + k,
                         lax.rem(k0 + group + k, R)).start()

        for k in range(group):
            ring_dma(k0 + k, lax.rem(k0 + k, R)).wait()
        datas = [ring[lax.rem(k0 + k, R)] for k in range(group)]

        if "body" in stubs:
            for data in datas:
                lacc[0:CHUNK] += data
            return (carry[0] + group,) + carry[1:]

        @pl.when(t == 0)
        def _seed():
            lacc[0:CHUNK] = datas[0]

        blocks = [permuted(k0 + k, datas[k]) for k in range(group)]
        for block in blocks:
            carry = place(*block, carry)
        return carry

    carry0 = (jnp.int32(0), jnp.int32(0), shift, shift,
              jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
    if group > 1:
        out = lax.fori_loop(0, nch // group, body_group, carry0)
    else:
        out = lax.fori_loop(0, nch, body_a, carry0)
    nl_out[0] = out[0]
    drain(payload_out, stage, sem_w, out[6])
    drain(aux_out, rbuf, sem_r, out[7])


@functools.partial(jax.jit, static_argnames=("value_col", "num_bins",
                                             "interpret", "stubs", "group"),
                   donate_argnums=(0, 1))
def pass_a(payload, aux, start, count, pred, left_value, right_value,
           value_col, num_bins, interpret, stubs, group=1):
    P, B = payload.shape[1], num_bins
    scalars = jnp.stack([
        start, count, pred.col, pred.threshold,
        pred.default_left.astype(jnp.int32), pred.is_cat.astype(jnp.int32),
        pred.missing_type, pred.num_bin, pred.default_bin,
        pred.offset, pred.identity.astype(jnp.int32),
    ]).astype(jnp.int32)
    fvals = jnp.stack([left_value, right_value]).astype(jnp.float32)
    bitset = pred.bitset.astype(jnp.int32).reshape(1, B)
    kern = functools.partial(_pass_a_kernel, P=P, B=B, value_col=value_col,
                             stubs=stubs, group=group)
    depth = 2 * group
    payload_new, aux_new, nl = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[
                pltpu.VMEM((depth, CHUNK, P), jnp.float32),
                pltpu.VMEM((C2, P), jnp.float32),
                pltpu.VMEM((C2, P), jnp.float32),
                pltpu.VMEM((CHUNK, P), jnp.float32),
                pltpu.VMEM((CHUNK, P), jnp.float32),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=(jax.ShapeDtypeStruct(payload.shape, payload.dtype),
                   jax.ShapeDtypeStruct(aux.shape, aux.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pseg._SIDE_EFFECTS,
        interpret=interpret,
    )(scalars, fvals, bitset, payload, aux)
    return payload_new, aux_new, nl[0]


def main():
    interpret = "--interpret" in sys.argv[1:]
    if not interpret and jax.default_backend() != "tpu":
        sys.exit("ablate_partition_body: platform is %r, not tpu"
                 % jax.default_backend())
    n = 2048 if interpret else 1 << 22
    F, B, P = 28, 256, 128
    rng = np.random.default_rng(0)
    host = np.zeros((n + seg.GUARD, P), np.float32)
    host[:n, :F] = rng.integers(0, B, (n, F))
    host[:n, F] = rng.standard_normal(n)
    host[:n, F + 1] = rng.random(n) + 0.1
    host[:n, F + 2] = 1.0
    payload = jnp.asarray(host)
    pred = seg.SplitPredicate(
        col=jnp.int32(2), threshold=jnp.int32(100),
        default_left=jnp.bool_(True), is_cat=jnp.bool_(False),
        missing_type=jnp.int32(0), num_bin=jnp.int32(B),
        default_bin=jnp.int32(0), offset=jnp.int32(0),
        identity=jnp.bool_(True), bitset=jnp.zeros(B, jnp.int32))
    lv, rv = jnp.float32(1.5), jnp.float32(-2.5)
    start, count = jnp.int32(0), jnp.int32(n)
    fresh = jax.jit(lambda x: x + 0.0)

    def call(stubs, group=1):
        """Seconds of one donated call, its scalar fetched."""
        p_ = fresh(payload)
        a_ = jnp.zeros_like(p_)
        jax.block_until_ready((p_, a_))
        t0 = time.perf_counter()
        out = pass_a(p_, a_, start, count, pred, lv, rv, F + 3, B,
                     interpret, stubs, group)
        nl = int(out[2])
        return time.perf_counter() - t0, out, nl

    # the unstubbed copies write the lefts the portable partition writes
    ref, _, ref_nl = seg.partition_segment(
        payload, jnp.zeros_like(payload), start, count, pred, lv, rv, F + 3)
    for group in (1, 2, 4):
        _, out, nl = call((), group)
        full = nl // CHUNK * CHUNK
        assert nl == int(ref_nl), (group, nl, int(ref_nl))
        assert bool(jnp.array_equal(out[0][:full], ref[:full])), \
            "lefts differ (group=%d)" % group
    del out, ref

    chunks = n // CHUNK
    times = {}
    for stubs in [()] + [(s,) for s in STUBS] + [
            ("matmul2", "onehot", "rank"), ("blend", "rotate"),
            ("blend", "flush", "matmul2", "onehot", "parts", "rank",
             "rotate", "route"), ("group2",), ("group4",),
            ("group2", "blend", "rotate"), ("group4", "blend", "rotate"),
            ("group2", "route"), ("group4", "body")]:
        name = "+".join(stubs) or "full"
        group = max([int(s[5:]) for s in stubs if s.startswith("group")]
                    or [1])
        stubs = tuple(s for s in stubs if not s.startswith("group"))
        if interpret:
            call(stubs, group)
            times[name] = None
            continue
        call(stubs, group)
        ts = sorted(call(stubs, group)[0] for _ in range(5))
        times[name] = ts[2]
        print("%-60s %8.3f ms  %7.1f ns/chunk  (full - this: %7.1f ns/chunk)"
              % (name, ts[2] * 1e3, ts[2] / chunks * 1e9,
                 (times["full"] - ts[2]) / chunks * 1e9), flush=True)
    line = json.dumps({"rows": n, "lanes": P, "chunks": chunks,
                       "interpret": interpret, "seconds": times})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "ablate_partition_body.json"),
              "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
