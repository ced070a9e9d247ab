"""In-kernel ablation of the histogram kernel's chunk body: what each
piece costs a 256-row chunk on the chip.  (The body before PR 27, a B-wide
one-hot a feature expanded along lanes, was timed by the same instrument;
its table is in PERF.md §6 and its code went with the kernels that kept
it.)

The profiler's trace ends at the kernel's edge, so the body is timed by
leaving pieces out, in the manner of `exp/ablate_partition_body.py`.

`_new_kernel` is `pallas_segment._hist_kernel` as it stands (with no stub
and the product's own factoring and trip it returns the product's
histogram bit for bit, which is checked), with a static set of stubs:

    body     nothing but the chunk's DMA wait and one add of 8 of its rows
             (the DMA floor)
    extract  no `sel x data^T` product and no bf16 split: the value rows
             are ones under the row mask
    transpose  the bin columns' transposition to rows-in-lanes and the
             split into high and low part: the scratches keep what the
             first chunk wrote
    lo       the low one-hot's compare: the broadcast rows themselves are
             the product's right operand
    hi       the masked values' compare: the tiled values are the left
             operand
    product  no product: both operands are stored, 8 rows accumulated
    acc      no lane-masked sum over the group's row blocks: the first
             block is accumulated
    regroup  no move of the high blocks beside each other at the end

and, with `--race`, the choices the product's rule was made from: L the
power of two at or above sqrt(8B) or the one below (`rule` / `half`), 8 /
16 / 32 groups a loop trip, f32 / bf16 operands of the product.

A stubbed kernel computes nonsense; only its time is read.  With no stub
the kernel's output is checked against the portable engine.  Times are
wall clock round a call whose scalar result is fetched, the median of
five; the cost of a piece is full minus stubbed, per chunk of CHUNK rows.
Pieces overlap in the kernel's schedule, so the costs need not add up.

On the chip:   python exp/ablate_hist_body.py [--race] [higgs criteo epsilon]
CPU rehearsal: JAX_PLATFORMS=cpu python exp/ablate_hist_body.py --interpret
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

CHUNK = pseg.CHUNK
NEW_STUBS = ("body", "extract", "transpose", "lo", "hi", "product", "acc",
             "regroup")

#: the three train cells' kernel shapes: features, bins, payload lanes,
#: rows timed
SHAPES = {
    "higgs": (28, 256, 128, 1 << 22),
    "criteo": (67, 256, 128, 1 << 22),
    "epsilon": (2000, 64, 2048, 409_600),
}


def _new_plan(B, lo_round):
    """(L, H, G) of bin = hi * L + lo: `pseg._hist_factor` (`rule`), or
    the same with L the power of two below (`half`)."""
    L, H, G = pseg._hist_factor(B)
    if lo_round == "half" and L > 8:
        L, G = L // 2, 2 * G
        H = 1
        while H * L < B:
            H *= 2
    return L, H, G


def _new_kernel(scalars, payload_hbm, out_ref, chunk, sem, hiT, loT, dump, *,
                F, B, L, H, G, U, grad_col, hess_col, cnt_col, mxu_dtype,
                stubs):
    P = chunk.shape[2]
    start, count = scalars[0], scalars[1]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)
    iota_rows = pseg._row_iota()
    R, GPT = 8 * H, 128 // G
    lbits = L.bit_length() - 1

    def dma_for(k, slot):
        return pltpu.make_async_copy(
            payload_hbm.at[pl.ds(pl.multiple_of(base + k * CHUNK, 8),
                                 CHUNK), :],
            chunk.at[slot], sem.at[slot])

    @pl.when(nch > 0)
    def _prefetch_first():
        dma_for(0, 0).start()

    hi_idx = (lax.broadcasted_iota(jnp.int32, (G * R, CHUNK), 0) // 8) % H
    lo_idx = lax.broadcasted_iota(jnp.int32, (128, CHUNK), 0) % L
    lane_grp = lax.broadcasted_iota(jnp.int32, (R, 128), 1) // L
    cols = (grad_col, hess_col, cnt_col)
    a_lo = min(cols) // 128 * 128
    a_hi = min(P, max(cols) // 128 * 128 + 128)
    iota_r8 = lax.broadcasted_iota(jnp.int32, (8, a_hi - a_lo), 0)
    iota_pc = lax.broadcasted_iota(jnp.int32, (8, a_hi - a_lo), 1) + a_lo
    sel = (((iota_r8 < 3) & (iota_pc == grad_col)) |
           ((iota_r8 >= 3) & (iota_r8 < 6) & (iota_pc == hess_col)) |
           ((iota_r8 == 6) & (iota_pc == cnt_col))).astype(jnp.float32)
    rr = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 0)
    if "transpose" in stubs or "product" in stubs:
        hiT[:] = jnp.zeros(hiT.shape, hiT.dtype)
        loT[:] = jnp.zeros(loT.shape, loT.dtype)
        dump[:] = jnp.zeros(dump.shape, dump.dtype)

    def body(k, _):
        slot = lax.rem(k, 2)

        @pl.when(k + 1 < nch)
        def _prefetch_next():
            dma_for(k + 1, lax.rem(k + 1, 2)).start()

        dma_for(k, slot).wait()
        if "body" in stubs:
            out_ref[0:8, 0:128] += chunk[slot, 0:8, 0:128]
            return 0
        ok = ((iota_rows >= shift - k * CHUNK) &
              (iota_rows < shift + count - k * CHUNK)).astype(jnp.float32)
        if "extract" in stubs:
            vals = jnp.ones((8, CHUNK), jnp.float32) * ok[None, :]
        else:
            raw = lax.dot_general(
                sel, chunk[slot, :, a_lo:a_hi],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST)                 # [8, C]
            hi = raw.astype(jnp.bfloat16).astype(jnp.float32)
            r1 = raw - hi
            mid = r1.astype(jnp.bfloat16).astype(jnp.float32)
            lo = r1 - mid
            vals = jnp.where((rr == 0) | (rr == 3), hi,
                             jnp.where((rr == 1) | (rr == 4), mid,
                                       jnp.where((rr == 2) | (rr == 5), lo,
                                                 raw)))
            vals = vals * ok[None, :]
        vals_t = jnp.concatenate([vals] * (G * H), axis=0)       # [G R, C]

        def tile(t, w, ft):
            lane0 = t * 128 if isinstance(t, int) \
                else pl.multiple_of(t * 128, 128)
            if "transpose" not in stubs:
                binT = chunk[slot, :, pl.ds(lane0, w)].T.astype(jnp.int32)
                hiT[0:w] = binT >> lbits
                loT[0:w] = binT & (L - 1)

            def group(j):
                hi_sel = jnp.concatenate(
                    [jnp.broadcast_to(hiT[pl.ds(j * G + g, 1), :],
                                      (R, CHUNK)) for g in range(G)],
                    axis=0)                                      # [G R, C]
                lo_sel = jnp.concatenate(
                    [jnp.broadcast_to(loT[pl.ds(j * G + g, 1), :],
                                      (L, CHUNK)) for g in range(G)],
                    axis=0)                                      # [128, C]
                if "hi" in stubs:
                    V = vals_t + hi_sel.astype(jnp.float32)
                else:
                    V = jnp.where(hi_sel == hi_idx, vals_t, 0.0)
                if "lo" in stubs:
                    LoT = lo_sel.astype(jnp.float32)
                else:
                    LoT = jnp.where(lo_sel == lo_idx, 1.0, 0.0)
                row0 = (t * GPT + j) * R
                if not (isinstance(t, int) and isinstance(j, int)):
                    row0 = pl.multiple_of(row0, R)
                if "product" in stubs:
                    dump[0:G * R] = V
                    dump[G * R:G * R + 128] = LoT
                    out_ref[pl.ds(row0, 8), :] += (
                        dump[0:8, 0:128] + dump[G * R:G * R + 8, 0:128])
                    return
                res = lax.dot_general(
                    V.astype(mxu_dtype), LoT.astype(mxu_dtype),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)          # [G R, 128]
                if "acc" in stubs:
                    upd = res[0:R]
                else:
                    upd = jnp.where(lane_grp == 0, res[0:R], 0.0)
                    for g in range(1, G):
                        upd = upd + jnp.where(
                            lane_grp == g, res[g * R:(g + 1) * R], 0.0)
                out_ref[pl.ds(row0, R), :] += upd

            pseg._in_trips(
                -(-ft // G), U,
                lambda first, n: [group(first + i) for i in range(n)])

        whole = F // 128
        pseg._in_trips(whole, 1, lambda t, _: tile(t, 128, 128))
        if F % 128:
            tile(whole, min(128, P - 128 * whole), F % 128)
        return 0

    lax.fori_loop(0, nch, body, 0)

    if H == 1 or "regroup" in stubs:
        return
    block_of_lane = lax.broadcasted_iota(jnp.int32, (8, 128), 1) // L

    def regrouped(src):
        dest = []
        for d in range(H):
            for i in range(G):
                g, h = divmod(d * G + i, H)
                piece = src[8 * h:8 * h + 8]
                if i != g:
                    piece = pltpu.roll(piece, ((i - g) * L) % 128, axis=1)
                placed = piece if i == 0 \
                    else jnp.where(block_of_lane == i, piece, placed)
            dest.append(placed)
        return jnp.concatenate(dest, axis=0)

    def regroup(first, n):
        """Groups first .. first + n - 1: every load, then every move, then
        every store, so that the groups' chains interleave."""
        rows = [first * R + i * R for i in range(n)]
        if not isinstance(first, int):
            rows = [pl.multiple_of(r, R) for r in rows]
        moved = [regrouped(out_ref[pl.ds(r, R), :]) for r in rows]
        for r, block in zip(rows, moved):
            out_ref[pl.ds(r, R), :] = block

    pseg._in_trips(out_ref.shape[0] // R, 8, regroup)


@functools.partial(jax.jit, static_argnames=(
    "F", "B", "interpret", "stubs", "lo_round", "U", "mxu"))
def new_hist(payload, start, count, *, F, B, interpret, stubs,
             lo_round="rule", U=pseg._HIST_TRIP_GROUPS, mxu="f32"):
    P = payload.shape[1]
    L, H, G = _new_plan(B, lo_round)
    whole = (F - 1) // 128
    n_groups = whole * (128 // G) + -(-(F - 128 * whole) // G)
    scalars = jnp.stack([start, count]).astype(jnp.int32)
    kern = functools.partial(
        _new_kernel, F=F, B=B, L=L, H=H, G=G, U=U, grad_col=F,
        hess_col=F + 1, cnt_col=F + 2, stubs=stubs,
        mxu_dtype=jnp.bfloat16 if mxu == "bf16" else jnp.float32)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, CHUNK, P), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((128, CHUNK), jnp.int32),
                            pltpu.VMEM((128, CHUNK), jnp.int32),
                            pltpu.VMEM((8 * H * G + 128, CHUNK),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups * 8 * H, 128), jnp.float32),
        interpret=interpret,
    )(scalars, payload)
    if stubs:
        return out
    r = out.reshape(-1, H, 8, 128)
    ghc = jnp.stack([r[:, :, 0] + r[:, :, 1] + r[:, :, 2],
                     r[:, :, 3] + r[:, :, 4] + r[:, :, 5], r[:, :, 6]])
    return ghc.reshape(3, -1, H * L)[:, :F, :B].transpose(1, 2, 0)


@functools.partial(jax.jit, static_argnames=("n", "F", "B", "P"))
def make_payload(key, *, n, F, B, P):
    """[n + GUARD, P] payload made on the device: F bin columns, then
    gradient, hessian and count."""
    kb, kg, kh = jax.random.split(key, 3)
    pay = jnp.zeros((n + seg.GUARD, P), jnp.float32)
    pay = pay.at[:n, :F].set(
        jax.random.randint(kb, (n, F), 0, B).astype(jnp.float32))
    pay = pay.at[:n, F].set(jax.random.normal(kg, (n,)))
    pay = pay.at[:n, F + 1].set(jax.random.uniform(kh, (n,)) + 0.1)
    return pay.at[:n, F + 2].set(1.0)


def median_s(run, interpret):
    """Median seconds of five calls of run(), which fetches its result,
    after one that compiles; None in a rehearsal."""
    def call():
        t0 = time.perf_counter()
        run()
        return time.perf_counter() - t0

    call()
    return None if interpret else sorted(call() for _ in range(5))[2]


def timed(fn, payload, n, **kw):
    """Seconds of a call over rows [0, n), the sum fetched."""
    return median_s(lambda: float(jnp.sum(
        fn(payload, jnp.int32(0), jnp.int32(n), **kw))), kw["interpret"])


def call_floor(fn, payload, **kw):
    """Seconds a call costs beside its rows: 64 calls over one chunk each
    in one program (the accumulator's zero fill, its regrouping and its
    write to HBM, and the epilogue to [F, B, 3])."""
    @jax.jit
    def many(p):
        def step(carry, start):
            h = fn(p, start, jnp.int32(CHUNK), stubs=(), **kw)
            return carry + jnp.sum(h), None
        return lax.scan(step, jnp.float32(0.0),
                        jnp.arange(64, dtype=jnp.int32) * CHUNK)[0]

    t = median_s(lambda: float(many(payload)), kw["interpret"])
    return None if t is None else t / 64


def ablate(name, fn, stub_sets, payload, n, F, B, interpret, times, **kw):
    kw = dict(F=F, B=B, interpret=interpret, **kw)
    chunks = n // CHUNK
    ref = seg.segment_histogram(payload, jnp.int32(128), jnp.int32(n - 1000),
                                num_features=F, num_bins=B, grad_col=F,
                                hess_col=F + 1, cnt_col=F + 2)
    got = fn(payload, jnp.int32(128), jnp.int32(n - 1000), stubs=(), **kw)
    np.testing.assert_array_equal(np.asarray(got[..., 2]),
                                  np.asarray(ref[..., 2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=n * 2e-7)
    floor = times["%s call" % name] = call_floor(fn, payload, **kw)
    if floor is not None:
        print("%-44s %8.1f us a call of one chunk" % (name, floor * 1e6),
              flush=True)
    for stubs in stub_sets:
        label = "%s %s" % (name, "+".join(stubs) or "full")
        t = times[label] = timed(fn, payload, n, stubs=stubs, **kw)
        if t is None:
            continue
        full = times["%s full" % name]
        print("%-44s %8.3f ms  %8.1f ns/chunk  (full - this: %8.1f)"
              % (label, t * 1e3, t / chunks * 1e9,
                 (full - t) / chunks * 1e9), flush=True)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    interpret = "--interpret" in sys.argv[1:]
    race = "--race" in sys.argv[1:]
    if not interpret and jax.default_backend() != "tpu":
        sys.exit("ablate_hist_body: platform is %r, not tpu"
                 % jax.default_backend())
    shapes = [a for a in args if a in SHAPES] or list(SHAPES)
    out = {}
    for shape in shapes:
        F, B, P, n = SHAPES[shape]
        if interpret:
            n = 1024
            if shape == "epsilon":
                F, P = 300, 384
        payload = make_payload(jax.random.PRNGKey(27), n=n, F=F, B=B, P=P)
        times = {}
        got = new_hist(payload, jnp.int32(128), jnp.int32(n - 1000), F=F,
                       B=B, interpret=interpret, stubs=())
        ref = pseg.segment_histogram(
            payload, jnp.int32(128), jnp.int32(n - 1000), num_features=F,
            num_bins=B, grad_col=F, hess_col=F + 1, cnt_col=F + 2,
            interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        if race:
            for lo_round in ("rule", "half"):
                for U in (8, 16, 32):
                    for mxu in ("f32", "bf16"):
                        t = timed(new_hist, payload, n, F=F, B=B,
                                  interpret=interpret, stubs=(),
                                  lo_round=lo_round, U=U, mxu=mxu)
                        label = "race %s U=%d %s" % (lo_round, U, mxu)
                        times[label] = t
                        if t is not None:
                            print("%-44s %8.3f ms  %8.1f ns/chunk" % (
                                label, t * 1e3, t / (n // CHUNK) * 1e9),
                                flush=True)
        ablate("new", new_hist,
               [()] + [(s,) for s in NEW_STUBS] + [("lo", "hi")],
               payload, n, F, B, interpret, times)
        out[shape] = {"features": F, "bins": B, "lanes": P, "rows": n,
                      "chunks": n // CHUNK, "seconds": times}
        del payload
    line = json.dumps({"interpret": interpret, "shapes": out})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "ablate_hist_body.%s.json"
                           % "-".join(["new"] + shapes)), "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
