"""Kernel-level check: every Pallas segment kernel, compiled by Mosaic on
the chip and compared with the portable lax engine (or with an already
checked sibling kernel).

One verdict per kernel (histogram, RMW / accumulator / column-block
partition, the two engines of a 1,024-lane payload under a NaN-routing
split, precision), each with a fetch-forced time printed as
information.  A section that raises records its error and the run carries
on to the next kernel, so one call to the chip answers for all of them;
the exit code is non-zero if any section failed.  The last stdout line is
one JSON object, also written to ``chiprun_out/smoke_tpu_kernels.json``.

On the chip:   python exp/smoke_tpu_kernels.py [section ...]
CPU rehearsal: JAX_PLATFORMS=cpu python exp/smoke_tpu_kernels.py --interpret
(the Pallas interpreter at a reduced row count; proves the script, says
nothing about Mosaic).  Section names (`partition_acc blocks precision`
are the three that run `_acc_kernel`; `missing_wide` is the Bosch cell's
1,024 lanes on both its engines; `state_cols` is the three kernels of
`ops/state_columns.py`) keep the run to those.
"""
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import pallas_segment as pseg
from lightgbm_tpu.ops import state_columns as scols

INTERPRET = "--interpret" in sys.argv[1:]
if not INTERPRET and jax.default_backend() != "tpu":
    sys.exit("smoke_tpu_kernels: platform is %r, not tpu (pass --interpret "
             "to rehearse the script on the CPU)" % jax.default_backend())

N = 2048 if INTERPRET else 8192
IK = dict(interpret=INTERPRET)
rng = np.random.default_rng(0)


def median_ms(fn, reps=5):
    """Median wall time of fn(), which must fetch its result to the host."""
    if INTERPRET:
        return None
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return round(sorted(ts)[reps // 2] * 1e3, 3)


def make_payload(n, F, B, width=None, grads=None):
    """[n + GUARD, P] payload: F bin columns then grad, hess, count."""
    P = width or -(-(F + 12) // 128) * 128
    pay = np.zeros((n + seg.GUARD, P), np.float32)
    pay[:n, :F] = rng.integers(0, B, (n, F))
    pay[:n, F] = rng.standard_normal(n) if grads is None else grads
    pay[:n, F + 1] = rng.random(n) + 0.1
    pay[:n, F + 2] = 1.0
    return jnp.asarray(pay)


def make_pred(col, threshold, B):
    return seg.SplitPredicate(
        col=jnp.int32(col), threshold=jnp.int32(threshold),
        default_left=jnp.bool_(True), is_cat=jnp.bool_(False),
        missing_type=jnp.int32(0), num_bin=jnp.int32(B),
        default_bin=jnp.int32(0), offset=jnp.int32(0),
        identity=jnp.bool_(True), bitset=jnp.zeros(B, jnp.int32))


def hist_kw(F, B):
    return dict(num_features=F, num_bins=B, grad_col=F, hess_col=F + 1,
                cnt_col=F + 2)


def segs(*pairs):
    """Clip (start, count) pairs to the rehearsal row count."""
    return [(s, min(c, N - s)) for s, c in pairs if s < N]


def check_partition(fn, pay, pred, value_col, pairs):
    """fn(payload, aux, start, count, right_first) against the portable
    partition, with the left child first and with the right one."""
    for s, c in pairs:
        for right_first in (jnp.bool_(False), jnp.bool_(True)):
            p, _, nl = fn(pay, jnp.zeros_like(pay), jnp.int32(s),
                          jnp.int32(c), right_first)
            pr, _, nlr = seg.partition_segment(
                pay, jnp.zeros_like(pay), jnp.int32(s), jnp.int32(c), pred,
                jnp.float32(1.5), jnp.float32(-2.5), value_col, right_first)
            assert int(nl) == int(nlr), (s, c, int(nl), int(nlr))
            err = float(jnp.abs(p - pr).max())
            assert err == 0.0, (s, c, bool(right_first), err)


# Higgs-shaped payload shared by most sections
F, B = 28, 256
VAL = F + 3
PAY = make_payload(N, F, B, width=128)
PRED = make_pred(2, 100, B)
KW = hist_kw(F, B)
LV, RV = jnp.float32(1.5), jnp.float32(-2.5)


def histogram():
    """segment_histogram (the bin id factored into a high and a low part)
    at the three train cells' shapes and payload widths, segments of one
    row to the whole payload: the counts equal the portable engine's to
    the last digit, the sums a float64 histogram's within f32 accumulation
    error, and gradients that differ only below bf16's 8 mantissa bits
    keep those bits (a bf16-rounded sum is off by ~n * 2^-10 a bin).
    Then the MS-LTR / Expo / Bosch widths against the portable engine."""
    info = {}
    for Fw, Bw, Pw in ((28, 256, 128), (67, 256, 128), (2000, 64, 2048)):
        if INTERPRET and Fw > 100:
            continue
        assert pseg.fits_vmem(Fw, Bw, Pw), (Fw, Bw, Pw)
        gvals = (1.0 + rng.integers(1, 2 ** 11, N) * 2.0 ** -20).astype(
            np.float32)
        pay = make_payload(N, Fw, Bw, width=Pw, grads=gvals)
        host = np.asarray(pay)
        kw = hist_kw(Fw, Bw)
        worst = 0.0
        for s, c in segs((0, N), (128, N - 1000), (7, 1), (1023, 1),
                         (513, 256), (9, 1015), (5, 4099), (0, 0)):
            h = np.asarray(pseg.segment_histogram(
                pay, jnp.int32(s), jnp.int32(c), **kw, **IK))
            ref = np.asarray(seg.segment_histogram(
                pay, jnp.int32(s), jnp.int32(c), **kw))
            assert np.array_equal(h[..., 2], ref[..., 2]), (Fw, Bw, s, c)
            h64 = np.zeros((Fw, Bw, 2), np.float64)
            rows = host[s:s + c]
            for f in range(Fw):
                np.add.at(h64[f], rows[:, f].astype(np.int64),
                          rows[:, Fw:Fw + 2].astype(np.float64))
            # a bin holds ~c / B rows of ~1.0: an f32 sum carries a few
            # 2^-24 of that, a bf16 gradient would lose 2^-10 of it
            err = float(np.abs(h[..., :2] - h64).max())
            assert err <= max(c / Bw, 1) * 2.0 ** -16, (Fw, Bw, s, c, err)
            worst = max(worst, err)
        info["%dx%d_err_vs_f64" % (Fw, Bw)] = worst
        info["%dx%d_ms" % (Fw, Bw)] = median_ms(
            lambda: np.asarray(pseg.segment_histogram(
                pay, jnp.int32(0), jnp.int32(N), **kw, **IK))[0, 0, 2])
    shapes = ((137, 256), (700, 256), (968, 64))
    for Fw, Bw in shapes[:1] if INTERPRET else shapes:
        assert pseg.fits_vmem(Fw, Bw), (Fw, Bw)
        pay = make_payload(N, Fw, Bw)
        kw = hist_kw(Fw, Bw)
        ref = seg.segment_histogram(pay, jnp.int32(128), jnp.int32(N - 1000),
                                    **kw)
        h = pseg.segment_histogram(pay, jnp.int32(128), jnp.int32(N - 1000),
                                   **kw, **IK)
        err = float(jnp.abs(h - ref).max())
        assert err < 1e-2, (Fw, Bw, err)
        info["%dx%d_ms" % (Fw, Bw)] = median_ms(
            lambda: np.asarray(pseg.segment_histogram(
                pay, jnp.int32(0), jnp.int32(N), **kw, **IK))[0, 0, 2])
    info["factor_at_256_64"] = [pseg._hist_factor(256), pseg._hist_factor(64)]
    return info


def partition_rmw():
    """partition_segment (read-modify-write windows)."""
    check_partition(
        lambda p, a, s, c, rf: pseg.partition_segment(
            p, a, s, c, PRED, LV, RV, VAL, B, rf, **IK),
        PAY, PRED, VAL, segs((128, 3000), (7, 8000), (513, 256)))
    return {"ms": median_ms(lambda: int(pseg.partition_segment(
        PAY, jnp.zeros_like(PAY), jnp.int32(0), jnp.int32(N), PRED, LV, RV,
        VAL, B, **IK)[2]))}


def partition_acc():
    """partition_segment_acc (pass A's placement is a masked store of a
    [C + 8, P] window of the permuted block's scratch, both at traced
    tile-aligned starts, pass B's a traced sublane pltpu.roll of a [2C, P]
    concatenate; the index arithmetic has rows in lanes), under
    a numerical predicate, a categorical one whose set bits sit at the
    packed bitset's word edges, and a missing-value one over an
    EFB-decoded column: no cell has a categorical column, so the vector
    shifts and the word select are seen by Mosaic here or nowhere; and
    under lopsided splits (nine rows in ten and all of them on one side)
    over segments of some hundred chunks, where one accumulator's ring
    flushes every chunk and goes round with its DMAs in flight."""
    edges = np.isin(np.arange(B), (0, 31, 32, 63, 64, 100, 191, 192, B - 1))
    preds = {
        "numerical": PRED,
        "categorical": PRED._replace(
            col=jnp.int32(5), is_cat=jnp.bool_(True),
            bitset=jnp.asarray(edges, jnp.int32)),
        "missing_nan": PRED._replace(
            col=jnp.int32(9), missing_type=jnp.int32(seg.MISSING_NAN),
            default_left=jnp.bool_(False), threshold=jnp.int32(60),
            num_bin=jnp.int32(120), default_bin=jnp.int32(7),
            offset=jnp.int32(40), identity=jnp.bool_(False)),
    }
    for pred in preds.values():
        check_partition(
            lambda p, a, s, c, rf: pseg.partition_segment_acc(
                p, a, s, c, pred, LV, RV, VAL, B, rf, **IK),
            PAY, pred, VAL,
            segs((128, 3000), (7, 8000), (513, 256), (0, 8192)))
    for threshold in (229, B - 1):
        pred = PRED._replace(threshold=jnp.int32(threshold))
        check_partition(
            lambda p, a, s, c, rf: pseg.partition_segment_acc(
                p, a, s, c, pred, LV, RV, VAL, B, rf, **IK),
            PAY, pred, VAL, segs((7, 8000), (5, 100_000)))
    # bins past 256: the column is read out at HIGHEST, 32 words of bitset
    wide_b = 1000
    wide = make_payload(N, F, wide_b, width=128)
    for pred in (make_pred(3, 701, wide_b), make_pred(4, 0, wide_b)._replace(
            is_cat=jnp.bool_(True),
            bitset=jnp.asarray(np.arange(wide_b) % 7 == 3, jnp.int32))):
        check_partition(
            lambda p, a, s, c, rf: pseg.partition_segment_acc(
                p, a, s, c, pred, LV, RV, VAL, wide_b, rf, **IK),
            wide, pred, VAL, segs((7, 8000), (513, 256)))
    return {"predicates": sorted(preds) + ["lopsided", "1000_bins"],
            "ms": median_ms(lambda: int(pseg.partition_segment_acc(
                PAY, jnp.zeros_like(PAY), jnp.int32(0), jnp.int32(N), PRED,
                LV, RV, VAL, B, **IK)[2]))}


def blocks():
    """partition_segment_acc_blocks, the engine of payloads no single-pass
    plan holds: bit-equal to the portable partition at a ragged 1,280
    lanes (blocks of 512, 512 and 256, the last two chunks a trip), then
    at the Epsilon cell's own shape (409,600 rows x 2,048 lanes, 2,000
    columns x 64 bins, segments of 256 to 409,600 rows) against a stable
    partition done in numpy on the host (the portable engine beside it
    would not fit the chip), and the whole payload timed a block width."""
    Fw, Bw = 1200, 64
    Pw = -(-(Fw + 8) // 128) * 128
    pay = make_payload(N, Fw, Bw, width=Pw)
    pred = make_pred(700, 30, Bw)
    check_partition(
        lambda p, a, s, c, rf: pseg.partition_segment_acc_blocks(
            p, a, s, c, pred, LV, RV, Fw + 3, Bw, rf, **IK),
        pay, pred, Fw + 3, segs((128, 3000), (7, 8000), (513, 256)))
    info = {"ragged_1280_ms": median_ms(
                lambda: np.asarray(pseg.partition_segment_acc_blocks(
                    pay, jnp.zeros_like(pay), jnp.int32(0), jnp.int32(N),
                    pred, LV, RV, Fw + 3, Bw, **IK)[0])[0, 0]),
            "ragged_1280_portable_ms": median_ms(
                lambda: np.asarray(seg.partition_segment(
                    pay, jnp.zeros_like(pay), jnp.int32(0), jnp.int32(N),
                    pred, LV, RV, Fw + 3)[0])[0, 0])}
    del pay

    Fw, Pw = 2000, 2048
    rows = N if INTERPRET else 409_600
    host = np.zeros((rows + seg.GUARD, Pw), np.float32)
    host[:rows, :Fw] = rng.integers(0, Bw, (rows, Fw), dtype=np.uint8)
    host[:rows, Fw] = rng.standard_normal(rows)
    host[:rows, Fw + 1] = rng.random(rows) + 0.1
    host[:rows, Fw + 2] = 1.0
    pay = jnp.asarray(host)
    vcol = Fw + 3

    def run(**kw):
        # the copies are donated: payload, scratch and nothing else
        return jax.jit(
            lambda p, a, s, c, col, thr, rf:
            pseg.partition_segment_acc_blocks(
                p, a, s, c, make_pred(col, thr, Bw), LV, RV, vcol, Bw, rf,
                **kw, **IK),
            donate_argnums=(0, 1))

    kernel = run()
    cases = [(0, rows, 1300, 30), (7, 256, 3, 20), (128, 3000, 511, 40),
             (513, 100_000, 512, 31), (300_001, 65_536, 1999, 10),
             # lopsided: 19 rows in 20 on one side, and all of them
             (9, 50_000, 77, 60), (3, 50_000, 1400, 63)]
    # the right child first in every other case
    for i, (s0, c0, col, thr) in enumerate(cases):
        if s0 + c0 > rows:
            continue
        right_first = bool(i % 2)
        out, _, nl = kernel(pay + 0.0, jnp.zeros_like(pay), jnp.int32(s0),
                            jnp.int32(c0), jnp.int32(col), jnp.int32(thr),
                            jnp.bool_(right_first))
        got = np.asarray(out)
        del out
        left = host[s0:s0 + c0, col] <= thr
        first = ~left if right_first else left
        want = host.copy()
        want[s0:s0 + c0] = np.concatenate([host[s0:s0 + c0][first],
                                           host[s0:s0 + c0][~first]])
        values = (RV, LV) if right_first else (LV, RV)
        want[s0:s0 + c0, vcol] = np.where(
            np.arange(c0) < first.sum(), *map(np.float32, values))
        assert int(nl) == int(left.sum()), (s0, c0, int(nl), int(left.sum()))
        assert np.array_equal(got, want), (s0, c0, col, right_first)
    info["epsilon_rows"] = rows
    for block_w in (512, 256):
        fn = run(block_w=block_w)
        info["epsilon_block%d_ms" % block_w] = median_ms(
            lambda: int(fn(pay + 0.0, jnp.zeros_like(pay), jnp.int32(0),
                           jnp.int32(rows), jnp.int32(1300),
                           jnp.int32(30), jnp.bool_(False))[2]), reps=3)
    info["epsilon_copy_ms"] = median_ms(
        lambda: float((pay + 0.0)[0, 0]), reps=3)
    return info


def missing_wide():
    """The Bosch cell's shape, 968 columns x 64 bins in 1,024 lanes, on
    BOTH engines its band has (the read-modify-write kernel in one pass,
    the accumulator kernel in two 512-lane blocks), under the split that
    cell makes at every node: the column has a NaN bin that four rows in
    five sit in, and they go where `default_left` says.  The split column
    in block 0 and in block 1 (the block pass that does not hold it routes
    from the snapshot alone), the missing rows sent left and sent right,
    each segment with the left child first and with the right one."""
    Fw, Bw, Pw = 968, 64, 1024
    nan_bin = Bw - 1
    host = np.array(make_payload(N, Fw, Bw, width=Pw))
    cols = (100, 700)
    for col in cols:
        host[:N, col] = np.where(rng.random(N) < 0.81, nan_bin,
                                 rng.integers(0, nan_bin, N))
    pay = jnp.asarray(host)
    engines = {"rmw": pseg.partition_segment,
               "blocks": pseg.partition_segment_acc_blocks}
    info = {}
    for name, kernel in engines.items():
        for col in cols:
            for default_left in (True, False):
                pred = make_pred(col, 20, Bw)._replace(
                    missing_type=jnp.int32(seg.MISSING_NAN),
                    default_left=jnp.bool_(default_left))
                check_partition(
                    lambda p, a, s, c, rf: kernel(
                        p, a, s, c, pred, LV, RV, Fw + 3, Bw, rf, **IK),
                    pay, pred, Fw + 3,
                    segs((128, 3000), (7, 8000), (513, 256), (0, N)))
        info[name + "_ms"] = median_ms(lambda: int(kernel(
            pay, jnp.zeros_like(pay), jnp.int32(0), jnp.int32(N), pred, LV,
            RV, Fw + 3, Bw, **IK)[2]))
    info["cases"] = "2 engines x column %s x default left/right x 4 " \
        "segments x child order" % (cols,)
    return info


def precision():
    """The MXU's default f32 matmul is one bf16 pass: the partition must
    still permute payload values and radix-4096 index columns exactly, and
    the histogram must keep f32-class sums (bf16-class would be ~0.5).
    Only bites on hardware; the interpreter is plain f32."""
    IDX = F + 4
    gvals = (1.0 + rng.random(N) * 2.0 ** -18).astype(np.float32)
    pay = np.array(make_payload(N, F, B, width=128, grads=gvals))
    pay[:N, F + 1] = 1.0
    pay[:N, IDX] = np.arange(N, dtype=np.float32) % 4096
    out = np.asarray(pseg.partition_segment_acc(
        jnp.asarray(pay), jnp.zeros_like(jnp.asarray(pay)), jnp.int32(0),
        jnp.int32(N), PRED, LV, RV, VAL, B, **IK)[0])
    assert np.array_equal(np.sort(out[:N, IDX]), np.sort(pay[:N, IDX])), \
        "idx column corrupted by the partition matmul"
    assert np.array_equal(np.sort(out[:N, F]), np.sort(gvals)), \
        "payload values bf16-rounded by the partition matmul"
    h = pseg.segment_histogram(jnp.asarray(pay), jnp.int32(0), jnp.int32(N),
                               **KW, **IK)
    h64 = np.zeros((F, B), np.float64)
    for f in range(F):
        np.add.at(h64[f], pay[:N, f].astype(np.int64),
                  gvals.astype(np.float64))
    gerr = float(np.abs(np.asarray(h)[:, :, 0] - h64).max())
    assert gerr < 1e-3, gerr
    return {"hist_grad_err_vs_f64": gerr}


def state_cols():
    """The three state-column kernels (`ops/state_columns.py`) against
    the lax form, bit for bit: columns inside one lane tile of 128, 256
    and 2,048 lanes and across a tile edge, rows that fill no whole
    block, values no arithmetic would survive (NaN, infinities, -0.0,
    the largest index of the wide layout); then each kernel
    timed at the Higgs cell's shape (10,502,408 x 128) and the Epsilon
    cell's (409,864 x 2,048), a call in a chain of five, with the
    payload's plain copy beside them, and (`--race`) at other block
    heights."""
    form = "pallas-interpret" if INTERPRET else "pallas"

    def same(got, want, *what):
        """Bit for bit, and where not: the first places and both values."""
        got, want = np.asarray(got), np.asarray(want)
        bad = np.argwhere((got.view(np.uint32) != want.view(np.uint32))
                          & ~(np.isnan(got) & np.isnan(want)))
        assert not len(bad), (what, len(bad), [
            (tuple(map(int, at)), float(got[tuple(at)]),
             float(want[tuple(at)])) for at in bad[:6]])

    # (no denormal: the kernels keep one, XLA's select on the chip
    # flushes it to zero, as every later use of it would)
    odd = np.array([np.nan, np.inf, -np.inf, -0.0, 4095.0, 16777215.0,
                    3.4e38, 1.1754944e-38], np.float32)
    for P, cols in ((128, (28, 29, 30, 31, 32, 33, 35)),
                    (256, (125, 126, 127, 128, 129)),
                    (256, (137, 138, 139, 141, 145, 146, 147, 148)),
                    (2048, (2000, 2001, 2002, 2004, 2005, 2012))):
        n = 5000 + seg.GUARD
        host = rng.standard_normal((n, P)).astype(np.float32)
        host[:odd.size, cols[0]] = odd
        host[-odd.size:, cols[-1]] = odd
        pay = jnp.asarray(host)
        same(scols.read_cols(pay, cols, form),
             np.ascontiguousarray(host[:, cols].T), "read", P)
        vals = rng.standard_normal((len(cols), n)).astype(np.float32)
        vals[0, :odd.size] = odd
        vals[-1, -odd.size:] = odd
        for k in (2, 3, len(cols)):
            same(scols.write_cols(pay, cols[-k:], jnp.asarray(vals[:k]),
                                  form),
                 scols.write_cols(pay, cols[-k:], jnp.asarray(vals[:k]),
                                  "lax"), "write", P, k)
        for dst in (cols[0], cols[2], cols[-1]):
            for on in (True, False):
                args = (jnp.int32(dst), (cols[0], cols[-1]), cols[1],
                        jnp.float32(0.1), jnp.bool_(on))
                same(scols.add_scaled(pay, *args, form),
                     scols.add_scaled(pay, *args, "lax"), "add", P, dst, on)
    if INTERPRET:
        return {}

    def chain_ms(step, pay, reps=5):
        """ms a call of `step(payload) -> payload`, donated, in a chain."""
        fn = jax.jit(step, donate_argnums=(0,))
        pay = fn(pay)
        float(pay[0, 0])
        t0 = time.perf_counter()
        for _ in range(reps):
            pay = fn(pay)
        float(pay[0, 0])
        return round((time.perf_counter() - t0) * 1e3 / reps, 3), pay

    info = {}
    heights = (1024, 2048, 4096) if "--race" in sys.argv[1:] \
        else (scols._BLOCK_ROWS,)
    for name, rows, P, c0 in (("higgs", 10_502_408, 128, 28),
                              ("epsilon", 409_864, 2048, 2000)):
        pay = jnp.zeros((rows, P), jnp.float32) + 1.0
        label, weight, cnt, score, grad, hess, value = (
            c0, c0 + 1, c0 + 2, c0 + 4, c0 + 5, c0 + 6, c0 + 7)
        vals = jnp.ones((2, rows), jnp.float32)
        info["%s_copy_ms" % name], pay = chain_ms(lambda p: p + 1.0, pay)
        default = scols._BLOCK_ROWS
        for height in heights:
            scols._BLOCK_ROWS = height
            for fn in (scols._state_cols_read, scols._state_cols_write,
                       scols._state_cols_axpy):
                fn.clear_cache()
            tag = "%s_%%s_ms" % name if len(heights) == 1 \
                else "%s_%%s_ms@%d" % (name, height)
            # the read's vectors feed the next call's write, as the fill's
            info[tag % "read_write"], pay = chain_ms(
                lambda p: scols.write_cols(
                    p, (grad, hess),
                    scols.read_cols(p, (score, label, weight, cnt),
                                    "pallas")[:2] * 0.5, "pallas"), pay)
            info[tag % "write"], pay = chain_ms(
                lambda p: scols.write_cols(p, (grad, hess), vals, "pallas"),
                pay)
            info[tag % "add"], pay = chain_ms(
                lambda p: scols.add_scaled(
                    p, jnp.int32(score), (score, score), value,
                    jnp.float32(0.1), jnp.bool_(True), "pallas"), pay)
        scols._BLOCK_ROWS = default
        del pay, vals
    return info


SECTIONS = (histogram, partition_rmw, partition_acc, blocks, missing_wide,
            precision, state_cols)


def main():
    from lightgbm_tpu.runtime.doctor import device_report
    device = device_report()
    print("platform=%s kind=%s jax=%s interpret=%s rows=%d"
          % (device["platform"], device["kind"], jax.__version__, INTERPRET,
             N), flush=True)
    sections = {f.__name__: f for f in SECTIONS}
    wanted = [a for a in sys.argv[1:] if not a.startswith("--")]
    if set(wanted) - set(sections):
        sys.exit("smoke_tpu_kernels: no section %s (has: %s)"
                 % (sorted(set(wanted) - set(sections)), sorted(sections)))
    verdicts = {}
    for fn in [sections[a] for a in wanted] or SECTIONS:
        name = fn.__name__
        t0 = time.perf_counter()
        try:
            info = fn()
            verdicts[name] = {"ok": True, **info}
        except Exception as e:  # a verdict, not a crash: next kernel runs
            traceback.print_exc()
            verdicts[name] = {"ok": False, "error": "%s: %s" % (
                type(e).__name__, str(e)[:2000])}
        verdicts[name]["seconds"] = round(time.perf_counter() - t0, 1)
        print("%-14s %s" % (name, json.dumps(verdicts[name])), flush=True)
    out = {"device": device, "interpret": INTERPRET, "rows": N,
           "verdicts": verdicts}
    line = json.dumps(out)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "smoke_tpu_kernels.json"),
              "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0 if all(v["ok"] for v in verdicts.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
