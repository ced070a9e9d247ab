"""The two partition engines a payload of 640 to 1,664 lanes can take,
raced width by width on the chip: the read-modify-write kernel in one
pass (`pallas-rmw`) against the accumulator kernel a 512-lane column
block at a time (`pallas-blocks`).  `grower2.partition_engine` gives a
width to the one that wins here (PERF.md section 6, PR 37; ROADMAP C3).

A width's payload is `--rows` rows of 64-bin columns; the split is the
one the Bosch cell makes at every node: the split column has a NaN bin
that four rows in five sit in, routed by `default_left`.  Each engine
partitions segments of the whole payload down to a 512th of it, the
larger child first and the smaller (staged) one first, from a fresh copy
of the payload (the copy is timed alone and taken off).  The two engines'
outputs are compared bit for bit on the way.  A width at which a kernel
does not compile is recorded with its error (Mosaic refuses the
read-modify-write kernel at 1,664 lanes: 16.46 MB of VMEM for 16).
`--full` adds the Bosch cell's own shape, 1,015,808 rows x 1,024 lanes.

    python exp/race_partition_band.py [--rows N] [--widths 640,1024] [--full]

Prints a line a width and one JSON object last, also written to
chiprun_out/race_partition_band.json.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import pallas_segment as pseg
from lightgbm_tpu.ops import segment as seg

BINS = 64
NAN_BIN = BINS - 1
NAN_SHARE = 0.81
BAND = tuple(range(640, 1665, 128))
LV, RV = jnp.float32(1.5), jnp.float32(-2.5)


def make_payload(rows, lanes, col, key):
    """[rows + GUARD, lanes] on the device: every lane a bin but the last
    eight (values), lane `col` four fifths NaN bin."""
    kb, ku, kn = jax.random.split(key, 3)
    n = rows + seg.GUARD
    pay = jax.random.randint(kb, (n, lanes), 0, NAN_BIN).astype(jnp.float32)
    split = jnp.where(jax.random.uniform(ku, (n,)) < NAN_SHARE, NAN_BIN,
                      jax.random.randint(kn, (n,), 0, NAN_BIN))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.where(lane == col, split.astype(jnp.float32)[:, None], pay)


def timed(fn, *args, reps=3):
    """Median seconds of fn(*args), its result's first element fetched."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return sorted(out)[reps // 2]


def race(rows, lanes, interpret=False):
    """{engine: {case: ns a row}} at one shape, the two engines' payloads
    compared bit for bit in every case."""
    value_col = lanes - 5
    col = lanes - 200               # in the LAST block of a blocked payload
    pay = make_payload(rows, lanes, col, jax.random.PRNGKey(lanes))
    aux = jnp.zeros_like(pay)
    copy = jax.jit(lambda p: p + 0.0)
    copy_s = timed(copy, pay)
    engines = {"pallas-rmw": pseg.partition_segment,
               "pallas-blocks": pseg.partition_segment_acc_blocks}
    assert pseg.partition_fits_vmem(lanes, BINS) \
        and pseg.partition_blocks_fits_vmem(lanes, BINS), lanes

    def runner(kernel):
        # the scratch is donated and handed back; the payload is copied
        # inside, so every call partitions the same unsorted rows
        def run(p, a, start, count, threshold, default_left, right_first):
            pred = seg.SplitPredicate(
                col=jnp.int32(col), threshold=threshold,
                default_left=default_left, is_cat=jnp.bool_(False),
                missing_type=jnp.int32(seg.MISSING_NAN),
                num_bin=jnp.int32(BINS), default_bin=jnp.int32(0),
                offset=jnp.int32(0), identity=jnp.bool_(True),
                bitset=jnp.zeros(BINS, jnp.int32))
            return kernel(p + 0.0, a, start, count, pred, LV, RV, value_col,
                          BINS, right_first, interpret=interpret)
        return jax.jit(run, donate_argnums=(1,))

    # (start, count, threshold, default_left, right_first).  Threshold 30
    # of 62 value bins with the NaN rows left sends 90% left, with them
    # right 9%; threshold 62 with them right is "has a value" (19%)
    # against "has none"
    cases = {"whole_larger_first": (0, rows, 30, True, False),
             "whole_smaller_first": (0, rows, 30, True, True),
             "whole_has_value": (0, rows, 62, False, True),
             "eighth": (rows // 3, rows // 8, 30, True, False),
             "64th": (rows // 3, rows // 64, 30, False, True),
             "512th": (rows // 3, rows // 512, 30, True, False)}
    out, kept = {}, {}
    for name, kernel in engines.items():
        fn = runner(kernel)
        out[name] = {}
        for case, (start, count, thr, dleft, rfirst) in cases.items():
            args = (jnp.int32(start), jnp.int32(count), jnp.int32(thr),
                    jnp.bool_(dleft), jnp.bool_(rfirst))
            ts = []
            for rep in range(4):            # the first compiles
                t0 = time.perf_counter()
                p, aux, nl = fn(pay, aux, *args)
                jax.block_until_ready(nl)
                ts.append(time.perf_counter() - t0)
                if rep == 0:
                    # the rows the case moved (as many as a host holds
                    # cheaply) and the left child's count
                    got = (np.asarray(p[start:start + min(count, 4096)]),
                           int(nl))
                    if case in kept:
                        assert got[1] == kept[case][1], (lanes, case)
                        assert np.array_equal(got[0], kept[case][0]), \
                            (lanes, case)
                    kept[case] = got
                del p
            seconds = sorted(ts[1:])[1] - copy_s
            out[name][case] = round(seconds / count * 1e9, 3)
    out["copy_ms"] = round(copy_s * 1e3, 3)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 18)
    ap.add_argument("--widths", default=",".join(map(str, BAND)))
    ap.add_argument("--full", action="store_true",
                    help="also 1,015,808 rows x 1,024 lanes")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse the script on the CPU (no times)")
    args = ap.parse_args(argv)
    if not args.interpret and jax.default_backend() != "tpu":
        sys.exit("race_partition_band: platform is %r, not tpu"
                 % jax.default_backend())
    shapes = [(args.rows, int(w)) for w in args.widths.split(",") if w]
    if args.full:
        shapes.append((1_015_808, 1024))
    results = {}
    for rows, lanes in shapes:
        try:
            res = race(rows, lanes, args.interpret)
        except Exception as e:      # a verdict (Mosaic refusing a kernel
            # for want of VMEM, as the RMW one at 1,664 lanes), not a crash
            res = {"error": "%s: %s" % (type(e).__name__, str(e)[:600])}
        results["%dx%d" % (rows, lanes)] = res
        print("%9d x %5d  %s" % (rows, lanes, json.dumps(res)), flush=True)
    line = json.dumps({"bins": BINS, "nan_share": NAN_SHARE,
                       "unit": "ns a row of the segment, the payload's "
                               "copy taken off", "shapes": results})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "race_partition_band.json"),
              "w") as fh:
        fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
