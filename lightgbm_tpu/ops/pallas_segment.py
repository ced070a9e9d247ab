"""Pallas TPU kernels for the segment engine's two hot paths.

The portable lax implementations in `ops.segment` materialize the joint
(feature, bin) one-hot and the permutation matrices through HBM — the very
traffic that made the round-1 histogram 30-50x slower than a CPU.  These
kernels keep every one-hot in VMEM:

- `segment_histogram`: walks a leaf's contiguous chunks with manual
  HBM->VMEM DMA at dynamic offsets (the trip count is a runtime scalar, so
  one compilation serves every segment).  The bin id is factored,
  bin = hi * L + lo (`_hist_factor`): a feature's histogram over a chunk
  is ONE MXU product of its low one-hot [L, C] with its (grad, hess,
  count) parts masked by the high part [8H, C], both built in VMEM with
  rows in lanes.  Mirrors the role of the reference OpenCL kernels
  (src/treelearner/ocl/histogram256.cl:73-121 and the 16/64 variants) —
  the B<=256/64/16 specialization falls out of the static num_bins arg.
- `partition_segment_acc`: the three compact passes of
  `ops.segment.partition_segment` fused into one kernel (`_acc_kernel`);
  each chunk's stable partition is a one-hot permutation matmul in VMEM,
  put at the cursors of per-side accumulator rings that send each full,
  aligned window to HBM by one DMA.  `partition_segment_acc_blocks` runs
  the same kernel once a 512-lane window of a payload too wide for one
  pass, routing every pass from a snapshot of the split column
  (`_snap_window_kernel`);
  `partition_segment` (`_partition_kernel`) is the older read-modify-write
  kernel, whose plan fits between the two (640-1,664 lanes): raced there
  on the chip it is five to six times slower than the block kernel
  (PERF.md section 6, PR 37), and `partition_engine` chooses it for no
  shape any more (the scripts under exp/ that race it name it themselves).
  All three take `right_first`, data like the predicate: which child lies
  FIRST in the parent's range (`ops.segment.partition_segment`).  The
  first side is the one written in place, the other is staged in `aux`
  and moved once more, so a caller that knows the children's sizes names
  the larger one first.

The module holds what `grower2.partition_engine` and
`ops.segment.resolve_impl` choose from shape and platform, and nothing
else.  The partition kernels alias payload/aux in/out so no copy of the
[N, P] training state is ever made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import xla_obs
from .segment import CHUNK, GUARD, first_second
from .split import MISSING_NAN, MISSING_ZERO

#: the partition kernels write HBM in place through aliased outputs; those
#: writes must never be DCE'd or reordered
_SIDE_EFFECTS = pltpu.CompilerParams(has_side_effects=True)

#: VMEM the kernel may plan for (chip has ~16 MB/core; leave headroom for
#: the compiler's own buffers)
_VMEM_BUDGET = 13 * 2**20


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


#: feature groups a loop trip of the histogram kernel takes.  A group's
#: body is one dependent chain (row loads, compares, product, masked sum);
#: the chains of the groups of a trip lie in one basic block for the
#: scheduler to interleave and to spread over the four MXUs, and a tile of
#: fewer than two trips is not a loop at all.  On the chip (PERF.md §6, PR
#: 27; ns a 256-row chunk at 8 / 16 / 32 groups a trip): 1,804 / 1,509 /
#: 1,222 at 67 x 256, 24,193 / 19,801 / 17,345 at 2,000 x 64, where a
#: tile has 32 groups.  The compiler's time follows the unrolled code,
#: and the column tiles are a loop, so it is 1 s at 2,000 x 64.
_HIST_TRIP_GROUPS = 32


def _hist_factor(num_bins: int):
    """(L, H, G) of the factored bin id, bin = hi * L + lo, from the number
    of bins alone.  A feature's histogram over a chunk is one product of
    its seven value parts under the high part's mask [8H, C] with its low
    one-hot [L, C]; G = 128 / L features share a product so that their low
    one-hots fill the MXU's 128 columns.  A feature then costs L + 8H
    one-hot rows a chunk and, on the MXU, 2L cycles of weight loads and
    16B/L of streamed rows: both least at L = sqrt(8B).  L is the power of
    two at or above it (64 at 256 bins, 32 at 64), the side on which the
    weights cost more than the rows: the other side reads 34%, 62% and 59%
    slower at the three cells' shapes (PERF.md §6, PR 27); and at least a
    sublane tile.  H is rounded up to a power of two; the top high block
    is then ragged (bins past num_bins - 1 never occur and are sliced
    off)."""
    L = max(8, 1 << ((8 * num_bins - 1).bit_length() + 1) // 2)
    H = 1
    while H * L < num_bins:
        H *= 2
    return L, H, 128 // L


def _hist_groups(num_features: int, num_bins: int) -> int:
    """Feature groups the histogram kernel accumulates: 128 / G a whole
    128-column tile, and the last tile's."""
    G = _hist_factor(num_bins)[2]
    full = (num_features - 1) // 128
    return full * (128 // G) + -(-(num_features - 128 * full) // G)


def fits_vmem(num_features: int, num_bins: int,
              payload_width: int = None) -> bool:
    """True when the histogram kernel's VMEM plan fits the budget: the
    double-buffered payload chunk, the [groups * 8H, 128] accumulator
    (8 * F * H * L * 4 bytes), the transposed high / low parts of one
    128-column tile, the hoisted index planes and tiled values, and the
    selected rows, operands and product of four groups in flight (Mosaic
    allots the scratches and the accumulator and next to nothing else:
    at 28 x 256 over 8,192 lanes it asks for 16.48 MB, 16.47 of them the
    chunk, the accumulator and the parts).  Bins are capped at 256: the
    kernel's exactness argument needs every bin value to be
    bf16-representable (the reference OpenCL family has the same 256-bin
    kernel ceiling, ocl/histogram256.cl).

    payload_width, when known, sizes the chunk buffers with the REAL lane
    count the kernel DMAs (the num_features+32 estimate assumed the bin
    columns dominate the payload — false in feature-parallel mode, where a
    shard histograms Gloc = G/n leading columns of full-width rows and the
    estimate under-budgeted VMEM by ~n x)."""
    if num_bins > 256:
        return False
    _, H, G = _hist_factor(num_bins)
    rows = 8 * H * G                        # masked value rows of a group
    chunk_w = (_pad128(payload_width) if payload_width is not None
               else _pad128(num_features + 32))
    est = (2 * 4 * CHUNK * chunk_w                          # chunk x2 (DMA)
           + 4 * 128 * 8 * H * _hist_groups(num_features, num_bins)
           + 2 * 4 * 128 * CHUNK                            # high / low parts
           + 4 * CHUNK * (2 * rows + 128)                   # planes, values
           + 4 * 4 * (2 * CHUNK * (rows + 128) + rows * 128))
    return est <= _VMEM_BUDGET


#: slots (groups of chunks in pass A, chunks in pass B) of the accumulator
#: partition's read ring: the one in use and one in flight.  The body runs
#: at its dependent chain's latency, not the DMA's (PERF.md §6, PR 25); a
#: ring of four was exact on the chip and read no win for its VMEM.
_RING_DEPTH = 2

#: lanes a pass of the column-block partition moves: the widest multiple
#: of 128 whose accumulator plan fits VMEM (11.4 MiB of 13 at one chunk a
#: trip; 640 lanes would plan 13.6), since what does not grow with the
#: width (routing, rank, one-hot) is paid once a pass (PERF.md §5).
_BLOCK_WIDTH = 512


#: windows of CHUNK rows in an accumulator's ring.  A flush of a full
#: window is a DMA out of the ring itself, and the cursor may not run on
#: into the window behind before THAT window's last flush has landed: with
#: two windows that is the flush one chunk of rows earlier (the wait the
#: stage buffer cost before PR 38, 114-146 ns of a 128-lane chunk's 800
#: on a lopsided split, which pays it every chunk), with three the one
#: before it, a trip of the body away.  In a 512-lane block the wait does
#: not show either way, and the third window fits every plan the gates
#: admit (PERF.md section 6, PR 38): one length for every shape.
_ACC_WINDOWS = 3


def _acc_plan_bytes(payload_width: int, num_bins: int, group: int) -> int:
    """VMEM plan of the accumulator partition kernel with pass A taking
    `group` chunks a loop trip: the read ring (`_RING_DEPTH` groups of
    chunks), the two accumulators (each a ring of `_ACC_WINDOWS` chunks
    and a chunk's tail behind it, which takes the part of a placement's
    aligned window that lies past the ring's end: 8C rows; and nothing
    beside them since PR 38: a flush is a DMA out of the ring itself and
    the final blend reads into a slot of the read ring, so the flush
    stage and the blend buffer are gone), the P-wide placement
    intermediates of each chunk in flight (10C rows: the parts, the
    permuted block's [2C + 24, P] scratch and the windows read from it
    take some 7C since PR 36; the plan keeps the room of the doubled and
    rotated blocks it was written for), the [C, C] machinery (`tri_t`
    and the row iota once, a one-hot as a mask and as f32 a chunk in
    flight, and the four more the plan has carried since the widths it
    admits were proven on the chip) and the masked split window of each
    chunk of a trip.  The index arithmetic itself is a few [8, C]
    vectors, and the categorical bitset is `num_bins` bits of scalar
    memory: neither is planned for, and `num_bins` no longer enters.
    (Before PR 38 the accumulators and their two buffers were 6C rows;
    the gates answer at 8C as they did at 6C at every lane-padded width
    to 4,480 lanes: tests/test_pallas_segment.py holds the table.)"""
    P, C = payload_width, CHUNK
    return (4 * P * C * (_RING_DEPTH * group          # ring
                         + 2 * (_ACC_WINDOWS + 1)     # two rings, their tails
                         + 10 * group)                # placement intermediates
            + 4 * C * C * (6 + 2 * group)
            + 4 * 128 * C * group)


#: chunks pass A of the accumulator partition takes a loop trip where VMEM
#: allows.  The body of one chunk is a dependent chain (routing -> rank ->
#: one-hot -> matmuls) the scheduler cannot shorten; the chains of several
#: chunks in one basic block interleave, and a trip's index arithmetic is
#: done once for its chunks (PERF.md §6, PR 29: 981 / 800 / 765 ns a chunk
#: at 1 / 2 / 4 on the chip at 128 lanes; 1,591 / 1,353 / 1,240 with the
#: body PR 25 left).  Not 4: every chunk of a trip is a copy of the body
#: for Mosaic to compile, and at 4 the fused step of PR 25's body compiled
#: 4.6 s longer than the parent's on the chip's host (0.5 s at 2), which
#: a training job pays for every new data set (`gbdt.step` is keyed on
#: the data).  A 512-lane block reads SLOWER at 2 (3,213 against 2,916).
_PASS_A_GROUP = 2


def _pass_a_group(payload_width: int, num_bins: int,
                  extra_bytes: int = 0) -> int:
    """Chunks a trip of pass A: `_PASS_A_GROUP` where its VMEM plan fits
    beside `extra_bytes` (a column block's split-window ring), else 1,
    the plan the fits_vmem gates admit or refuse.  More chunks in flight
    need more VMEM, so the width of the payload decides."""
    fits = (_acc_plan_bytes(payload_width, num_bins, _PASS_A_GROUP)
            + extra_bytes <= _VMEM_BUDGET)
    return _PASS_A_GROUP if fits else 1


def partition_acc_fits_vmem(payload_width: int, num_bins: int) -> bool:
    """True when the accumulator-window partition kernel's VMEM plan fits
    with pass A one chunk a trip (narrower payloads take more,
    `_pass_a_group`)."""
    return _acc_plan_bytes(payload_width, num_bins, 1) <= _VMEM_BUDGET


def partition_fits_vmem(payload_width: int, num_bins: int) -> bool:
    """True when the partition kernel's VMEM plan fits: its scratch
    (chunk + two RMW windows) and live row intermediates all span the FULL
    payload width P — unlike the histogram kernel it has no feature tiling,
    so very wide payloads (Epsilon-shaped, P ~ 2048) take the portable
    partition while the histogram still rides the Pallas kernel."""
    P = payload_width
    win = CHUNK + 8
    est = (4 * (CHUNK + 2 * win) * P           # scratch: chunk, wstage, wread
           + 4 * (3 * CHUNK + win) * P         # live rows: data/lrows/rrows + shifted
           + 4 * (2 * CHUNK * CHUNK + 2 * win * CHUNK)   # perm/tri + smat/iotas
           + 4 * CHUNK * num_bins)             # categorical bitset one-hot
    return est <= _VMEM_BUDGET


def _row_iota():
    return lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)[:, 0]


def _bf16_parts(data):
    """Exact bf16 hi/mid/lo decomposition of f32 rows (each part is
    bf16-representable, so one-pass MXU matmuls against 0/1 matrices are
    exact; hi+mid+lo reconstructs the f32 value exactly).  astype round
    trips are safe in Mosaic — see the note in _hist_kernel."""
    hi = data.astype(jnp.bfloat16).astype(jnp.float32)
    r1 = data - hi
    mid = r1.astype(jnp.bfloat16).astype(jnp.float32)
    lo = r1 - mid
    return hi, mid, lo


def _go_left_rows(scalars, bitset_ref, data, B, iota_p):
    """[C] i32 0/1 routing of payload rows by the split predicate (without
    the caller's window-validity mask) — Bin::Split semantics, one row a
    SUBLANE.  The read-modify-write `_partition_kernel` is its one caller:
    the accumulator kernel routes with rows in lanes (`_go_left_lanes`).
    Selects the split feature's storage column by
    lane reduction (dynamic lane indexing is not a Mosaic primitive; the
    masked sum is), then decodes the EFB bundle value to the feature's own
    bin.  All predicate logic is i32 arithmetic — Mosaic cannot
    re-truncate materialized bool vectors back to i1 for select_n."""
    col = scalars[2]
    threshold = scalars[3]
    default_left = scalars[4]
    is_cat = scalars[5]
    missing_type = scalars[6]
    num_bin = scalars[7]
    default_bin = scalars[8]
    offset = scalars[9]
    identity = scalars[10]
    raw = jnp.sum(jnp.where(iota_p == col, data, 0.0),
                  axis=1).astype(jnp.int32)                  # [C]
    e = raw - offset
    in_range = ((e >= 0) & (e < num_bin - 1)).astype(jnp.int32)
    bump = (e >= default_bin).astype(jnp.int32)
    decoded = in_range * (e + bump) + (1 - in_range) * default_bin
    fbin = identity * raw + (1 - identity) * decoded
    miss = (((missing_type == MISSING_NAN) &
             (fbin == num_bin - 1)).astype(jnp.int32) |
            ((missing_type == MISSING_ZERO) &
             (fbin == default_bin)).astype(jnp.int32))
    gl_num = (miss * default_left +
              (1 - miss) * (fbin <= threshold).astype(jnp.int32))
    iota_b = lax.broadcasted_iota(jnp.int32, (CHUNK, B), 1)
    hits = ((fbin[:, None] == iota_b) &
            (bitset_ref[:] > 0)).astype(jnp.int32)
    gl_cat = (jnp.sum(hits, axis=1) > 0).astype(jnp.int32)
    return is_cat * gl_cat + (1 - is_cat) * gl_num


#: where the packed categorical bitset starts in the accumulator kernels'
#: scalar-prefetch vector: behind the 11 scalars of the predicate, the
#: split window's first lane (which only the column-block wrapper fills)
#: and `right_first`
_BITSET_WORD0 = 13


def _acc_scalars(start, count, pred, col, win_lo, num_bins, right_first):
    """The accumulator kernels' scalar-prefetch vector: segment, split
    predicate (`col` as the kernel is to see it), the split window's
    first lane, whether the right child lies first, then `pred.bitset`
    packed into ceil(B / 32) int32 words, bit b of word w the membership
    of bin 32 w + b."""
    words = -(-num_bins // 32)
    bits = jnp.pad(pred.bitset.astype(jnp.uint32),
                   (0, 32 * words - num_bins)).reshape(words, 32)
    packed = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=1,
                     dtype=jnp.uint32)
    return jnp.concatenate([
        jnp.stack([
            start, count, col, pred.threshold,
            pred.default_left.astype(jnp.int32),
            pred.is_cat.astype(jnp.int32), pred.missing_type, pred.num_bin,
            pred.default_bin, pred.offset, pred.identity.astype(jnp.int32),
            win_lo, right_first,
        ]).astype(jnp.int32),
        lax.bitcast_convert_type(packed, jnp.int32)])


def _go_left_lanes(scalars, raw, B):
    """i32 0/1 routing of rows by the split predicate, ROWS IN LANES: `raw`
    holds the split column's stored bins as int32, a chunk a row ([8, C]:
    two vregs where one row a sublane takes 32), and so does the result.
    The same integer arithmetic as `_go_left_rows` (Bin::Split: EFB
    decode, missing type, default direction, threshold).  Categorical
    membership reads the packed bitset from scalar memory,
    (word[fbin >> 5] >> (fbin & 31)) & 1, the word picked by a chain of
    scalar-against-vector selects: no [C, B] one-hot, and no branch on
    `is_cat` (a region boundary would stop a trip's chains from
    interleaving).  A bin outside [0, B) picks no word and is no member,
    as it matches no lane of the one-hot."""
    threshold = scalars[3]
    default_left = scalars[4]
    is_cat = scalars[5]
    missing_type = scalars[6]
    num_bin = scalars[7]
    default_bin = scalars[8]
    offset = scalars[9]
    identity = scalars[10]
    e = raw - offset
    in_range = ((e >= 0) & (e < num_bin - 1)).astype(jnp.int32)
    bump = (e >= default_bin).astype(jnp.int32)
    decoded = in_range * (e + bump) + (1 - in_range) * default_bin
    fbin = identity * raw + (1 - identity) * decoded
    miss = (((missing_type == MISSING_NAN) &
             (fbin == num_bin - 1)).astype(jnp.int32) |
            ((missing_type == MISSING_ZERO) &
             (fbin == default_bin)).astype(jnp.int32))
    gl_num = (miss * default_left +
              (1 - miss) * (fbin <= threshold).astype(jnp.int32))
    word_of = fbin >> 5
    word = jnp.zeros_like(fbin)
    for w in range(-(-B // 32)):
        word = jnp.where(word_of == w, scalars[_BITSET_WORD0 + w], word)
    gl_cat = (word >> (fbin & 31)) & 1
    return is_cat * gl_cat + (1 - is_cat) * gl_num


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def _in_trips(n, per_trip, body):
    """body(first, count) over the static range [0, n): whole trips of
    `per_trip` as a loop where there are two or more, what is left (or
    all of it) unrolled with a static `first`."""
    trips = n // per_trip
    done = 0
    if trips > 1:
        def trip(u, carry):
            body(u * per_trip, per_trip)
            return carry

        lax.fori_loop(0, trips, trip, 0)
        done = trips * per_trip
    if n > done:
        body(done, n - done)


def _hist_kernel(scalars, payload_hbm, out_ref, chunk, sem, hi_rows, lo_rows,
                 *, F, B, grad_col, hess_col, cnt_col):
    """chunk is a DOUBLE buffer [2, CHUNK, P]: while slot k%2 feeds the
    products, the DMA for chunk k+1 streams into the other slot.

    The bin id is factored, bin = hi * L + lo (`_hist_factor`).  For one
    feature f over a chunk's rows r

        hist_f[(hi, part), lo] = sum_r vals[part, r] * [hi_f(r) == hi] * [lo_f(r) == lo]

    is one product `V_f [8H, C] x LoT_f [L, C]^T`, and G = 128 / L features
    share one: their masked values stacked in the rows, their low one-hots
    in the 128 columns, `[G 8H, C] x [128, C]^T`.  Of its G x G blocks the
    G on the diagonal are the features' histograms; a lane mask sums them
    into the group's [8H, 128] rows of the accumulator (row hi * 8 + part,
    lane g * L + lo), so the accumulator is 8 * F * H * L * 4 bytes and no
    larger.  Both operands have ROWS IN LANES: one transposition of a
    128-column block of the chunk gives every bin column of the block as a
    row, a feature's row broadcast over sublanes and compared with a
    sublane index is its one-hot, and nothing is expanded across lanes
    (the expand of a B-wide one-hot was 58-75% of the body before; PERF.md
    §6, PR 27).  The groups of a block are a loop, `_HIST_TRIP_GROUPS` a
    trip, and so are the blocks.  When the segment is done the high blocks
    of each group are moved beside each other, so that a part's row reads
    (feature, bin) along the lanes as `_unfactor_hist` wants it."""
    P = chunk.shape[2]
    L, H, G = _hist_factor(B)
    R, tile_groups = 8 * H, 128 // G
    lo_bits = L.bit_length() - 1
    start = scalars[0]
    count = scalars[1]
    # HBM row slices must start at a multiple of the f32 sublane tiling (8);
    # a segment starts anywhere, so chunks stride from the aligned base and
    # the first `shift` rows are masked out of chunk 0.
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)
    iota_rows = _row_iota()

    def dma_for(k, slot):
        return pltpu.make_async_copy(
            payload_hbm.at[pl.ds(pl.multiple_of(base + k * CHUNK, 8),
                                 CHUNK), :],
            chunk.at[slot], sem.at[slot])

    @pl.when(nch > 0)
    def _prefetch_first():
        dma_for(0, 0).start()

    # what a group's selected rows are compared with, built once: the high
    # part a row of V stands for, the low part a row of LoT stands for, the
    # feature of the group a lane of the product belongs to
    hi_of_row = (lax.broadcasted_iota(jnp.int32, (G * R, CHUNK), 0) // 8) % H
    lo_of_row = lax.broadcasted_iota(jnp.int32, (128, CHUNK), 0) % L
    feature_of_lane = lax.broadcasted_iota(jnp.int32, (R, 128), 1) // L
    # the value columns lie in one or two 128-lane blocks of the payload;
    # only those enter the extraction
    value_cols = (grad_col, hess_col, cnt_col)
    v_lo = min(value_cols) // 128 * 128
    v_hi = min(P, max(value_cols) // 128 * 128 + 128)
    iota_r8 = lax.broadcasted_iota(jnp.int32, (8, v_hi - v_lo), 0)
    iota_pc = lax.broadcasted_iota(jnp.int32, (8, v_hi - v_lo), 1) + v_lo
    sel = (((iota_r8 < 3) & (iota_pc == grad_col)) |
           ((iota_r8 >= 3) & (iota_r8 < 6) & (iota_pc == hess_col)) |
           ((iota_r8 == 6) & (iota_pc == cnt_col))).astype(jnp.float32)
    part_of_row = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 0)

    def body(k, _):
        slot = lax.rem(k, 2)

        @pl.when(k + 1 < nch)
        def _prefetch_next():
            dma_for(k + 1, lax.rem(k + 1, 2)).start()

        dma_for(k, slot).wait()
        ok = ((iota_rows >= shift - k * CHUNK) &
              (iota_rows < shift + count - k * CHUNK)).astype(jnp.float32)
        # The MXU runs f32 matmuls as ONE bf16 pass by default, which would
        # round the gradients to 8 mantissa bits.  Instead of paying the
        # 3-pass HIGHEST contract, the value rows carry an EXACT bf16
        # decomposition: rows (g_hi, g_mid, g_lo, h_hi, h_mid, h_lo, cnt) —
        # each part is bf16-representable and so is a part times a 0/1
        # mask, so the one-pass contract is exact and the f32 histogram is
        # recovered as the sum of three part-histograms.  (Extraction of
        # the g/h/cnt columns is a tiny matmul — HIGHEST there costs
        # nothing.)
        raw = lax.dot_general(
            sel, chunk[slot, :, v_lo:v_hi],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST)                     # [8, C]
        # astype round trips are safe HERE (unlike histogram.py, which
        # must use lax.reduce_precision): Mosaic lowers the trunc/ext pair
        # directly and never runs XLA's excess-precision simplifier that
        # would delete it — validated on hardware by exp/smoke_tpu_kernels
        # (count equality + grad-bit-survival + float64 checks).
        hi = raw.astype(jnp.bfloat16).astype(jnp.float32)
        r1 = raw - hi
        mid = r1.astype(jnp.bfloat16).astype(jnp.float32)
        lo = r1 - mid
        vals = jnp.where((part_of_row == 0) | (part_of_row == 3), hi,
                         jnp.where((part_of_row == 1) | (part_of_row == 4),
                                   mid,
                                   jnp.where((part_of_row == 2) |
                                             (part_of_row == 5), lo, raw)))
        vals = vals * ok[None, :]
        vals_tiled = jnp.concatenate([vals] * (G * H), axis=0)   # [G R, C]

        def tile(t, width, features):
            """The bin columns [128 t, 128 t + features) of the resident
            chunk: transposed once, then a product a group of G."""
            lane0 = t * 128 if isinstance(t, int) \
                else pl.multiple_of(t * 128, 128)
            bins = chunk[slot, :, pl.ds(lane0, width)].T.astype(jnp.int32)
            hi_rows[0:width] = bins >> lo_bits                   # [w, C]
            lo_rows[0:width] = bins & (L - 1)

            def group(j):
                hi_sel = jnp.concatenate(
                    [jnp.broadcast_to(hi_rows[pl.ds(j * G + g, 1), :],
                                      (R, CHUNK)) for g in range(G)],
                    axis=0)                                      # [G R, C]
                lo_sel = jnp.concatenate(
                    [jnp.broadcast_to(lo_rows[pl.ds(j * G + g, 1), :],
                                      (L, CHUNK)) for g in range(G)],
                    axis=0)                                      # [128, C]
                masked = jnp.where(hi_sel == hi_of_row, vals_tiled, 0.0)
                onehot = jnp.where(lo_sel == lo_of_row, 1.0, 0.0)
                prod = lax.dot_general(
                    masked, onehot,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)          # [G R, 128]
                own = jnp.where(feature_of_lane == 0, prod[0:R], 0.0)
                for g in range(1, G):
                    own = own + jnp.where(feature_of_lane == g,
                                          prod[g * R:(g + 1) * R], 0.0)
                row0 = (t * tile_groups + j) * R
                if not (isinstance(t, int) and isinstance(j, int)):
                    row0 = pl.multiple_of(row0, R)
                out_ref[pl.ds(row0, R), :] += own

            # the last group may reach past the tile's features: it reads
            # other columns' rows, and `_unfactor_hist` drops what they give
            _in_trips(-(-features // G), _HIST_TRIP_GROUPS,
                      lambda first, n: [group(first + i) for i in range(n)])

        # column tiles walk the SAME resident chunk — the payload is read
        # from HBM once per histogram no matter how wide it is.  Whole
        # tiles of 128 bin columns are a loop; the last, with fewer, is
        # its own code.  (A payload is lane-padded on the chip; only the
        # interpreter sees a last tile narrower than 128 lanes.)
        whole = F // 128
        _in_trips(whole, 1, lambda t, _: tile(t, 128, 128))
        if F % 128:
            tile(whole, min(128, P - 128 * whole), F % 128)
        return 0

    lax.fori_loop(0, nch, body, 0)

    if H == 1:
        return
    block_of_lane = lax.broadcasted_iota(jnp.int32, (8, 128), 1) // L

    def regrouped(src):
        """A group's rows are (hi, part) and its lanes (g, lo); its
        H * 128 numbers a part are wanted in the order (g, hi, lo).  Lane
        block i of row block d is then the block g of the rows of hi with
        g * H + hi = d * G + i: a lane rotation and a select each."""
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

    _in_trips(out_ref.shape[0] // R, 8, regroup)


@functools.partial(xla_obs.jit, site="pallas.segment_histogram",
                   static_argnames=("num_features", "num_bins", "grad_col",
                                    "hess_col", "cnt_col", "interpret"))
def _segment_histogram(payload, start, count, *, num_features, num_bins,
                       grad_col, hess_col, cnt_col, interpret=False):
    """hist[F, B, 3] over payload rows [start, start+count) — TPU kernel."""
    F, B, P = num_features, num_bins, payload.shape[1]
    H = _hist_factor(B)[1]
    scalars = jnp.stack([start, count]).astype(jnp.int32)
    kern = functools.partial(_hist_kernel, F=F, B=B, grad_col=grad_col,
                             hess_col=hess_col, cnt_col=cnt_col)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, CHUNK, P), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((128, CHUNK), jnp.int32),
                pltpu.VMEM((128, CHUNK), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((_hist_groups(F, B) * 8 * H, 128),
                                       jnp.float32),
        interpret=interpret,
    )(scalars, payload)
    return _unfactor_hist(out, F, B)


#: the jitted functions keep their underscore: the profiler names a
#: kernel's custom call after its jitted wrapper, and the benchmark's trace
#: reader matches `_segment_histogram`, `_partition_segment_acc` and
#: `_partition_segment_acc_blocks`
segment_histogram = _segment_histogram


def _unfactor_hist(out, F, B):
    """[groups * 8H, 128] kernel accumulator -> [F, B, 3].  A group's 8H
    rows are H blocks of the 8 parts, its H * 128 numbers a part the
    histograms of its G features one after the other, bin by bin; the parts
    are the exact bf16 decomposition (g_hi, g_mid, g_lo, h_hi, h_mid,
    h_lo, cnt) — recombine, then slice off the padding."""
    L, H, _ = _hist_factor(B)
    r = out.reshape(-1, H, 8, 128)
    ghc = jnp.stack([r[:, :, 0] + r[:, :, 1] + r[:, :, 2],
                     r[:, :, 3] + r[:, :, 4] + r[:, :, 5],
                     r[:, :, 6]])                          # [3, n, H, 128]
    return ghc.reshape(3, -1, H * L)[:, :F, :B].transpose(1, 2, 0)


# ---------------------------------------------------------------------------
# partition
# ---------------------------------------------------------------------------

#: both partition kernels overrun DMA windows past the segment end: the
#: RMW kernel by WIN rows, the accumulator kernel by up to a full flushed
#: window (CHUNK rows past the last real row) — the GUARD tail must cover
#: whichever is larger.
assert CHUNK <= GUARD, "segment.GUARD must cover a full flush window"

#: rows in a write window: a write at an arbitrary cursor d becomes a
#: read-modify-write of the aligned window [d - d%8, ...) — 8 slack rows
#: cover the worst-case misalignment (sublane tiling of f32 HBM memrefs).
#: Payload buffers must carry at least this much guard tail past the last
#: real row, or the final write window DMAs out of bounds.
WIN = CHUNK + 8
assert WIN <= GUARD, "segment.GUARD must cover the RMW write window"


def _partition_kernel(scalars, fvals, bitset_ref, payload_hbm, aux_hbm,
                      payload_out, aux_out, nl_out,
                      chunk, wstage, wread, sem_in, sem_out, *,
                      P, B, value_col):
    """payload_hbm/aux_hbm are aliased with payload_out/aux_out — the kernel
    reads and writes the same HBM buffers through the `_out` refs.
    "Left" in the body is the FIRST side, "right" the staged one: with
    scalars[11] (`right_first`) set the routing is turned round, the
    wrapper hands the values over swapped and reads `nl_out` as the
    right child's count."""
    start = scalars[0]
    count = scalars[1]
    right_first = scalars[11]
    left_value = fvals[0]
    right_value = fvals[1]
    # reads stride CHUNK from the 8-aligned base below `start`; the first
    # `shift` rows of window 0 belong to the previous segment and mask out
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    iota_rows = _row_iota()
    iota_w = lax.broadcasted_iota(jnp.int32, (WIN, 1), 0)[:, 0]
    iota_p = lax.broadcasted_iota(jnp.int32, (1, P), 1)

    def read_chunk(src_ref, k, buf):
        dma = pltpu.make_async_copy(
            src_ref.at[pl.ds(pl.multiple_of(base + k * CHUNK, 8), CHUNK), :],
            buf, sem_in)
        dma.start()
        dma.wait()
        return buf[:]

    def valid_mask(k):
        return ((iota_rows >= shift - k * CHUNK) &
                (iota_rows < shift + count - k * CHUNK)).astype(jnp.int32)

    def go_left(data, k):
        return (_go_left_rows(scalars, bitset_ref, data, B, iota_p)
                ^ right_first) * valid_mask(k)               # [C] i32 0/1

    def compact_rows(keep_i, data, value):
        """Stable forward compaction of data rows with keep_i=1 (exclusive
        prefix sum as a strict-lower-triangular matvec — Mosaic has no
        cumsum; counts <= CHUNK are exact in f32), with the per-row tree
        output written into the value column on the way through."""
        iota_i = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        iota_j = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        tri = (iota_j < iota_i).astype(jnp.float32)
        dest = jnp.dot(tri, keep_i.astype(jnp.float32)[:, None],
                       preferred_element_type=jnp.float32)[:, 0].astype(jnp.int32)
        iota_c = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        perm = ((dest[None, :] == iota_c) &
                (keep_i[None, :] > 0)).astype(jnp.float32)
        # HIGHEST: the default one-pass-bf16 MXU matmul would round every
        # payload value it permutes (and corrupt the >8-bit idx columns);
        # the cost is invisible — this kernel is DMA-latency-bound.
        rows = jnp.dot(perm, data, preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST)
        return jnp.where(iota_p == value_col, value, rows)

    def write_rows(dst_ref, d, rows, keep_cnt, src_off):
        """Write rows[src_off : src_off+keep_cnt) to dst_ref[d : d+keep_cnt).

        The destination cursor is arbitrary but HBM slices must start
        8-aligned, so the write is a read-modify-write of the enclosing
        aligned WIN-row window; the source rows are moved to their in-window
        offset by a shift-permutation matmul (dynamic sublane rolls are not
        a Mosaic primitive, matmuls are).  Rows outside [d, d+keep_cnt) are
        written back with the values just read, so trailing unconsumed rows
        and the prologue of already-written rows both survive — this also
        subsumes the old segment-end blend path.  Empty writes (common on
        skewed splits: most chunks contribute to only one side) skip the
        whole round trip."""
        @pl.when(keep_cnt > 0)
        def _go():
            sw = lax.rem(d, 8)
            basew = pl.multiple_of(d - sw, 8)
            dma_r = pltpu.make_async_copy(
                dst_ref.at[pl.ds(basew, WIN), :], wread, sem_in)
            dma_r.start()
            dma_r.wait()
            delta = sw - src_off
            iota_wi = lax.broadcasted_iota(jnp.int32, (WIN, CHUNK), 0)
            iota_wj = lax.broadcasted_iota(jnp.int32, (WIN, CHUNK), 1)
            smat = (iota_wi - iota_wj == delta).astype(jnp.float32)
            shifted = jnp.dot(smat, rows,
                              preferred_element_type=jnp.float32,
                              precision=lax.Precision.HIGHEST)     # [WIN, P]
            region = ((iota_w >= sw) &
                      (iota_w < sw + keep_cnt)).astype(jnp.float32)[:, None]
            wstage[:] = region * shifted + (1.0 - region) * wread[:]
            dma_w = pltpu.make_async_copy(
                wstage, dst_ref.at[pl.ds(basew, WIN), :], sem_out)
            dma_w.start()
            dma_w.wait()

    # pass A: ONE read of the segment; lefts forward-compact in place in
    # payload (the write cursor trails the read cursor, and the RMW windows
    # write back every row outside the compacted block unchanged), rights
    # staged compacted into aux scratch.
    def body_a(k, carry):
        nl, nr = carry
        data = read_chunk(payload_out, k, chunk)
        gl = go_left(data, k)
        keep_r = valid_mask(k) - gl
        lrows = compact_rows(gl, data, left_value)
        write_rows(payload_out, start + nl, lrows, jnp.sum(gl), 0)
        rrows = compact_rows(keep_r, data, right_value)
        write_rows(aux_out, start + nr, rrows, jnp.sum(keep_r), 0)
        return (nl + jnp.sum(gl), nr + jnp.sum(keep_r))

    num_left, num_right = lax.fori_loop(
        0, nch, body_a, (jnp.int32(0), jnp.int32(0)))
    nl_out[0] = num_left

    # pass B: copy the staged rights back behind the lefts (touches only
    # the rights region, ~half the old blended full-segment pass C).  Window
    # k of the aligned read stream holds source rows [lo, hi) of the staged
    # rights; they land at the destination cursor advanced by the rows of
    # all previous windows.
    nrch = jnp.where(num_right > 0,
                     (shift + num_right + CHUNK - 1) // CHUNK, 0)

    def body_b(k, _):
        data = read_chunk(aux_out, k, chunk)
        lo = jnp.maximum(shift - k * CHUNK, 0)
        hi = jnp.minimum(shift + num_right - k * CHUNK, CHUNK)
        done = jnp.maximum(k * CHUNK - shift, 0)
        write_rows(payload_out, start + num_left + done, data,
                   jnp.maximum(hi - lo, 0), lo)
        return 0

    lax.fori_loop(0, nrch, body_b, 0)


@functools.partial(xla_obs.jit, site="pallas.partition_segment",
                   static_argnames=("value_col", "num_bins", "interpret"))
def _partition_segment(payload, aux, start, count, pred, left_value,
                       right_value, value_col, num_bins, right_first=False,
                       interpret=False):
    """Same contract as ops.segment.partition_segment, fused on-chip.
    (Named as its siblings are: a trace calls the kernel's custom call
    after this wrapper, and the benchmark's `kernel.partition_s_per_iter`
    matches `_partition_segment*`.)"""
    P = payload.shape[1]
    B = num_bins
    scalars = jnp.stack([
        start, count, pred.col, pred.threshold,
        pred.default_left.astype(jnp.int32), pred.is_cat.astype(jnp.int32),
        pred.missing_type, pred.num_bin, pred.default_bin,
        pred.offset, pred.identity.astype(jnp.int32), right_first,
    ]).astype(jnp.int32)
    fvals = jnp.stack(first_second(
        right_first, left_value, right_value)).astype(jnp.float32)
    bitset = pred.bitset.astype(jnp.int32).reshape(1, B)
    kern = functools.partial(_partition_kernel, P=P, B=B,
                             value_col=value_col)
    payload_new, aux_new, nl = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[
                pltpu.VMEM((CHUNK, P), jnp.float32),
                pltpu.VMEM((WIN, P), jnp.float32),
                pltpu.VMEM((WIN, P), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct(payload.shape, payload.dtype),
                   jax.ShapeDtypeStruct(aux.shape, aux.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        input_output_aliases={3: 0, 4: 1},
        compiler_params=_SIDE_EFFECTS,
        interpret=interpret,
    )(scalars, fvals, bitset, payload, aux)
    return payload_new, aux_new, jnp.where(right_first, count - nl[0], nl[0])


partition_segment = _partition_segment


# ---------------------------------------------------------------------------
# partition, accumulator-window variant
# ---------------------------------------------------------------------------

C2 = 2 * CHUNK

#: rows of a chunk's permuted block (pass A): the first side's rows from
#: row `cursor & 7` of the block, the staged side's from the same part of
#: ITS cursor in the first tile behind them: at most 7 + 7 + 7 rows more
#: than the chunk's own
BLOCK_ROWS = CHUNK + 24
#: rows of the block's scratch: the staged side's [C + 8, P] window starts
#: at a tile up to row C + 8; the rows past the block's own are never
#: written, and the placement's mask keeps them out
BLOCK_SCRATCH_ROWS = BLOCK_ROWS + CHUNK
#: rows of an accumulator: a ring of `_ACC_WINDOWS` windows of CHUNK rows
#: and a chunk's tail, so that the [C + 8, P] window at any tile of the
#: ring lies inside the buffer
ACC_ROWS = (_ACC_WINDOWS + 1) * CHUNK


def _acc_kernel(scalars, fvals, payload_hbm, aux_hbm, *rest,
                P, B, value_col, group=1, lane_lo=None):
    """Accumulator-window partition: same contract as `_partition_kernel`,
    restructured around the measured bottleneck (per-chunk latency, not
    bandwidth).  Lefts and rights accumulate in VMEM, each side in a RING
    of `_ACC_WINDOWS` windows of CHUNK rows (`lacc`, `racc`: [ACC_ROWS, P],
    the ring and a chunk's tail behind it), and a window goes to HBM
    ALIGNED and FULL when the side's cursor leaves it — so the per-chunk
    read-modify-write round trips of the RMW kernel collapse to one
    amortized direct write per side, reads prefetch on a double-buffered
    ring, and exactness costs three ONE-pass matmuls on a bf16-exact
    hi/mid/lo decomposition instead of a 6-pass HIGHEST.  Only the LAST
    window of a segment needs a blend read (its tail crosses into the
    next leaf's rows).

    A flush is ONE DMA out of the ring's own window (`flush`), nothing
    copied: the cursor runs on into the next window and, past the last,
    comes back into the first.  The one wait it costs (`reserve`) is for
    the flush `_ACC_WINDOWS - 1` earlier, whose window the cursor is
    about to enter, and only the put that fills a window pays it; a put
    that crosses the ring's end is two aligned masked stores (`put`).
    Until PR 38 a flush waited for the one before it, copied the window
    to a stage and slid the accumulator's second half onto its first:
    two [C, P] copies and a wait a chunk of rows, which a lopsided split
    pays every chunk on one side (PERF.md §6, PR 38).

    "Lefts" below are the rows of the FIRST side, which pass A writes in
    place, and "rights" those of the STAGED side, which it parks in `aux`
    and pass B reads back and appends: a row of the staged side is read
    and written twice.  Which child is which is one prefetched scalar,
    `right_first`: `routed` turns the predicate round under the validity
    mask and nothing else in the body can tell; the wrapper hands over
    the children's values in that order and reads `nl_out` as the first
    side's count.  It is data, like `is_cat`: one body, one compilation.

    Pass A places a chunk's rows with ONE permutation, not one compaction
    per side: lefts in order, then rights in order, is a stable partition
    of the chunk's valid rows, so one destination vector and one one-hot
    applied to the three parts give a block with each side's rows in one
    run.  The one-hot puts a row where it is told at no cost, so it is
    told the part of each accumulator's cursor that is no multiple of a
    sublane tile: with a cursor written 8 q + r, the lefts go to rows
    [r_l, r_l + nl_k) of a [C + 24, P] block and the rights to
    [s, s + nr_k), s the first row behind the lefts that is r_r modulo 8.
    Each side's placement is then ONE masked store into the tile-aligned
    [C + 8, P] window of its accumulator from tile q, of the window of
    the block that starts at the side's own tile (`put`): no rotate,
    nothing of an accumulator computed or read (PERF.md §6, PR 36; pass
    B, whose rows arrive contiguous, keeps its one rotate of the doubled
    chunk, to the cursor's part under 8, and places the head of it by
    the same `put`).

    Where the 256 rows of a chunk go is arithmetic on 256 numbers, and it
    is done with ROWS IN LANES, the chunks of a trip as the rows of one
    [8, C] vector (`routed`): an NT product of a one-hot row against the
    128-lane window that holds the split column gives the column as a row
    (its other lanes zeroed first, so that nothing they hold reaches the
    product; one bf16 pass is exact for bins under 256, and HIGHEST is
    asked for past them; a transposition and a row load read 9% slower,
    PERF.md §6, PR 29); the predicate (`_go_left_lanes`), the validity
    mask, the lefts' ranks (ONE product `[8, C] x tri_t` a trip; the
    rights' ranks are iota arithmetic, the valid rows of a chunk being
    one contiguous range) and the destination follow on two vregs; and a
    chunk's row of the destination, non-members at -1, is broadcast along
    sublanes into the one-hot's compare with no relayout.  (A per-row
    quantity born from a lane reduction lives one row a SUBLANE, one lane
    in 128 at work: that was 640 of a chunk's 1,600 ns.)  Per chunk: 4
    MXU contractions (the column, the 3 parts) and a share of the trip's
    rank product, one [C + 24, C] matrix built on the VPU, one store of
    the block, two masked stores of its windows; what does not depend on
    the chunk
    (`tri_t`, the index planes, the column's one-hot row) is built once
    before the loop.

    That body is one dependent chain (routing -> rank -> one-hot ->
    matmuls -> block -> windows) and the chip runs it at the chain's
    latency, not at any unit's rate (PERF.md §6, PR 25), so pass A takes
    `group` chunks a loop trip: every wait and load first, then the
    trip's index arithmetic (the cursors' parts under 8 at each chunk are
    the carried cursors plus the counts of the trip's earlier chunks: no
    accumulator is read), then each chunk's permuted block into a scratch
    of its own, then the blocks placed in order.  The chains of a trip
    share a basic block and interleave.  The ring holds its depth
    in groups; a trip's chunks past the segment's last are not read and
    count as empty.

    With `lane_lo` set (one pass of the column-block partition,
    `partition_segment_acc_blocks`), the kernel moves only the payload's
    lanes [lane_lo, lane_lo + P) and routes rows from a frozen copy of the
    split column's 128-lane window (`route_hbm`, a further input, read
    chunk by chunk beside the block into a ring of its own): every pass
    over a segment then computes the same routing, whichever block holds
    the split column, and the passes together apply one row permutation
    to the whole width with VMEM bounded by the block's.  scalars[2]
    arrives localized to that window; `value_col` is local to the block
    (-1, which matches no lane, in every block but the value column's).
    Pass B needs no routing: membership there is positional."""
    blocks = lane_lo is not None
    if blocks:
        route_hbm, *rest = rest
    payload_out, aux_out, nl_out, *rest = rest
    ring, lacc, racc, blk, sem_ring, sem_w, sem_r, *rest = rest
    if blocks:
        route_ring, sem_route = rest
    start = scalars[0]
    count = scalars[1]
    right_first = scalars[12]
    left_value = fvals[0]
    right_value = fvals[1]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)
    iota_rows = _row_iota()
    iota_win = lax.broadcasted_iota(jnp.int32, (WIN, 1), 0)
    iota_p = lax.broadcasted_iota(jnp.int32, (1, P), 1)
    # an accumulator is a ring of NW windows of CHUNK rows and a chunk's
    # tail behind it; a cursor is a row of the ring, [0, RS)
    NW = _ACC_WINDOWS
    RS = NW * CHUNK
    # the split column's 128-lane window of a chunk (in ring slot `slot`,
    # loaded as `data`) and the column's place in it: a column block's
    # frozen copy, the chunk itself (at 128 lanes, and under the
    # interpreter's ragged widths), or a lane slice of it
    route_col = scalars[2]
    if blocks:
        def split_window(slot, data):
            return route_ring[slot]
    elif P % 128 or P == 128:
        def split_window(slot, data):
            return data
    else:
        win_lane0 = pl.multiple_of(route_col // 128 * 128, 128)
        route_col = route_col - win_lane0

        def split_window(slot, data):
            return ring[slot, :, pl.ds(win_lane0, 128)]

    def window(ref, row0):
        """CHUNK rows of an HBM buffer from (8-aligned) `row0`, over the
        lanes this kernel moves."""
        rows = pl.ds(row0, CHUNK)
        return ref.at[rows, pl.ds(lane_lo, P)] if blocks else ref.at[rows, :]

    def ring_dma(src_ref, k, slot):
        return pltpu.make_async_copy(
            window(src_ref, pl.multiple_of(base + k * CHUNK, 8)),
            ring.at[slot], sem_ring.at[slot])

    def read_a(k, slot):
        """Pass A's reads of chunk k: the rows and, in a column block,
        the frozen split window beside them."""
        dmas = [ring_dma(payload_out, k, slot)]
        if blocks:
            dmas.append(pltpu.make_async_copy(
                route_hbm.at[pl.ds(pl.multiple_of(base + k * CHUNK, 8),
                                   CHUNK), :],
                route_ring.at[slot], sem_route.at[slot]))
        return dmas

    # chunk-independent machinery, built once before the chunk loop (as
    # the histogram kernel does for its own).  The iotas are built at
    # [C, C] directly: slicing a [2C, C] one crashes Mosaic's
    # ApplyVectorLayout — a broadcasted iota is stored replicated along
    # its constant dim, and vector.extract_strided_slice asks that dim for
    # more vregs than the replicated layout holds (hardware-bisected,
    # round 4).
    iota_ci = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    iota_bi = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, CHUNK), 0)
    iota_b = lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, 1), 0)
    # tri_t[j, i] = 1 where row j comes before row i: gl x tri_t is the
    # exclusive prefix count of the lefts (<= C, exact in one bf16 pass)
    tri_t = (iota_ci < lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
             ).astype(jnp.float32)
    # the index planes of a trip: row g of an [8, C] vector is chunk
    # k0 + g, a lane is a row of the chunk
    chunk_of_row = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 0)
    row_of_lane = lax.broadcasted_iota(jnp.int32, (8, CHUNK), 1)
    # the split column's lane of its window, as a mask and as the one-hot
    # rows of the product that reads the column out
    route_w = P if P % 128 else 128
    lane_of_window = lax.broadcasted_iota(jnp.int32, (1, route_w), 1)
    route_sel = (lax.broadcasted_iota(jnp.int32, (8, route_w), 1) ==
                 route_col).astype(jnp.float32)

    def put(acc, cursor, cnt, rows, spill):
        """Rows [r, r + cnt) of `rows` ([C + 8, P]; r = cursor & 7, where
        the one-hot or pass B's rotate put them) to the ring's rows
        [cursor, cursor + cnt): ONE masked store of the TILE-ALIGNED
        window that holds them, no rotate and no read of the accumulator
        (a select against the window read back took 1% longer at 128
        lanes and 3% in a 512-lane block, PERF.md §6, PR 36).  Where the
        rows run past the ring's end, that store leaves them in the tail
        behind it and a second one, of the block's window `spill(over)`
        that starts `over` rows further on, puts them at the ring's head:
        the ring, a window and a chunk are multiples of a sublane tile,
        so both stores are aligned.  A masked-out sublane is not written:
        neither store touches a row outside [cursor, cursor + cnt), which
        may hold uninitialized memory or lie in a window whose flush is
        still flying."""
        r = cursor & 7
        tile = pl.multiple_of(cursor - r, 8)
        region = (iota_win >= r) & (iota_win < r + cnt)
        pltpu.store(acc.at[pl.ds(tile, WIN)], rows,
                    mask=jnp.broadcast_to(region, rows.shape))

        @pl.when(cursor + cnt > RS)
        def _wrap():
            over = pl.multiple_of(RS - tile, 8)
            pltpu.store(acc.at[pl.ds(0, WIN)], spill(over),
                        mask=jnp.broadcast_to(iota_win + over < r + cnt,
                                              rows.shape))

    def fills(cursor, cnt):
        """1 where `cnt` more rows fill the cursor's window."""
        return (lax.rem(cursor, CHUNK) + cnt >= CHUNK).astype(jnp.int32)

    def moved(cursor, cnt):
        """The cursor `cnt` rows on, round the ring."""
        return jnp.where(cursor + cnt >= RS, cursor + cnt - RS, cursor + cnt)

    def landed(acc, dst_ref, sem, f):
        """Wait for flush `f` of an accumulator (the descriptor's
        addresses only size the semaphore wait)."""
        pltpu.make_async_copy(acc.at[pl.ds(0, CHUNK)], window(dst_ref, 0),
                              sem.at[lax.rem(f, NW)]).wait()

    def reserve(acc, dst_ref, sem, f, fl):
        """Before the put that fills the window of flush `f` (`fl` says
        it does): that put may run on into the window behind, whose last
        flush was number f + 1 - NW and may still fly.  It is the ONE
        wait a flush costs; every other store of the kernel stays inside
        windows no flush is reading."""
        @pl.when((fl > 0) & (f >= NW - 1))
        def _():
            landed(acc, dst_ref, sem, f + 1 - NW)

    def flush(acc, dst_ref, sem, f):
        """Flush number `f` of an accumulator: its window f % NW, full,
        to the f-th window of the segment's aligned stream, by ONE DMA
        out of the ring itself: no copy to a stage, no slide (PR 38).
        Not waited here: it flies while the cursor fills the next
        window, and `reserve` waits for it NW - 1 flushes on."""
        h = lax.rem(f, NW)
        pltpu.make_async_copy(
            acc.at[pl.ds(pl.multiple_of(h * CHUNK, CHUNK), CHUNK)],
            window(dst_ref, pl.multiple_of(base + f * CHUNK, 8)),
            sem.at[h]).start()

    def drain(acc, dst_ref, sem, flushes):
        """Wait for what `reserve` has not: the last NW - 1 of an
        accumulator's `flushes`, before their HBM rows are read or the
        kernel exits."""
        for j in range(1, NW):
            @pl.when(flushes >= j)
            def _(j=j):
                landed(acc, dst_ref, sem, flushes - j)

    # the ring holds its depth in GROUPS of chunks for pass A, in chunks
    # for pass B
    G = group
    R = _RING_DEPTH

    @pl.when(nch > 0)
    def _prefetch_first():
        # fill the ring: R-1 groups in flight before the loop starts
        for i in range((R - 1) * G):
            @pl.when(i < nch)
            def _start(i=i):
                for dma in read_a(i, i):
                    dma.start()

    # ---- pass A: one read of the segment; lefts accumulate toward payload
    # windows, rights accumulate toward aux staging windows -------------
    def above(x):
        """[8, C]: under each chunk of a trip, the sum of `x`'s rows of
        the trip's earlier chunks (a sublane broadcast each; none at
        G = 1)."""
        out = jnp.zeros_like(x)
        for g in range(G - 1):
            out = out + jnp.where(chunk_of_row > g, x[g:g + 1, :], 0)
        return out

    def routed(k0, windows, lo_, ro_):
        """(gl, dest) of the trip's chunks k0 .. k0 + G - 1, [8, C] i32
        with a chunk a row and the chunk's rows in lanes: the routing
        under the validity mask, and where the chunk's ONE stable
        partition puts each row of its block: the lefts from row
        r_l = (the left cursor at that chunk) & 7, the rights from row
        r_r = (the right cursor) & 7 of the first tile behind them, both
        in original order; a row outside the segment to -1, which is no
        row.  The cursors at a chunk are the carried ones (`lo_`, `ro_`)
        plus the counts of the trip's earlier chunks; the ring's length
        is 0 modulo 8, so a cursor that wraps keeps its part.  `windows`
        hold the split column: [C, 128] of the chunk, or of a column
        block's snapshot."""
        raw = None
        for g, window in enumerate(windows):
            column = lax.dot_general(
                route_sel, jnp.where(lane_of_window == route_col, window, 0.0),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=None if B <= 256 else lax.Precision.HIGHEST)
            raw = column if raw is None \
                else jnp.where(chunk_of_row == g, column, raw)   # [8, C]
        first = shift - (k0 + chunk_of_row) * CHUNK
        valid = ((row_of_lane >= first) &
                 (row_of_lane < first + count)).astype(jnp.int32)
        # (routing is integer arithmetic under the validity mask: what an
        # unread split window holds cannot reach a row)
        gl = (_go_left_lanes(scalars, raw.astype(jnp.int32), B)
              ^ right_first) * valid
        rank_l = jnp.dot(gl.astype(jnp.float32), tri_t,
                         preferred_element_type=jnp.float32
                         ).astype(jnp.int32)
        # the chunk's valid rows are one contiguous range, so the valid
        # rows before row i are iota arithmetic and the rights among them
        # are those that are not lefts: no second prefix count
        rank_r = jnp.maximum(row_of_lane - jnp.maximum(first, 0), 0) - rank_l
        nl = jnp.sum(gl, axis=1, keepdims=True)
        # the trip's earlier chunks: their lefts by a second lane sum,
        # their rows from where the segment lies in the stream
        nl_above = jnp.sum(above(gl), axis=1, keepdims=True)
        span = chunk_of_row * CHUNK
        n_above = (jnp.clip(shift + count - k0 * CHUNK, 0, span) -
                   jnp.clip(shift - k0 * CHUNK, 0, span))
        r_l = (lo_ + nl_above) & 7
        r_r = (ro_ + n_above - nl_above) & 7
        dest = jnp.where(gl > 0, r_l + rank_l,
                         ((r_l + nl + 7) & -8) + r_r + rank_r)
        return gl, jnp.where(valid > 0, dest, -1)

    def permuted(g, k, data, gl, dest, lo_):
        """(nlk, nrk) of chunk k, row g of the trip's index vectors, and
        its [C + 24, P] block into its scratch: source row j at row
        dest[g, j] (-1: nowhere) by one 0/1 one-hot, the lane-major
        destination broadcast along sublanes, applied to the exact parts
        (three one-pass matmuls); the value column takes the first
        child's output in the rows under the first side's end and the
        staged child's from there on.  This is what of a chunk's
        placement reads no accumulator, so that the chunks of a trip are
        independent up to here.  `lo_` is the left cursor at this chunk
        up to flushes."""
        nlk = jnp.sum(jnp.where(chunk_of_row == g, gl, 0))
        j0 = jnp.maximum(shift - k * CHUNK, 0)
        j1 = jnp.minimum(shift + count - k * CHUNK, CHUNK)
        mat = (iota_bi == dest[g:g + 1, :]).astype(jnp.float32)
        hi, mid, lo = _bf16_parts(data)
        perm = (jnp.dot(mat, hi, preferred_element_type=jnp.float32) +
                jnp.dot(mat, mid, preferred_element_type=jnp.float32) +
                jnp.dot(mat, lo, preferred_element_type=jnp.float32))
        if value_col >= 0:
            perm = jnp.where(
                iota_p == value_col,
                jnp.where(iota_b < (lo_ & 7) + nlk, left_value, right_value),
                perm)
        blk[g, 0:BLOCK_ROWS] = perm
        return nlk, jnp.maximum(j1 - j0, 0) - nlk

    def place(g, nlk, nrk, carry):
        nl, nr, lo_, ro_, lfl, rfl = carry
        # each side is a tile-aligned window of the block, put at the
        # tile of its cursor: the first side's the block's head, the
        # staged side's from the first tile behind the first side's rows
        tile_r = pl.multiple_of(((lo_ & 7) + nlk + 7) & -8, 8)
        fl, fr = fills(lo_, nlk), fills(ro_, nrk)
        reserve(lacc, payload_out, sem_w, lfl, fl)
        put(lacc, lo_, nlk, blk[g, 0:WIN],
            lambda over: blk[g, pl.ds(over, WIN)])

        @pl.when(fl > 0)
        def _flush_l():
            flush(lacc, payload_out, sem_w, lfl)

        reserve(racc, aux_out, sem_r, rfl, fr)
        put(racc, ro_, nrk, blk[g, pl.ds(tile_r, WIN)],
            lambda over: blk[g, pl.ds(pl.multiple_of(tile_r + over, 8), WIN)])

        @pl.when(fr > 0)
        def _flush_r():
            flush(racc, aux_out, sem_r, rfl)

        return (nl + nlk, nr + nrk, moved(lo_, nlk), moved(ro_, nrk),
                lfl + fl, rfl + fr)

    def body_a(t, carry):
        k0 = t * G
        slots = [lax.rem(k0 + i, R * G) for i in range(G)]
        for k in [k0 + (R - 1) * G + i for i in range(G)]:
            @pl.when(k < nch)
            def _prefetch_next(k=k):
                for dma in read_a(k, lax.rem(k, R * G)):
                    dma.start()

        # every wait and load before any chunk's arithmetic: a DMA wait
        # is a barrier the scheduler moves nothing across
        for dma in read_a(k0, slots[0]):
            dma.wait()
        for i in range(1, G):
            @pl.when(k0 + i < nch)
            def _wait(i=i):
                for dma in read_a(k0 + i, slots[i]):
                    dma.wait()

        # a chunk past the segment's last was not read: its slot holds an
        # older chunk or nothing yet, and 0 x NaN would poison the matmuls
        datas = [ring[slots[0]]] + [
            jnp.where(k0 + i < nch, ring[slots[i]], 0.0)
            for i in range(1, G)]
        @pl.when(t == 0)
        def _seed():
            # the first window's prologue rows (under `shift`, so in its
            # first tile) belong to the previous leaf; seeding them from
            # chunk 0 makes every later flush a plain full-window write
            lacc[0:8] = ring[slots[0], 0:8]

        lo_, ro_ = carry[2], carry[3]
        gl, dest = routed(k0, [split_window(slot, data)
                               for slot, data in zip(slots, datas)],
                          lo_, ro_)
        counts = []
        for i in range(G):
            counts.append(permuted(i, k0 + i, datas[i], gl, dest, lo_))
            lo_ = lo_ + counts[i][0]
        for i in range(G):
            carry = place(i, *counts[i], carry)
        return carry

    num_left, num_right, lo_, ro_, lfl, rfl = lax.fori_loop(
        0, (nch + G - 1) // G, body_a,
        (jnp.int32(0), jnp.int32(0), shift, shift,
         jnp.int32(0), jnp.int32(0)))
    nl_out[0] = num_left

    # rights not yet flushed go out as one final aux window, from where
    # they lie in the ring (junk tails in the scratch buffer are
    # harmless), a flush like any other: `reserve` keeps the count of
    # flushes in flight, which `drain` relies on (the chip halts a kernel
    # that exits with a semaphore not at zero); pass B reads aux, so
    # drain the right-flush pipeline before it starts
    tail = (lax.rem(ro_, CHUNK) > 0).astype(jnp.int32)

    reserve(racc, aux_out, sem_r, rfl, tail)

    @pl.when(tail > 0)
    def _flush_r_tail():
        flush(racc, aux_out, sem_r, rfl)

    drain(racc, aux_out, sem_r, rfl + tail)

    # ---- pass B: append the staged rights behind the lefts, continuing
    # in the SAME left accumulator (rights start exactly at the left
    # cursor — the handoff needs no flush, no read, no shift) -----------
    nchb = jnp.where(num_right > 0,
                     (shift + num_right + CHUNK - 1) // CHUNK, 0)

    @pl.when(nchb > 0)
    def _prefetch_b():
        for i in range(R - 1):
            @pl.when(i < nchb)
            def _start(i=i):
                ring_dma(aux_out, i, i).start()

    def body_b(k, carry):
        lo_, lfl = carry
        slot = lax.rem(k, R)

        @pl.when(k + R - 1 < nchb)
        def _prefetch_next():
            ring_dma(aux_out, k + R - 1, lax.rem(k + R - 1, R)).start()

        ring_dma(aux_out, k, slot).wait()
        j0 = jnp.maximum(shift - k * CHUNK, 0)
        j1 = jnp.minimum(shift + num_right - k * CHUNK, CHUNK)
        cnt = jnp.maximum(j1 - j0, 0)
        member = ((iota_rows >= j0) & (iota_rows < j1)).astype(jnp.int32)
        # non-member rows of the staged window can be uninitialized aux
        # memory; zero them BEFORE placement
        data = jnp.where(member[:, None] > 0, ring[slot], 0.0)
        # staged rights are already contiguous: placement is a pure rotate
        # of the doubled window that brings row j0 to the cursor's part
        # under 8, then pass A's aligned masked store of the [C + 8, P]
        # head: no decomposition, no matmul, no read of the accumulator
        placed = pltpu.roll(jnp.concatenate([data, data], axis=0),
                            (lo_ & 7) - j0 + C2, axis=0)
        rows = jnp.where(iota_p == value_col, right_value, placed[0:WIN])
        fl = fills(lo_, cnt)
        reserve(lacc, payload_out, sem_w, lfl, fl)

        def spill(over):
            # a value has no dynamic slice: through the block's scratch,
            # once a turn of the ring
            blk[0, 0:WIN] = rows
            return blk[0, pl.ds(over, WIN)]

        put(lacc, lo_, cnt, rows, spill)

        @pl.when(fl > 0)
        def _flush_l():
            flush(lacc, payload_out, sem_w, lfl)

        return moved(lo_, cnt), lfl + fl

    lo_, lfl = lax.fori_loop(0, nchb, body_b, (lo_, lfl))

    # the final RMW below rewrites HBM rows behind the last flush and the
    # kernel must not exit with a flying DMA — drain the left-flush
    # pipeline
    drain(lacc, payload_out, sem_w, lfl)

    # ---- final window: its tail crosses into the next leaf's rows — the
    # one place the kernel pays a blend read, into a slot of the read ring
    # (every read of either pass has been waited for) ---------------------
    off = lax.rem(lo_, CHUNK)

    @pl.when((count > 0) & (off > 0))
    def _final():
        wbase = pl.multiple_of(base + lfl * CHUNK, 8)
        dma_r = pltpu.make_async_copy(
            window(payload_out, wbase), ring.at[0], sem_ring.at[0])
        dma_r.start()
        dma_r.wait()
        region = (iota_rows < off)[:, None]
        ring[0] = jnp.where(
            region, lacc[pl.ds(pl.multiple_of(lo_ - off, CHUNK), CHUNK)],
            ring[0])
        dma_w = pltpu.make_async_copy(
            ring.at[0], window(payload_out, wbase), sem_ring.at[0])
        dma_w.start()
        dma_w.wait()


@functools.partial(xla_obs.jit, site="pallas.partition_segment_acc",
                   static_argnames=("value_col", "num_bins", "interpret"))
def _partition_segment_acc(payload, aux, start, count, pred, left_value,
                           right_value, value_col, num_bins,
                           right_first=False, interpret=False):
    """Same contract as `partition_segment`, accumulator-window kernel."""
    P = payload.shape[1]
    B = num_bins
    scalars = _acc_scalars(start, count, pred, pred.col, 0, B, right_first)
    fvals = jnp.stack(first_second(
        right_first, left_value, right_value)).astype(jnp.float32)
    group = _pass_a_group(P, B)
    kern = functools.partial(_acc_kernel, P=P, B=B, value_col=value_col,
                             group=group)
    payload_new, aux_new, nl = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            scratch_shapes=[
                pltpu.VMEM((_RING_DEPTH * group, CHUNK, P),
                           jnp.float32),                  # read ring
                pltpu.VMEM((ACC_ROWS, P), jnp.float32),   # left accumulator
                pltpu.VMEM((ACC_ROWS, P), jnp.float32),   # right accumulator
                pltpu.VMEM((group, BLOCK_SCRATCH_ROWS, P),
                           jnp.float32),                  # permuted blocks
                pltpu.SemaphoreType.DMA((_RING_DEPTH * group,)),
                pltpu.SemaphoreType.DMA((_ACC_WINDOWS,)),  # left flushes
                pltpu.SemaphoreType.DMA((_ACC_WINDOWS,)),  # right flushes
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct(payload.shape, payload.dtype),
                   jax.ShapeDtypeStruct(aux.shape, aux.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        input_output_aliases={2: 0, 3: 1},
        compiler_params=_SIDE_EFFECTS,
        interpret=interpret,
    )(scalars, fvals, payload, aux)
    return payload_new, aux_new, jnp.where(right_first, count - nl[0], nl[0])


partition_segment_acc = _partition_segment_acc


# ---------------------------------------------------------------------------
# partition, column-block variant (ultra-wide payloads)
# ---------------------------------------------------------------------------

def partition_blocks_fits_vmem(payload_width: int, num_bins: int) -> bool:
    """VMEM plan of ONE column-block partition pass: the accumulator
    kernel's plan at the block width (pass A one chunk a trip) plus the
    split-window ring (128 lanes a slot)."""
    return (_acc_plan_bytes(min(_BLOCK_WIDTH, payload_width), num_bins, 1)
            + _route_ring_bytes(1)) <= _VMEM_BUDGET


def _route_ring_bytes(group: int) -> int:
    """The split-window ring of a column-block pass: one [C, 128] slot
    beside each slot of the read ring."""
    return _RING_DEPTH * group * 4 * 128 * CHUNK


def _snap_window_kernel(scalars, payload_hbm, snap_out, buf, sem):
    """Copy the split column's 128-lane window for the segment's chunk
    span into a side buffer, BEFORE any block pass rewrites those lanes —
    all routing reads then come from this frozen snapshot, so every pass
    computes the identical permutation no matter which block owns the
    split column.  This is also the ONE kernel with a traced (but
    128-aligned) lane base; the block passes read the snapshot at lane 0."""
    start = scalars[0]
    count = scalars[1]
    win_lo = scalars[11]
    shift = lax.rem(start, 8)
    base = start - shift
    nch = jnp.where(count > 0, (shift + count + CHUNK - 1) // CHUNK, 0)

    def body(k, _):
        rows = pl.ds(pl.multiple_of(base + k * CHUNK, 8), CHUNK)
        d_in = pltpu.make_async_copy(
            payload_hbm.at[rows, pl.ds(pl.multiple_of(win_lo, 128), 128)],
            buf, sem)
        d_in.start()
        d_in.wait()
        d_out = pltpu.make_async_copy(buf, snap_out.at[rows, :], sem)
        d_out.start()
        d_out.wait()
        return 0

    lax.fori_loop(0, nch, body, 0)


@functools.partial(xla_obs.jit, site="pallas.partition_segment_acc_blocks",
                   static_argnames=("value_col", "num_bins", "interpret",
                                    "block_w"))
def _partition_segment_acc_blocks(payload, aux, start, count, pred,
                                  left_value, right_value, value_col,
                                  num_bins, right_first=False,
                                  interpret=False, block_w=_BLOCK_WIDTH):
    """Same contract as `partition_segment`, applied block-by-block over
    the payload's lane windows (ultra-wide payloads)."""
    P = payload.shape[1]
    if P % 128 != 0:
        raise ValueError("column-block partition requires a lane-padded "
                         "payload (P %% 128 == 0), got %d" % P)
    B = num_bins
    win_lo = (pred.col // 128) * 128
    scalars = _acc_scalars(start, count, pred, pred.col - win_lo, win_lo, B,
                           right_first)
    fvals = jnp.stack(first_second(
        right_first, left_value, right_value)).astype(jnp.float32)
    # freeze the split column's window before any pass rewrites its lanes
    snap = pl.pallas_call(
        _snap_window_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((CHUNK, 128), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((payload.shape[0], 128),
                                       jnp.float32),
        compiler_params=_SIDE_EFFECTS,
        interpret=interpret,
    )(scalars, payload)
    # one pass a lane window, each an instance of the accumulator kernel
    # that moves its own lanes and routes from the snapshot
    nl = None
    for c in range(0, P, block_w):
        bw = min(block_w, P - c)
        vloc = value_col - c if c <= value_col < c + bw else -1
        group = _pass_a_group(bw, B, _route_ring_bytes(_PASS_A_GROUP))
        slots = _RING_DEPTH * group
        kern = functools.partial(_acc_kernel, P=bw, B=B, value_col=vloc,
                                 group=group, lane_lo=c)
        payload, aux, nl_k = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                           pl.BlockSpec(memory_space=pl.ANY),
                           pl.BlockSpec(memory_space=pltpu.SMEM)),
                scratch_shapes=[
                    pltpu.VMEM((slots, CHUNK, bw), jnp.float32),  # read ring
                    pltpu.VMEM((ACC_ROWS, bw), jnp.float32),  # left acc.
                    pltpu.VMEM((ACC_ROWS, bw), jnp.float32),  # right acc.
                    pltpu.VMEM((group, BLOCK_SCRATCH_ROWS, bw),
                               jnp.float32),              # permuted blocks
                    pltpu.SemaphoreType.DMA((slots,)),
                    pltpu.SemaphoreType.DMA((_ACC_WINDOWS,)),
                    pltpu.SemaphoreType.DMA((_ACC_WINDOWS,)),
                    pltpu.VMEM((slots, CHUNK, 128),
                               jnp.float32),              # split-window ring
                    pltpu.SemaphoreType.DMA((slots,)),
                ],
            ),
            out_shape=(jax.ShapeDtypeStruct(payload.shape, payload.dtype),
                       jax.ShapeDtypeStruct(aux.shape, aux.dtype),
                       jax.ShapeDtypeStruct((1,), jnp.int32)),
            input_output_aliases={2: 0, 3: 1},
            compiler_params=_SIDE_EFFECTS,
            interpret=interpret,
        )(scalars, fvals, payload, aux, snap)
        nl = nl_k if nl is None else nl
    return payload, aux, jnp.where(right_first, count - nl[0], nl[0])


partition_segment_acc_blocks = _partition_segment_acc_blocks
