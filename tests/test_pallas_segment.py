"""Pallas segment kernels must match the portable lax implementations.

Runs in Pallas interpreter mode so the kernels are validated on the CPU test
mesh; the driver's TPU bench exercises the compiled path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import pallas_segment as pseg
from lightgbm_tpu.ops.segment import SplitPredicate

F, B = 5, 16
COLS = dict(grad_col=F, hess_col=F + 1, cnt_col=F + 2)
VALUE_COL = F + 3
P = F + 4


def _payload(n_pad, seed=0):
    rng = np.random.default_rng(seed)
    pay = np.zeros((n_pad + seg.GUARD, P), np.float32)
    pay[:n_pad, :F] = rng.integers(0, B, size=(n_pad, F))
    pay[:n_pad, F] = rng.standard_normal(n_pad)
    pay[:n_pad, F + 1] = rng.random(n_pad)
    pay[:n_pad, F + 2] = 1.0
    return jnp.asarray(pay)


def _lanes(pay, lanes):
    """The payload as it is (`ragged`: the interpreter alone sees a width
    that is no multiple of 128) or lane-padded as the fast path's is on
    the chip (`padded`)."""
    if lanes == "ragged":
        return pay
    return jnp.pad(pay, ((0, 0), (0, -pay.shape[1] % 128)))


@pytest.mark.parametrize("start,count", [(0, 1000), (256, 700), (100, 37),
                                         (0, 0), (513, 256), (7, 1),
                                         (9, 1015), (1023, 1)])
@pytest.mark.parametrize("lanes", ["ragged", "padded"])
def test_histogram_matches(start, count, lanes):
    pay = _lanes(_payload(1024), lanes)
    ref = seg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                num_features=F, num_bins=B, **COLS)
    got = pseg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                 num_features=F, num_bins=B, interpret=True,
                                 **COLS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _hist_payload(f, b, n_pad=640, width=None, seed=None, bins=None,
                  grads=None):
    """[n_pad + GUARD, width] payload of f bin columns at b bins, then
    gradient, hessian and count; `bins` / `grads` replace the random
    ones."""
    rng = np.random.default_rng(f + b if seed is None else seed)
    pay = np.zeros((n_pad + seg.GUARD, width or f + 4), np.float32)
    pay[:n_pad, :f] = (rng.integers(0, b, size=(n_pad, f))
                       if bins is None else bins)
    pay[:n_pad, f] = rng.standard_normal(n_pad) if grads is None else grads
    pay[:n_pad, f + 1] = rng.random(n_pad)
    pay[:n_pad, f + 2] = 1.0
    return jnp.asarray(pay), dict(grad_col=f, hess_col=f + 1, cnt_col=f + 2)


@pytest.mark.parametrize("f,b,start,count", [
    (137, 256, 0, 300),    # MS-LTR shape: two column tiles, a loop of two trips
    (70, 64, 100, 351),    # groups of 4, ragged last group
    (700, 256, 256, 260),  # Expo/Yahoo shape: six column tiles
    (968, 64, 0, 300),     # Bosch shape at the GPU-recommended max_bin=63
])
@pytest.mark.parametrize("lanes", ["ragged", "padded"])
def test_histogram_matches_tiled(f, b, start, count, lanes):
    """Column-tiled kernel vs portable engine at wide-feature shapes the
    old F*B <= 8192 gate excluded (reference handles these through the
    OpenCL workgroup grid, ocl/histogram256.cl:73-121)."""
    if seg.CHUNK == 256:   # gate expectations assume the default chunk
        assert pseg.fits_vmem(f, b), "gate must admit this shape now"
    pay, cols = _hist_payload(f, b)
    pay = _lanes(pay, lanes)
    ref = seg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                num_features=f, num_bins=b, **cols)
    got = pseg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                 num_features=f, num_bins=b, interpret=True,
                                 **cols)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("f,b,width,start,count", [
    (28, 255, 128, 3, 600),      # higgs-train: L 64, H 4, two features a group
    (67, 256, 128, 256, 260),    # criteo-dp4-train: 34 groups, the last ragged
    (2000, 64, 2048, 7, 300),    # epsilon-train: 16 column tiles, L 32, H 2
    (7, 255, 128, 100, 351),     # a feature-parallel shard's leading columns
    (30, 100, 128, 9, 500),      # top high block ragged: 100 = 3 * 32 + 4
    (19, 37, 128, 0, 640),       # 37 = 32 + 5: the top block holds 5 bins
    (33, 255, 128, 100, 37),     # 255 = 3 * 64 + 63
    (5, 3, 128, 0, 300),         # fewer bins than a sublane tile
    (126, 256, 256, 513, 1),     # one row; the value columns in two blocks
    # epsilon-train's shape over whole, shifted, one-row and empty segments
    (2000, 64, 2048, 0, 1000), (2000, 64, 2048, 256, 700),
    (2000, 64, 2048, 100, 37), (2000, 64, 2048, 0, 0),
    (2000, 64, 2048, 7, 1), (2000, 64, 2048, 9, 1015),
])
def test_histogram_factored_shapes(f, b, width, start, count):
    """The factored bin id (bin = hi * L + lo) at the cells' shapes and
    payload widths, at bin counts that are no multiple of L (the top high
    block is padded) and at feature counts that leave the last group and
    the last loop trip ragged: equal to the portable engine, the counts to
    the last digit."""
    L, H, G = pseg._hist_factor(b)
    assert H * L >= b and G * L == 128
    pay, cols = _hist_payload(f, b, max(640, start + count + 9), width=width)
    ref = seg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                num_features=f, num_bins=b, **cols)
    got = pseg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                                 num_features=f, num_bins=b, interpret=True,
                                 **cols)
    assert got.shape == (f, b, 3)
    np.testing.assert_array_equal(np.asarray(got[..., 2]),
                                  np.asarray(ref[..., 2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", [256, 255, 100, 64, 37])
@pytest.mark.parametrize("which", ["first", "last"])
def test_histogram_one_bin(b, which):
    """Every row in bin 0, or in bin b - 1 (the last low part of the top
    high block): the whole segment lands in that bin of every feature."""
    f, n = 9, 600
    bins = np.full((640, f), 0 if which == "first" else b - 1)
    pay, cols = _hist_payload(f, b, width=128, bins=bins)
    got = np.asarray(pseg.segment_histogram(
        pay, jnp.int32(5), jnp.int32(n), num_features=f, num_bins=b,
        interpret=True, **cols))
    at = 0 if which == "first" else b - 1
    np.testing.assert_array_equal(got[:, at, 2], np.full(f, n))
    assert np.count_nonzero(np.delete(got, at, axis=1)) == 0
    exact = np.asarray(pay, np.float64)[5:5 + n, f:f + 2].sum(0)
    np.testing.assert_allclose(got[0, at, :2], exact, rtol=2e-6)


@pytest.mark.parametrize("f,b", [(28, 256), (70, 64)])
def test_histogram_keeps_gradient_bits(f, b):
    """No precision is given up: gradients whose low mantissa bits matter
    (1 + k * 2^-20: bf16 rounds every one to 1.0) sum to the float64 sum
    within f32 accumulation error.  A histogram that summed bf16-rounded
    gradients would be off by up to n * 2^-9 a bin."""
    n = 640
    rng = np.random.default_rng(b)
    grads = (1.0 + rng.integers(1, 2 ** 11, n) * 2.0 ** -20).astype(
        np.float32)
    bins = rng.integers(0, 4, size=(n, f)) * (b // 4)
    pay, cols = _hist_payload(f, b, width=128, bins=bins, grads=grads)
    got = np.asarray(pseg.segment_histogram(
        pay, jnp.int32(0), jnp.int32(n), num_features=f, num_bins=b,
        interpret=True, **cols))
    exact = np.zeros((f, b))
    for col in range(f):
        np.add.at(exact[col], bins[:, col], grads.astype(np.float64))
    # ~160 rows a bin: the sum is near 160, its f32 ulp 1.5e-5; a bf16
    # gradient would lose 160 * 2^-10 = 0.16
    np.testing.assert_allclose(got[..., 0], exact, rtol=0, atol=1e-4)
    rounded = np.asarray(jnp.asarray(grads).astype(jnp.bfloat16), np.float64)
    assert abs(rounded.sum() - grads.astype(np.float64).sum()) > 0.1


def test_partition_vmem_gate():
    """The read-modify-write kernel has no feature tiling: its plan holds
    a Bosch-wide payload (1,024 lanes) and not an Epsilon-wide one
    (2,048).  Both take the column-block kernel all the same
    (`test_partition_engine_by_shape`): at 1,024 lanes it won the race on
    the chip by 7.3 times (PERF.md section 6, PR 37), and the histogram
    stays on its own Pallas kernel at either width."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    assert pseg.partition_fits_vmem(128, 256)   # Higgs-shaped payload
    assert pseg.partition_fits_vmem(1024, 64)   # Bosch-shaped payload
    assert not pseg.partition_fits_vmem(2048, 64)  # Epsilon-shaped payload


def test_vmem_gate_admits_benchmark_shapes():
    """Every BASELINE.md dense workload shape must ride the TPU kernel;
    only the extreme wide-sparse shapes (pre-EFB Allstate) may fall back."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    assert pseg.fits_vmem(28, 255)    # Higgs
    assert pseg.fits_vmem(137, 256)   # MS-LTR
    assert pseg.fits_vmem(700, 256)   # Expo / Yahoo LTR
    assert pseg.fits_vmem(968, 64)    # Bosch at GPU max_bin=63
    assert pseg.fits_vmem(2000, 64)   # Epsilon at GPU max_bin=63
    assert not pseg.fits_vmem(4228, 256)  # raw Allstate: portable path
    # the three train cells, at the widths their payloads have on the chip
    assert pseg.fits_vmem(28, 255, 128)      # higgs-train
    assert pseg.fits_vmem(67, 256, 128)      # criteo-dp4-train, a shard
    assert pseg.fits_vmem(2000, 64, 2048)    # epsilon-train
    # the plan counts the kernel's real buffers: the accumulator is
    # 8 * F * H * L * 4 bytes however the bin id is factored
    L, H, G = pseg._hist_factor(64)
    assert (L, H, G) == (32, 2, 4) and pseg._hist_factor(256) == (64, 4, 2)
    assert pseg._hist_groups(2000, 64) * 8 * H * 128 == 8 * 2000 * 64


#: the plans `grower2.partition_engine` chooses between, by kernel and
#: payload lanes: the read-modify-write kernel; the accumulator kernel with
#: pass A two chunks a trip (128, 256 lanes) and one (384); the
#: column-block kernel over two 512-lane blocks (the Bosch cell's 1,024
#: lanes) and at the edges of the band it took from the read-modify-write
#: kernel: 640 lanes (a block of 512 and one of 128) and 1,664 (three and
#: one of 128)
PLANS = [("partition_segment", 128), ("partition_segment_acc", 128),
         ("partition_segment_acc", 256), ("partition_segment_acc", 384),
         ("partition_segment_acc_blocks", 1024),
         ("partition_segment_acc_blocks", 640),
         ("partition_segment_acc_blocks", 1664)]


def _widened(pay, width):
    return jnp.pad(pay, ((0, 0), (0, width - pay.shape[1])))


def _pred(feature=1, threshold=None, default_left=False, is_cat=False,
          bitset=None, missing_type=0, num_bin=None, default_bin=0,
          offset=0, identity=True, bins=B):
    return SplitPredicate(
        col=jnp.int32(feature),
        threshold=jnp.int32(bins // 2 if threshold is None else threshold),
        default_left=jnp.bool_(default_left), is_cat=jnp.bool_(is_cat),
        bitset=jnp.asarray(bitset if bitset is not None else
                           np.zeros(bins, bool)),
        missing_type=jnp.int32(missing_type),
        num_bin=jnp.int32(bins if num_bin is None else num_bin),
        default_bin=jnp.int32(default_bin), offset=jnp.int32(offset),
        identity=jnp.bool_(identity))


#: the portable partition under jit: called bare it dispatches operation
#: by operation, 0.4 s a call where the interpreted kernel takes 0.01
_portable = jax.jit(seg.partition_segment, static_argnums=(7,))


def _check_exact(kernel, pay, start, count, pred, value_col, bins,
                 right_first=False):
    """Payload, the second child's rows staged in aux and num_left of a
    Pallas partition, bit for bit against the portable one."""
    aux = jnp.zeros_like(pay)
    lv, rv = jnp.float32(1.5), jnp.float32(-2.5)
    ref_pay, _, ref_nl = _portable(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        value_col, jnp.bool_(right_first))
    nl = int(ref_nl)
    n_first = count - nl if right_first else nl
    got_pay, got_aux, got_nl = getattr(pseg, kernel)(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        value_col, bins, jnp.bool_(right_first), interpret=True)
    assert int(got_nl) == nl
    np.testing.assert_array_equal(np.asarray(got_pay), np.asarray(ref_pay))
    np.testing.assert_array_equal(
        np.asarray(got_aux)[start:start + count - n_first],
        np.asarray(ref_pay)[start + n_first:start + count])
    return nl


@pytest.mark.parametrize("start,count,predkw", [
    (0, 1000, {}),
    (256, 700, dict(feature=3, threshold=4)),
    (100, 37, dict(missing_type=2, default_left=True, threshold=3)),
    (0, 600, dict(is_cat=True,
                  bitset=(np.arange(B) % 3 == 0))),
    (513, 256, dict(feature=0, threshold=0)),
    (7, 1, {}),
    (9, 1015, dict(feature=2, threshold=B // 3)),
    (255, 513, dict(feature=4, threshold=1)),
    # count < CHUNK with shift + count crossing a chunk edge
    (100, 254, dict(feature=3, threshold=7)),
    # a segment that ends exactly on a chunk edge, from a shifted start
    (7, 505, dict(feature=0, threshold=5)),
    (3, 1000, dict(feature=4, threshold=11)),
    # EFB bundle decode: storage col 2 holds an offset-encoded member
    (64, 500, dict(feature=2, threshold=3, offset=5, identity=False,
                   num_bin=9, default_bin=0)),
])
@pytest.mark.parametrize("kernel,width", PLANS)
def test_partition_matches(start, count, predkw, kernel, width):
    """Every plan `partition_engine` can choose, under every predicate."""
    pay = _widened(_payload(1024, seed=start + count), width)
    impl = getattr(pseg, kernel)
    aux = jnp.zeros_like(pay)
    pred = _pred(**predkw)
    lv, rv = jnp.float32(-0.25), jnp.float32(0.75)

    ref_pay, _, ref_nl = _portable(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv, VALUE_COL)
    got_pay, _, got_nl = impl(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        VALUE_COL, B, interpret=True)

    assert int(got_nl) == int(ref_nl)
    np.testing.assert_allclose(np.asarray(got_pay), np.asarray(ref_pay),
                               rtol=1e-6, atol=0)


def _routed_payload(skew, start, count):
    """The split column (feature 1, threshold B // 2) set so that whole
    chunks of the kernel's aligned read stream route one way: the cases in
    which one side of the chunk's single permutation is empty."""
    pay = np.array(_payload(1024, seed=count))
    base = start - start % 8
    chunk_of = (np.arange(pay.shape[0]) - base) // seg.CHUNK
    left, right = 0.0, float(B - 1)
    if skew == "all_left":
        pay[:, 1] = left
    elif skew == "all_right":
        pay[:, 1] = right
    elif skew == "left_chunk":       # chunk 1 all left, its neighbours mixed
        pay[chunk_of == 1, 1] = left
    elif skew == "right_chunk":
        pay[chunk_of == 1, 1] = right
    elif skew == "no_left_first":    # nl_k = 0 in the (shifted) first chunk
        pay[chunk_of == 0, 1] = right
    elif skew == "no_right_first":
        pay[chunk_of == 0, 1] = left
    return jnp.asarray(pay)


@pytest.mark.parametrize("start,count", [(0, 1024), (7, 777), (100, 1),
                                         (256, 512), (513, 511),
                                         (100, 254), (7, 505), (3, 1000)])
@pytest.mark.parametrize("skew", ["all_left", "all_right", "left_chunk",
                                  "right_chunk", "no_left_first",
                                  "no_right_first"])
@pytest.mark.parametrize("kernel,width", [PLANS[1], PLANS[4]])
def test_partition_acc_skewed(start, count, skew, kernel, width):
    """One-sided chunks exercise the accumulator kernel's empty-side and
    rare-flush paths, and the ends of the one permutation that places a
    chunk (lefts to [0, nl_k), rights behind them): all rows of the
    segment, of a middle chunk or of the first, shifted chunk route one
    way, in one pass (`higgs-train`'s plan) and a column block at a time
    (`epsilon-train`'s).  Payload, the rights staged in aux and num_left,
    bit for bit."""
    pay = _widened(_routed_payload(skew, start, count), width)
    _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B)


def _whiles(jaxpr):
    """The body jaxpr of each `while` of a jaxpr, in program order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append(eqn.params["body_jaxpr"].jaxpr)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.extend(_whiles(sub))
    return out


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _while_count(jaxpr, primitive):
    """Equations of one primitive inside each `while` of a jaxpr, in
    program order."""
    return [sum(eqn.primitive.name == primitive for eqn in _eqns(body))
            for body in _whiles(jaxpr)]


def _while_dots(jaxpr):
    return _while_count(jaxpr, "dot_general")


def _while_rotates(jaxpr):
    """`pltpu.roll`s inside each `while`."""
    return _while_count(jaxpr, "roll")


def _while_shapes(jaxpr):
    """The shapes of every value computed inside each `while`."""
    return [{tuple(v.aval.shape) for eqn in _eqns(body) for v in eqn.outvars
             if hasattr(v.aval, "shape")} for body in _whiles(jaxpr)]


def _assert_flush_is_one_dma(jaxpr, width, group, loops=slice(None)):
    """Since PR 38 a full window goes to HBM by ONE DMA out of the
    accumulator's ring: neither loop of `_acc_kernel` stores a [C, P]
    value (the copy to a flush stage, the slide of the accumulator's
    second half onto its first, before), and the DMAs it starts out of an
    accumulator ([(windows + 1) C, P]) are one a side and chunk in pass A
    (`group` chunks a trip) and one a chunk in pass B."""
    acc = ((pseg._ACC_WINDOWS + 1) * seg.CHUNK, width)
    bodies = _whiles(jaxpr)[loops]
    assert len(bodies) == 2
    for body, flushes in zip(bodies, (2 * group, 1)):
        stores = [eqn.invars[1].aval.shape for eqn in _eqns(body)
                  if eqn.primitive.name in ("swap", "masked_swap")]
        assert stores and (seg.CHUNK, width) not in stores
        from_acc = [eqn for eqn in _eqns(body)
                    if eqn.primitive.name == "dma_start"
                    and eqn.invars[0].aval.shape == acc]
        assert len(from_acc) == flushes


def test_pass_a_is_one_permutation():
    """Pass A of `_acc_kernel` places a chunk with ONE permutation: its
    loop body holds 4 MXU contractions a chunk (the split column read out
    as a row, the one-hot against the 3 exact parts) and ONE rank product
    for the chunks of a trip together, and pass B none.  A second compaction per side (8 a chunk, the body
    before PR 25) must not come back quietly, nor a rank mat-vec a chunk;
    and the index arithmetic has rows in lanes: nothing [C, B]-shaped (the
    categorical bitset's one-hot, before PR 29) and no per-row [C] vector
    is computed in the loop.  Since PR 36 the block goes to the
    accumulators by tile-aligned windows: pass A's loop holds no rotate
    and computes no value of the accumulators' [2C, P] (the doubled block,
    its two rotated copies and the selects over them, before); pass B
    keeps its one rotate of a doubled window.  Since PR 38 neither loop
    copies a window to flush it (`_assert_flush_is_one_dma`).  Traced
    only, nothing runs."""
    pay = _payload(1024)
    closed = jax.make_jaxpr(
        lambda p, a: pseg._partition_segment_acc(
            p, a, jnp.int32(7), jnp.int32(777), _pred(), jnp.float32(1.0),
            jnp.float32(-1.0), VALUE_COL, B))(
        pay, jnp.zeros_like(pay))
    assert _while_dots(closed.jaxpr) == [4 * pseg._pass_a_group(P, B) + 1, 0]
    pass_a, pass_b = _while_shapes(closed.jaxpr)
    assert (seg.CHUNK, B) not in pass_a and (seg.CHUNK,) not in pass_a
    assert (8, seg.CHUNK) in pass_a
    assert _while_rotates(closed.jaxpr) == [0, 1]
    assert (pseg.C2, P) not in pass_a and (pseg.C2, P) in pass_b
    assert (pseg.BLOCK_ROWS, P) in pass_a and (pseg.WIN, P) in pass_a
    _assert_flush_is_one_dma(closed.jaxpr, P, pseg._pass_a_group(P, B))


@pytest.mark.parametrize("width,group", [(P, 2), (256, 2), (384, 1)])
@pytest.mark.parametrize("start,count", [(0, 1024), (7, 777), (100, 254),
                                         (513, 37)])
def test_partition_acc_groups(width, group, start, count):
    """Pass A takes as many chunks a loop trip as the payload's width
    leaves VMEM for; every group size gives the portable partition bit
    for bit, whole trips and trips whose last chunks lie past the
    segment (1 to 5 chunks here) alike."""
    assert pseg._pass_a_group(width, B) == group
    pay = _widened(_payload(1024, seed=count), width)
    aux = jnp.zeros_like(pay)
    pred = _pred(feature=2, threshold=B // 3)
    lv, rv = jnp.float32(-0.25), jnp.float32(0.75)
    ref_pay, _, ref_nl = _portable(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv, VALUE_COL)
    got_pay, _, got_nl = pseg.partition_segment_acc(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        VALUE_COL, B, interpret=True)
    assert int(got_nl) == int(ref_nl)
    np.testing.assert_array_equal(np.asarray(got_pay), np.asarray(ref_pay))


def _sided_payload(first_rows, start, count, width, right_first, seed,
                   n_pad=1536):
    """A payload whose split column (feature 1, threshold B // 2) sends
    `first_rows(k, nv)` of the `nv` segment rows of chunk k of the
    kernel's aligned read stream to the FIRST side (the left child, or
    the right one with `right_first`), at random places; and the counts
    it made, a (first, second) pair a chunk."""
    pay = np.array(_payload(n_pad, seed=seed))
    rng = np.random.default_rng(seed)
    first_bin, second_bin = (B - 1, 0) if right_first else (0, B - 1)
    base = start - start % 8
    counts = []
    for k in range(-(-(start + count - base) // seg.CHUNK)):
        rows = np.arange(max(start, base + k * seg.CHUNK),
                         min(start + count, base + (k + 1) * seg.CHUNK))
        n_first = int(np.clip(first_rows(k, len(rows)), 0, len(rows)))
        pay[rows, 1] = second_bin
        pay[rng.choice(rows, n_first, replace=False), 1] = first_bin
        counts.append((n_first, len(rows) - n_first))
    return _widened(jnp.asarray(pay), width), counts


#: how many of a chunk's `nv` segment rows go to the first side, by chunk
#: k of the stream: the ends of the one-hot's block (a side empty, a whole
#: chunk one side), a side's count on a tile edge and one past it, and
#: counts that walk both cursors through the residues
BLOCK_EDGES = {
    "no_first": lambda k, nv: 0,
    "no_staged": lambda k, nv: nv,
    "whole_chunks": lambda k, nv: nv if k % 2 == 0 else 0,
    "first_on_tile": lambda k, nv: 64,
    "first_off_tile": lambda k, nv: 65,
    "staged_on_tile": lambda k, nv: nv - 64,
    "staged_off_tile": lambda k, nv: nv - 65,
    "walk": lambda k, nv: (3 * k + 5) % (nv + 1),
}


@pytest.mark.parametrize("edge", list(BLOCK_EDGES))
@pytest.mark.parametrize("start", range(8))
@pytest.mark.parametrize("kernel,width", PLANS[1:])
def test_pass_a_block_edges(kernel, width, start, edge):
    """Pass A puts a chunk's rows at the accumulators' cursors by the
    one-hot's destination (the part of each cursor under 8) and a
    tile-aligned window a side (the rest): every start modulo 8, the edges
    of the [C + 24, P] block, segments of 1 to 5 chunks whose last chunk
    is whole, a row short or nearly empty (two chunks a trip at 128 and
    256 lanes: an odd count leaves the last trip half empty), the left
    child first and the right one; payload, staged rows and count bit for
    bit the portable partition's."""
    at = start + list(BLOCK_EDGES).index(edge)
    chunks, cut = 1 + at % 5, (0, 1, 100, 250)[at % 4]
    count = chunks * seg.CHUNK - start - cut
    for right_first in (False, True):
        pay, counts = _sided_payload(BLOCK_EDGES[edge], start, count, width,
                                     right_first, seed=at)
        assert len(counts) == chunks
        nl = _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B,
                          right_first)
        n_first = sum(first for first, _ in counts)
        assert nl == (count - n_first if right_first else n_first)


@pytest.mark.parametrize("r_staged", range(8))
@pytest.mark.parametrize("r_first", range(8))
@pytest.mark.parametrize("kernel,width", [PLANS[1], PLANS[4]])
def test_pass_a_cursor_residues(kernel, width, r_first, r_staged):
    """Every pair of the two cursors' parts under 8 meets a FULL chunk
    whose first side ends two rows past a tile edge: the block's longest
    reach (row C + 20 where both parts are 7), in one pass at two chunks a
    trip and a column block at a time; then a short chunk on the cursors
    that chunk left.  With the left child first and with the right one."""
    start = (r_first + r_staged) % 8
    lead = 40 + (-r_staged) % 8
    count = 2 * seg.CHUNK + 37 - start
    for right_first in (False, True):
        pay, counts = _sided_payload(
            lambda k, nv: (lead, 130, 1)[k], start, count, width,
            right_first, seed=8 * r_first + r_staged)
        # both cursors start at `start % 8`; where chunk 0 leaves them
        assert ((start + counts[0][0]) % 8, (start + counts[0][1]) % 8) \
            == (r_first, r_staged)
        assert counts[1] == (130, seg.CHUNK - 130) and len(counts) == 3
        _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B,
                     right_first)


#: windows of an accumulator's ring (PR 38); a cursor that leaves the last
#: one comes back into the first
NW = pseg._ACC_WINDOWS


@pytest.mark.parametrize("share", [100, 90, 0])
@pytest.mark.parametrize("start", [0, 5])
@pytest.mark.parametrize("kernel,width", [PLANS[1], PLANS[4]])
def test_acc_ring_goes_round(kernel, width, start, share):
    """Segments long enough for an accumulator's ring to go round three
    times: all of the rows, nine in ten or none on the first side, so
    that the first side's ring (pass A), the staged side's (pass A) and
    the first side's again (pass B, appending the staged rows) each wrap
    and every put past the ring's end lands in the window flushed NW - 1
    chunks earlier.  One pass and a column block at a time, each child
    order, bit for bit."""
    chunks = 3 * NW + 2
    count = chunks * seg.CHUNK - start - 3
    for right_first in (False, True):
        pay, counts = _sided_payload(
            lambda k, nv: nv * share // 100, start, count, width, right_first,
            seed=share + start, n_pad=(chunks + 1) * seg.CHUNK)
        assert len(counts) == chunks
        _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B,
                     right_first)


@pytest.mark.parametrize("r", range(8))
@pytest.mark.parametrize("window", range(NW))
@pytest.mark.parametrize("side", ["first", "staged"])
@pytest.mark.parametrize("kernel,width", [PLANS[1], PLANS[4]])
def test_acc_ring_window_ends(kernel, width, side, window, r):
    """A side's cursor in each window of its ring, at each part under 8,
    meets a chunk whose rows cross that window's end: the last window's
    is the ring's, where the put is two stores, the second at the ring's
    head.  Whole chunks walk the cursor to the window, one chunk sets its
    part (row 200 + r of the window), the next brings 130 rows; the other
    side takes what is left."""
    start = (3 * r + window) % 8
    lead = [seg.CHUNK] * window + [200 + r - (start if window == 0 else 0),
                                   130, 9]
    count = len(lead) * seg.CHUNK - start - 100
    for right_first in (False, True):
        pay, counts = _sided_payload(
            lambda k, nv: lead[k] if side == "first" else nv - lead[k],
            start, count, width, right_first, seed=8 * window + r,
            n_pad=(len(lead) + 1) * seg.CHUNK)
        mine = [c[side == "staged"] for c in counts]
        # both cursors start at `start % 8`: where the side's stands when
        # the chunk of 130 comes
        at = start + sum(mine[:window + 1])
        assert (at // seg.CHUNK, at % seg.CHUNK) == (window, 200 + r)
        assert mine[window + 1] == 130
        _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B,
                     right_first)


@pytest.mark.parametrize("r", range(8))
@pytest.mark.parametrize("window", range(NW))
@pytest.mark.parametrize("kernel,width", [PLANS[1], PLANS[4]])
def test_acc_ring_pass_b_hand_off(kernel, width, window, r):
    """Pass B takes the first side's accumulator over where pass A left
    its cursor (in each window of the ring, at each part under 8) and
    appends a ring's length of staged rows and more behind it: its puts
    cross every window's end and the ring's, from whichever window the
    hand-off fell in."""
    start = (5 * r + window) % 8
    first = window * seg.CHUNK + 100 + r - start      # rows pass A keeps
    chunks = window + NW + 3
    count = chunks * seg.CHUNK - start - 50

    def first_rows(k, nv):
        # spread over the leading chunks, 200 rows at a time
        return int(np.clip(first - 200 * k, 0, 200))

    for right_first in (False, True):
        pay, counts = _sided_payload(
            first_rows, start, count, width, right_first,
            seed=8 * window + r, n_pad=(chunks + 1) * seg.CHUNK)
        at = start + sum(c[0] for c in counts)
        assert (at // seg.CHUNK, at % 8) == (window, (100 + r) % 8)
        assert sum(c[1] for c in counts) > (NW + 1) * seg.CHUNK
        _check_exact(kernel, pay, start, count, _pred(), VALUE_COL, B,
                     right_first)


#: the accumulator kernel in one pass (`higgs-train`'s plan) and a 512-lane
#: column block at a time (`epsilon-train`'s)
ACC_PLANS = [("partition_segment_acc", 128),
             ("partition_segment_acc_blocks", 1024)]


@pytest.mark.parametrize("b", [64, 255, 256])
@pytest.mark.parametrize("members", ["edges", "all_but_edges"])
@pytest.mark.parametrize("start,count", [(0, 1000), (7, 777)])
@pytest.mark.parametrize("kernel,width", ACC_PLANS)
def test_partition_categorical_word_edges(b, members, start, count, kernel,
                                          width):
    """The categorical bitset reaches the accumulator kernel packed into
    ceil(B / 32) int32 words: the bins at the words' edges (0, 31, 32, 63,
    64, B - 1; bit 31 is the word's sign) are members and their
    neighbours are not, or the other way round, and the split column holds
    only those bins."""
    edges = np.array(sorted({0, 31, 32, 63, 64, b - 1} & set(range(b))))
    near = np.array(sorted({1, 30, 33, 62, 65, b - 2} & set(range(b))))
    bitset = np.isin(np.arange(b), edges) == (members == "edges")
    rng = np.random.default_rng(b + start)
    bins = rng.integers(0, b, size=(1024, 6))
    bins[:, 1] = rng.choice(np.concatenate([edges, near]), 1024)
    pay, _ = _hist_payload(6, b, 1024, width=width, bins=bins)
    nl = _check_exact(kernel, pay, start, count,
                      _pred(bins=b, is_cat=True, bitset=bitset), 9, b)
    want = np.isin(bins[start:start + count, 1], edges) \
        == (members == "edges")
    assert nl == want.sum() and 0 < nl < count


@pytest.mark.parametrize("missing_type", [1, 2])
@pytest.mark.parametrize("default_left", [False, True])
@pytest.mark.parametrize("decode", ["identity", "efb"])
@pytest.mark.parametrize("kernel,width", ACC_PLANS)
def test_partition_missing_and_decode(missing_type, default_left, decode,
                                      kernel, width):
    """`MISSING_ZERO` (1: the default bin is missing) and `MISSING_NAN`
    (2: the last bin is) with the missing rows sent each way, on a raw
    column and on an EFB bundle's member (`identity` 0: stored value less
    `offset`, out-of-range rows to the default bin, the default bin
    skipped): the lane-major predicate is `Bin::Split` to the row."""
    from lightgbm_tpu.ops.split import MISSING_NAN, MISSING_ZERO
    assert (MISSING_ZERO, MISSING_NAN) == (1, 2)
    kw = dict(missing_type=missing_type, default_left=default_left,
              threshold=4)
    if decode == "efb":
        # the member's 9 bins (default bin 2 not stored) sit at stored
        # values 5 .. 12 of storage column 3
        kw.update(feature=3, offset=5, num_bin=9, default_bin=2,
                  identity=False)
    else:
        kw.update(feature=2, default_bin=3)
    pay = _widened(_payload(1024, seed=missing_type), width)
    nl = _check_exact(kernel, pay, 9, 1015, _pred(**kw), VALUE_COL, B)
    assert 0 < nl < 1015


@pytest.mark.parametrize("width,feature", [(256, 5), (256, 130), (384, 5),
                                           (384, 130), (384, 290)])
@pytest.mark.parametrize("start,count", [(0, 1024), (7, 777), (100, 1),
                                         (513, 37)])
def test_partition_acc_split_window(width, feature, start, count):
    """Past 128 lanes the one-pass kernel transposes the 128-lane window
    of the chunk that holds the split column (a lane slice of the ring at
    a traced, aligned offset): the column in each window of a 256- and a
    384-lane payload, over whole, shifted and one-row segments."""
    f, b = width - 60, 64
    pay, _ = _hist_payload(f, b, 1024, width=width, seed=feature + count)
    _check_exact("partition_segment_acc", pay, start, count,
                 _pred(feature, b // 3, bins=b), f + 3, b)


@pytest.mark.parametrize("start,count,feature,kind", [
    (7, 1, 100, "numerical"), (100, 254, 600, "categorical"),
    (513, 37, 1100, "missing"), (9, 1015, 1700, "categorical")])
def test_partition_blocks_epsilon_predicates(start, count, feature, kind):
    """The epsilon cell's width under the predicates its cell never
    sends, the split column in each of the four blocks, a one-row
    segment and shifted first chunks."""
    Fw, Bw = 2000, 64
    pay, _ = _wide_payload(1024, Fw, Bw, seed=start + count)
    kw = {"numerical": {},
          "categorical": dict(is_cat=True, bitset=np.isin(
              np.arange(Bw) % 32, (0, 5, 31))),
          "missing": dict(missing_type=2, default_left=True,
                          threshold=9)}[kind]
    _check_exact("partition_segment_acc_blocks", pay, start, count,
                 _pred(feature, bins=Bw, **kw), Fw + 3, Bw)


@pytest.mark.parametrize("kernel,width", ACC_PLANS)
@pytest.mark.parametrize("kind", ["numerical", "categorical"])
def test_partition_acc_past_256_bins(kernel, width, kind):
    """The partition is exact at any bin count (`partition_engine` sends
    a `max_bin` past the histogram kernel's 256 here all the same): bins
    up to 999, thirty-two words of bitset, and the column read out at the
    precision such bins need."""
    b = 1000
    rng = np.random.default_rng(b)
    bins = rng.integers(0, b, size=(1024, 6))
    pay, _ = _hist_payload(6, b, 1024, width=width, bins=bins)
    kw = dict(threshold=701) if kind == "numerical" else dict(
        is_cat=True, bitset=(np.arange(b) % 7 == 3) | (np.arange(b) > 990))
    nl = _check_exact(kernel, pay, 7, 1000, _pred(bins=b, **kw), 9, b)
    assert 0 < nl < 1000


def test_acc_scalars_pack_the_bitset():
    """Bit b of word w of the kernels' scalar vector is bin 32 w + b, the
    words behind the predicate's scalars, the split window's lane and
    `right_first`."""
    for b in (64, 255, 256, 16):
        bitset = np.random.default_rng(b).random(b) < 0.5
        bitset[[0, b - 1]] = True
        got = np.asarray(pseg._acc_scalars(
            jnp.int32(3), jnp.int32(9),
            _pred(bins=b, bitset=bitset), jnp.int32(1), 128, b,
            jnp.bool_(True)))
        words = got[pseg._BITSET_WORD0:].astype(np.uint32)
        assert got.shape == (pseg._BITSET_WORD0 + -(-b // 32),)
        assert list(got[:3]) == [3, 9, 1] and list(got[11:13]) == [128, 1]
        unpacked = (words[:, None] >> np.arange(32, dtype=np.uint32)) & 1
        np.testing.assert_array_equal(unpacked.reshape(-1)[:b], bitset)
        assert not unpacked.reshape(-1)[b:].any()


def test_payload_col_write_matches_dus():
    """seg.payload_col_write is the lane-masked replacement for the DUS
    column writes that OOM'd the chip at full scale (round 4); it must
    match .at[:, col].set/.add/.multiply exactly for vector and scalar
    values and for traced column indices."""
    rng = np.random.default_rng(3)
    pay = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    vec = jnp.asarray(rng.standard_normal(64).astype(np.float32))

    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 3, vec), pay.at[:, 3].set(vec))
    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 5, vec, "add"), pay.at[:, 5].add(vec))
    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 0, vec, "mul"),
        pay.at[:, 0].multiply(vec))
    # scalar value broadcast, each op
    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 7, 2.5), pay.at[:, 7].set(2.5))
    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 1, 2.5, "add"), pay.at[:, 1].add(2.5))
    np.testing.assert_array_equal(
        seg.payload_col_write(pay, 2, 0.5, "mul"),
        pay.at[:, 2].multiply(0.5))

    # traced column index (the fused step passes k as a traced scalar)
    @jax.jit
    def via_traced_col(p, c, v):
        return seg.payload_col_write(p, c, v, "add")

    np.testing.assert_array_equal(
        via_traced_col(pay, jnp.int32(4), vec), pay.at[:, 4].add(vec))


def _wide_payload(n_pad, F_wide, B_wide, seed=0):
    """Ultra-wide payload: F_wide bin columns, aux (grad/hess/cnt) after
    them, lane-padded width like the fast path's _FastState.P."""
    return _hist_payload(F_wide, B_wide, n_pad,
                         width=-(-(F_wide + 8) // 128) * 128, seed=seed)


# ---------------------------------------------------------------------------
# column-block partition (ultra-wide payloads)
# ---------------------------------------------------------------------------

#: the engine of a lane-padded payload of 640 to 1,664 lanes, where both
#: the read-modify-write kernel's plan and the column-block kernel's fit:
#: the one that won the race on the chip (`exp/race_partition_band.py`,
#: PERF.md section 6, PR 37)
BAND_ENGINE = "pallas-blocks"


@pytest.mark.parametrize("hist_impl,backend,width,bins,engine", [
    ("auto", "tpu", 128, 256, "pallas-acc"),      # higgs-train
    ("auto", "tpu", 256, 256, "pallas-acc"),
    ("auto", "tpu", 512, 64, "pallas-acc"),       # the widest single pass
    ("auto", "tpu", 640, 64, BAND_ENGINE),        # the band's lower edge
    ("auto", "tpu", 1024, 64, BAND_ENGINE),       # bosch-train
    ("auto", "tpu", 1024, 256, BAND_ENGINE),
    ("auto", "tpu", 1664, 64, BAND_ENGINE),       # the band's upper edge
    ("auto", "tpu", 1792, 64, "pallas-blocks"),   # no single-pass plan fits
    ("auto", "tpu", 1000, 64, "lax"),             # in the band, not padded
    ("auto", "tpu", 2048, 64, "pallas-blocks"),   # epsilon-train
    ("auto", "tpu", 4352, 256, "pallas-blocks"),  # raw Allstate
    ("auto", "tpu", 2000, 64, "lax"),             # not lane-padded
    ("lax", "tpu", 2048, 64, "lax"),
    ("auto", "cpu", 2048, 64, "lax"),
])
def test_partition_engine_by_shape(monkeypatch, hist_impl, backend, width,
                                   bins, engine):
    """The partition engine follows from the platform and the payload's
    shape alone: the column-block kernel serves what neither single-pass
    plan holds, and nothing narrower resolves differently for it."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    from lightgbm_tpu.boosting import grower2
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert grower2.partition_engine(hist_impl, width, bins) == engine


@pytest.mark.parametrize("bins", [16, 64, 255, 256, 1024])
def test_partition_engine_outside_the_band_is_the_plans_order(monkeypatch,
                                                              bins):
    """At 128 to 4,480 lanes: outside the band the engine is the first
    plan that fits in the order accumulator, read-modify-write, column
    blocks (the rule before the band was raced); inside it, the race's
    winner where the payload is lane-padded."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    from lightgbm_tpu.boosting import grower2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    band = []
    for width in range(128, 4481, 128):
        by_order = ("pallas-acc" if pseg.partition_acc_fits_vmem(width, bins)
                    else "pallas-rmw" if pseg.partition_fits_vmem(width, bins)
                    else "pallas-blocks"
                    if pseg.partition_blocks_fits_vmem(width, bins)
                    else "lax")
        got = grower2.partition_engine("auto", width, bins)
        if by_order == "pallas-rmw":
            band.append(width)
            assert got == BAND_ENGINE, width
            # not lane-padded, in the band: no kernel, as past it
            assert grower2.partition_engine("auto", width + 8,
                                            bins) == "lax", width
        else:
            assert got == by_order, width
    assert band[0] == 640 and band[-1] == (1664 if bins < 1024 else 1536)
    assert band == list(range(band[0], band[-1] + 1, 128))


#: what the four gates answered at PR 37, before the accumulators became
#: rings (taken from that commit, the same at 16, 64, 255, 256 and 1,024
#: bins): by lane-padded width from .. to, the partition engine, pass A's
#: chunks a trip in one pass and in a column block of that width (512 at
#: most) beside its split-window ring, whether the one-pass plan fits,
#: whether the column-block plan fits
GATES_AT_PR37 = [
    ((128, 256), ("pallas-acc", 2, 2, True, True)),
    ((384, 512), ("pallas-acc", 1, 1, True, True)),
    ((640, 4480), ("pallas-blocks", 1, 1, False, True)),
]


@pytest.mark.parametrize("bins", [16, 64, 255, 256, 1024])
def test_gates_answer_as_before_the_rings(monkeypatch, bins):
    """`_acc_plan_bytes` counts what the body holds since PR 38 (two
    rings of three windows and a tail each, 8C rows, where two [2C, P]
    accumulators, a flush stage and a blend buffer were 6C), and the
    gates that read it answer as they did: `partition_engine`,
    `_pass_a_group`, `partition_acc_fits_vmem` and
    `partition_blocks_fits_vmem` at every lane-padded width from 128 to
    4,480 lanes, 175 shapes with the five bin counts.  (Whether the chip
    agrees with the plan is Mosaic's verdict, tests/test_tpu_compile.py.)"""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    from lightgbm_tpu.boosting import grower2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    widths = []
    for (lo, hi), want in GATES_AT_PR37:
        for width in range(lo, hi + 1, 128):
            widths.append(width)
            got = (grower2.partition_engine("auto", width, bins),
                   pseg._pass_a_group(width, bins),
                   pseg._pass_a_group(
                       min(pseg._BLOCK_WIDTH, width), bins,
                       pseg._route_ring_bytes(pseg._PASS_A_GROUP)),
                   pseg.partition_acc_fits_vmem(width, bins),
                   pseg.partition_blocks_fits_vmem(width, bins))
            assert got == want, width
    assert widths == list(range(128, 4481, 128))
    assert pseg.ACC_ROWS == (pseg._ACC_WINDOWS + 1) * seg.CHUNK


@pytest.mark.parametrize("backend,features,bins,width,kw,engine", [
    ("tpu", 28, 256, 128, {}, "pallas"),          # higgs-train
    ("tpu", 67, 256, 128, {}, "pallas"),          # criteo-dp4-train, a shard
    ("tpu", 2000, 64, 2048, {}, "pallas"),        # epsilon-train
    # a feature-parallel shard histograms its 500 owned columns of full rows
    ("tpu", 2000, 64, 2048,
     dict(axis_name="x", mode="feature", num_machines=4), "pallas"),
    ("tpu", 28, 257, 128, {}, "lax"),             # past the bf16-exact bins
    ("tpu", 4228, 256, 4352, {}, "lax"),          # raw Allstate: past VMEM
    ("tpu", 28, 256, 128, dict(quantized=True, qmax=127), "lax"),
    ("cpu", 28, 256, 128, {}, "lax"),
])
def test_histogram_engine_by_shape(monkeypatch, backend, features, bins,
                                   width, kw, engine):
    """The histogram engine, like the partition's, follows from the
    platform and the shape alone: the one Pallas kernel wherever its plan
    fits, the lax engine past 256 bins, past the plan, for the int32
    histograms of quantized gradients and off the TPU."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    from lightgbm_tpu.boosting import grower2
    from lightgbm_tpu.boosting.grower import GrowerConfig
    from lightgbm_tpu.ops.split import FeatureMeta
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    zeros = np.zeros(features, np.int32)
    meta = FeatureMeta(num_bin=zeros + bins, missing_type=zeros,
                       default_bin=zeros, is_trivial=zeros > 0,
                       is_categorical=zeros > 0,
                       penalty=np.ones(features, np.float32), monotone=zeros)
    cfg = GrowerConfig(num_leaves=255, max_depth=-1, lambda_l1=0.0,
                       lambda_l2=0.0, max_delta_step=0.0, min_data_in_leaf=20,
                       min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    cols = grower2.PayloadCols(grad=features, hess=features + 1,
                               cnt=features + 2, value=features + 3)
    grower = grower2.make_partitioned_grower(
        meta, cfg, bins, cols, features, jit=False, payload_width=width,
        **kw)
    assert grower.engines["histogram"] == engine
    if bins > 256:
        with pytest.raises(ValueError):
            seg.resolve_impl("pallas", features, bins)


def test_partition_blocks_vmem_gate():
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    # the shapes the full-width kernels cannot plan
    assert pseg.partition_blocks_fits_vmem(2048, 64)    # Epsilon payload
    assert pseg.partition_blocks_fits_vmem(4352, 256)   # raw Allstate
    assert not pseg.partition_fits_vmem(2048, 64)
    assert not pseg.partition_acc_fits_vmem(4352, 256)


@pytest.mark.parametrize("start,count,predkw", [
    (0, 1000, {}),
    (256, 700, dict(feature=3, threshold=4)),
    (100, 37, dict(missing_type=2, default_left=True, threshold=3)),
    (0, 600, dict(is_cat=True, bitset=(np.arange(B) % 3 == 0))),
    (7, 1, {}),
    (9, 1015, dict(feature=2, threshold=B // 3)),
    # EFB bundle decode through the split-window scalars
    (64, 500, dict(feature=2, threshold=3, offset=5, identity=False,
                   num_bin=9, default_bin=0)),
    # the split column in the last window but one, and in the ragged tail
    (7, 777, dict(feature=1100, threshold=B // 2)),
    (513, 300, dict(feature=1199, threshold=5)),
])
@pytest.mark.parametrize("block_w", [640, 384])
def test_partition_blocks_matches(start, count, predkw, block_w):
    """Ultra-wide payload in two lane windows, and in four (the last a
    ragged 128 lanes that takes pass A two chunks a trip): the per-block
    passes reproduce the portable partition bit for bit -- one consistent
    permutation across every window, value column written only by its own
    block."""
    Fw = 1200
    Pw = -(-(Fw + 8) // 128) * 128   # 1280: 2 x 640, or 3 x 384 + 128
    pay, _ = _wide_payload(1024, Fw, B, seed=start + count)
    assert pay.shape[1] == Pw
    aux = jnp.zeros_like(pay)
    vcol = Fw + 3
    pred = _pred(**predkw)
    lv, rv = jnp.float32(-0.25), jnp.float32(0.75)
    ref_pay, _, ref_nl = _portable(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv, vcol)
    got_pay, _, got_nl = pseg.partition_segment_acc_blocks(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        vcol, B, interpret=True, block_w=block_w)
    assert int(got_nl) == int(ref_nl)
    np.testing.assert_array_equal(np.asarray(got_pay), np.asarray(ref_pay))


@pytest.mark.parametrize("start,count,feature", [
    (0, 1024, 0), (7, 777, 511), (100, 254, 512), (513, 37, 1999),
    (9, 1015, 1300), (256, 256, 700)])
def test_partition_blocks_epsilon_shape(start, count, feature):
    """The epsilon cell's own width: 2,000 bin columns at 64 bins in a
    2,048-lane payload, four 512-lane blocks, the value columns in the
    last; bit-equal to the portable partition wherever the split column
    lies."""
    Fw, Bw, Pw = 2000, 64, 2048
    pay, _ = _wide_payload(1024, Fw, Bw, seed=start + count)
    assert pay.shape[1] == Pw
    aux = jnp.zeros_like(pay)
    pred = SplitPredicate(
        col=jnp.int32(feature), threshold=jnp.int32(Bw // 3),
        default_left=jnp.bool_(False), is_cat=jnp.bool_(False),
        bitset=jnp.zeros(Bw, bool), missing_type=jnp.int32(0),
        num_bin=jnp.int32(Bw), default_bin=jnp.int32(0),
        offset=jnp.int32(0), identity=jnp.bool_(True))
    lv, rv = jnp.float32(-0.25), jnp.float32(0.75)
    ref_pay, _, ref_nl = _portable(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv, Fw + 3)
    got_pay, _, got_nl = pseg.partition_segment_acc_blocks(
        pay, aux, jnp.int32(start), jnp.int32(count), pred, lv, rv,
        Fw + 3, Bw, interpret=True)
    assert int(got_nl) == int(ref_nl)
    np.testing.assert_array_equal(np.asarray(got_pay), np.asarray(ref_pay))


def test_partition_blocks_pass_a_is_one_permutation():
    """Every column block runs `_acc_kernel`'s pass A: 4 MXU contractions
    a chunk and one rank product a trip in its loop (the 256-lane tail
    takes two chunks a trip), none in pass B; the snapshot kernel's loop
    holds none; no [C, B]-shaped value in any loop.  Traced only."""
    pay, _ = _wide_payload(1024, 1200, B, seed=3)
    closed = jax.make_jaxpr(
        lambda p, a: pseg._partition_segment_acc_blocks(
            p, a, jnp.int32(7), jnp.int32(777), _pred(), jnp.float32(1.0),
            jnp.float32(-1.0), 1203, B))(
        pay, jnp.zeros_like(pay))
    assert _while_dots(closed.jaxpr) == [0, 5, 0, 5, 0, 9, 0]
    shapes = _while_shapes(closed.jaxpr)
    assert not any((seg.CHUNK, B) in loop for loop in shapes)
    # the snapshot, then pass A and pass B of each block: a rotate in
    # pass B alone, nothing of an accumulator's shape in pass A
    assert _while_rotates(closed.jaxpr) == [0, 0, 1, 0, 1, 0, 1]
    for i, (pass_a, width) in enumerate(zip(shapes[1::2], (512, 512, 256))):
        assert (pseg.C2, width) not in pass_a
        assert (pseg.BLOCK_ROWS, width) in pass_a
        _assert_flush_is_one_dma(closed.jaxpr, width, 1 + (width == 256),
                                 slice(1 + 2 * i, 3 + 2 * i))


def test_partition_blocks_narrow_pin():
    """At a width the single-pass kernel also handles, blocks (one
    window) agree with it bit for bit."""
    pay128 = _widened(_payload(1024, seed=11), 128)
    aux = jnp.zeros_like(pay128)
    pred = _pred(feature=2, threshold=B // 3)
    lv, rv = jnp.float32(1.5), jnp.float32(-2.5)
    ref_pay, _, ref_nl = pseg.partition_segment_acc(
        pay128, aux, jnp.int32(100), jnp.int32(800), pred, lv, rv,
        VALUE_COL, B, interpret=True)
    got_pay, _, got_nl = pseg.partition_segment_acc_blocks(
        pay128, aux, jnp.int32(100), jnp.int32(800), pred, lv, rv,
        VALUE_COL, B, interpret=True)
    assert int(got_nl) == int(ref_nl)
    np.testing.assert_array_equal(np.asarray(got_pay), np.asarray(ref_pay))


def test_hist_vmem_gate_uses_real_payload_width():
    """The histogram VMEM gate must budget the REAL payload lane count
    when the caller knows it: a feature-parallel shard histograms few
    owned columns (small F) of very wide rows, where the old
    num_features+32 estimate under-budgeted the chunk buffers."""
    if seg.CHUNK != 256:
        pytest.skip("VMEM gate expectations assume the default CHUNK")
    # same histogram shape, honest width: an ultra-wide payload's chunk
    # buffers alone exceed the budget even though only 28 columns are
    # histogrammed (2 x 4 x CHUNK x width of double-buffered DMA)
    assert pseg.fits_vmem(28, 255)
    assert pseg.fits_vmem(28, 255, payload_width=128)
    assert not pseg.fits_vmem(28, 255, payload_width=8192)
    # feature-parallel on four chips: a shard's leading columns of
    # full-width rows, at the Higgs and the Epsilon payloads
    assert pseg.fits_vmem(7, 255, payload_width=128)
    assert pseg.fits_vmem(500, 64, payload_width=2048)
    # resolve_impl threads the width through (TPU-only decision; on CPU
    # both resolve to lax)
    assert seg.resolve_impl("auto", 28, 255, 4224) in ("pallas", "lax")
