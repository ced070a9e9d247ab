"""Segment engine: O(rows-touched) histogram + partition over a row-payload.

The reference keeps rows of each leaf contiguous through DataPartition
(src/treelearner/data_partition.hpp) so ConstructHistogram only scans the
split leaf's rows (src/io/dense_bin.hpp:66-132, ordered gather
src/io/dataset.cpp:664-678).  TPUs have no fast random scatter/gather, so the
same idea is re-expressed in MXU-native primitives:

- training rows live in ONE row-major payload matrix [N_pad + C, P] (f32):
  bin columns, then value columns (grad/hess/count-mask/leaf-value/...);
  rows of every tree leaf are kept physically contiguous;
- a split's stable partition is three chunked passes (compact-left,
  compact-right, blended copy-back), each chunk compacted by a one-hot
  permutation matrix applied as a matmul — a scatter the MXU can run;
- a leaf's histogram is built by walking only that leaf's chunks and
  contracting a joint (feature, bin) one-hot with the value columns.

This module is the portable lax implementation used on CPU meshes and as
the semantic reference; `ops.pallas_histogram` / `ops.pallas_partition`
override the two hot kernels on TPU with VMEM-resident one-hots.

Chunks are fixed at C rows; `start`/`count` are dynamic scalars, so every
pass is a `lax.while_loop` with a data-dependent trip count — no
recompilation per segment size.  The payload carries a C-row guard at the
end: compact passes may write up to C garbage rows past a segment into the
scratch buffer, and the copy-back blends row-exactly, so no pass ever needs
a partial-chunk write.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .bundle import decode_bin
from .split import MISSING_NAN, MISSING_ZERO

# rows per chunk: small enough that the joint one-hot [C, F*B] and the
# permutation matrix [C, C] sit comfortably in VMEM on the Pallas path.
# LIGHTGBM_TPU_CHUNK lets a hardware session A/B larger chunks (fewer
# per-chunk DMA waits, more VMEM per buffer — every kernel's VMEM-fit
# plan recomputes from this constant); the tested/shipped default is 256.
# Exactness is chunk-size-independent up to 2^24 (f32-exact prefix
# counts); the sublane alignment story only needs CHUNK % 8 == 0.
# a ValueError (not assert): the sublane-alignment assumption is baked
# into every Pallas kernel and the GUARD sizing, and the check must
# survive python -O
_chunk_raw = os.environ.get("LIGHTGBM_TPU_CHUNK", "256")
try:
    CHUNK = int(_chunk_raw)
except ValueError:
    raise ValueError(
        "LIGHTGBM_TPU_CHUNK must be an integer multiple of 8 in "
        "[8, 2048], got %r" % _chunk_raw) from None
if CHUNK % 8 != 0 or not 8 <= CHUNK <= 2048:
    raise ValueError(
        "LIGHTGBM_TPU_CHUNK must be a multiple of 8 in [8, 2048], got %d"
        % CHUNK)

# guard rows past the last real row.  The portable passes write up to CHUNK
# garbage rows past a segment; the Pallas partition kernel additionally
# writes aligned CHUNK+8-row windows (HBM row slices must start at a
# multiple of the f32 sublane tiling of 8, so a write at an arbitrary
# cursor becomes a read-modify-write of the enclosing aligned window).
GUARD = CHUNK + 8


def resolve_impl(impl: str, num_features: int, num_bins: int,
                 payload_width: int = None) -> str:
    """Pick the segment-engine implementation at trace time.

    "auto" (Config.tpu_histogram_impl default) chooses the Pallas kernels on
    a TPU backend when the joint one-hot fits VMEM, otherwise the portable
    lax path.  "pallas" / "lax" force a choice (tests, debugging).

    payload_width: the REAL payload lane count, when the caller knows it —
    the kernel DMAs full payload rows, so the VMEM plan must budget the
    actual width.  Feature-parallel shards histogram only their owned
    leading columns (num_features = Gloc) but still stream full-width rows;
    the old num_features+32 estimate under-budgeted exactly there."""
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(
            "tpu_histogram_impl must be one of auto|pallas|lax, got %r" % impl)
    if impl == "auto":
        from . import pallas_segment
        if (jax.default_backend() == "tpu"
                and pallas_segment.fits_vmem(num_features, num_bins,
                                             payload_width)):
            return "pallas"
        return "lax"
    if impl == "pallas" and num_bins > 256:
        raise ValueError(
            "tpu_histogram_impl=pallas requires max_bin <= 256 (the kernel's "
            "exactness argument needs bf16-representable bin values, like "
            "the reference's 256-bin OpenCL kernel ceiling)")
    return impl


def payload_col_write(payload: jax.Array, col, vec, op: str = "set"):
    """payload[:, col] <op>= vec as a lane-masked elementwise select.

    A DUS column write (``payload.at[:, col].set(vec)``) on the lane-tiled
    [N, P] payload makes XLA materialize BOTH a payload-sized copy and the
    [N, 1] update operand re-tiled to the payload's T(8, 128) layout — a
    128x padding expansion.  At 10.5M rows that is 2 x 5 GB of HLO temp,
    which OOMs the 16 GB v5e (measured from the compiler's HBM breakdown,
    round 4).  The masked select instead is ONE in-place elementwise pass
    over the donated buffer, over all P lanes: 16.3 ms at 10.5M rows x 128
    lanes, 10.2 ms at 409,864 x 2,048, to change one lane.  Consecutive
    writes of `[N]` vectors made by one fusion do share a pass (gradient
    and hessian did); a write after another kind of operation, a scalar's
    broadcast or an add does not, and each column sliced out for them is a
    pass of its own (PERF.md section 6, PR 32: the chip's trace).  The
    fused step's updates go through `ops.state_columns`, which touches
    only the lane tile that holds the column; this stays for the edits no
    cell runs and as that module's form off the TPU.
    `col` may be a traced scalar; `vec` a [N] vector or scalar.
    """
    mask = lax.broadcasted_iota(jnp.int32, (1, payload.shape[1]), 1) == col
    v = vec if jnp.ndim(vec) == 0 else vec[:, None]
    if op == "add":
        v = payload + v
    elif op == "mul":
        v = payload * v
    else:
        assert op == "set", op
    return jnp.where(mask, v, payload)


class SplitPredicate(NamedTuple):
    """Scalars describing one split's routing decision
    (Bin::Split semantics, src/io/dense_bin.hpp:190-283).  `col` is the
    STORAGE column (the feature's EFB bundle); offset/identity decode the
    stored value back to the feature's own bin."""
    col: jax.Array           # i32 storage-column index into the bin columns
    threshold: jax.Array     # i32 bin threshold (numerical)
    default_left: jax.Array  # bool — where missing rows go
    is_cat: jax.Array        # bool — categorical bitset split
    bitset: jax.Array        # [B] bool — bins routed left (categorical)
    missing_type: jax.Array  # i32 (of the split feature)
    num_bin: jax.Array       # i32
    default_bin: jax.Array   # i32
    offset: jax.Array        # i32 bin offset inside the bundle
    identity: jax.Array      # bool — raw-bin passthrough (no bundle)


def go_left_chunk(chunk: jax.Array, pred: SplitPredicate) -> jax.Array:
    """[C] bool routing for one payload chunk (bin cols at [:, :G])."""
    C = chunk.shape[0]
    fcol = lax.dynamic_slice(chunk, (0, pred.col), (C, 1))[:, 0]
    fbin = decode_bin(fcol, pred.identity, pred.offset, pred.num_bin,
                      pred.default_bin)
    miss = ((pred.missing_type == MISSING_NAN) & (fbin == pred.num_bin - 1)) | \
           ((pred.missing_type == MISSING_ZERO) & (fbin == pred.default_bin))
    gl_num = jnp.where(miss, pred.default_left, fbin <= pred.threshold)
    B = pred.bitset.shape[0]
    onehot = fbin[:, None] == jnp.arange(B, dtype=jnp.int32)[None, :]
    gl_cat = jnp.sum(onehot & pred.bitset[None, :], axis=1) > 0
    return jnp.where(pred.is_cat, gl_cat, gl_num)


def _compact_matmul(chunk: jax.Array, keep: jax.Array) -> jax.Array:
    """Stable-compact kept rows to the front via a one-hot permutation
    matmul — the TPU-native scatter.  HIGHEST precision: the TPU MXU's
    default one-bf16-pass f32 matmul would round every payload value it
    permutes (and corrupt >8-bit idx columns)."""
    C = chunk.shape[0]
    dest = jnp.cumsum(keep.astype(jnp.int32)) - keep.astype(jnp.int32)
    perm = ((dest[None, :] == jnp.arange(C, dtype=jnp.int32)[:, None])
            & keep[None, :]).astype(chunk.dtype)
    return jnp.matmul(perm, chunk, precision=jax.lax.Precision.HIGHEST)


def first_second(right_first, left, right):
    """(first, second) of a split's (left, right) pair, by which child
    lies first in the parent's range."""
    return (jnp.where(right_first, right, left),
            jnp.where(right_first, left, right))


def partition_segment_stage(payload: jax.Array, aux: jax.Array,
                            start: jax.Array, count: jax.Array,
                            pred: SplitPredicate, right_first=False):
    """Passes A+B of the stable partition: compact the rows of the FIRST
    child of [start, start+count) into aux[start..] (the left one, the
    right one with `right_first`), then the other child's after them.
    payload is only READ — the frontier-batched grower stages candidate
    splits here and copies back (`partition_segment_commit`) only for the
    splits that commit, so an evaluated-but-uncommitted leaf's rows keep
    their exact sequential-grower order.  Compact writes overrun up to one
    chunk past the segment end in aux; callers staging several segments
    must stage them in ASCENDING start order so an overrun only ever
    clobbers a region that is (re)staged afterwards.
    Returns (aux, num_first), the first child's row count."""
    C = CHUNK
    nch = (count + C - 1) // C

    def read(buf, k):
        return lax.dynamic_slice(buf, (start + k * C, 0),
                                 (C, buf.shape[1]))

    def valid_rows(k):
        return jnp.arange(C, dtype=jnp.int32) < (count - k * C)

    right_first = jnp.asarray(right_first, jnp.bool_)

    def go_first(chunk):
        return go_left_chunk(chunk, pred) ^ right_first

    # pass A: compact the first child's rows of each chunk, append at
    # aux[start + running)
    def body_a(carry):
        k, nf, aux = carry
        chunk = read(payload, k)
        keep = go_first(chunk) & valid_rows(k)
        compact = _compact_matmul(chunk, keep)
        aux = lax.dynamic_update_slice(aux, compact, (start + nf, 0))
        return k + 1, nf + jnp.sum(keep.astype(jnp.int32)), aux

    _, num_first, aux = lax.while_loop(lambda c: c[0] < nch, body_a,
                                       (jnp.int32(0), jnp.int32(0), aux))

    # pass B: compact the other child's rows, append at
    # aux[start + num_first + running)
    def body_b(carry):
        k, ns, aux = carry
        chunk = read(payload, k)
        keep = (~go_first(chunk)) & valid_rows(k)
        compact = _compact_matmul(chunk, keep)
        aux = lax.dynamic_update_slice(aux, compact,
                                       (start + num_first + ns, 0))
        return k + 1, ns + jnp.sum(keep.astype(jnp.int32)), aux

    _, _, aux = lax.while_loop(lambda c: c[0] < nch, body_b,
                               (jnp.int32(0), jnp.int32(0), aux))
    return aux, num_first


def partition_segment_commit(payload: jax.Array, aux: jax.Array,
                             start: jax.Array, count: jax.Array,
                             num_first: jax.Array, first_value: jax.Array,
                             second_value: jax.Array, value_col: int):
    """Pass C of the stable partition: blended copy-back aux -> payload
    over [start, start+count), writing the children's creation values
    (Tree::Split leaf_value_), the first child's over its `num_first`
    rows and the second's behind them, into the value column on the way
    through.  count = 0 is a no-op (uncommitted staged candidates)."""
    C = CHUNK
    nch = (count + C - 1) // C
    vcol_onehot = (jnp.arange(payload.shape[1]) == value_col)[None, :]

    def read(buf, k):
        return lax.dynamic_slice(buf, (start + k * C, 0),
                                 (C, buf.shape[1]))

    def body_c(carry):
        k, payload = carry
        src = read(aux, k)
        dst = read(payload, k)
        ok = (jnp.arange(C, dtype=jnp.int32) < (count - k * C))[:, None]
        pos = start + k * C + jnp.arange(C, dtype=jnp.int32)
        val = jnp.where(pos < start + num_first, first_value, second_value)
        src = jnp.where(vcol_onehot, val[:, None], src)
        blended = jnp.where(ok, src, dst)
        payload = lax.dynamic_update_slice(payload, blended,
                                           (start + k * C, 0))
        return k + 1, payload

    _, payload = lax.while_loop(lambda c: c[0] < nch, body_c,
                                (jnp.int32(0), payload))
    return payload


def partition_segment(payload: jax.Array, aux: jax.Array, start: jax.Array,
                      count: jax.Array, pred: SplitPredicate,
                      left_value: jax.Array, right_value: jax.Array,
                      value_col: int, right_first=False):
    """Stably partition payload rows [start, start+count) by the predicate.
    The contract of every partition engine: rows of the FIRST child (the
    left one, the right one with `right_first`, a traced bool) at
    [start, start + n_first), of the other behind them, each in its
    original order, the children's leaf outputs written into `value_col`.
    Nothing downstream needs the left child first (children are found by
    their segment tables), and the Pallas kernels move the second child's
    rows twice, so the grower names the larger child first.
    Returns (payload, aux, num_left) — num_left counts only rows whose
    count-mask survives in the caller's accounting; here it is the raw
    routed-row count used for segment offsets.  Composed of the stage
    (A+B) and commit (C) passes the frontier-batched grower runs apart.
    """
    aux, num_first = partition_segment_stage(payload, aux, start, count,
                                             pred, right_first)
    payload = partition_segment_commit(
        payload, aux, start, count, num_first,
        *first_second(right_first, left_value, right_value), value_col)
    return payload, aux, jnp.where(right_first, count - num_first, num_first)


def segment_histogram(payload: jax.Array, start: jax.Array, count: jax.Array,
                      *, num_features: int, num_bins: int,
                      grad_col: int, hess_col: int, cnt_col: int,
                      quantized: bool = False) -> jax.Array:
    """hist[F, B, 3] over payload rows [start, start+count).

    Only ceil(count / CHUNK) chunks are touched — the O(rows-touched)
    guarantee of the reference's ordered bins, with the scatter-free joint
    (feature, bin) one-hot contraction in place of per-row accumulation.

    quantized=True (gradient_quantization mode, ops.quantize): the
    grad/hess columns hold integer-VALUED f32 quantized gradients and the
    histogram accumulates int32.  On the scatter path the integers add
    directly; on the contraction path each CHUNK's partial histogram is
    f32-EXACT by construction (<= CHUNK * qmax < 2^23 per cell under the
    derive_qmax bound, and the bf16 part decomposition keeps products
    exact), so casting the per-chunk result to int32 before accumulating
    is exact at ANY total count — integer addition never rounds, which is
    what makes subtraction-trick siblings and cross-shard psums bit-exact.
    """
    C = CHUNK
    F, B = num_features, num_bins
    P = payload.shape[1]
    nch = (count + C - 1) // C
    iota_b = jnp.arange(B, dtype=jnp.int32)
    hist_dtype = jnp.int32 if quantized else jnp.float32
    # CPU test meshes scatter quickly but choke on one-hot contractions;
    # TPU is the inverse (and normally runs the Pallas kernels anyway)
    use_scatter = jax.default_backend() != "tpu"

    def body(carry):
        k, hist = carry
        chunk = lax.dynamic_slice(payload, (start + k * C, 0), (C, P))
        ok = (jnp.arange(C, dtype=jnp.int32) < (count - k * C)).astype(
            payload.dtype)
        binsf = chunk[:, :F].astype(jnp.int32)                 # [C, F]
        vals = jnp.stack([chunk[:, grad_col] * ok,
                          chunk[:, hess_col] * ok,
                          chunk[:, cnt_col] * ok], axis=1)     # [C, 3]
        if use_scatter:
            jidx = (binsf + iota_b[0] +
                    jnp.arange(F, dtype=jnp.int32)[None, :] * B)  # [C, F]
            upd = jnp.broadcast_to(vals[:, None, :], (C, F, 3)).reshape(-1, 3)
            if quantized:
                upd = upd.astype(jnp.int32)
            hist = hist.reshape(F * B, 3).at[jidx.reshape(-1)].add(
                upd).reshape(F, B, 3)
        else:
            from .histogram import _decompose_vals, _recombine_hist
            onehot = (binsf[:, :, None] == iota_b[None, None, :]).astype(
                payload.dtype)                                 # [C, F, B]
            # bf16-exact part columns keep the MXU contraction one-pass
            # AND exact (the default f32 matmul is one bf16 pass)
            chunk_hist = _recombine_hist(
                jnp.einsum("cfb,cd->fbd", onehot, _decompose_vals(vals),
                           preferred_element_type=jnp.float32))
            if quantized:
                chunk_hist = chunk_hist.astype(jnp.int32)
            hist = hist + chunk_hist
        return k + 1, hist

    hist0 = jnp.zeros((F, B, 3), hist_dtype)
    _, hist = lax.while_loop(lambda c: c[0] < nch, body,
                             (jnp.int32(0), hist0))
    return hist


def segment_histogram_batched(payload: jax.Array, starts: jax.Array,
                              counts: jax.Array, *, num_features: int,
                              num_bins: int, grad_col: int, hess_col: int,
                              cnt_col: int,
                              quantized: bool = False) -> jax.Array:
    """hist[K, F, B, 3] over K disjoint segments — portable batched engine.

    One traced region serves the whole frontier batch of the
    frontier-batched grower; a zero count yields a zero histogram (padding
    slots of a short frontier).  Each slice [k] is computed by the SAME
    per-chunk accumulation as `segment_histogram(payload, starts[k],
    counts[k])` — bit-identical per segment, which is what lets the batched
    grower stay byte-identical to the sequential one."""
    K = starts.shape[0]

    def body(k, hist):
        h = segment_histogram(payload, starts[k], counts[k],
                              num_features=num_features, num_bins=num_bins,
                              grad_col=grad_col, hess_col=hess_col,
                              cnt_col=cnt_col, quantized=quantized)
        return lax.dynamic_update_slice(hist, h[None], (k, 0, 0, 0))

    hist0 = jnp.zeros((K, num_features, num_bins, 3),
                      jnp.int32 if quantized else jnp.float32)
    return lax.fori_loop(0, K, body, hist0)
