"""Per-iteration gradient/hessian quantization (Shi et al., NeurIPS 2022,
"Quantized Training of Gradient Boosting Decision Trees").

The histogram loop is the bandwidth bottleneck of histogram GBDT; the paper
shows the per-row gradient/hessian can be quantized to a few bits with
STOCHASTIC rounding and integer histogram accumulation at negligible
accuracy cost.  Here that maps onto the payload engine (`ops.segment`):

- once per (iteration, class), AFTER the bagging mask is applied, the f32
  gradients/hessians are scaled into an integer grid and stochastically
  rounded (`quantize_pair`); the integer-VALUED results live in the payload
  grad/hess columns (f32 lanes — small integers are exact), so every
  partition/ride-along mechanism is unchanged;
- histograms accumulate the integers into an int32 [F, B, 3] state
  (`segment_histogram(..., quantized=True)`) — integer addition is exact and
  order-independent, so subtraction-trick siblings, cross-engine results
  and cross-shard `psum`s of the histogram are all bit-exact;
- the f32 view is recovered only at the split-search boundary
  (`ops.split.dequantize_hist`), so the gain arithmetic is unchanged.

Overflow safety: an int32 histogram cell accumulates at most
rows_per_leaf * qmax, so the grid half-range is derived AT TRACE TIME as
`qmax = min(dtype_max, (2^31 - 1) // n_rows)` (`derive_qmax`) — the same
adaptive-width argument as the paper's 2-5 bit gradients at scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: integer grid half-range per requested packing width (the sign bit is
#: spent on the gradient's sign; hessians are non-negative and use [0, qmax])
QUANT_DTYPE_MAX = {"int8": 127, "int16": 32767}

#: bytes of gradient+hessian information per row fed to a histogram
#: dispatch, per packing width (f32 reference: 4 + 4)
QUANT_GH_BYTES = {"int8": 2, "int16": 4}
F32_GH_BYTES = 8


def derive_qmax(n_rows: int, dtype: str) -> int:
    """Trace-time integer grid half-range for `dtype` at `n_rows`.

    Caps the requested width by the int32 accumulator overflow bound
    (rows-per-leaf * max|q| < 2^31; the root leaf holds every row, so
    n_rows is the bound).  Raises when the surviving grid is too coarse
    to carry any gradient signal (< 2 levels per sign)."""
    if dtype not in QUANT_DTYPE_MAX:
        raise ValueError(
            "gradient_quant_dtype must be one of %s, got %r"
            % (sorted(QUANT_DTYPE_MAX), dtype))
    qmax = min(QUANT_DTYPE_MAX[dtype], (2 ** 31 - 1) // max(int(n_rows), 1))
    if qmax < 2:
        raise ValueError(
            "gradient_quantization: %d rows leave no int32 headroom for "
            "an integer histogram (rows * qmax must stay below 2^31)"
            % n_rows)
    return qmax


def stochastic_round(x: jax.Array, key: jax.Array, lo: float,
                     hi: float) -> jax.Array:
    """floor(x + u), u ~ U[0, 1) — unbiased (E[floor(x+u)] = x), clipped to
    [lo, hi] (the clip only fires at the grid edge, where rounding up would
    leave the grid).  Exact zero stays exactly zero (u < 1), so masked-out
    rows keep contributing nothing."""
    u = jax.random.uniform(key, x.shape, jnp.float32)
    return jnp.clip(jnp.floor(x + u), lo, hi)


def quantize_pair(g: jax.Array, h: jax.Array, qseed: jax.Array, qmax: float):
    """Quantize one class's (already masked) gradient/hessian vectors.

    Returns (qg, qh, qscale): integer-VALUED f32 vectors ready for the
    payload grad/hess columns, and the [2] f32 per-class scale factors
    (gradient, hessian) the split boundary dequantizes with.  Scales are
    per-iteration per-class maxima over the masked rows (the paper's
    max-scaling); an all-zero vector gets scale 1 so the division is
    always finite."""
    key = jax.random.PRNGKey(jnp.asarray(qseed, jnp.int32))
    kg, kh = jax.random.split(key)
    gmax = jnp.max(jnp.abs(g))
    hmax = jnp.max(h)
    gscale = jnp.where(gmax > 0, gmax, jnp.float32(qmax)) / qmax
    hscale = jnp.where(hmax > 0, hmax, jnp.float32(qmax)) / qmax
    qg = stochastic_round(g / gscale, kg, -qmax, qmax)
    qh = stochastic_round(h / hscale, kh, 0.0, qmax)
    return qg, qh, jnp.stack([gscale, hscale])
