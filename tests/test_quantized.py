"""Quantized-gradient training (gradient_quantization, ops/quantize.py).

Pins the tentpole contracts of the quantized histogram engine:
exact quantize/round-trip behavior, stochastic-rounding unbiasedness, the
int32 overflow guard, cross-engine bit-equality of the integer histogram
accumulation (portable scatter / contraction / Pallas-interpret int8
kernel), end-to-end quality parity against the f32 path, and the
default-off byte-identity guarantee.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops.quantize import (QUANT_DTYPE_MAX, derive_qmax,
                                       quantize_pair, stochastic_round)
from lightgbm_tpu.ops.split import dequantize_hist


# ---------------------------------------------------------------------------
# quantize / round-trip / overflow guard
# ---------------------------------------------------------------------------

def test_stochastic_round_unbiased():
    """E[floor(x + u)] = x: the mean quantization error over many draws
    vanishes (the paper's key requirement — biased rounding accumulates
    across 254 splits per tree; stochastic rounding does not)."""
    x = jnp.asarray(np.linspace(-5.0, 5.0, 41), jnp.float32)
    acc = np.zeros(x.shape, np.float64)
    reps = 4000
    for s in range(reps):
        acc += np.asarray(stochastic_round(x, jax.random.PRNGKey(s),
                                           -127.0, 127.0))
    err = acc / reps - np.asarray(x)
    assert np.abs(err).max() < 0.03, err


def test_stochastic_round_exact_on_grid():
    """Integers round to themselves deterministically (u < 1 never lifts
    an exact grid point), zero stays zero, and the edge clip holds."""
    x = jnp.asarray([-127.0, -3.0, 0.0, 5.0, 127.0], jnp.float32)
    for s in range(20):
        out = np.asarray(stochastic_round(x, jax.random.PRNGKey(s),
                                          -127.0, 127.0))
        np.testing.assert_array_equal(out, np.asarray(x))


def test_quantize_pair_roundtrip_bound():
    """Quantized values are integers on the grid, within range, and the
    dequantized reconstruction is within one grid step of the input
    (the deterministic part of the quantization error bound)."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(4096) * 0.7, jnp.float32)
    h = jnp.asarray(rng.random(4096), jnp.float32)
    for qmax in (127.0, 32767.0):
        qg, qh, qscale = quantize_pair(g, h, jnp.int32(7), qmax)
        qg, qh = np.asarray(qg), np.asarray(qh)
        gs, hs = float(qscale[0]), float(qscale[1])
        assert np.all(qg == np.round(qg)) and np.all(np.abs(qg) <= qmax)
        assert np.all(qh == np.round(qh)) and np.all(qh >= 0)
        assert np.abs(qg * gs - np.asarray(g)).max() <= gs * (1 + 1e-6)
        assert np.abs(qh * hs - np.asarray(h)).max() <= hs * (1 + 1e-6)


def test_quantize_pair_zero_mass_safe():
    qg, qh, qscale = quantize_pair(jnp.zeros(64), jnp.zeros(64),
                                   jnp.int32(0), 127.0)
    assert np.isfinite(np.asarray(qscale)).all()
    assert not np.asarray(qg).any() and not np.asarray(qh).any()


def test_derive_qmax_overflow_guard():
    """rows-per-leaf x max|q| must stay below 2^31 (trace-time check)."""
    assert derive_qmax(200_000, "int8") == 127
    assert derive_qmax(200_000, "int16") == (2 ** 31 - 1) // 200_000
    assert derive_qmax(10_500_000, "int16") == (2 ** 31 - 1) // 10_500_000
    with pytest.raises(ValueError, match="headroom"):
        derive_qmax(2 ** 31, "int8")
    with pytest.raises(ValueError, match="gradient_quant_dtype"):
        derive_qmax(1000, "int4")


def test_dequantize_hist_channels():
    hist = jnp.asarray(np.arange(24).reshape(2, 4, 3), jnp.int32)
    out = np.asarray(dequantize_hist(hist, 0.5, 0.25))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out[..., 0], np.arange(24).reshape(2, 4, 3)[..., 0] * 0.5)
    np.testing.assert_allclose(out[..., 1], np.arange(24).reshape(2, 4, 3)[..., 1] * 0.25)
    np.testing.assert_allclose(out[..., 2], np.arange(24).reshape(2, 4, 3)[..., 2])


# ---------------------------------------------------------------------------
# integer histogram engines agree to the bit
# ---------------------------------------------------------------------------

F, B = 5, 16
COLS = dict(grad_col=F, hess_col=F + 1, cnt_col=F + 2)
P = F + 4


def _quant_payload(n_pad, seed=0, qmax=127):
    rng = np.random.default_rng(seed)
    pay = np.zeros((n_pad + seg.GUARD, P), np.float32)
    pay[:n_pad, :F] = rng.integers(0, B, size=(n_pad, F))
    pay[:n_pad, F] = rng.integers(-qmax, qmax + 1, n_pad)
    pay[:n_pad, F + 1] = rng.integers(0, qmax + 1, n_pad)
    pay[:n_pad, F + 2] = 1.0
    return jnp.asarray(pay)


@pytest.mark.parametrize("start,count", [(0, 1000), (256, 700), (100, 37),
                                         (0, 0), (513, 256), (7, 1)])
def test_quant_hist_matches_f32_engine(start, count):
    """Integer accumulation == the f32 engine on integer-valued payloads
    (both are exact there), with an int32 result."""
    pay = _quant_payload(1024)
    hq = seg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                               num_features=F, num_bins=B, quantized=True,
                               **COLS)
    hf = seg.segment_histogram(pay, jnp.int32(start), jnp.int32(count),
                               num_features=F, num_bins=B, **COLS)
    assert hq.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hq),
                                  np.asarray(hf).astype(np.int64))


def test_quant_hist_batched_matches_single():
    pay = _quant_payload(1024, seed=5)
    starts = jnp.asarray([0, 128, 900], jnp.int32)
    counts = jnp.asarray([100, 600, 0], jnp.int32)
    hb = seg.segment_histogram_batched(pay, starts, counts, num_features=F,
                                       num_bins=B, quantized=True, **COLS)
    assert hb.dtype == jnp.int32
    for k in range(3):
        hk = seg.segment_histogram(pay, starts[k], counts[k], num_features=F,
                                   num_bins=B, quantized=True, **COLS)
        np.testing.assert_array_equal(np.asarray(hb[k]), np.asarray(hk))
    assert not np.asarray(hb[2]).any()


# ---------------------------------------------------------------------------
# end-to-end training
# ---------------------------------------------------------------------------

def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    npos = y.sum()
    nneg = len(y) - npos
    return (ranks[y > 0].sum() - npos * (npos + 1) / 2) / max(npos * nneg, 1)


def _binary_problem(n, f=20, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    logit = (X @ w) * 0.5 + 0.4 * X[:, 0] * X[:, 1] + 0.3 * np.abs(X[:, 2])
    logit += rng.standard_normal(n).astype(np.float32) * 0.8
    y = (logit > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "metric": "auc", "verbose": -1, "seed": 11}


@pytest.fixture(scope="module")
def auc_parity_baseline():
    """The f32 reference run for the AUC-parity pins — trained ONCE and
    shared by both dtype parametrizations (the baseline is identical
    across them; retraining it per-param was pure tier-1 wall time)."""
    X, y = _binary_problem(24_000)
    Xtr, ytr, Xte, yte = X[:20_000], y[:20_000], X[20_000:], y[20_000:]
    params = dict(BASE, num_leaves=31)
    bf = lgb.train(dict(params), lgb.Dataset(Xtr, label=ytr),
                   num_boost_round=11)
    return Xtr, ytr, Xte, yte, params, _auc(yte, bf.predict(Xte))


@pytest.mark.parametrize("qdtype", ["int16", "int8"])
def test_quant_training_auc_parity(qdtype, auc_parity_baseline):
    """Quantized training tracks the f32 path on held-out AUC (the
    paper's headline claim) at a tier-1-sized slice of the bench config;
    the full 200k-row bench-config pin is the `slow` test below."""
    Xtr, ytr, Xte, yte, params, auc_f = auc_parity_baseline
    bq = lgb.train(dict(params, gradient_quantization=True,
                        gradient_quant_dtype=qdtype),
                   lgb.Dataset(Xtr, label=ytr), num_boost_round=11)
    assert bq._engine._quant_enabled
    assert bq._engine._fast_active
    auc_q = _auc(yte, bq.predict(Xte))
    assert auc_f > 0.75          # the problem is learnable
    assert abs(auc_q - auc_f) <= 0.002, (auc_q, auc_f)
    # the telemetry the bench reports
    rep = bq._engine.quant_report
    assert rep["hist_gh_bytes_per_row"] == (2 if qdtype == "int8" else 4)
    assert rep["hist_bytes_reduction_vs_f32"] == \
        (4.0 if qdtype == "int8" else 2.0)


@pytest.mark.slow
def test_quant_training_auc_parity_bench_config():
    """The acceptance pin: gradient_quantization=true on the 200k-row
    bench config (28 features, 255 leaves, 255 bins, lr 0.1) reaches
    |dAUC| <= 0.002 vs the f32 path at iteration 11."""
    X, y = _binary_problem(250_000, f=28, seed=7)
    Xtr, ytr, Xte, yte = X[:200_000], y[:200_000], X[200_000:], y[200_000:]
    params = {"objective": "binary", "metric": "auc", "num_leaves": 255,
              "max_bin": 255, "learning_rate": 0.1, "verbose": -1}
    bf = lgb.train(dict(params), lgb.Dataset(Xtr, label=ytr),
                   num_boost_round=11)
    auc_f = _auc(yte, bf.predict(Xte))
    for qdtype in ("int16", "int8"):
        bq = lgb.train(dict(params, gradient_quantization=True,
                            gradient_quant_dtype=qdtype),
                       lgb.Dataset(Xtr, label=ytr), num_boost_round=11)
        assert bq._engine._quant_enabled
        auc_q = _auc(yte, bq.predict(Xte))
        assert abs(auc_q - auc_f) <= 0.002, (qdtype, auc_q, auc_f)


def test_quant_default_off_byte_identity():
    """With gradient_quantization unset (or explicitly false) the model
    text is byte-identical to current main's f32 path — the quantized
    machinery must leave zero trace on the default path."""
    X, y = _binary_problem(6_000)
    params = dict(BASE, num_leaves=15)
    m_unset = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=5).model_to_string()
    m_false = lgb.train(dict(params, gradient_quantization=False),
                        lgb.Dataset(X, label=y),
                        num_boost_round=5).model_to_string()
    assert m_unset == m_false
    m_quant = lgb.train(dict(params, gradient_quantization=True),
                        lgb.Dataset(X, label=y),
                        num_boost_round=5).model_to_string()
    assert m_quant != m_unset  # sanity: the knob actually engages


def test_quant_deterministic_across_runs():
    """Same config + seed => identical quantized models (the stochastic
    rounding stream is keyed by (seed, iteration, class), not wall
    clock)."""
    X, y = _binary_problem(6_000)
    params = dict(BASE, num_leaves=15, gradient_quantization=True,
                  gradient_quant_dtype="int8")
    m1 = lgb.train(dict(params), lgb.Dataset(X, label=y),
                   num_boost_round=4).model_to_string()
    m2 = lgb.train(dict(params), lgb.Dataset(X, label=y),
                   num_boost_round=4).model_to_string()
    assert m1 == m2


def test_quant_frontier_batch_compatible():
    """Quantized mode composes with the frontier-batched grower (the
    batched dispatch carries the int32 histograms)."""
    X, y = _binary_problem(8_000)
    params = dict(BASE, num_leaves=31, gradient_quantization=True,
                  gradient_quant_dtype="int8", tpu_frontier_batch=4)
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                    num_boost_round=4)
    assert bst._engine._quant_enabled
    rounds = bst._engine.split_rounds_per_tree()
    assert rounds is not None and rounds < 30  # batching engaged
    assert _auc(y, bst.predict(X)) > 0.75


def test_quant_goss_falls_back_with_warning():
    """GOSS amplifies gradients inside its fused step — quantization
    declines (warned) and training stays f32."""
    X, y = _binary_problem(6_000)
    bst = lgb.train(dict(BASE, num_leaves=15, boosting="goss",
                         gradient_quantization=True),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert not bst._engine._quant_enabled
    assert bst.num_trees() == 3


def test_quant_bagging_and_multiclass():
    """Bagging masks ride into the quantized columns (0 stays exactly 0
    under stochastic rounding); multiclass draws per-class scales."""
    X, y = _binary_problem(8_000)
    bst = lgb.train(dict(BASE, num_leaves=15, bagging_fraction=0.6,
                         bagging_freq=1, gradient_quantization=True),
                    lgb.Dataset(X, label=y), num_boost_round=4)
    assert bst._engine._quant_enabled
    rng = np.random.default_rng(2)
    y3 = rng.integers(0, 3, len(y)).astype(np.float64)
    bst3 = lgb.train({"objective": "multiclass", "num_class": 3,
                      "num_leaves": 7, "verbose": -1,
                      "gradient_quantization": True},
                     lgb.Dataset(X, label=y3), num_boost_round=3)
    assert bst3._engine._quant_enabled
    assert bst3.num_trees() == 9
