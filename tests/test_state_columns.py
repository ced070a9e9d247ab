"""The state-column updates of the fused step (ops/state_columns.py): the
kernel form, run by the Pallas interpreter, against the lax form
(`segment.payload_col_write` and plain slices) bit for bit, the rule that
picks between them, the kernels under `shard_map` on four virtual
devices, and whole training runs under either form.  Interpret mode says
nothing about Mosaic: `test_tpu_compile.py` compiles the same kernels for
a described v5e, and `exp/smoke_tpu_kernels.py state_cols` runs them on
the chip."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import state_columns as sc

KERNEL = "pallas-interpret"
#: (lanes, state columns): the first, a middle and the last lane tile, a
#: pair of tiles across an edge; the Higgs, MS LTR and Epsilon cells' own
#: columns; index columns of the wide layout last
LAYOUTS = {
    "higgs-128": (128, (28, 29, 30, 31, 32, 33, 34, 35, 36, 37, 38)),
    "first-of-256": (256, (3, 5, 9)),
    "msltr-256": (256, (137, 138, 139, 140, 141, 142, 143, 147, 148)),
    "edge-of-256": (256, (125, 126, 127, 128, 129, 130)),
    "first-of-2048": (2048, (0, 7, 100)),
    "middle-of-2048": (2048, (1000, 1001, 1002, 1003, 1012)),
    "epsilon-2048": (2048, (2000, 2001, 2002, 2003, 2004, 2011, 2012)),
    "edge-of-2048": (2048, (1020, 1023, 1024, 1030)),
    "ragged-39": (39, (28, 29, 30, 31, 32, 38)),
}
ROWS = 700       # with the GUARD tail 964: blocks of 256 leave 196 over


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 256 rows, so a small payload has several and a ragged
    last one (the block height is read at trace time)."""
    def forget():
        for fn in (sc._state_cols_read, sc._state_cols_write,
                   sc._state_cols_axpy):
            fn.clear_cache()
    monkeypatch.setattr(sc, "_BLOCK_ROWS", 256)
    forget()
    yield
    forget()


def payload_of(lanes, cols, rows=ROWS, seed=0):
    """A payload whose state columns hold what each really holds: the
    last two an index split radix 4096 (the wide layout), the GUARD tail
    zeros but for the dead-slot index."""
    rng = np.random.default_rng(seed)
    pay = np.zeros((rows + seg.GUARD, lanes), np.float32)
    pay[:rows] = rng.standard_normal((rows, lanes))
    idx = rng.permutation(rows + seg.GUARD) + 16_000_000
    pay[:, cols[-1]] = idx // 4096
    pay[:, cols[-2]] = idx % 4096
    return pay


@pytest.mark.parametrize("layout", LAYOUTS)
def test_read_is_the_columns(layout, small_blocks):
    lanes, cols = LAYOUTS[layout]
    pay = payload_of(lanes, cols)
    got = np.asarray(sc.read_cols(jnp.asarray(pay), cols, KERNEL))
    assert got.dtype == np.float32 and got.shape == (len(cols), len(pay))
    np.testing.assert_array_equal(got, pay[:, cols].T)
    np.testing.assert_array_equal(
        got, np.asarray(sc.read_cols(jnp.asarray(pay), cols, "lax")))
    hi, lo = got[-1].astype(np.int64), got[-2].astype(np.int64)
    assert sorted(hi * 4096 + lo) == list(
        range(16_000_000, 16_000_000 + len(pay)))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_write_is_payload_col_write(layout, small_blocks):
    """Two columns (the fill's), three (a sampled fill's) and all of
    them, whatever the order: every other lane and the GUARD tail's other
    columns as they were."""
    lanes, cols = LAYOUTS[layout]
    pay = payload_of(lanes, cols)
    rng = np.random.default_rng(1)
    for take in (cols[1:3], (cols[-1], cols[0], cols[1]), cols):
        vals = rng.standard_normal((len(take), len(pay))).astype(np.float32)
        want = jnp.asarray(pay)
        for c, v in zip(take, vals):
            want = seg.payload_col_write(want, c, jnp.asarray(v))
        got = np.asarray(sc.write_cols(jnp.asarray(pay), take,
                                       jnp.asarray(vals), KERNEL))
        np.testing.assert_array_equal(got, np.asarray(want))
        rest = [c for c in range(lanes) if c not in take]
        np.testing.assert_array_equal(got[:, rest], pay[:, rest])


@pytest.mark.parametrize("moved", [True, False], ids=["tree", "stump"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_add_is_payload_col_write(layout, moved, small_blocks):
    """score[:, dst] += value * scale where the tree split, for every
    destination of the range (a class's score column, traced), source
    before, after and in another tile than the destination; a stump
    (`moved` false, or a scale of 0) moves nothing."""
    lanes, cols = LAYOUTS[layout]
    pay = payload_of(lanes, cols)
    for src in (cols[0], cols[-1]):
        for dst in cols[:-1]:
            for scale in (0.1, 0.0):
                upd = jnp.where(moved, jnp.asarray(pay[:, src])
                                * jnp.float32(scale), 0.0)
                want = np.asarray(seg.payload_col_write(
                    jnp.asarray(pay), dst, upd, "add"))
                got = np.asarray(sc.add_scaled(
                    jnp.asarray(pay), jnp.int32(dst), (cols[0], cols[-2]),
                    src, jnp.float32(scale), jnp.bool_(moved), KERNEL))
                np.testing.assert_array_equal(got, want)
                if not moved or scale == 0.0:
                    np.testing.assert_array_equal(got, pay)


@pytest.mark.parametrize("rows", [8, 120, 256 - seg.GUARD % 256, 2048, 4000])
def test_any_row_count(rows):
    """Fewer rows than a block, a whole number of blocks, the default
    block height with a ragged last block."""
    lanes, cols = LAYOUTS["edge-of-256"]
    pay = payload_of(lanes, cols, rows=rows, seed=rows)
    dev = jnp.asarray(pay)
    np.testing.assert_array_equal(
        np.asarray(sc.read_cols(dev, cols, KERNEL)), pay[:, cols].T)
    vals = jnp.asarray(pay[:, :2].T + 1.0)
    np.testing.assert_array_equal(
        np.asarray(sc.write_cols(dev, cols[2:4], vals, KERNEL)),
        np.asarray(sc.write_cols(dev, cols[2:4], vals, "lax")))
    args = (jnp.int32(cols[1]), (cols[0], cols[2]), cols[4],
            jnp.float32(-0.3), jnp.bool_(True))
    np.testing.assert_array_equal(
        np.asarray(sc.add_scaled(dev, *args, KERNEL)),
        np.asarray(sc.add_scaled(dev, *args, "lax")))


def test_values_no_arithmetic_survives():
    """NaN, infinities, a denormal, -0.0 and the largest float: a column
    moves through the transpose, which computes nothing."""
    lanes, cols = LAYOUTS["higgs-128"]
    odd = np.array([np.nan, np.inf, -np.inf, 1e-45, -0.0, 3.4e38,
                    16777215.0], np.float32)
    pay = payload_of(lanes, cols)
    pay[:odd.size, cols[0]] = odd
    got = np.asarray(sc.read_cols(jnp.asarray(pay), cols, KERNEL))
    assert got[0, :odd.size].tobytes() == odd.tobytes()
    vals = np.zeros((2, len(pay)), np.float32)
    vals[1, -odd.size:] = odd
    got = np.asarray(sc.write_cols(jnp.asarray(pay), cols[3:5],
                                   jnp.asarray(vals), KERNEL))
    assert got[-odd.size:, cols[4]].tobytes() == odd.tobytes()


@pytest.mark.parametrize("backend,lanes,cols,form", [
    ("cpu", 128, (28, 38), "lax"),
    ("tpu", 128, (28, 38), "pallas"),
    ("tpu", 2048, (2000, 2012), "pallas"),
    ("tpu", 256, (120, 132), "pallas"),         # a window of two tiles
    ("tpu", 512, (120, 260), "lax"),            # a many-class snapshot
    ("tpu", 39, (28, 38), "lax"),               # no whole lane tile
])
def test_form_by_platform_and_shape(monkeypatch, backend, lanes, cols, form):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sc.resolve_form(lanes, cols) == form


def test_kernels_keep_clear_of_the_segment_kernels_names():
    """The benchmark's kernel readers match a custom call's name, which
    is its jitted wrapper's, by these prefixes
    (benchmarks/layer_metrics/kernel.*.py)."""
    for fn in (sc._state_cols_read, sc._state_cols_write,
               sc._state_cols_axpy):
        assert not re.match(r"^%?_partition_segment|^%?_segment_histogram",
                            fn.__name__), fn.__name__
        assert fn.__name__.startswith("_state_cols_")


# -- four virtual devices -----------------------------------------------------

@pytest.fixture(scope="module")
def mesh4():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    return Mesh(np.array(devices[:4]), ("rows",))


@pytest.mark.parametrize("op", ["read", "write", "add"])
def test_device_blocks_under_shard_map_equal_one_device(mesh4, op):
    """Each device's block of rows (its own GUARD tail included) takes
    the kernel on its own, as `_FastState` wraps it on a mesh."""
    lanes, cols = LAYOUTS["edge-of-256"]
    block = 300 + seg.GUARD
    pay = payload_of(lanes, cols, rows=4 * block - seg.GUARD)
    by_rows, by_lanes = PS("rows", None), PS(None, "rows")
    dev = jax.device_put(jnp.asarray(pay), NamedSharding(mesh4, by_rows))
    vals = jnp.asarray(pay[:, 10:12].T)

    def on_blocks(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh4, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    if op == "read":
        got = on_blocks(lambda p: sc.read_cols(p, cols, KERNEL),
                        (by_rows,), by_lanes)(dev)
        want = sc.read_cols(jnp.asarray(pay), cols, "lax")
    elif op == "write":
        got = on_blocks(lambda p, v: sc.write_cols(p, cols[1:3], v, KERNEL),
                        (by_rows, by_lanes), by_rows)(dev, vals)
        want = sc.write_cols(jnp.asarray(pay), cols[1:3], vals, "lax")
    else:
        args = (jnp.int32(cols[0]), jnp.float32(0.1), jnp.bool_(True))
        span = (cols[0], cols[1])
        got = on_blocks(
            lambda p, d, s, o: sc.add_scaled(p, d, span, cols[4], s, o,
                                             KERNEL),
            (by_rows, PS(), PS(), PS()), by_rows)(dev, *args)
        want = sc.add_scaled(jnp.asarray(pay), args[0], span, cols[4],
                             *args[1:], "lax")
    assert len(got.addressable_shards) == 4
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- whole training runs ------------------------------------------------------

def _problem(kind, n=1500, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 9)).astype(np.float32)
    z = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * rng.standard_normal(n)
    if kind == "regression":
        return X, z, {}
    if kind == "multiclass":
        return X, np.digitize(z, [-0.5, 0.5]).astype(np.float64), {}
    if kind == "lambdarank":
        sizes = rng.integers(3, 40, 200)
        sizes = sizes[np.cumsum(sizes) <= n]
        sizes[-1] += n - sizes.sum()
        return X, np.digitize(z, [-1.0, 0.0, 0.8, 1.6]).astype(np.float64), \
            {"group": sizes}
    return X, (z > 0).astype(np.float64), {}


RUNS = {
    "binary": ("binary", {}),
    "regression": ("regression", {}),
    "lambdarank": ("lambdarank", {}),
    "multiclass": ("multiclass", {"num_class": 3}),
    "binary-bagging": ("binary", {"bagging_fraction": 0.7,
                                  "bagging_freq": 2}),
    "binary-goss": ("binary", {"boosting": "goss"}),
    "multiclass-goss": ("multiclass", {"num_class": 3, "boosting": "goss"}),
    "binary-quantized": ("binary", {"use_quantized_grad": True}),
    "binary-data-parallel": ("binary", {"tree_learner": "data"}),
    "binary-feature-parallel": ("binary", {"tree_learner": "feature"}),
    "binary-stumps": ("binary", {"min_gain_to_split": 1e9}),
}


def _train(run, form, monkeypatch, rounds=8):
    kind, more = RUNS[run]
    X, y, extra = _problem("binary" if kind == "binary" else kind)
    monkeypatch.setattr(sc, "resolve_form", lambda lanes, cols: form)
    params = {"objective": kind, "num_leaves": 7, "verbose": -1, "seed": 5,
              "min_data_in_leaf": 5, **more}
    bst = lgb.train(params, lgb.Dataset(X, label=y, **extra),
                    num_boost_round=rounds)
    engine = bst._engine
    assert engine._fast_active, "%s left the fast path" % run
    assert engine._fast.state_form == form
    return bst


@pytest.mark.parametrize("run", RUNS)
def test_models_are_byte_identical_under_either_form(run, monkeypatch):
    kernel = _train(run, KERNEL, monkeypatch)
    plain = _train(run, "lax", monkeypatch)
    assert kernel.model_to_string() == plain.model_to_string()
    assert kernel.num_trees() >= 1
    np.testing.assert_array_equal(kernel._engine._fast.raw_scores(),
                                  plain._engine._fast.raw_scores())


@pytest.mark.parametrize("run", ["binary-bagging", "lambdarank"])
def test_wide_index_layout_under_either_form(run, monkeypatch):
    """Past 2^24 rows the index rides two columns, radix 4,096: the
    ranking fill reads both out of the same tile and decodes them."""
    from lightgbm_tpu.boosting import gbdt as gb
    monkeypatch.setattr(gb, "_IDX_WIDE_THRESHOLD", 1)
    kernel = _train(run, KERNEL, monkeypatch, rounds=4)
    plain = _train(run, "lax", monkeypatch, rounds=4)
    assert kernel._engine._fast.wide_idx
    assert kernel.model_to_string() == plain.model_to_string()


def test_engines_keep_their_two_keys(monkeypatch):
    """The benchmark holds `grower.engines` equal to the configuration's
    (`benchmarks/drivers/train.py`): the state columns' form is an
    attribute of the fast state, not a third key."""
    bst = _train("binary", KERNEL, monkeypatch, rounds=1)
    assert sorted(bst._engine.engines) == ["histogram", "partition"]


@pytest.mark.parametrize("form,selects", [(KERNEL, False), ("lax", True)])
def test_step_holds_no_select_over_the_payload(form, selects, monkeypatch):
    """`gbdt.step` as lowered: under the kernel form no `select` has the
    payload's shape (the lax form has one a column write)."""
    fs = _train("binary", form, monkeypatch, rounds=1)._engine._fast
    rows, lanes = fs.payload.shape
    assert rows > sc._BLOCK_ROWS or lanes != sc.LANES
    text = fs._step.lower(fs.payload, fs.aux,
                          jnp.ones(9, jnp.bool_), jnp.float32(0.1),
                          jnp.int32(0)).as_text()
    whole = re.compile(r"stablehlo\.select.*tensor<%dx%dxf32>"
                       % (rows, lanes))
    assert bool(whole.search(text)) == selects
