"""Compile/retrace ledger (ISSUE 10, runtime/xla_obs.py).

Pins the tentpole's acceptance gates:

* wrapper semantics — `xla_obs.jit` counts compiles vs cache hits,
  preserves donate/static/`__wrapped__` behavior, and feeds the
  `lgbm_xla_*` / `lgbm_program_cache_events_total` metric families;
* the STEADY-STATE ZERO-RETRACE pin — after warmup, further training
  iterations (gbdt, pipeline depth 0 and 1) and further serving batches
  compile NOTHING through any registered site;
* a FORCED shape change is detected and named: the retrace record (and
  the `lgbm_xla_retraces_total` labels) carry the site and the shape
  delta that triggered it;
* serving responses carry `compiled: true/false` and prewarm compiles
  are tagged under `site="serving.prewarm"`.
"""
import functools

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime import telemetry, xla_obs


def _synth(n=3000, f=8, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------------
# wrapper semantics
# ---------------------------------------------------------------------------

def test_jit_counts_compiles_and_hits():
    import jax.numpy as jnp

    @functools.partial(xla_obs.jit, site="t.unit_counts",
                       static_argnames=("k",))
    def f(x, *, k):
        return x * k

    rec = xla_obs.LEDGER.register("t.unit_counts")
    c0, calls0 = rec.compiles, rec.calls
    f(jnp.ones(8), k=2)                      # compile
    f(jnp.ones(8), k=2)                      # hit
    f(jnp.ones(8), k=3)                      # new static arg -> compile
    f(jnp.ones(16), k=2)                     # new shape -> compile
    assert rec.compiles - c0 == 3
    assert rec.calls - calls0 == 4
    assert rec.last_sig == ("f32[16]", "k=2")
    assert rec.compile_seconds > 0
    # metrics landed in the registry families
    assert telemetry.counter("lgbm_xla_compiles_total").value(
        site="t.unit_counts") >= 3
    assert telemetry.counter("lgbm_program_cache_events_total").value(
        site="t.unit_counts", event="hit") >= 1
    st = telemetry.histogram("lgbm_xla_compile_seconds").state(
        site="t.unit_counts")
    assert st["count"] >= 3


def test_jit_requires_site_and_exposes_wrapped():
    import jax.numpy as jnp

    with pytest.raises(ValueError):
        xla_obs.jit(lambda x: x, site="")

    @functools.partial(xla_obs.jit, site="t.wrapped_outer")
    def outer(x):
        return inner.__wrapped__(x) * 2      # the gbdt inline pattern

    @functools.partial(xla_obs.jit, site="t.wrapped_inner")
    def inner(x):
        return x + 1

    out = outer(jnp.ones(4))
    assert float(np.asarray(out)[0]) == 4.0
    # the inlined trace notes the inner site but is not its own compile
    # event (it rode the outer program's compile)
    assert xla_obs.LEDGER.register("t.wrapped_outer").compiles >= 1
    assert xla_obs.LEDGER.register("t.wrapped_inner").compiles == 0


def test_sig_delta_names_the_change():
    assert xla_obs.sig_delta(None, ("f32[8]",)) == "first_trace"
    d = xla_obs.sig_delta(("f32[8]", "k=2"), ("f32[16]", "k=2"))
    assert d == "arg0:f32[8]->f32[16]"
    d2 = xla_obs.sig_delta(("f32[8]",), ("f32[8]", "k=3"))
    assert "arg1" in d2 and "<absent>" in d2


def test_cache_event_and_snapshot_delta():
    xla_obs.cache_event("t.pycache", "miss")
    xla_obs.cache_event("t.pycache", "hit", 3)
    rec = xla_obs.LEDGER.register("t.pycache")
    assert rec.cache_misses >= 1 and rec.cache_hits >= 3
    snap = xla_obs.snapshot()
    assert xla_obs.delta(snap) == {}
    j = xla_obs.LEDGER.to_json()
    assert "t.pycache" in j["sites"]
    assert j["sites"]["t.pycache"]["cache_hits"] >= 3


def test_forced_retrace_names_site_and_delta():
    import jax.numpy as jnp

    @functools.partial(xla_obs.jit, site="t.retrace")
    def f(x):
        return x.sum()

    f(jnp.ones(8))
    n0 = len(xla_obs.LEDGER.retraces)
    xla_obs.mark_steady(True)
    try:
        f(jnp.ones(8))                       # hit: no violation
        assert len(xla_obs.LEDGER.retraces) == n0
        f(jnp.ones(32))                      # FORCED shape change
    finally:
        xla_obs.mark_steady(False)
    assert len(xla_obs.LEDGER.retraces) == n0 + 1
    ev = xla_obs.LEDGER.retraces[-1]
    assert ev["site"] == "t.retrace"
    assert "f32[8]->f32[32]" in ev["delta"]
    # and the metric labels name both
    assert telemetry.counter("lgbm_xla_retraces_total").value(
        site="t.retrace", delta=ev["delta"]) >= 1


# ---------------------------------------------------------------------------
# the steady-state zero-retrace pins (ISSUE acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [0, 1])
def test_train_steady_state_compiles_nothing(depth):
    """gbdt at pipeline depth 0 and 1: after warmup, N further
    iterations trace NOTHING through any registered site."""
    X, y = _synth()
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "pipeline_depth": depth, "verbose": -1},
                      lgb.Dataset(X, label=y))
    for _ in range(3):                        # warmup: compiles expected
        bst.update()
    bst._engine.flush()
    snap = xla_obs.snapshot()
    for _ in range(5):                        # N further iterations
        bst.update()
    bst._engine.flush()
    assert xla_obs.delta(snap) == {}, \
        "steady-state training recompiled: %r" % xla_obs.delta(snap)


def test_serve_steady_state_and_forced_shape_change():
    """The predictor's shape-bucketed cache: M further batches at warm
    bucket shapes compile nothing; a batch landing in a NEW bucket is a
    detected retrace naming predictor.tree_parallel and the row delta."""
    from lightgbm_tpu.models.device_predictor import DevicePredictor

    X, y = _synth(600, 6, seed=11)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 13,
                       "verbose": -1}, lgb.Dataset(X, label=y))
    for _ in range(3):
        bst.update()
    bst._engine.flush()
    dp = DevicePredictor(bst._model)
    dp.predict_raw(X[:40])                    # warm bucket 64
    dp.predict_raw(X[:200])                   # warm bucket 256
    snap = xla_obs.snapshot()
    for rows in (40, 50, 64, 200, 180):       # M further batches, warm
        dp.predict_raw(X[:rows])
    assert xla_obs.delta(snap) == {}, xla_obs.delta(snap)

    n0 = len(xla_obs.LEDGER.retraces)
    xla_obs.mark_steady(True)
    try:
        dp.predict_raw(X[:600])               # NEW bucket (1024): forced
    finally:
        xla_obs.mark_steady(False)
    new = [e for e in xla_obs.LEDGER.retraces[n0:]
           if e["site"] == "predictor.tree_parallel"]
    assert new, "forced shape change was not detected"
    assert "1024" in new[-1]["delta"]


def test_program_cache_hit_events_flow():
    """Python-side pack-cache traffic lands in the events family during
    ordinary training."""
    before = telemetry.counter("lgbm_program_cache_events_total").value(
        site="gbdt.pack_cache", event="hit")
    X, y = _synth(2000, 6, seed=23)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "verbose": -1}, lgb.Dataset(X, label=y))
    for _ in range(4):
        bst.update()
    bst._engine.flush()
    after = telemetry.counter("lgbm_program_cache_events_total").value(
        site="gbdt.pack_cache", event="hit")
    assert after > before


# ---------------------------------------------------------------------------
# serving wiring (the ISSUE small-fix satellite)
# ---------------------------------------------------------------------------

def test_serving_compiled_flag_and_prewarm_tag(tmp_path):
    from lightgbm_tpu.models.gbdt_model import GBDTModel
    from lightgbm_tpu.models.tree import Tree
    from lightgbm_tpu.runtime.serving import ServingRuntime

    rng = np.random.default_rng(7)
    model = GBDTModel()
    model.num_class = 1
    model.num_tree_per_iteration = 1
    model.max_feature_idx = 5
    model.objective_str = "binary sigmoid:1"
    # an unusual tree count -> packed shapes no other test traced
    for _ in range(7):
        t = Tree(9)
        while t.num_leaves < 9:
            leaf = int(rng.integers(0, t.num_leaves))
            t.split(leaf, int(rng.integers(0, 6)), 0,
                    float(rng.standard_normal()), 0.01, 0.01,
                    10, 10, 1.0, 2, False)
        model.trees.append(t)

    pre0 = telemetry.counter("lgbm_program_cache_events_total").value(
        site="serving.prewarm", event="compile")
    with ServingRuntime(model_str=model.save_model_to_string(),
                        batch_window_s=0.001) as rt:
        # prewarm compiled the smallest bucket for this fresh model shape
        assert telemetry.counter(
            "lgbm_program_cache_events_total").value(
                site="serving.prewarm", event="compile") > pre0
        r1 = rt.predict(rng.standard_normal((40, 6)))   # new bucket (64)
        assert r1.compiled is True
        r2 = rt.predict(rng.standard_normal((40, 6)))   # warm bucket
        assert r2.compiled is False


# ---------------------------------------------------------------------------
# ISSUE 24: every launch is a span, and the fused step's phases are scopes
# ---------------------------------------------------------------------------

def test_every_call_is_a_launch_span_beside_its_compile_event():
    import jax.numpy as jnp
    from lightgbm_tpu.runtime import tracing

    f = xla_obs.jit(lambda x: x + 1, site="t.launch_span")
    tracing.reset()
    with tracing.span("caller") as ctx:
        f(jnp.ones(4))                          # compiles
        f(jnp.ones(4))                          # hit
    prev = tracing.set_enabled(False)
    try:
        f(jnp.ones(4))                          # recorder off: no span
    finally:
        tracing.set_enabled(prev)
    evs = [e for e in tracing.export_chrome()["traceEvents"]
           if e["ph"] == "X"]
    launches = [e for e in evs if e["name"] == "launch/t.launch_span"]
    compiles = [e for e in evs if e["name"] == "xla compile t.launch_span"]
    assert len(launches) == 2 and len(compiles) == 1
    assert all(e["args"]["parent"] == ctx[1] for e in launches + compiles)
    # the compiling launch is the long one: the compile event says why
    assert launches[0]["dur"] >= compiles[0]["dur"] * 0.5
    tracing.reset()


def _step_op_names(extra, categorical=()):
    """[(opcode, `op_name`)] of `gbdt.step` lowered for a small booster
    (instructions of one array; a tuple's shape has spaces in it)."""
    import re
    return re.findall(r'= \S+ ([\w-]+)\(.*op_name="([^"]*)"',
                      _step_hlo_text(extra, categorical))


def _step_hlo_text(extra, categorical=()):
    """`gbdt.step` lowered for a small booster, as HLO text with the
    `op_name` metadata."""
    import jax.numpy as jnp
    from jax._src.lib import xla_client

    X, y = _synth(n=1024, f=6)
    for j in categorical:           # twelve codes in place of the values
        X[:, j] = np.floor(np.abs(X[:, j]) * 5) % 12
    params = dict({"objective": "binary", "num_leaves": 7, "verbose": -1},
                  **extra)
    # a ranking objective wants query groups: 16 queries of 64 rows
    group = np.full(16, 64) if params["objective"] == "lambdarank" else None
    bst = lgb.Booster(params, lgb.Dataset(
        X, label=y, group=group, params=params,
        categorical_feature=list(categorical) or "auto"))
    bst.update()
    eng = bst._engine
    fs = eng._fast
    assert eng._fast_active
    lowered = fs._step.lower(fs.payload, fs.aux, eng._feature_sample(),
                             jnp.float32(0.1), jnp.int32(0))
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    return lowered.compiler_ir(dialect="hlo").get_hlo_module().to_string(opts)


@pytest.fixture(scope="module")
def serial_step_op_names():
    return _step_op_names({})


@pytest.fixture(scope="module")
def mesh_step_op_names():
    return _step_op_names({"tree_learner": "data", "num_machines": 4})


def test_phase_helper_knows_its_names():
    from lightgbm_tpu.boosting import grower2
    assert len(set(grower2.PHASES)) == 12
    with pytest.raises(ValueError):
        grower2.phase("probe")


@pytest.mark.parametrize("name", ["grad", "root_hist", "partition", "hist",
                                  "subtract", "split_search", "tree_update",
                                  "score"])
def test_fused_step_carries_a_scope_for_every_phase(serial_step_op_names,
                                                    name):
    assert [n for _, n in serial_step_op_names if "lgbm.%s" % name in n]
    # the serial step reduces nothing across devices
    assert not [n for _, n in serial_step_op_names if "lgbm.allreduce" in n]


@pytest.mark.parametrize("name", ["grad_pairs", "grad_permute"])
def test_ranking_step_scopes_its_pairwise_program_and_its_permutations(
        name, serial_step_op_names):
    """An objective that couples rows enters two phases of its own
    inside `lgbm.grad`: every gather and scatter of its fill is a
    permutation, and a row-wise objective's step has neither scope."""
    names = _step_op_names({"objective": "lambdarank"})
    inner = [(op, n) for op, n in names if "lgbm.%s" % name in n]
    assert inner and all("lgbm.grad/" in n for _, n in inner)
    moved = {op for op, n in names
             if op in ("gather", "scatter") and "lgbm.grad/" in n
             and "lgbm.grad_permute" not in n}
    assert not moved
    assert not [n for _, n in serial_step_op_names if "lgbm.%s" % name in n]


def test_categorical_step_scopes_its_search_and_loops_over_no_bins(
        serial_step_op_names):
    """The categorical search enters `lgbm.cat_search` inside
    `lgbm.split_search`: its one sort is there and no loop is (the walk
    over the sorted bins is unrolled, `max_cat_threshold` steps whatever
    the bin count); a step without a categorical column has no such
    scope, so it compiles to the program it compiled to before."""
    import re
    text = _step_hlo_text({"min_data_per_group": 5, "cat_smooth": 1.0},
                          categorical=(1, 4))
    inner = re.findall(r'^.* = .*?\)? ([\w-]+)\(.*op_name="([^"]*lgbm\.'
                       r'cat_search[^"]*)"', text, re.M)
    # (the sort's comparator and the calls of inlined functions are
    # computations of their own and keep the innermost scope only)
    assert inner and all("lgbm.split_search/" in n
                         or n.startswith("lgbm.cat_search") for _, n in inner)
    ops = [op for op, _ in inner]
    assert ops.count("sort") == 2                       # root, children
    assert not {"while", "gather", "scatter"} & set(ops)
    assert not [n for _, n in serial_step_op_names if "lgbm.cat_search" in n]


@pytest.mark.parametrize("name", ["allreduce", "root_hist", "hist",
                                  "partition", "split_search"])
def test_mesh_step_uses_the_same_names_and_scopes_its_collectives(
        mesh_step_op_names, name):
    assert [n for _, n in mesh_step_op_names if "lgbm.%s" % name in n], name
    if name == "allreduce":
        # every collective of the grower sits under the scope, the
        # histogram's reduce-scatter among them
        reduced = {op: n for op, n in mesh_step_op_names
                   if op in ("all-reduce", "reduce-scatter", "all-gather")}
        assert "reduce-scatter" in reduced or "all-reduce" in reduced
        assert all("lgbm.allreduce" in n for n in reduced.values()), reduced

