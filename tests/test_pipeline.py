"""Async boosting pipeline (ISSUE 5): byte-identical models pipeline on
vs off across every boosting family, the tier-1 sync-audit pin (0
blocking host fetches on the tree->tree critical path at
pipeline_depth=1), flush barriers at model reads, deferred no-split
stop, and the bounded pack caches."""
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime import syncs


def _data(n=1500, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 10)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2
         + rng.standard_normal(n) * 0.3 > 0).astype(float)
    return X, y


def _train(extra, depth, rounds=10, y=None, valid=False, seed=0):
    X, yb = _data(seed=seed)
    y = yb if y is None else y
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "pipeline_depth": depth}
    params.update(extra)
    ds = lgb.Dataset(X, label=y)
    kwargs = {}
    if valid:
        Xv = X[:400] + 0.01
        kwargs = dict(valid_sets=[lgb.Dataset(Xv, label=y[:400],
                                              reference=ds)],
                      early_stopping_rounds=3)
    return lgb.train(params, ds, num_boost_round=rounds,
                     verbose_eval=False, **kwargs)


CONFIGS = {
    "gbdt": {"metric": "auc"},
    "bagging": {"bagging_freq": 2, "bagging_fraction": 0.7,
                "metric": "auc"},
    "dart": {"boosting": "dart", "drop_rate": 0.3, "metric": "auc"},
    "goss": {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.2,
             "learning_rate": 0.3, "metric": "auc"},
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_byte_identical_on_vs_off(name):
    extra = CONFIGS[name]
    b1 = _train(extra, depth=1, rounds=12)
    b0 = _train(extra, depth=0, rounds=12)
    assert b1.model_to_string() == b0.model_to_string()


def test_byte_identical_multiclass():
    rng = np.random.default_rng(3)
    ym = rng.integers(0, 3, 1500).astype(float)
    extra = {"objective": "multiclass", "num_class": 3,
             "metric": "multi_logloss"}
    b1 = _train(extra, depth=1, y=ym)
    b0 = _train(extra, depth=0, y=ym)
    assert b1.model_to_string() == b0.model_to_string()
    assert b1.num_trees() == 30


def test_byte_identical_with_valid_and_early_stopping():
    b1 = _train({"metric": "auc"}, depth=1, rounds=40, valid=True)
    b0 = _train({"metric": "auc"}, depth=0, rounds=40, valid=True)
    assert b1.model_to_string() == b0.model_to_string()
    assert b1.best_iteration == b0.best_iteration


def test_byte_identical_depth_2():
    b2 = _train({"metric": "auc"}, depth=2, rounds=12)
    b0 = _train({"metric": "auc"}, depth=0, rounds=12)
    assert b2.model_to_string() == b0.model_to_string()


def test_sync_audit_zero_critical_path_fetches_at_depth_1():
    """THE sync-audit pin: the fused fast path at pipeline_depth=1 runs
    the tree->tree loop with ZERO blocking host fetches — every per-tree
    fetch happens on the assembler thread, off the critical path.  The
    same loop at depth 0 pays exactly one critical-path fetch per tree."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "pipeline_depth": 1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    bst.update()          # warm-up: build + compile outside the window
    bst._engine.flush()
    syncs.reset()
    for _ in range(5):
        bst.update()
    snap = syncs.snapshot()
    assert snap["critical_path"] == 0, snap
    bst._engine.flush()
    assert syncs.snapshot()["by_label"].get("pipeline_drain") == 5
    assert bst.num_trees() == 6

    params["pipeline_depth"] = 0
    bst0 = lgb.Booster(params, lgb.Dataset(X, label=y))
    bst0.update()
    syncs.reset()
    for _ in range(5):
        bst0.update()
    snap0 = syncs.snapshot()
    assert snap0["critical_path"] == 5, snap0
    assert snap0["critical_by_label"] == {"tree_fetch": 5}

    # byte-identity of the two manually-driven runs
    assert bst.model_to_string() == bst0.model_to_string()


def test_model_reads_flush_the_pipeline():
    """update() may return with assemblies in flight; any model read
    (num_trees / current_iteration / save / dump / importance / predict)
    must drain first and see every dispatched tree."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "pipeline_depth": 2}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    for i in range(4):
        bst.update()
        assert bst.num_trees() == i + 1
        assert bst.current_iteration() == i + 1
    assert len(bst.feature_importance("split")) == 10
    assert bst.dump_model()["tree_info"] is not None
    p = bst.predict(X[:50])
    assert p.shape == (50,)


def test_deferred_no_split_stop_matches_synchronous():
    """min_gain_to_split too high for ANY split: the synchronous loop
    stops after appending one stump.  The pipelined loop discovers the
    stop at drain time and rolls back whatever it over-dispatched — the
    final model must be identical at every depth."""
    X, y = _data()
    ref = None
    for depth in (0, 1, 2):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "pipeline_depth": depth, "min_gain_to_split": 1e9}
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=10, verbose_eval=False)
        assert bst.num_trees() == 1, depth
        assert bst.current_iteration() == 1, depth
        s = bst.model_to_string()
        ref = s if ref is None else ref
        assert s == ref, depth


def test_eval_round_is_one_packed_fetch():
    """The eval-round satellite: training with a valid set at
    metric_freq=1 pays ONE eval_fetch per iteration (train+valid scores
    packed into a single device_get), not one per dataset."""
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    params = {"objective": "binary", "metric": "auc", "verbose": -1,
              "num_leaves": 15, "pipeline_depth": 1}
    v1 = lgb.Dataset(X[:300] + 0.01, label=y[:300], reference=ds)
    v2 = lgb.Dataset(X[300:600] + 0.01, label=y[300:600], reference=ds)
    syncs.reset()
    lgb.train(params, ds, num_boost_round=5, verbose_eval=False,
              valid_sets=[ds, v1, v2])
    snap = syncs.snapshot()
    # one packed eval fetch per iteration, none of them critical-path
    assert snap["by_label"].get("eval_fetch") == 5, snap
    assert snap["critical_by_label"].get("eval_fetch") is None


def test_pack_caches_are_bounded():
    from lightgbm_tpu.boosting import gbdt as g
    cache = type(g._PACK_CACHE)()
    for i in range(3 * g._PACK_CACHE_MAX):
        g._pack_cache_put(cache, ("spec", i), i)
    assert len(cache) == g._PACK_CACHE_MAX
    # LRU: the newest keys survive
    assert ("spec", 3 * g._PACK_CACHE_MAX - 1) in cache
    assert ("spec", 0) not in cache


def test_sentinel_disables_pipeline_but_trains():
    """sentinel_nonfinite != off is documented as pipeline-disabling:
    the tree fetch stays synchronous (critical path) so the sentinel
    screens every iteration before the next dispatch."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "pipeline_depth": 1, "sentinel_nonfinite": "abort"}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    bst.update()
    syncs.reset()
    for _ in range(3):
        bst.update()
    snap = syncs.snapshot()
    assert snap["critical_by_label"].get("tree_fetch") == 3, snap
    assert bst.num_trees() == 4


# ---------------------------------------------------------------------------
# ISSUE 24: the seconds update() waits for the device are a span
# ---------------------------------------------------------------------------

def _ring_spans():
    """The spans the program's seams opened (a collection, `host/gc`,
    comes when it comes: the tests of ISSUE 35 ask for it by name)."""
    from lightgbm_tpu.runtime import tracing
    return [e for e in tracing.export_chrome()["traceEvents"]
            if e["ph"] == "X" and e["name"] != "host/gc"]


@pytest.mark.parametrize("where", ["submit", "flush", "no_wait"])
def test_assembler_wait_span_only_when_it_waits(where):
    import threading

    from lightgbm_tpu.boosting.pipeline import TreeAssembler
    from lightgbm_tpu.runtime import tracing

    def settle(asm):
        while asm.pending:
            time.sleep(0.001)

    tracing.reset()
    release = threading.Event()
    asm = TreeAssembler(depth=1)
    with tracing.span("train/iteration") as ctx:
        if where == "no_wait":
            asm.submit(lambda: None)
            settle(asm)                 # nothing pending: nothing to wait for
            asm.submit(lambda: None)
            settle(asm)
            asm.flush()
        else:
            asm.submit(release.wait)
            threading.Timer(0.05, release.set).start()
            if where == "flush":
                asm.flush()
            else:
                asm.submit(lambda: None)    # depth 1: the first unit counts
                settle(asm)
    asm.flush()
    evs = _ring_spans()
    waits = [e for e in evs if e["name"] == "assembler/wait"]
    assert len([e for e in evs if e["name"] == "assembler/drain"]) \
        == (1 if where == "flush" else 2)
    if where == "no_wait":
        assert waits == []
    else:
        assert len(waits) == 1
        assert waits[0]["args"]["parent"] == ctx[1]
        assert waits[0]["args"]["pending"] == 1
        assert waits[0]["dur"] >= 20e3      # us: it did wait for the timer
    tracing.reset()


@pytest.mark.parametrize("name, parent", [
    ("fetch/pipeline_drain", "assembler/drain"),
    ("launch/gbdt.pack_fetch", "assembler/drain"),
    ("assembler/drain", "train/iteration"),
    ("launch/gbdt.step", "train/iteration"),
])
def test_drain_spans_hang_under_the_dispatching_iteration(name, parent):
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    bst = _train({}, depth=1, rounds=2)
    assert bst.num_trees() == 2
    evs = _ring_spans()
    by_id = {e["args"]["span"]: e for e in evs}
    found = [e for e in evs if e["name"] == name]
    assert len(found) >= 2
    main = {e["tid"] for e in evs if e["name"] == "train/iteration"}
    for e in found:
        up = by_id[e["args"]["parent"]]
        assert up["name"] == parent
        assert e["args"]["trace"] == up["args"]["trace"]
        # the host half runs on the assembler's thread, the launch of
        # the step on the dispatching one
        assert (e["tid"] in main) == (name == "launch/gbdt.step")
    tracing.reset()


def test_a_blocking_fetch_is_timed_not_only_counted():
    import jax.numpy as jnp

    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    before = syncs.snapshot()
    syncs.device_get(jnp.ones(3), label="t_fetch")
    syncs.block_until_ready(jnp.ones(3), label="t_barrier")
    assert syncs.delta(before)["by_label"] == {"t_fetch": 1, "t_barrier": 1}
    assert [e["name"] for e in _ring_spans()] \
        == ["fetch/t_fetch", "fetch/t_barrier"]
    tracing.reset()



# ---------------------------------------------------------------------------
# ISSUE 35: the drain says which unit it drains and what its thread did
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["train/iteration", "assembler/drain"])
def test_iteration_and_drain_carry_the_host_account(name):
    import os

    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    bst = _train({}, depth=1, rounds=3)
    assert bst.num_trees() == 3
    found = [e for e in _ring_spans() if e["name"] == name]
    assert len(found) == 3
    for e in found:
        assert 0 <= e["args"]["cpu_ns"] <= e["dur"] * 1e3 + 1e6
        if os.path.exists("/proc/thread-self/schedstat"):
            assert e["args"]["runq_ns"] >= 0
        # the process's account rides the dispatch thread's span only
        assert ("majflt" in e["args"]) == (name == "train/iteration")
    tracing.reset()


@pytest.mark.parametrize("classes", [1, 3])
def test_a_drain_names_its_unit_and_a_wait_the_unit_it_awaits(classes):
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    rounds = 3
    extra, y = {}, None
    if classes > 1:
        extra = {"objective": "multiclass", "num_class": classes}
        y = (np.arange(len(_data()[1])) % classes).astype(np.float64)
    bst = _train(extra, depth=1, rounds=rounds, y=y)
    assert bst.num_trees() == rounds * classes
    evs = _ring_spans()
    iters = [e for e in evs if e["name"] == "train/iteration"]
    numbers = [e["args"]["iteration"] for e in iters]
    assert numbers == list(range(numbers[0], numbers[0] + rounds))
    drains = [e for e in evs if e["name"] == "assembler/drain"]
    # every unit under the iteration that dispatched it, the trees in
    # the model's order
    by_id = {e["args"]["span"]: e for e in iters}
    assert [d["args"]["iteration"] for d in drains] \
        == [by_id[d["args"]["parent"]]["args"]["iteration"] for d in drains]
    assert [d["args"]["tree"] for d in drains] \
        == list(range(rounds * classes))
    waits = [e for e in evs if e["name"] == "assembler/wait"]
    assert waits
    drained = {d["args"]["tree"]: d for d in drains}
    for w in waits:
        # the awaited unit's drain ends inside the wait (or the wait
        # found it just ended)
        d = drained[w["args"]["awaits"]]
        assert d["ts"] + d["dur"] <= w["ts"] + w["dur"] + 1e3
    tracing.reset()
