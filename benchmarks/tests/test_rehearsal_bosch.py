"""The `bosch-train` cell end to end on the CPU: the configuration's own
file at its own width (968 columns in 52 stations, four cells in five
NaN, 0.58% positives) cut to the rows a CPU trains, through the
`run_cell` the command line calls, with its three readers; the task's
generator held to what the configuration states of it; and faults in the
missing-value path read as not correct."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result
from test_rehearsal_expo_cat import DRIVER_COMPARED

MISSING_READERS = {"split.missing_share", "split.default_left_share",
                   "partition.missing_row_share"}


def load_task():
    from benchmarks.run import load_module
    return load_module(os.path.join(BENCH, "tasks", "binary_missing.py"))


@pytest.fixture()
def bosch_tree(bench_tree):
    """The rehearsal tree with the real `bosch` configuration, rows cut for
    the CPU (whose engines are the portable ones) and the hessian floor
    with them (20,000 rows hold a fiftieth of a million's hessian), the
    gauges read in quarters, so that 21 distinct values a column leave the
    bins nothing to merge and the program's search sees every threshold
    the plain one sees, a tolerance on tree 0's values for leaves of a
    hessian of 2 summed in float32 row after row by the portable engine
    (6.8e-4 read; the chip's limit is the file's), the real `train` mix
    asked for fewer iterations, and the manifest's own three entries for
    the cell."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in real["configs"] if c["name"] == "bosch")
    cell = next(w for w in real["workloads"] if w["name"] == "bosch-train")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("bosch", "train", 1)
    assert entry["reduced"] == ["num_iterations"]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert (config["rows"], config["features"], config["heldout_rows"]) \
        == (1000000, 968, 183747)
    assert config["task"] == "binary_missing"
    assert config["params"] == {
        "objective": "binary", "num_leaves": 255, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100, "sparse_threshold": 1.0}
    assert config["engines"]["histogram"] == "pallas"
    assert config["engines"]["partition"] == "pallas-blocks"
    config.update(rows=20000, heldout_rows=12000, quality_band=[0.8, 1.0],
                  quality_at_iter=4, gauge_steps=4, leaf_value_atol=5e-3,
                  leaf_value_largest_atol=5e-3,
                  engines={"histogram": "lax", "partition": "lax"})
    config["params"] = dict(config["params"], min_sum_hessian_in_leaf=2.0,
                            verbose=-1)
    path = os.path.join(bench_tree["root"], "bosch-cut.json")
    json.dump(config, open(path, "w"))
    traffic = os.path.join(bench_tree["bench_dir"], "traffic")
    mix = json.load(open(os.path.join(traffic, "train.json")))
    json.dump(dict(mix, warmup_iters=2, min_iters=2, trace_iters=2),
              open(os.path.join(traffic, "train-two.json"), "w"))
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "bosch-cut", "file": path})
    manifest["workloads"].append({"name": "bosch-cut-train",
                                  "config": "bosch-cut",
                                  "traffic": "train-two", "chips": 1})
    ours = [dict(m, workloads=["bosch-cut-train"])
            for m in real["per_layer"] if m["name"] in MISSING_READERS]
    assert [m["name"] for m in real["per_layer"][-3:]] \
        == ["split.missing_share", "split.default_left_share",
            "partition.missing_row_share"]
    assert all(m["workloads"] == ["bosch-train"]
               and m["moves"] == "train_s_per_iter"
               for m in real["per_layer"][-3:])
    assert {m["layer"] for m in ours} == {"grower-split-search",
                                          "segment-kernels"}
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] not in MISSING_READERS] + ours
    json.dump(manifest, open(bench_tree["manifest_path"], "w"))
    return bench_tree


def _detail(tree, trace=0):
    return json.load(open(os.path.join(
        tree["root"], "chiprun_out", "bench",
        "bosch-cut-train.s3.t%d.json" % trace)))["detail"]


def test_bosch_cell_untraced(bosch_tree, capfd):
    result = run_tiny(bosch_tree, "bosch-cut-train", seconds=0.5)
    check_result(result, trace=False)
    assert set(result["metrics"]) == {"train_s_per_iter", "heldout_quality",
                                      "setup_s"}
    detail = _detail(bosch_tree)
    checks = detail["verify"][0]["checks"]
    # 968 bin columns and 10 value columns; the chip pads them to 1,024
    assert checks["payload"]["lanes"] == 978
    assert checks["fast_path"]
    tree0 = checks["tree0"]
    assert tree0["counts_ok"] and tree0["max_count_diff"] == 0
    assert tree0["nan_aware_nodes"] == tree0["leaves"] - 1
    assert 0 < tree0["default_left_nodes"] < tree0["nan_aware_nodes"]
    # ended by the hessian floor, not by num_leaves
    assert 8 <= tree0["leaves"] <= 57
    value, limit = tree0["root_gain_rel_diff"]
    assert value <= 1e-6 and limit == 1e-4
    assert tree0["root_feature"] == tree0["root_feature_plain"] \
        or value <= 1e-9
    assert tree0["root_default_left"] == tree0["root_default_left_plain"]
    # the task's own three entries, after the driver's
    assert list(result["compared"])[:5] == DRIVER_COMPARED
    assert result["compared"]["tree0_root_gain_rel_diff"] == [value, limit]
    assert result["compared"]["tree0_largest_value_diff"] \
        == tree0["largest_value_diff"]
    assert result["compared"]["tree0_root_default_left"] \
        == [tree0["root_default_left"], tree0["root_default_left_plain"]]
    # the root is cut on a reading of the final test, which a third of the
    # parts skip
    assert 0.30 < tree0["root_nan_rows"] / 20000 < 0.35
    assert 0.003 < tree0["positive_share"] < 0.009
    err = capfd.readouterr().err
    assert "[bench] compared tree0_root_gain_rel_diff" in err
    assert "[bench] compared tree0_root_default_left" in err
    # both limits on tree 0's values: the quartile and the largest
    assert "[bench] compared tree0_largest_value_diff" in err
    largest, limit = tree0["largest_value_diff"]
    assert tree0["max_value_diff"] == tree0["value_diff_quartiles"][2] \
        <= largest == tree0["value_diffs_largest"][0][0] <= limit == 5e-3
    assert detail["train"][0]["binning"]["path"] == "native"


def test_bosch_cell_traced_reads_its_layers_and_every_train_reader(
        bosch_tree):
    result = run_tiny(bosch_tree, "bosch-cut-train", seconds=0.5, trace=True)
    check_result(result, trace=True)
    names = set(result["metrics"])
    assert MISSING_READERS <= names
    assert result["metrics"]["split.missing_share"]["value"] == 100.0
    assert 5.0 < result["metrics"]["split.default_left_share"]["value"] < 95.0
    assert result["metrics"]["partition.missing_row_share"]["value"] > 25.0
    assert result["metrics"]["partition.staged_row_share"]["value"] < 50.0
    assert result["metrics"]["loop.dispatches_per_iter"]["value"] == 2.0
    assert result["metrics"]["loop.blocking_fetches_per_iter"]["value"] == 1.0
    # the three are this cell's own: no other cell reports them
    plain = run_tiny(bosch_tree, "tiny-train", seconds=0.5, trace=True)
    assert set(plain["metrics"]) <= names
    for name in MISSING_READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


def test_readers_find_nothing_in_a_program_without_the_counters(bosch_tree):
    """The parent's program has `splits` and the row counters of PR 34 and
    none of this PR's: each reader returns None and does not raise."""
    from benchmarks import run as harness

    class Fast:
        K = 1
        counters = {"splits": [3, 4], "rows_partitioned": [10, 20],
                    "rows_staged": [1, 2]}

    class Engine:
        _fast = Fast()

    class Booster:
        _engine = Engine()

    run = harness.Run({}, {}, {"driver": "train"}, 3, True,
                      bosch_tree["bench_dir"])
    run.state["bst"] = Booster()
    run.window["iters"] = 2
    assert [run.metric(name) for name in sorted(MISSING_READERS)] \
        == [None, None, None]
    Fast.counters = dict(Fast.counters, missing_splits=[3, 3],
                         default_left_splits=[1, 2], rows_missing=[6, 9])
    run._values.clear()
    assert run.metric("split.missing_share") == pytest.approx(600 / 7)
    assert run.metric("split.default_left_share") == 50.0
    assert run.metric("partition.missing_row_share") == 50.0
    run.state["bst"] = None
    run._values.clear()
    assert run.metric("split.missing_share") is None


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_generator_is_the_same_on_one_thread_and_on_eight(seed, monkeypatch):
    from benchmarks.lib import parallel
    task = load_task()
    # several chunks of rows, so the threads have something to share out
    monkeypatch.setattr(task, "CHUNK_ROWS", 1 << 10)
    cfg = {"rows": 5000, "heldout_rows": 2000, "features": 968}
    made = {}
    for threads in (1, 8):
        monkeypatch.setattr(parallel, "threads", lambda n=threads: n)
        made[threads] = [task.make(cfg, seed, part) for part in (0, 1)]
    for one, eight in zip(made[1], made[8]):
        assert set(one) == {"X", "y"}
        for key in ("X", "y"):
            assert one[key].tobytes() == eight[key].tobytes()
            assert one[key].dtype == np.float32
    train, held = made[1]
    assert train["X"].tobytes() != task.make(cfg, seed + 1, 0)["X"].tobytes()
    assert train["X"][:2000].tobytes() != held["X"].tobytes()
    assert task.dataset_args(train) == {}
    with pytest.raises(ValueError):
        task.make(dict(cfg, features=900), seed, 0)


def test_generator_gives_the_table_the_configuration_states():
    """On 60,000 parts: 968 columns in 52 stations on 4 lines, a station's
    columns empty TOGETHER, 81% of the cells NaN and no column under 30%
    or over 99% empty, up to 193 distinct readings a column (49 on the
    eight go / no-go gauges), routes that keep to their line, the final
    test's four stations seen together by two parts in three; and 0.58%
    positives to the part, whatever the seed."""
    task = load_task()
    config = json.load(open(os.path.join(BENCH, "configs", "bosch.json")))
    consts = config["generator_constants"]
    assert (consts["task_seed"], consts["stations"], consts["routes"],
            consts["gauge_steps"], consts["z_clip"]) \
        == (task.TASK_SEED, task.STATIONS, task.ROUTES, task.GAUGE_STEPS,
            task.Z_CLIP)
    assert consts["line_stations"] == list(task.LINE_STATIONS)
    assert consts["final_stations"] == list(task.FINAL_STATIONS)
    assert (consts["signal_columns"], consts["strong_columns"],
            consts["strong_weight"], consts["column_weight"],
            consts["steepness"], consts["station_effect"]) \
        == (task.SIGNAL_COLUMNS, task.STRONG_COLUMNS, task.STRONG_WEIGHT,
            task.COLUMN_WEIGHT, task.STEEPNESS, task.STATION_EFFECT)
    plant = task.plant()
    assert len(plant.sizes) == 52 and plant.sizes.sum() == 968
    assert plant.sizes.min() >= 4 and plant.sizes.max() > 3 * plant.sizes.min()
    data = task.make(dict(config, rows=60000), 2**31 + 11, 0)
    X = data["X"]
    assert X.shape == (60000, 968) and X.dtype == np.float32
    empty = np.isnan(X)
    assert 0.80 < empty.mean() < 0.82
    by_column = empty.mean(axis=0)
    assert 0.30 < by_column.min() and by_column.max() < 0.99
    for s in range(task.STATIONS):                  # empty together
        block = empty[:, plant.first[s]:plant.first[s + 1]]
        assert (block == block[:, :1]).all()
    # the final test: all four stations or none, two parts in three
    final = np.flatnonzero(plant.final)
    assert list(final) == list(task.FINAL_STATIONS)
    assert plant.sizes[final].sum() == 197 and (plant.line_of[final] == 3).all()
    seen_final = ~empty[:, plant.first[final]]
    assert (seen_final == seen_final[:, :1]).all()
    assert 0.66 < seen_final[:, 0].mean() < 0.70
    assert by_column[~plant.final[plant.station_of]].min() > 0.7
    distinct = [len(np.unique(X[~empty[:, c], c])) for c in (0, 400, 967)]
    assert all(100 < d <= 193 for d in distinct)
    gauges = plant.signal_columns[plant.strong]
    assert len(gauges) == task.STRONG_COLUMNS == 8
    assert all(40 < len(np.unique(X[~empty[:, c], c])) <= 49 for c in gauges)
    # a route's stations lie on its own line and the last, hardly elsewhere
    on_line = np.array([[plant.visits[r, plant.line_of == line].sum()
                         for line in range(4)] for r in range(task.ROUTES)])
    first_three = np.sort(on_line[:, :3], axis=1)
    assert first_three[:, :2].sum() <= 0.15 * first_three[:, 2].sum()
    # the same NUMBER of failures in every table of one size
    assert data["y"].sum() == 348 == round(0.0058 * 60000)
    assert task.make(dict(config, rows=60000), 5, 0)["y"].sum() == 348
    assert task.failures(np.arange(183747.0)).sum() == 1066
    # both signs among the stations and among the columns that matter, the
    # columns all of the final test and none of a station whose visit counts
    assert len(set(np.sign(plant.station_effect[plant.signal_stations]))) == 2
    assert len(set(np.sign(plant.signal_weight))) == 2
    assert len(set(np.sign(plant.signal_weight[plant.strong]))) == 2
    assert len(plant.signal_columns) == task.SIGNAL_COLUMNS == 190
    assert plant.final[plant.station_of[plant.signal_columns]].all()
    assert not set(plant.station_of[plant.signal_columns]) \
        & set(plant.signal_stations)
    # the routes that take the final test have every signal column
    assert plant.signal_seen[plant.final_routes].all()
    assert not plant.signal_seen[~plant.final_routes].any()


def test_one_wrong_leaf_of_tree_0_reads_not_correct():
    """Tree 0 is held to two limits: the third quartile of its leaves'
    value differences (`max_value_diff`, which the driver holds to
    `leaf_value_atol`) and the LARGEST of them (`leaf_value_largest_atol`,
    folded into `counts_ok`).  One leaf moved leaves the quartile where it
    was and fails the second."""
    import copy
    import lightgbm_tpu as lgb
    task = load_task()
    config = json.load(open(os.path.join(BENCH, "configs", "bosch.json")))
    assert config["leaf_value_atol"] < config["leaf_value_largest_atol"] \
        <= 8 * config["leaf_value_atol"]
    cfg = dict(config, rows=20000, gauge_steps=4, leaf_value_largest_atol=5e-3,
               params=dict(config["params"], min_sum_hessian_in_leaf=2.0,
                           verbose=-1))
    data = task.make(cfg, 3, 0)
    bst = lgb.train(cfg["params"], lgb.Dataset(data["X"], label=data["y"]),
                    num_boost_round=1)
    tree = bst._engine.model.trees[0]
    sound = task.first_tree(tree, data, cfg)
    assert sound["counts_ok"] and sound["largest_value_diff"][0] <= 5e-3
    moved = copy.deepcopy(tree)
    moved.leaf_value[int(tree.num_leaves) // 2] += 2e-2
    off = task.first_tree(moved, data, cfg)
    assert not off["counts_ok"]
    assert off["largest_value_diff"][0] == pytest.approx(2e-2, rel=0.1)
    assert off["max_value_diff"] <= 2 * sound["max_value_diff"] + 1e-9
    assert off["max_count_diff"] == 0
    assert off["root_gain_rel_diff"] == sound["root_gain_rel_diff"]


def test_plain_search_finds_both_directions():
    """`column_search` on a column whose missing rows look like its LOW
    readings sends them left with the low ones, on one whose missing rows
    look like the HIGH readings right; and "has a reading" against "has
    none" is a split it offers."""
    task = load_task()
    rng = np.random.default_rng(5)
    n = 4000
    x = rng.integers(0, 10, n).astype(np.float64)
    x[rng.random(n) < 0.5] = np.nan
    hess = np.full(n, 0.25)
    p = task.search_params({"min_sum_hessian_in_leaf": 5.0,
                            "min_data_in_leaf": 1})
    for missing_risk, high_risk, want_left in ((0.1, 0.6, True),
                                               (0.6, 0.6, False)):
        risk = np.where(np.isnan(x), missing_risk,
                        np.where(x >= 5, high_risk, 0.1))
        y = rng.random(n) < risk
        grad = np.where(y, -0.5, 0.5)
        gain, value, left = task.column_search(
            x, grad, hess, float(grad.sum()),
            float(hess.sum()) + 2 * task.K_EPSILON, p)
        assert left == want_left and value == 4.0 and gain > 0
    y = rng.random(n) < np.where(np.isnan(x), 0.7, 0.1)
    grad = np.where(y, -0.5, 0.5)
    gain, value, left = task.column_search(
        x, grad, hess, float(grad.sum()),
        float(hess.sum()) + 2 * task.K_EPSILON, p)
    assert (value, left) == (9.0, False)
    assert task.auc(np.array([0, 0, 1, 1]), np.array([1., 2., 2., 3.])) \
        == pytest.approx(0.875)


def the_search_scans_one_direction(monkeypatch):
    """Every numerical search scans ONE direction, the one in which its
    best split does not lie: a legal split, and a worse one than both
    directions find."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.ops import split
    sound = split._numerical_gain_tensor

    def one_way(*args, **kw):
        gains, sums, shift = sound(*args, **kw)
        best = jnp.argmax(jnp.max(gains, axis=(0, 2)))
        keep = jnp.arange(2)[None, :, None] != best
        return jnp.where(keep, gains, -jnp.inf), sums, shift

    monkeypatch.setattr(split, "_numerical_gain_tensor", one_way)
    monkeypatch.setattr(gbdt, "_PGROWER_CACHE", {})
    return "root"


def the_partition_forgets_the_default_direction(monkeypatch):
    """The partition routes a row without a value as a row with the
    largest one: right, whatever `default_left` says.  The split search
    counted those rows on the left."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.ops import segment
    sound = segment.go_left_chunk

    def forgetful(chunk, pred):
        return sound(chunk, pred._replace(default_left=jnp.bool_(False)))

    monkeypatch.setattr(segment, "go_left_chunk", forgetful)
    monkeypatch.setattr(gbdt, "_PGROWER_CACHE", {})
    return "tree0_max_count_diff"


@pytest.mark.parametrize("fault", [
    the_search_scans_one_direction,
    the_partition_forgets_the_default_direction])
def test_a_fault_in_the_missing_value_path_reads_not_correct(
        bosch_tree, monkeypatch, fault):
    sound = run_tiny(bosch_tree, "bosch-cut-train", seconds=0.5)
    assert sound["correct"] is True
    caught_by = fault(monkeypatch)
    result = run_tiny(bosch_tree, "bosch-cut-train", seconds=0.5)
    assert result["correct"] is False
    tree0 = _detail(bosch_tree)["verify"][-1]["checks"]["tree0"]
    if caught_by == "root":
        # rows and values are the tree's own: the root's split is not the
        # best the plain search finds
        assert result["compared"]["tree0_max_count_diff"][0] == 0
        value, limit = tree0["root_gain_rel_diff"]
        assert value > 10 * limit
        assert tree0["root_default_left"] \
            != tree0["root_default_left_plain"]
    else:
        value, limit = result["compared"][caught_by]
        assert value > limit
