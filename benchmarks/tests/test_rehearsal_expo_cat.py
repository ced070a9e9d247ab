"""The `expo-cat-train` cell end to end on the CPU: the configuration's own
file at its own shape (8 columns, six of them categorical, 313 airports
into 255 bins) cut to the rows a CPU trains, through the `run_cell` the
command line calls; the task's generator held to what the configuration
states of it; and faults in the timed path read as not correct."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result

CAT_READERS = {"split.categorical_share", "ingest.encode_cat_s"}
#: a device-trace reader: a CPU trace has no device plane, so it finds
#: nothing here and is left out; the chip's reading is in PERF.md
CAT_DEVICE_READERS = {"grower.cat_search_s_per_iter"}
#: what drivers/train.py compares of every train cell, in its order
DRIVER_COMPARED = ["trees_failed", "payload_devices", "tree0_max_count_diff",
                   "tree0_max_value_diff", "heldout_in_band"]


def load_task():
    from benchmarks.run import load_module
    return load_module(os.path.join(BENCH, "tasks", "binary_cat.py"))


@pytest.fixture()
def expo_tree(bench_tree):
    """The rehearsal tree with the real `expo-cat` configuration, rows cut
    for the CPU (whose engines are the portable ones; up to 200,000 rows
    the bins are found from every row, so the task's plain search knows
    them exactly), the real `train` mix asked for fewer iterations, and
    the manifest's own three entries for the cell."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in real["configs"] if c["name"] == "expo-cat")
    cell = next(w for w in real["workloads"] if w["name"] == "expo-cat-train")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("expo-cat", "train", 1)
    assert entry["reduced"] == ["num_iterations"]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    assert (config["rows"], config["features"], config["heldout_rows"],
            config["categorical_feature"]) \
        == (10000000, 8, 1000000, [0, 1, 2, 4, 5, 6])
    assert config["task"] == "binary_cat"
    assert config["params"] == {"objective": "binary", "num_leaves": 255,
                                "max_bin": 255, "learning_rate": 0.1}
    assert config["engines"] == {"histogram": "pallas",
                                 "partition": "pallas-acc"}
    config.update(rows=60000, heldout_rows=20000, quality_band=[0.6, 1.0],
                  quality_at_iter=4,
                  engines={"histogram": "lax", "partition": "lax"})
    config.pop("leaf_value_atol", None)
    config["params"] = dict(config["params"], num_leaves=31, verbose=-1)
    path = os.path.join(bench_tree["root"], "expo-cut.json")
    json.dump(config, open(path, "w"))
    traffic = os.path.join(bench_tree["bench_dir"], "traffic")
    mix = json.load(open(os.path.join(traffic, "train.json")))
    json.dump(dict(mix, warmup_iters=2, min_iters=2, trace_iters=2),
              open(os.path.join(traffic, "train-two.json"), "w"))
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "expo-cut", "file": path})
    manifest["workloads"].append({"name": "expo-cut-train",
                                  "config": "expo-cut",
                                  "traffic": "train-two", "chips": 1})
    all_ours = CAT_READERS | CAT_DEVICE_READERS
    ours = [dict(m, workloads=["expo-cut-train"])
            for m in real["per_layer"] if m["name"] in all_ours]
    assert {m["name"] for m in ours} == all_ours
    assert all(m["workloads"] == ["expo-cut-train"] for m in ours)
    assert {m["layer"] for m in ours} == {"grower-split-search", "ingest"}
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] not in all_ours] + ours
    json.dump(manifest, open(bench_tree["manifest_path"], "w"))
    return bench_tree


def _detail(tree, trace=0):
    return json.load(open(os.path.join(
        tree["root"], "chiprun_out", "bench",
        "expo-cut-train.s3.t%d.json" % trace)))["detail"]


def test_expo_cat_cell_untraced(expo_tree):
    result = run_tiny(expo_tree, "expo-cut-train", seconds=0.5)
    check_result(result, trace=False)
    assert set(result["metrics"]) == {"train_s_per_iter", "heldout_quality",
                                      "setup_s"}
    detail = _detail(expo_tree)
    checks = detail["verify"][0]["checks"]
    # 8 bin columns and 10 value columns; the chip pads them to 128
    assert checks["payload"]["lanes"] == 18
    assert checks["fast_path"] and checks["tree0"]["counts_ok"]
    tree0 = checks["tree0"]
    assert tree0["max_count_diff"] == 0 and tree0["categorical_nodes"] >= 15
    assert tree0["root_is_categorical"] and tree0["root_feature"] == 5
    assert tree0["root_feature_plain"] == 5
    value, limit = tree0["root_gain_rel_diff"]
    assert value <= 1e-6 and limit == 1e-4
    # the task's own entry, after the driver's
    assert list(result["compared"])[:5] == DRIVER_COMPARED
    assert result["compared"]["tree0_root_gain_rel_diff"] == [value, limit]
    assert 2 <= tree0["largest_left_set"] <= 32
    # every column within 256 bins, coded by the native library
    assert detail["train"][0]["binning"]["path"] == "native"


def test_expo_cat_cell_traced_reads_its_layers_and_every_train_reader(
        expo_tree):
    result = run_tiny(expo_tree, "expo-cut-train", seconds=0.5, trace=True)
    check_result(result, trace=True)
    names = set(result["metrics"])
    assert CAT_READERS <= names and not CAT_DEVICE_READERS & names
    assert result["metrics"]["split.categorical_share"]["value"] >= 50.0
    assert result["metrics"]["ingest.encode_cat_s"]["value"] > 0.0
    assert result["metrics"]["ingest.encode_cat_s"]["value"] \
        <= result["metrics"]["ingest.encode_s"]["value"]
    assert result["metrics"]["loop.dispatches_per_iter"]["value"] == 2.0
    assert result["metrics"]["loop.blocking_fetches_per_iter"]["value"] == 1.0
    plain = run_tiny(expo_tree, "tiny-train", seconds=0.5, trace=True)
    assert set(plain["metrics"]) <= names
    assert not CAT_READERS & set(plain["metrics"])
    for name in CAT_READERS | CAT_DEVICE_READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_generator_is_the_same_on_one_thread_and_on_eight(seed, monkeypatch):
    from benchmarks.lib import parallel
    task = load_task()
    # several chunks of rows, so the threads have something to share out
    monkeypatch.setattr(task, "CHUNK_ROWS", 1 << 12)
    cfg = {"rows": 9000, "heldout_rows": 3000, "features": 8,
           "categorical_feature": [0, 1, 2, 4, 5, 6]}
    made = {}
    for threads in (1, 8):
        monkeypatch.setattr(parallel, "threads", lambda n=threads: n)
        made[threads] = [task.make(cfg, seed, part) for part in (0, 1)]
    for one, eight in zip(made[1], made[8]):
        assert set(one) == {"X", "y", "categorical_feature"}
        for key in ("X", "y"):
            assert one[key].tobytes() == eight[key].tobytes()
            assert one[key].dtype == np.float32
    train, held = made[1]
    assert train["X"].tobytes() != task.make(cfg, seed + 1, 0)["X"].tobytes()
    assert train["X"][:3000].tobytes() != held["X"].tobytes()
    assert task.dataset_args(train) == {
        "categorical_feature": [0, 1, 2, 4, 5, 6]}


def test_generator_gives_the_columns_the_configuration_states():
    """On 400,000 rows: the cardinalities, 21.5% positive, whole
    non-negative codes, the airports' sizes by rank, and the gaps round
    the last bin that `offered` needs at the cell's own size."""
    task = load_task()
    config = json.load(open(os.path.join(BENCH, "configs", "expo-cat.json")))
    consts = config["generator_constants"]
    assert (consts["task_seed"], consts["airports_regular"],
            consts["latent_cut"], consts["day_rise"]) \
        == (task.TASK_SEED, task.AIRPORTS_REGULAR, task.LATENT_CUT,
            task.DAY_RISE)
    assert consts["effect_sd"] == {task.COLUMNS[c]: sd
                                   for c, sd in task.EFFECT_SD.items()}
    assert config["feature_names"] == list(task.COLUMNS)
    data = task.make(dict(config, rows=400000), 2**31 + 11, 0)
    X, y = data["X"], data["y"]
    assert X.shape == (400000, 8) and X.dtype == np.float32
    assert (X == np.floor(X)).all() and (X >= 0).all()
    assert abs(float(y.mean()) - 0.215) < 0.003
    for col, n in task.CARDINALITY.items():
        values = np.unique(X[:, col])
        assert values.min() >= task.FIRST_CODE[col]
        assert values.max() <= task.FIRST_CODE[col] + n - 1
        if n < 100:
            assert len(values) == n
    assert X[:, task.DEPTIME].max() <= 2359 and (X[:, task.DEPTIME] % 100
                                                 < 60).all()
    assert 30 <= X[:, task.DISTANCE].min() and X[:, task.DISTANCE].max() <= 4960
    # 1 + 58 small airports hold 0.039% of the rows, the largest 9.7%
    shares = task._shares(task.ORIGIN)
    assert len(shares) == 313 and abs(shares.sum() - 1) < 1e-12
    assert 0.09 < shares[0] < 0.10
    assert abs(shares[254:].sum() - 3.9e-4) < 1e-6
    # at the cell's rows: every regular airport far over the small field,
    # the small field far over an airstrip: `offered` settles
    rows = config["rows"]
    counts = {i: int(round(s * rows)) for i, s in enumerate(shares)}
    kept, full = task.offered(counts, config["params"]["max_bin"])
    assert kept == list(range(255)) and not full
    with pytest.raises(ValueError):     # a small field of 120 rows
        task.offered({**counts, 254: 120}, 255)
    # up to the sample's size the rule is exact: no gaps are asked for
    kept, full = task.offered({i: int(round(s * 150000)) + 1
                               for i, s in enumerate(shares)}, 255)
    assert len(kept) == 255 and not full
    assert task.offered({1: 500, 2: 300, 3: 2}, 255) == ([1, 2], False)
    assert task.offered({1: 500, 2: 300, 3: 4}, 255) == ([1, 2, 3], True)


def the_search_stops_after_one_category(monkeypatch):
    """Every sorted-subset search walks one bin from either end and no
    further: a legal split, and a worse one than the walk finds."""
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.ops import split
    sound = split._categorical_best

    def short(*args, **kw):
        return sound(*args, **dict(kw, max_cat_threshold=1))

    monkeypatch.setattr(split, "_categorical_best", short)
    monkeypatch.setattr(gbdt, "_GROWER_CACHE", {})
    return "root"


def a_category_coded_into_its_neighbours_bin(monkeypatch):
    """The encoder's table sends one airport's rows to the bin of
    another: the trees are grown on rows the model file does not
    describe."""
    from lightgbm_tpu.io import binning
    sound = binning.BinMapper.categorical_table

    def swapped(self, max_len):
        table = sound(self, max_len)
        if table is not None and len(table) > 300:
            a, b = np.flatnonzero(table >= 0)[[3, 4]]
            table[a] = table[b]
        return table

    monkeypatch.setattr(binning.BinMapper, "categorical_table", swapped)
    return "tree0_max_count_diff"


@pytest.mark.parametrize("fault", [the_search_stops_after_one_category,
                                   a_category_coded_into_its_neighbours_bin])
def test_a_fault_in_the_categorical_path_reads_not_correct(
        expo_tree, monkeypatch, fault):
    sound = run_tiny(expo_tree, "expo-cut-train", seconds=0.5)
    assert sound["correct"] is True
    caught_by = fault(monkeypatch)
    result = run_tiny(expo_tree, "expo-cut-train", seconds=0.5)
    assert result["correct"] is False
    tree0 = _detail(expo_tree)["verify"][-1]["checks"]["tree0"]
    if caught_by == "root":
        # rows and values are the tree's own: the root's split is not
        # the best the plain search finds
        assert result["compared"]["tree0_max_count_diff"][0] == 0
        value, limit = tree0["root_gain_rel_diff"]
        assert value > 10 * limit
        assert tree0["largest_left_set"] == 1
    else:
        value, limit = result["compared"][caught_by]
        assert value > limit
