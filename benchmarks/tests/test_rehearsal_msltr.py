"""The `msltr-train` cell end to end on the CPU: the configuration's own
file at its own width (137 columns, 255 bins, LambdaRank over query
groups) cut to a few hundred queries, through the `run_cell` the command
line calls; the task's generator held to what the configuration states
of it; and two faults in the timed path read as not correct."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result

RANK_READERS = {"rank.pair_slots_per_pair", "rank.objective_init_s"}
#: device-trace readers: a CPU trace has no device plane, so these find
#: nothing here and are left out; the chip's readings are in PERF.md
RANK_DEVICE_READERS = {"rank.pairwise_s_per_iter", "rank.permute_s_per_iter"}


def load_task():
    from benchmarks.run import load_module
    return load_module(os.path.join(BENCH, "tasks", "rank.py"))


@pytest.fixture()
def msltr_tree(bench_tree):
    """The rehearsal tree with the real `msltr` configuration, rows and
    queries cut for the CPU (whose engines are the portable ones), the
    real `train` mix asked for fewer iterations, and the manifest's own
    four `rank.*` entries."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in real["configs"] if c["name"] == "msltr")
    cell = next(w for w in real["workloads"] if w["name"] == "msltr-train")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("msltr", "train", 1)
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    assert (config["rows"], config["features"], config["queries"],
            config["longest_query"], config["heldout_rows"],
            config["heldout_queries"]) \
        == (2270296, 137, 18919, 1251, 753611, 6306)
    assert config["task"] == "rank"
    assert config["params"]["objective"] == "lambdarank"
    assert config["engines"] == {"histogram": "pallas",
                                 "partition": "pallas-acc"}
    config.update(rows=12000, queries=100, longest_query=700,
                  heldout_rows=4000, heldout_queries=40,
                  quality_band=[0.2, 1.0], quality_at_iter=4,
                  engines={"histogram": "lax", "partition": "lax"})
    config["params"] = dict(config["params"], num_leaves=31, verbose=-1)
    path = os.path.join(bench_tree["root"], "msltr-cut.json")
    json.dump(config, open(path, "w"))
    traffic = os.path.join(bench_tree["bench_dir"], "traffic")
    mix = json.load(open(os.path.join(traffic, "train.json")))
    json.dump(dict(mix, warmup_iters=2, min_iters=2, trace_iters=2),
              open(os.path.join(traffic, "train-two.json"), "w"))
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "msltr-cut", "file": path})
    manifest["workloads"].append({"name": "msltr-cut-train",
                                  "config": "msltr-cut",
                                  "traffic": "train-two", "chips": 1})
    ours = [dict(m, workloads=["msltr-cut-train"])
            for m in real["per_layer"] if m["name"].startswith("rank.")]
    assert {m["name"] for m in ours} == RANK_READERS | RANK_DEVICE_READERS
    assert all(m["layer"] == "objective" for m in ours)
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if not m["name"].startswith("rank.")] + ours
    json.dump(manifest, open(bench_tree["manifest_path"], "w"))
    return bench_tree


def test_msltr_cell_untraced(msltr_tree):
    result = run_tiny(msltr_tree, "msltr-cut-train", seconds=0.5)
    check_result(result, trace=False)
    assert set(result["metrics"]) == {"train_s_per_iter", "heldout_quality",
                                      "setup_s"}
    detail = json.load(open(os.path.join(
        msltr_tree["root"], "chiprun_out", "bench",
        "msltr-cut-train.s3.t0.json")))["detail"]
    checks = detail["verify"][0]["checks"]
    # 137 bin columns and 10 value columns; the chip pads them to 256
    assert checks["payload"]["lanes"] == 147
    assert checks["fast_path"] and checks["tree0"]["counts_ok"]
    assert checks["tree0"]["max_count_diff"] == 0
    assert checks["heldout"]["metric"] == "ndcg@10"


def test_msltr_cell_traced_reads_the_objective_and_every_train_reader(
        msltr_tree):
    """Every `rank.*` reader that has something to read on a CPU returns
    a number, and the readers the other train cells report are all still
    there."""
    result = run_tiny(msltr_tree, "msltr-cut-train", seconds=0.5, trace=True)
    check_result(result, trace=True)
    names = set(result["metrics"])
    assert RANK_READERS <= names and not RANK_DEVICE_READERS & names
    # a hundred queries fill no lane tile of 128, so the cut reads far
    # over the cell's own 1.9 (tests/test_rank_layout.py holds the plan
    # at the cell's query count to under 2.2)
    assert result["metrics"]["rank.pair_slots_per_pair"]["value"] >= 1.0
    assert result["metrics"]["rank.objective_init_s"]["value"] > 0.0
    assert result["metrics"]["loop.dispatches_per_iter"]["value"] == 2.0
    assert result["metrics"]["loop.blocking_fetches_per_iter"]["value"] == 1.0
    plain = run_tiny(msltr_tree, "tiny-train", seconds=0.5, trace=True)
    assert set(plain["metrics"]) <= names | RANK_READERS
    assert not RANK_READERS & set(plain["metrics"])
    for name in RANK_READERS | RANK_DEVICE_READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_generator_is_the_same_on_one_thread_and_on_eight(seed, monkeypatch):
    from benchmarks.lib import parallel
    task = load_task()
    # two chunks of rows, so the threads have something to share out
    monkeypatch.setattr(task, "CHUNK_ROWS", 1 << 12)
    cfg = {"rows": 9000, "queries": 80, "heldout_rows": 3000,
           "heldout_queries": 30, "features": 137, "longest_query": 600}
    made = {}
    for threads in (1, 8):
        monkeypatch.setattr(parallel, "threads", lambda n=threads: n)
        made[threads] = [task.make(cfg, seed, part) for part in (0, 1)]
    for one, eight in zip(made[1], made[8]):
        assert set(one) == {"X", "y", "group"}
        for key in one:
            assert one[key].tobytes() == eight[key].tobytes()
            assert one[key].dtype == eight[key].dtype
    train, held = made[1]
    assert train["X"].tobytes() != task.make(cfg, seed + 1, 0)["X"].tobytes()
    assert train["X"][:3000].tobytes() != held["X"].tobytes()
    assert task.dataset_args(train) == {"group": train["group"]}


def test_generator_gives_the_query_sizes_and_label_shares_it_states():
    """At the cell's own query count (the sizes alone: 18,919 integers)
    and on 300,000 rows of labels."""
    task = load_task()
    config = json.load(open(os.path.join(BENCH, "configs", "msltr.json")))
    for rows, queries, part in (("rows", "queries", 0),
                                ("heldout_rows", "heldout_queries", 1)):
        sizes = task.query_sizes(config[queries], config[rows],
                                 config["longest_query"], (2**31 + 5, part, 1))
        assert len(sizes) == config[queries]
        assert sizes.sum() == config[rows]
        assert sizes.min() >= 1 and sizes.max() == config["longest_query"]
        # sum(n_q^2) is what the objective's program costs: the
        # configuration states it for the training part
        if part == 0:
            stated = config["generator_constants"]["sum_query_size_squared"]
            assert abs(float((sizes * sizes).sum()) / stated - 1) < 0.08
    data = task.make(dict(config, rows=300000, queries=2500), 11, 0)
    shares = np.bincount(data["y"].astype(int), minlength=5) / 300000
    assert np.abs(shares - [0.52, 0.32, 0.13, 0.02, 0.01]).max() < 0.01
    # the last columns hold one value a query, the others do not
    ends = np.cumsum(data["group"])
    lo, hi = ends[0], ends[1]
    q_level = data["X"][lo:hi, -task.QUERY_LEVEL_FEATURES:]
    assert (q_level == q_level[0]).all()
    assert not (data["X"][lo:hi, :3] == data["X"][lo, :3]).all()


def gradients_rounded_to_bf16(monkeypatch):
    """The histogram sums gradients and hessians rounded to bfloat16:
    the step below the program's float32 (its MXU products are split into
    three bf16 parts and exact)."""
    import jax.numpy as jnp
    from lightgbm_tpu.objective import rank
    sound = rank.LambdarankNDCG.gradients_in_order

    def rounded(self, score, row):
        g, h = sound(self, score, row)
        return (g.astype(jnp.bfloat16).astype(jnp.float32),
                h.astype(jnp.bfloat16).astype(jnp.float32))

    monkeypatch.setattr(rank.LambdarankNDCG, "gradients_in_order", rounded)
    return "tree0_max_value_diff"


def a_query_boundary_moved_by_a_row(monkeypatch):
    """The trainer is told that the first query ends a row early and the
    second starts there: the sizes still sum to the rows."""
    import lightgbm_tpu as lgb
    whole = lgb.Dataset

    def moved(X, label=None, group=None, **kw):
        group = np.array(group)
        group[0] -= 1
        group[1] += 1
        return whole(X, label=label, group=group, **kw)

    monkeypatch.setattr(lgb, "Dataset", moved)
    return "tree0_max_value_diff"


@pytest.mark.parametrize("fault", [gradients_rounded_to_bf16,
                                   a_query_boundary_moved_by_a_row])
def test_a_fault_in_the_gradients_reads_not_correct(msltr_tree, monkeypatch,
                                                    fault):
    """Under the tolerance a sound run leaves three times of room below
    (read here, on the CPU, at the cut size; the cell's own is the
    configuration's, from the chip), each fault reads `correct: false`
    by tree 0's values."""
    path = os.path.join(msltr_tree["root"], "msltr-cut.json")
    sound = run_tiny(msltr_tree, "msltr-cut-train", seconds=0.5)
    assert sound["correct"] is True
    reading = sound["compared"]["tree0_max_value_diff"][0]
    config = json.load(open(path))
    config["leaf_value_atol"] = atol = max(3 * reading, 1e-9)
    json.dump(config, open(path, "w"))
    assert run_tiny(msltr_tree, "msltr-cut-train", seconds=0.5)["correct"]

    caught_by = fault(monkeypatch)
    result = run_tiny(msltr_tree, "msltr-cut-train", seconds=0.5)
    assert result["correct"] is False
    value, limit = result["compared"][caught_by]
    assert limit == atol and value > 3 * atol
    assert result["compared"]["tree0_max_count_diff"][0] == 0
