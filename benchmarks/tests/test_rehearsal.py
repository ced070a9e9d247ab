"""Every driver end to end on the CPU at a few thousand rows, through
the same `run_cell` the command line calls, with `require_tpu=False`;
the mesh cell on four virtual devices."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, DATA, ROOT, metric_entry, run_tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def check_result(result, trace):
    assert set(result) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert set(result["device"]) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if trace else set())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(result)      # one line of JSON, as printed


@pytest.mark.parametrize("cell, metrics", [
    ("tiny-train", {"train_s_per_iter", "heldout_quality", "setup_s"}),
    ("tiny-mesh-train", {"train_s_per_iter", "heldout_quality", "setup_s"}),
    ("tiny-predict", {"predict_rows_per_s", "setup_s"}),
    ("tiny-serve", {"serve_p99_ms", "setup_s"}),
])
def test_untraced_run_reports_the_cells_end_to_end_metrics(bench_tree, cell,
                                                           metrics):
    result = run_tiny(bench_tree, cell, seconds=1.5)
    check_result(result, trace=False)
    assert set(result["metrics"]) == metrics
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell, some", [
    ("tiny-train", {"ingest.dataset_s", "loop.dispatches_per_iter",
                    "loop.blocking_fetches_per_iter", "compile.cache_misses"}),
    ("tiny-predict", {"setup.warmup_s", "compile.seconds"}),
    ("tiny-serve", {"serve.queue_wait_ms", "serve.dispatch_to_values_ms",
                    "serve.rows_per_device_batch", "loadgen.max_lag_ms"}),
])
def test_traced_run_reports_per_layer_metrics(bench_tree, cell, some):
    result = run_tiny(bench_tree, cell, seconds=1.5, trace=True)
    check_result(result, trace=True)
    names = set(result["metrics"])
    assert some <= names
    e2e = {m["name"] for m in bench_tree["manifest"]["end_to_end"]}
    assert not names & e2e
    # a CPU trace has no device plane: every device-trace reader finds
    # nothing and is left out, and nothing is reported in its name
    assert not {"device.idle_share", "kernel.hist_s_per_iter",
                "hist_roofline", "predictor.device_s_per_mrow"} & names
    assert result["device"]["busy_s"] == 0.0


def test_mesh_cell_spreads_the_payload_over_four_devices(bench_tree):
    from benchmarks import run as harness
    result = run_tiny(bench_tree, "tiny-mesh-train", seconds=1.5)
    assert result["device"]["count"] == 4
    with open(os.path.join(bench_tree["root"], harness.OUT_DIR,
                           "tiny-mesh-train.s3.t0.json")) as fh:
        detail = json.load(fh)["detail"]
    assert detail["verify"][0]["checks"]["payload"]["devices"] == 4


def test_same_seed_same_inputs_other_seed_other_inputs():
    from benchmarks.lib import synth
    a = synth.binary_task(5000, 28, (1, 0))
    b = synth.binary_task(5000, 28, (1, 0))
    c = synth.binary_task(5000, 28, (2, 0))
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all()


def test_new_config_mix_and_metric_are_found_as_new_files(bench_tree):
    """A later PR adds a configuration, a mix and a per-layer metric as
    files and manifest entries; no file that was there changes."""
    root, bench_dir = bench_tree["root"], bench_tree["bench_dir"]
    before = {}
    for folder, _, names in os.walk(bench_dir):
        for name in names:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny-higgs.json")) as fh:
        config = dict(json.load(fh), name="other", rows=4000)
    with open(os.path.join(root, "other.json"), "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(bench_dir, "traffic", "train.json")) as fh:
        mix = dict(json.load(fh), warmup_iters=2)
    with open(os.path.join(bench_dir, "traffic", "train-short.json"),
              "w") as fh:
        json.dump(mix, fh)
    with open(os.path.join(bench_dir, "layer_metrics",
                           "loop.iterations.py"), "w") as fh:
        fh.write('LAYER = "boosting-loop"\nUNIT = "count"\n'
                 'MOVES = "train_s_per_iter"\nSOURCE = "program_counter"\n'
                 'DRIVERS = ("train",)\n\n\ndef read(run):\n'
                 '    return run.window["iters"]\n')
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "other",
                                "file": os.path.join(root, "other.json")})
    manifest["workloads"].append({"name": "other-train", "config": "other",
                                  "traffic": "train-short", "chips": 1})
    manifest["per_layer"].append(
        metric_entry(bench_dir, "loop.iterations"))
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(manifest, fh)

    result = run_tiny(bench_tree, "other-train", seconds=1.0, trace=True)
    assert result["correct"]
    assert result["metrics"]["loop.iterations"]["value"] == 3.0
    for path, content in before.items():
        assert open(path, "rb").read() == content


#: a learning task as a later PR would bring it: ranking over query groups,
#: its own data, walk, first-tree check and NDCG, in one new file
TOY_GROUPS = '''"""A toy ranking task: queries of uneven length, graded labels."""
import numpy as np


def make(cfg, seed, part):
    rng = np.random.default_rng([seed, part])
    sizes = rng.integers(2, 40, cfg["heldout_queries"] if part
                         else cfg["queries"])
    X = rng.standard_normal((int(sizes.sum()), cfg["features"]))
    score = X[:, 0] + 0.5 * X[:, 1] + 0.5 * rng.standard_normal(len(X))
    y = np.clip(np.floor(score + 1.5), 0, 4).astype(np.float32)
    return {"X": X, "y": y, "group": sizes}


def dataset_args(data):
    return {"group": data["group"]}


def _leaf(tree, X):
    """Numerical splits only: each row's leaf, node by node."""
    out = np.empty(len(X), np.int64)
    for i, x in enumerate(X):
        node = 0
        while node >= 0:
            left = x[tree.split_feature[node]] <= tree.threshold[node]
            node = (tree.left_child if left else tree.right_child)[node]
        out[i] = ~node
    return out


def first_tree(tree, data, cfg):
    nl = int(tree.num_leaves)
    count = np.bincount(_leaf(tree, data["X"]), minlength=nl)
    off = np.abs(count - np.asarray(tree.leaf_count[:nl], np.int64))
    return {"leaves": nl, "counts_ok": bool((off == 0).all()),
            "max_count_diff": int(off.max()), "max_value_diff": 0.0}


def _ndcg(y, score, k):
    gain = lambda order: ((2.0 ** y[order][:k] - 1)
                          / np.log2(np.arange(2, 2 + min(k, len(y))))).sum()
    best = gain(np.argsort(-y, kind="stable"))
    return gain(np.argsort(-score, kind="stable")) / best if best else 1.0


def heldout(trees, data, cfg):
    values = [np.asarray(t.leaf_value, np.float64)[_leaf(t, data["X"])]
              for t in trees]
    score = np.sum(values, axis=0)
    ends = np.cumsum(data["group"])
    return float(np.mean([_ndcg(data["y"][lo:hi], score[lo:hi], 10)
                          for lo, hi in zip(ends - data["group"], ends)]))
'''


def test_new_task_is_found_as_new_files(bench_tree):
    """A later PR adds a learning task (here LambdaRank over query
    groups: another objective, another `lgb.Dataset` keyword, another
    held-out measure) as tasks/<task>.py and a configuration that names
    it; the mix and the driver stay `train`, so the readers that say
    `DRIVERS = ("train",)` read it, and no file that was there changes."""
    root, bench_dir = bench_tree["root"], bench_tree["bench_dir"]
    before = {}
    for folder, _, names in os.walk(bench_dir):
        for name in names:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()

    with open(os.path.join(bench_dir, "tasks", "toy_groups.py"), "w") as fh:
        fh.write(TOY_GROUPS)
    config = {
        "name": "toy-rank", "task": "toy_groups", "chips": 1,
        "queries": 300, "heldout_queries": 100, "features": 10,
        "params": {"objective": "lambdarank", "num_leaves": 15,
                   "max_bin": 63, "learning_rate": 0.1,
                   "min_data_in_leaf": 5, "verbose": -1},
        "quality_metric": "ndcg@10", "quality_at_iter": 6,
        "quality_band": [0.5, 1.0],
        "engines": {"histogram": "lax", "partition": "lax"}}
    with open(os.path.join(root, "toy-rank.json"), "w") as fh:
        json.dump(config, fh)
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "toy-rank",
                                "file": os.path.join(root, "toy-rank.json")})
    manifest["workloads"].append({"name": "toy-rank-train",
                                  "config": "toy-rank", "traffic": "train",
                                  "chips": 1})
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(manifest, fh)

    result = run_tiny(bench_tree, "toy-rank-train", seconds=1.0, trace=True)
    check_result(result, trace=True)
    assert result["metrics"]["loop.dispatches_per_iter"]["value"] == 2.0
    ndcg, band = result["compared"]["heldout_in_band"]
    assert band == [0.5, 1.0] and 0.5 < ndcg < 1.0
    assert result["compared"]["tree0_max_count_diff"][0] == 0
    for path, content in before.items():
        assert open(path, "rb").read() == content


#: `binary` with numbers of its own in `compared`, one of them under a
#: name the driver uses
BINARY_COMPARED = '''"""`binary`, and two entries of its own in `compared`."""
import os

from benchmarks.run import load_module

binary = load_module(os.path.join(os.path.dirname(__file__), "binary.py"))
make, dataset_args, heldout = binary.make, binary.dataset_args, binary.heldout


def first_tree(tree, data, cfg):
    out = binary.first_tree(tree, data, cfg)
    out["compared"] = {"tree0_leaves": [out["leaves"], 15],
                       "trees_failed": [7, 0]}
    return out
'''


def test_a_tasks_own_compared_entries_follow_the_drivers(bench_tree):
    """`first_tree` may return `compared`: its entries come after the
    driver's, a name the driver uses keeps the driver's value, and what
    decides `correct` does not change."""
    root, bench_dir = bench_tree["root"], bench_tree["bench_dir"]
    with open(os.path.join(bench_dir, "tasks", "binary_compared.py"),
              "w") as fh:
        fh.write(BINARY_COMPARED)
    with open(os.path.join(DATA, "tiny-higgs.json")) as fh:
        config = dict(json.load(fh), task="binary_compared")
    with open(os.path.join(root, "tiny-compared.json"), "w") as fh:
        json.dump(config, fh)
    manifest = bench_tree["manifest"]
    manifest["configs"].append({
        "name": "tiny-compared",
        "file": os.path.join(root, "tiny-compared.json")})
    manifest["workloads"].append({"name": "tiny-compared-train",
                                  "config": "tiny-compared",
                                  "traffic": "train", "chips": 1})
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(manifest, fh)
    result = run_tiny(bench_tree, "tiny-compared-train", seconds=1.0)
    check_result(result, trace=False)
    assert list(result["compared"]) == [
        "trees_failed", "payload_devices", "tree0_max_count_diff",
        "tree0_max_value_diff", "heldout_in_band", "tree0_leaves",
        "compiled_in_window"]
    assert result["compared"]["trees_failed"] == [0, 0]
    assert result["compared"]["tree0_leaves"] == [15, 15]


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_binary_task_is_the_data_it_was(seed):
    """tasks/binary.py hands on what `synth.binary_task` makes: the same
    bytes for the same (seed, part), no copy, nothing else."""
    from benchmarks.lib import synth
    from benchmarks.run import load_module
    task = load_module(os.path.join(BENCH, "tasks", "binary.py"))
    cfg = {"generator": "binary_task", "rows": 5000, "heldout_rows": 1500,
           "features": 28}
    for part, rows in ((0, 5000), (1, 1500)):
        data = task.make(cfg, seed, part)
        X, y = synth.binary_task(rows, 28, (seed, part))
        assert set(data) == {"X", "y"}
        assert data["X"].tobytes() == X.tobytes()
        assert data["y"].tobytes() == y.tobytes()
        assert data["X"].dtype == X.dtype and data["y"].dtype == y.dtype
        assert task.dataset_args(data) == {}


def test_a_reader_that_finds_nothing_is_left_out(bench_tree):
    bench_dir = bench_tree["bench_dir"]
    with open(os.path.join(bench_dir, "layer_metrics", "nothing.py"),
              "w") as fh:
        fh.write('LAYER = "x"\nUNIT = "s"\nMOVES = "setup_s"\n'
                 'SOURCE = "host_clock"\nDRIVERS = None\n\n\n'
                 'def read(run):\n    return None\n')
    manifest = bench_tree["manifest"]
    manifest["per_layer"].append(metric_entry(bench_dir, "nothing"))
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(manifest, fh)
    result = run_tiny(bench_tree, "tiny-train", seconds=1.0, trace=True)
    assert "nothing" not in result["metrics"]


def run_command(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args, cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_the_command_exits_non_zero_with_no_result_line():
    done = run_command(["--workload", "higgs-train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert done.returncode == 2
    assert "not tpu" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_without_the_program_the_command_exits_non_zero(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "higgs-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_knee_sweep_runs_one_setup_and_a_window_a_rate(bench_tree):
    from benchmarks import sweep
    knee, device = sweep.sweep(
        "tiny-serve", [40.0, 80.0], 1.0, 3,
        manifest_path=bench_tree["manifest_path"],
        bench_dir=bench_tree["bench_dir"], root=bench_tree["root"],
        require_tpu=False)
    # which rate a shared CPU sustains in one second is not the test's
    # business; that the ladder ran, and what `sustained` means, is
    assert knee in (None, 40.0, 80.0) and device["platform"] == "cpu"
    assert sweep.sustained({"offered": 1000, "completed_in_window": 996,
                            "not_ok": {}, "backlog_end": 3,
                            "backlog_median": 4.0})
    assert not sweep.sustained({"offered": 1000, "completed_in_window": 990,
                                "not_ok": {}, "backlog_end": 3,
                                "backlog_median": 4.0})
    assert not sweep.sustained({"offered": 1000, "completed_in_window": 1000,
                                "not_ok": {"queue_full": 1}, "backlog_end": 0,
                                "backlog_median": 0.0})
    assert not sweep.sustained({"offered": 1000, "completed_in_window": 999,
                                "not_ok": {}, "backlog_end": 40,
                                "backlog_median": 4.0})
