"""The `epsilon-train` cell end to end on the CPU: the configuration's
own file at its own width (2,000 columns, 63 bins) cut to a few thousand
rows, through the `run_cell` the command line calls.  The cell is data
only: a configuration file beside the harness that was there."""
import json
import os

import pytest

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result

@pytest.fixture()
def epsilon_tree(bench_tree):
    """The rehearsal tree with the real `epsilon` configuration, rows cut
    for the CPU (whose engines are the portable ones), and the real
    `train` mix asked for fewer iterations."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in real["configs"] if c["name"] == "epsilon")
    cell = next(w for w in real["workloads"] if w["name"] == "epsilon-train")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("epsilon", "train", 1)
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    assert (config["rows"], config["features"], config["heldout_rows"]) \
        == (400000, 2000, 100000)
    assert config["engines"] == {"histogram": "pallas",
                                 "partition": "pallas-blocks"}
    config.update(rows=4000, heldout_rows=1000, quality_band=[0.5, 1.0],
                  engines={"histogram": "lax", "partition": "lax"})
    path = os.path.join(bench_tree["root"], "epsilon-cut.json")
    json.dump(config, open(path, "w"))
    traffic = os.path.join(bench_tree["bench_dir"], "traffic")
    mix = json.load(open(os.path.join(traffic, "train.json")))
    json.dump(dict(mix, warmup_iters=2, min_iters=2, trace_iters=2),
              open(os.path.join(traffic, "train-two.json"), "w"))
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "epsilon-cut", "file": path})
    manifest["workloads"].append({"name": "epsilon-cut-train",
                                  "config": "epsilon-cut",
                                  "traffic": "train-two", "chips": 1})
    json.dump(manifest, open(bench_tree["manifest_path"], "w"))
    config["quality_at_iter"] = 4
    json.dump(config, open(path, "w"))
    return bench_tree


def test_epsilon_cell_untraced(epsilon_tree):
    result = run_tiny(epsilon_tree, "epsilon-cut-train", seconds=0.5)
    check_result(result, trace=False)
    assert set(result["metrics"]) == {"train_s_per_iter", "heldout_quality",
                                      "setup_s"}
    detail = json.load(open(os.path.join(
        epsilon_tree["root"], "chiprun_out", "bench",
        "epsilon-cut-train.s3.t0.json")))["detail"]
    payload = detail["verify"][0]["checks"]["payload"]
    # 2,000 bin columns and 10 value columns; the chip pads them to 2,048
    assert payload["lanes"] == 2010 and payload["rows"] >= 4000
    assert detail["verify"][0]["checks"]["tree0"]["counts_ok"]


def test_epsilon_cell_traced_reports_the_ingest_split(epsilon_tree):
    result = run_tiny(epsilon_tree, "epsilon-cut-train", seconds=0.5,
                      trace=True)
    check_result(result, trace=True)
    assert {"ingest.find_bins_s", "ingest.encode_s", "ingest.dataset_s",
            "loop.dispatches_per_iter"} <= set(result["metrics"])


def test_epsilon_cell_is_data_only():
    """The cell brings a configuration file and nothing else: its
    generator, mix and driver are `higgs-train`'s, and every per-layer
    metric that applies to it has its reader."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert files["epsilon"].endswith(".json")
    epsilon, higgs = (json.load(open(os.path.join(ROOT, files[name])))
                      for name in ("epsilon", "higgs"))
    assert epsilon["generator"] == higgs["generator"]
    assert cells["epsilon-train"]["traffic"] == cells["higgs-train"]["traffic"]
    for metric in manifest["per_layer"]:
        if "epsilon-train" in metric.get("workloads", ["epsilon-train"]):
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics", metric["name"] + ".py")), metric
