"""`partition.staged_row_share`: read off the booster's counters in a
traced run of every train cell, silent for a program without them."""
import json
import os
import types

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result

NAME = "partition.staged_row_share"


def _reader():
    from benchmarks.run import load_module
    return load_module(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def _run(counters, iters=2, K=1):
    fast = types.SimpleNamespace(K=K)
    if counters is not None:
        fast.counters = counters
    bst = types.SimpleNamespace(_engine=types.SimpleNamespace(_fast=fast))
    return types.SimpleNamespace(state={"bst": bst}, window={"iters": iters})


def test_manifest_entry_is_the_readers():
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    [entry] = [m for m in real["per_layer"] if m["name"] == NAME]
    reader = _reader()
    assert entry == {"name": NAME, "unit": reader.UNIT, "better": "lower",
                     "source": reader.SOURCE, "layer": reader.LAYER,
                     "moves": reader.MOVES}
    assert reader.DRIVERS == ("train",) and reader.LAYER == "segment-kernels"
    assert entry["moves"] in {m["name"] for m in real["end_to_end"]}


def test_share_is_over_the_windows_trees_only():
    read = _reader().read
    counters = {"rows_partitioned": [1000, 400, 600],
                "rows_staged": [900, 100, 200]}
    assert read(_run(counters, iters=2)) == 100.0 * 300 / 1000
    assert read(_run(counters, iters=3)) == 100.0 * 1200 / 2000
    # fewer finished trees than the window ran, stumps, no window
    assert read(_run(counters, iters=4)) is None
    assert read(_run({"rows_partitioned": [0], "rows_staged": [0]},
                     iters=1)) is None
    assert read(_run(counters, iters=0)) is None


def test_a_program_without_the_counters_reads_nothing():
    """The parent of the PR that brought them has `splits` and
    `categorical_splits` only, an older one no counters at all."""
    read = _reader().read
    assert read(_run({"splits": [3, 3], "categorical_splits": [0, 0]})) is None
    assert read(_run(None)) is None
    assert read(types.SimpleNamespace(state={}, window={"iters": 2})) is None


def test_traced_train_cells_report_it_under_half(bench_tree):
    for cell in ("tiny-train", "tiny-mesh-train"):
        result = run_tiny(bench_tree, cell, seconds=0.5, trace=True)
        check_result(result, trace=True)
        share = result["metrics"][NAME]
        assert share["unit"] == "%" and 0.0 < share["value"] < 50.0, share
        assert result["metrics"]["loop.dispatches_per_iter"]["value"] == 2.0
        assert result["metrics"]["loop.blocking_fetches_per_iter"][
            "value"] == 1.0
    assert NAME not in run_tiny(bench_tree, "tiny-predict", seconds=0.5,
                                trace=True)["metrics"]
