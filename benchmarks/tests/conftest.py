"""The harness's own tests: a CPU rehearsal, run by hand with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not under tests/, so tier-1 neither gains nor loses by them.
Four virtual CPU devices stand in for a four-chip host; the compile
cache goes to a temporary directory, not to the checkout's.
"""
import json
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_tests_jax_cache_"))

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

DATA = os.path.join(HERE, "data")

#: the real mixes, cut to a rehearsal's size; everything else is theirs
TINY_MIXES = {
    "tiny-predict": ("predict-batch", {
        "pool_rows": 4096,
        "request_rows": [{"share": 1.0, "rows": [1024, 1024]}],
        "reference_sample": 500, "trace_seconds": 0.5}),
    "tiny-serve": ("serve-steady", {
        "rate_phases": [[1.0, 150.0]], "pool_rows": 4096,
        "request_rows": [{"share": 0.6, "rows": [1, 1]},
                         {"share": 0.4, "rows": [2, 300]}],
        "warm_buckets": [16, 32, 64, 128, 256, 512, 1024],
        "reference_sample": 500, "trace_seconds": 0.5}),
}


def metric_entry(bench_dir, name, **more):
    from benchmarks.run import load_module
    m = load_module(os.path.join(bench_dir, "layer_metrics", name + ".py"))
    return dict({"name": name, "unit": m.UNIT, "better": "lower",
                 "source": m.SOURCE, "layer": m.LAYER, "moves": m.MOVES},
                **more)


@pytest.fixture()
def bench_tree(tmp_path):
    """A copy of the benchmark's data-driven parts with a rehearsal
    manifest beside it: the real drivers, readers, tasks and mixes, tiny
    configurations and tiny mixes ADDED as new files."""
    bench_dir = tmp_path / "benchmarks"
    for part in ("drivers", "layer_metrics", "tasks", "traffic"):
        shutil.copytree(os.path.join(BENCH, part), bench_dir / part)
    for name, (base, changes) in TINY_MIXES.items():
        with open(os.path.join(BENCH, "traffic", base + ".json")) as fh:
            mix = dict(json.load(fh), **changes)
        (bench_dir / "traffic" / (name + ".json")).write_text(json.dumps(mix))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    with open(os.path.join(BENCH, "held", "manifest.json")) as fh:
        held = json.load(fh)
    names = {m["name"] for m in real["per_layer"]}
    manifest = {
        "configs": [
            {"name": "tiny-higgs", "file": os.path.join(DATA, "tiny-higgs.json")},
            {"name": "tiny-mesh", "file": os.path.join(DATA, "tiny-mesh.json")}],
        "workloads": [
            {"name": "tiny-train", "config": "tiny-higgs",
             "traffic": "train", "chips": 1},
            {"name": "tiny-mesh-train", "config": "tiny-mesh",
             "traffic": "train", "chips": 4},
            {"name": "tiny-predict", "config": "tiny-higgs",
             "traffic": "tiny-predict", "chips": 1},
            {"name": "tiny-serve", "config": "tiny-higgs",
             "traffic": "tiny-serve", "chips": 1}],
        "end_to_end": real["end_to_end"] + [
            m for m in held["end_to_end"] if m["name"] != "setup_s"],
        "per_layer": real["per_layer"] + [
            m for m in held["per_layer"] if m["name"] not in names],
    }
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return {"root": str(tmp_path), "bench_dir": str(bench_dir),
            "manifest_path": str(path), "manifest": manifest}


def run_tiny(tree, workload, seconds=1.0, trace=False, seed=3):
    from benchmarks import run as harness
    return harness.run_cell(
        workload, seed, seconds, trace, manifest_path=tree["manifest_path"],
        bench_dir=tree["bench_dir"], root=tree["root"], require_tpu=False)
