"""BENCHMARK.json against the contract's rules that a file can be held
to without a chip, and against the files it names."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "_dim", "_rank", "head", "expansion", "features", "max_bin",
               "num_leaves")
MIX_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load(path):
    with open(path) as fh:
        return json.load(fh)


MANIFESTS = {"BENCHMARK.json": os.path.join(ROOT, "BENCHMARK.json"),
             "held": os.path.join(BENCH, "held", "manifest.json")}


@pytest.fixture(params=sorted(MANIFESTS))
def manifest(request):
    return load(MANIFESTS[request.param])


def test_top_level(manifest):
    manifest.pop("note", None)
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"][:2] == ["python3", "benchmarks/run.py"]
    assert len(manifest["command"]) <= 32
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(MANIFESTS["BENCHMARK.json"]) <= 64 * 1024


def test_names_are_plain_and_used_once(manifest):
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in manifest[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for x in manifest["configs"] + manifest["workloads"]:
        assert len(x["why"]) <= 200, (x["name"], len(x["why"]))


def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["source"].startswith("https://")
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        body = load(os.path.join(ROOT, c["file"]))
        assert body["source"] == c["source"] and body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert set(body["reduced_detail"]) == set(c["reduced"])
        assert not any(w in key for key in c["reduced"] for w in WIDTH_WORDS)
        assert "assumed" in body and "deployment" in body
    sources = [c["source"] for c in manifest["configs"]]
    assert len(set(sources)) == len(sources)


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    configs = {c["name"]: load(os.path.join(ROOT, c["file"]))
               for c in manifest["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    for w in cells:
        assert w["chips"] in (1, 4)
        assert w["chips"] == configs[w["config"]]["chips"]
        mixes = [n for n in os.listdir(os.path.join(BENCH, "traffic"))
                 if os.path.splitext(n)[0] == w["traffic"]]
        assert len(mixes) == 1 and mixes[0].endswith(MIX_SUFFIXES)
        mix = load(os.path.join(BENCH, "traffic", mixes[0]))
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))
        assert mix["loop"] in ("open_loop", "closed_loop") and mix["who"]
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in manifest["workloads"]}
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert LAYER.match(m["layer"]), m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        there = [m for m in e2e.values() if cell in m.get("workloads", cells)]
        assert len(there) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_every_per_layer_metric_has_its_reader_and_they_agree(manifest):
    from benchmarks.run import load_module
    for m in manifest["per_layer"]:
        reader = load_module(os.path.join(BENCH, "layer_metrics",
                                          m["name"] + ".py"))
        assert (reader.UNIT, reader.SOURCE, reader.LAYER) \
            == (m["unit"], m["source"], m["layer"]), m["name"]
        assert callable(reader.read) and reader.__doc__


def test_files_under_paths_have_plain_names():
    for folder, dirs, names in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            rel = os.path.relpath(os.path.join(folder, name), ROOT)
            assert PLAIN_PATH.match(rel), rel
