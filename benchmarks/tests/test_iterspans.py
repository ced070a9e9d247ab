"""lib/iterspans.py and the four readers of an iteration's own account
(ISSUE 35), against a ring recorded by hand with the labels the program
gives and against the parent's ring, which has none of them.
`BENCHMARK.json` does not name the four yet (PERF.md, Open questions):
the rehearsal here adds them to its own manifest."""
import json
import os

import pytest

from conftest import BENCH, run_tiny

from benchmarks.lib import iterspans
from benchmarks.run import Run, load_module

READERS = ["loop.tree_arrival_s_per_iter", "loop.slowest_update_ratio",
           "loop.stalled_updates", "loop.gc_pause_ms_per_iter"]
#: what each reads on the parent's ring: two need only the spans' times
ON_THE_PARENT = {"loop.tree_arrival_s_per_iter": 1.0,
                 "loop.slowest_update_ratio": 1.0}
MS = 1_000_000


def read(name, run):
    reader = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))
    return reader.read(run)


def record(tracing, name, start_ms, dur_ms, span_id, parent=None, track=None,
           **labels):
    tracing.record(name, int(start_ms * MS), int(dur_ms * MS), trace="t" * 32,
                   span_id=span_id, parent=parent, track=track, **labels)


def window(tracing, labelled):
    """A warm-up iteration and a window of four, by hand.  Iteration k's
    unit is dispatched under it and drained on the other thread, its
    fetch closing in the NEXT iteration, where the dispatch thread waits
    for it.  The third of the window is the long one."""
    tracing.reset()
    starts = [1000, 2000, 3000, 4000, 7000]
    walls = [900, 1000, 1000, 3000, 1000]
    arrives = [1950, 2960, 3970, 6980, 7990]       # the trees, on the host
    for k, (t0, wall, arrival) in enumerate(zip(starts, walls, arrives)):
        it = "i%d" % k
        more = ({"iteration": 40 + k, "cpu_ns": 4 * MS, "runq_ns": k * MS}
                if labelled else {})
        record(tracing, "train/iteration", t0, wall, it, **more)
        record(tracing, "launch/gbdt.step", t0 + 1, 2, it + "l", it)
        more = ({"iteration": 40 + k, "tree": k, "cpu_ns": 2 * MS,
                 "runq_ns": MS // 2} if labelled else {})
        record(tracing, "assembler/drain", t0 + 10, arrival - t0 - 5,
               it + "d", it, track="worker", trees=1, **more)
        record(tracing, "fetch/pipeline_drain", t0 + 12, arrival - t0 - 12,
               it + "df", it + "d", track="worker")
    if labelled:
        # collections: 5 ms before the window (not counted), 30 ms on
        # the dispatch thread and 50 ms on the worker inside it, 20 ms
        # across the window's end of which 8 ms are inside
        record(tracing, "host/gc", 1500, 5, "g0", "i0", generation=0)
        record(tracing, "host/gc", 4100, 30, "g1", "i3", generation=2)
        record(tracing, "host/gc", 4200, 50, "g2", "i3d", track="worker",
               generation=1)
        record(tracing, "host/gc", 7992, 20, "g3", generation=0)
        with tracing.attach(("t" * 32, "i3")):
            tracing.instant("train/stall", iteration=43, verdict="tree_late")
        # a stall of the warm-up: not the window's
        tracing.instant("train/stall", iteration=40, verdict="compile")
    run = Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1, True,
              BENCH)
    run.window["iters"] = 4
    return run


@pytest.fixture()
def tracing():
    from lightgbm_tpu.runtime import tracing
    yield tracing
    tracing.reset()


@pytest.mark.parametrize("name, value", [
    # arrivals 2960, 3970, 6980, 7990: steps 1010, 3010, 1010 ms
    ("loop.tree_arrival_s_per_iter", 1.010),
    ("loop.slowest_update_ratio", 3.0),         # 3000 over a median of 1000
    ("loop.stalled_updates", 1.0),
    ("loop.gc_pause_ms_per_iter", (30 + 50 + 8) / 4),
])
def test_readers_on_a_ring_recorded_by_hand(tracing, name, value):
    assert read(name, window(tracing, labelled=True)) == pytest.approx(
        value, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_readers_on_the_parents_ring(tracing, name):
    """The parent records the spans and none of the labels: what needs a
    label reads None, what needs only the spans' times reads them, the
    drains paired with their iterations by parent id."""
    run = window(tracing, labelled=False)
    if name in ON_THE_PARENT:
        assert read(name, run) in (pytest.approx(1.010), 3.0)
    else:
        assert read(name, run) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_an_empty_ring_or_under_another_driver(
        tracing, name):
    tracing.reset()
    run = Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1, True,
              BENCH)
    run.window["iters"] = 4
    assert read(name, run) is None
    other = window(tracing, labelled=True)
    other.traffic = {"driver": "serve"}
    assert other.metric(name) is None


def test_events_keep_the_labels_and_the_instants(tracing):
    window(tracing, labelled=True)
    evs = iterspans.events()
    its = iterspans.window(Run({"name": "c", "chips": 1}, {},
                               {"driver": "train"}, 1, True, BENCH), evs)
    assert its == []                            # no window measured yet
    stalls = [e for e in evs if e.name == "train/stall"]
    assert [(e.ph, e.labels["verdict"]) for e in stalls] \
        == [("i", "tree_late"), ("i", "compile")]
    assert stalls[0].parent == "i3"
    [drain] = [e for e in evs if e.id == "i2d"]
    assert (drain.labels["tree"], drain.labels["iteration"]) == (2, 42)
    it = next(e for e in evs if e.id == "i2")
    assert iterspans.drains_of(it, evs) == [drain]
    # on the export's clock: 970 ms after the iteration opened at 3000
    assert iterspans.tree_arrival_ns(it, evs) - it.start_ns \
        == pytest.approx(970 * MS, abs=1000)


def test_the_rehearsal_reports_them(bench_tree):
    """A traced run of the tiny cell under a manifest that names the
    four, as the `benchmark` PR that appends them will."""
    from conftest import metric_entry
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    bench_tree["manifest"]["per_layer"].extend(
        metric_entry(bench_tree["bench_dir"], name) for name in READERS)
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(bench_tree["manifest"], fh)
    result = run_tiny(bench_tree, "tiny-train", seconds=1.5, trace=True)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(READERS) <= set(m)
    assert m["loop.tree_arrival_s_per_iter"] > 0
    assert m["loop.slowest_update_ratio"] >= 1.0
    assert m["loop.stalled_updates"] >= 0
    assert m["loop.gc_pause_ms_per_iter"] >= 0


@pytest.mark.parametrize("inject, verdict, profile", [
    ("fetch", "tree_late", False), ("gc", "gc", True), ("none", None, False)])
def test_the_stall_hunt_rehearsed(bench_tree, capsys, inject, verdict,
                                  profile):
    """`exp/stall_hunt.py` on the tiny cell: a window's report, and the
    two injected stalls named by the program's own rule."""
    import argparse

    from exp import stall_hunt
    from lightgbm_tpu.runtime import telemetry, tracing
    tracing.reset()
    telemetry._ITERATION_WALLS.clear()
    args = argparse.Namespace(
        workload="tiny-train", seed=5, seconds=1.5, windows=1, runs=0,
        profile=profile, chunk=40, ratio=3.0, inject=inject,
        inject_seconds=0.3,
        inject_objects=1_000_000)
    try:
        assert stall_hunt.one_run(
            args, manifest_path=bench_tree["manifest_path"],
            bench_dir=bench_tree["bench_dir"], root=bench_tree["root"],
            require_tpu=False) == 0
    finally:
        import gc
        gc.enable()
        telemetry.STALL_RATIO = 3.0
        telemetry._ITERATION_WALLS.clear()
    out = capsys.readouterr().out
    said = {}
    for line in out.splitlines():
        if line.startswith("[hunt]"):
            kind, fields = line[7:].split(None, 1)
            said.setdefault(kind, []).append(json.loads(fields))
    [window] = said["window"]
    assert window["iters"] == len(window["update_ms"]) >= 12
    assert len(window["arrival_step_ms"]) == window["iters"] - 1
    assert window["beats"] > 0
    assert set(window["fetch_behind_next_step"]) <= set(range(window["iters"]))
    verdicts = [s["verdict"] for s in said.get("stall", [])]
    if verdict:
        assert verdicts.count(verdict) == 1
        assert out.count("verdict=%s " % verdict) == 1      # ONE line
        if profile:
            [device] = [d for d in said["device"]
                        if d["iteration"] == said["stall"][
                            verdicts.index(verdict)]["iteration"]]
            assert device["found"] and device["wall_s"] > 0.05
    assert said["run"][0]["windows"] == 1
