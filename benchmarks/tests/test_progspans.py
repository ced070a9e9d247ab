"""lib/progspans.py and the readers built on it, against a trace written
by hand (data/spans.xplane.textproto: the answers are in its header),
against a ring recorded by hand, and in the CPU rehearsal."""
import os

import pytest

from conftest import BENCH, DATA, run_tiny

from benchmarks.lib import progspans, xplane
from benchmarks.run import Run, load_module

SPANS = os.path.join(DATA, "spans.xplane.textproto")
KNOWN = os.path.join(DATA, "known.xplane.textproto")

RING_READERS = ["ingest.find_bins_s", "ingest.encode_s",
                "ingest.construct_self_s", "loop.payload_build_s",
                "loop.host_launch_ms_per_iter", "loop.host_floor_ms_per_iter",
                "loop.assemble_ms_per_iter"]
TRACE_READERS = ["device.idle_unattributed_share",
                 "grower.split_search_s_per_iter",
                 "grower.subtract_s_per_iter", "step.grad_score_s_per_iter",
                 "grower.tree_update_s_per_iter"]


def read(name, run):
    reader = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))
    return reader.read(run)


def traced_run(tmp_path, textproto, iters=1):
    """A run as the harness leaves it for the readers of a traced window:
    `xtrace` loaded, the trace still on disk where `run.py` wrote it."""
    from jax.profiler import ProfileData
    run = Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1, True,
              str(tmp_path / "benchmarks"))
    where = tmp_path / "chiprun_out" / "bench_trace" / "cell"
    where.mkdir(parents=True)
    with open(textproto) as fh:
        (where / "t.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(fh.read()))
    run.xtrace = xplane.load(str(where))
    run.window["iters"] = iters
    return run


# -- the trace ---------------------------------------------------------------

def test_host_spans_keep_nesting_and_tell_two_threads_apart():
    spans = progspans.host_spans(SPANS)
    assert [s.name for s in spans] == [
        "train/iteration", "launch/gbdt.step", "assembler/wait",
        "assembler/drain", "fetch/pipeline_drain"]     # no bench/, no Pjit
    by = {s.name: s for s in spans}
    main, worker = by["train/iteration"].thread, by["assembler/drain"].thread
    assert main != worker                   # both lines are "python3"
    assert by["launch/gbdt.step"].thread == main
    assert by["fetch/pipeline_drain"].thread == worker
    it, wait = by["train/iteration"], by["assembler/wait"]
    assert (it.start_ns, it.dur_ns) == (1000, 1200)
    assert it.start_ns <= wait.start_ns \
        and wait.start_ns + wait.dur_ns <= it.start_ns + it.dur_ns
    assert progspans.host_spans(KNOWN) == []    # a program without spans


def test_an_idle_gap_goes_to_the_innermost_span_or_to_nobody():
    trace = xplane.load(SPANS)
    lo, hi = trace.window_ns()
    assert (lo, hi) == (1000, 3000)
    gaps = xplane.gaps(trace.devices[0].busy, lo, hi)
    assert gaps == [(2000, 2500), (2900, 3000)]
    assert progspans.charge_gaps(gaps, progspans.host_spans(SPANS)) \
        == {"fetch/pipeline_drain": 500, "unattributed": 100}
    assert progspans.charge_gaps(gaps, []) == {"unattributed": 600}


def test_phases_come_from_the_metadata_innermost_scope_last():
    [(plane, phases)] = progspans.op_phases(SPANS).items()
    assert plane == "/device:TPU:0"
    short = {xplane.SHORT_NAME.match(name).group(1): phase
             for name, phase in phases.items()}
    assert short == {"while.1": "tree_update", "kernel.2": "hist",
                     "fusion.3": "split_search", "fusion.4": "subtract",
                     "fusion.6": "grad"}            # copy.5 has no tf_op
    assert progspans.op_phases(KNOWN) == {}     # a trace without the stat


def test_wire_reader_reads_every_kind_of_field():
    buf = (b"\x08\x96\x01"                  # 1: varint 150
           b"\x12\x03abc"                   # 2: bytes
           b"\x1d\x01\x00\x00\x00"          # 3: fixed32
           b"\x21" + b"\x02" + b"\x00" * 7)  # 4: fixed64
    assert list(progspans._fields(buf)) == [
        (1, 150), (2, b"abc"), (3, b"\x01\x00\x00\x00"),
        (4, b"\x02" + b"\x00" * 7)]


@pytest.mark.parametrize("name, value", [
    ("device.idle_unattributed_share", 100.0 * 100 / 600),
    ("grower.split_search_s_per_iter", 200e-9 / 2),
    ("grower.subtract_s_per_iter", 200e-9 / 2),
    ("step.grad_score_s_per_iter", 200e-9 / 2),
    ("grower.tree_update_s_per_iter", 300e-9 / 2),
])
def test_trace_readers_on_the_hand_written_trace(tmp_path, capsys, name,
                                                 value):
    run = traced_run(tmp_path, SPANS, iters=2)
    assert read(name, run) == pytest.approx(value)
    if name == "device.idle_unattributed_share":
        line = capsys.readouterr().out
        assert "idle_by" in line and "fetch/pipeline_drain" in line
        assert run.detail["idle_by"][0]["idle_s"] == pytest.approx(600e-9)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_in_a_trace_without_spans_or_scopes(
        tmp_path, name):
    assert read(name, traced_run(tmp_path, KNOWN)) is None
    untraced = Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1,
                   False, str(tmp_path / "benchmarks"))
    assert read(name, untraced) is None


# -- the ring ----------------------------------------------------------------

MS = 1_000_000


def record(tracing, name, start_ms, dur_ms, span_id, parent=None, track=None):
    tracing.record(name, int(start_ms * MS), int(dur_ms * MS), trace="t" * 32,
                   span_id=span_id, parent=parent, track=track)


@pytest.fixture()
def ring():
    """Set-up and a window of two iterations, recorded by hand.  The
    dispatch thread is the recording one, the assembler's a track."""
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    record(tracing, "dataset/construct", 0, 1000, "c")
    record(tracing, "dataset/find_bins", 100, 200, "c1", "c")
    record(tracing, "dataset/encode", 300, 10, "c2", "c")       # native: no
    record(tracing, "dataset/encode", 310, 290, "c3", "c")      # python
    record(tracing, "dataset/bundle", 600, 100, "c4", "c")
    # a warm-up iteration: not of the window
    record(tracing, "train/iteration", 2000, 900, "w")
    record(tracing, "booster/payload", 2010, 400, "p", "w")
    record(tracing, "launch/gbdt.payload_build", 2020, 380, "p1", "p")
    record(tracing, "launch/gbdt.step", 2500, 300, "w1", "w")
    # the window
    for i, t0 in enumerate((3000, 4000)):
        it = "i%d" % i
        record(tracing, "train/iteration", t0, 100, it)
        record(tracing, "launch/gbdt.step", t0 + 10, 4, it + "l", it)
        record(tracing, "assembler/wait", t0 + 20, 70, it + "w", it)
        record(tracing, "fetch/eval_fetch", t0 + 92, 3, it + "f", it)
        # the host half, on the other thread, outlives the iteration
        record(tracing, "assembler/drain", t0 + 50, 500, it + "d", it,
               track="worker")
        record(tracing, "launch/gbdt.pack_fetch", t0 + 51, 1, it + "dl",
               it + "d", track="worker")
        record(tracing, "fetch/pipeline_drain", t0 + 52, 490, it + "df",
               it + "d", track="worker")
    yield Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1, True,
              os.path.join(BENCH))
    tracing.reset()


@pytest.mark.parametrize("name, value", [
    ("ingest.find_bins_s", 0.2),
    ("ingest.encode_s", 0.3),                   # both paths it tried
    ("ingest.construct_self_s", 1.0 - 0.2 - 0.3 - 0.1),
    ("loop.payload_build_s", 0.4),
    ("loop.host_launch_ms_per_iter", 4.0),      # not the worker's launch
    ("loop.host_floor_ms_per_iter", 100.0 - 70.0 - 3.0),
    ("loop.assemble_ms_per_iter", 500.0 - 490.0),
])
def test_ring_readers_on_a_ring_recorded_by_hand(ring, name, value):
    ring.window["iters"] = 2
    assert read(name, ring) == pytest.approx(value)


@pytest.mark.parametrize("name", RING_READERS)
def test_ring_readers_find_nothing_in_the_parents_ring(name):
    """The parent of the PR that added the spans records only
    `train/iteration` and `assembler/drain`: nothing is read, nothing is
    raised, and no iteration is mistaken for host floor."""
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    record(tracing, "train/iteration", 0, 100, "i")
    record(tracing, "assembler/drain", 50, 500, "d", "i", track="worker")
    run = Run({"name": "cell", "chips": 1}, {}, {"driver": "train"}, 1, True,
              BENCH)
    run.window["iters"] = 1
    try:
        assert read(name, run) is None
    finally:
        tracing.reset()


# -- the rehearsal -----------------------------------------------------------

@pytest.mark.parametrize("cell", ["tiny-train", "tiny-mesh-train"])
def test_traced_rehearsal_reports_the_span_metrics_and_no_device_ones(
        bench_tree, cell):
    from lightgbm_tpu.runtime import tracing
    tracing.reset()
    result = run_tiny(bench_tree, cell, seconds=1.5, trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert set(RING_READERS) <= names
    assert not set(TRACE_READERS) & names       # a CPU trace: no device
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # the parent span covers the call the harness times, parts included
    parts = m["ingest.find_bins_s"] + m["ingest.encode_s"] \
        + m["ingest.construct_self_s"]
    assert 0 < parts <= m["ingest.dataset_s"]
    assert parts >= 0.9 * m["ingest.dataset_s"]
    assert 0 < m["loop.host_launch_ms_per_iter"] \
        <= m["loop.host_floor_ms_per_iter"]
    assert m["loop.assemble_ms_per_iter"] > 0
    assert {u["unit"] for k, u in result["metrics"].items()
            if k.startswith("loop.") and k.endswith("ms_per_iter")} == {"ms"}
