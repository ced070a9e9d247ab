"""The trace reduction: on a trace written by hand, whose answers can be
checked on paper, and on a cut of a trace recorded on the chip (12 ms of
one `gbdt.step` of higgs-train, PR 22: four partitions and three
histograms late in a tree), which pins the names today's trace gives
the planes, the lines and the kernels."""
import os
import re

import pytest

from benchmarks.lib import xplane
from conftest import BENCH, DATA


@pytest.fixture(scope="module")
def known():
    return xplane.load(os.path.join(DATA, "known.xplane.textproto"))


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(os.path.join(DATA, "higgs-train.cut.xplane.pb.gz"))


def test_intervals():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) \
        == [(0, 3), (5, 8)]
    assert xplane.length([(0, 3), (5, 8)]) == 6
    assert xplane.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert xplane.gaps([(2, 3), (5, 12)], 0, 10) == [(0, 2), (3, 5)]


def test_window_is_what_the_annotations_cover(known):
    assert known.window_ns() == (1000, 3000)
    assert [s[0] for s in known.spans] == ["bench/update", "bench/drain"]


def test_self_times_nest_and_busy_is_the_union(known):
    dev = known.devices[0]
    by_name = {op.short: op for op in dev.ops}
    assert by_name["while.1"].dur_ns == 1000
    assert by_name["while.1"].self_ns == 300      # 1000 - 300 - 200 - 200
    assert by_name["kernel_a.1"].self_ns == 300
    assert dev.busy == [(1000, 2000), (2500, 2700)]
    assert dev.modules == [("jit_step(123)", 1000, 1700)]
    busy_s, window_s = xplane.busy_seconds(known)
    assert window_s == pytest.approx(2000e-9)
    assert busy_s == pytest.approx((1200 + 400) / 2 * 1e-9)  # two chips' mean


def test_patterns_match_the_instruction_not_its_operands(known):
    dev = known.devices[0]
    lo, hi = known.window_ns()
    collective = re.compile(r"^%?all-reduce")
    assert xplane.seconds_matching(dev, collective, lo, hi) \
        == pytest.approx(200e-9)        # kernel_b names all-reduce.1 too
    assert xplane.seconds_matching(dev, re.compile(r"^%?kernel_"), lo, hi) \
        == pytest.approx(500e-9)
    assert xplane.intervals_matching(dev, collective, lo, hi) \
        == [(1400, 1600)]
    # the loop's own event is not work: leaves only
    assert xplane.leaf_intervals(dev, lo, hi, exclude=collective) \
        == [(1100, 1400), (1700, 1900), (2500, 2700)]


def test_top_ops_and_idle_gaps(known):
    top = dict(xplane.top_ops(known))
    assert top["kernel_a"] == pytest.approx(700e-9)     # both chips
    assert top["while"] == pytest.approx(300e-9)
    assert xplane.label(known.devices[0].ops[0]) == "while"
    # device 0 idles [2000, 2500) and [2700, 3000); the first gap's middle
    # lies in bench/update ([1000, 2300)), the second's in bench/drain
    assert dict(xplane.idle_gaps(known)) == {
        "bench/update": pytest.approx(500e-9),
        "bench/drain": pytest.approx(300e-9)}


def load_reader(name):
    from benchmarks.run import load_module
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_recorded_trace_names(recorded):
    """What a v5e trace calls things today (PERF.md, names in the trace)."""
    assert [d.ordinal for d in recorded.devices] == [0]
    dev = recorded.devices[0]
    assert len(dev.ops) == 767
    assert dev.modules[0][0].startswith("jit_step(")
    assert {s[0] for s in recorded.spans} == {"bench/update", "bench/drain"}
    lo, hi = recorded.window_ns()
    partition = load_reader("kernel.partition_s_per_iter").PATTERN
    hist = load_reader("kernel.hist_s_per_iter").PATTERN
    collective = load_reader("mesh.collective_s_per_iter").PATTERN
    labels = [xplane.label(op) for op in dev.ops]
    assert labels.count("_partition_segment_acc") == 4
    assert labels.count("_segment_histogram") == 3
    assert xplane.seconds_matching(dev, partition, lo, hi) \
        == pytest.approx(0.008461271)
    assert xplane.seconds_matching(dev, hist, lo, hi) \
        == pytest.approx(0.002820642)
    assert xplane.seconds_matching(dev, collective, lo, hi) == 0.0
    assert all("tpu_custom_call" in op.name for op in dev.ops
               if partition.search(op.name) or hist.search(op.name))
    # self times partition the busy time: nothing is counted twice
    assert sum(op.self_ns for op in dev.ops) == xplane.length(dev.busy)
    assert [row[0] for row in xplane.top_ops(recorded, 2)] \
        == ["_partition_segment_acc", "_segment_histogram"]
