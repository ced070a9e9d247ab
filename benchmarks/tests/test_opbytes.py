"""The least-work functions on a tree built by hand: three leaves."""
import types

import numpy as np
import pytest

from benchmarks.lib import opbytes, peaks


def three_leaf_tree():
    """Root (100 rows) splits 70 | 30; its left child (internal node 1)
    splits 25 | 45.  Leaves: 0 = 25, 1 = 30, 2 = 45."""
    return types.SimpleNamespace(
        num_leaves=3,
        left_child=np.array([1, ~0]), right_child=np.array([~1, ~2]),
        internal_count=np.array([100, 70]),
        leaf_count=np.array([25, 30, 45]))


def test_partition_reads_and_writes_every_parent_once():
    t = three_leaf_tree()
    assert opbytes.partition_rows([t]) == 100 + 70
    assert opbytes.partition_bytes([t], lanes=128) == 170 * 128 * 4 * 2


def test_histogram_reads_the_root_and_each_smaller_child():
    t = three_leaf_tree()
    # root 100, then min(70, 30) = 30, then min(25, 45) = 25
    assert opbytes.histogram_rows([t]) == 155
    assert opbytes.histogram_bytes([t], lanes=128) == 155 * 128 * 4
    assert opbytes.histogram_ops([t], features=28, bins=256) \
        == 155 * 28 * 256 * 7 * 2
    assert opbytes.histogram_rows([t, t]) == 310


def test_a_stump_needs_no_kernel_work():
    stump = types.SimpleNamespace(
        num_leaves=1, left_child=np.zeros(1, int),
        right_child=np.zeros(1, int), internal_count=np.zeros(1, int),
        leaf_count=np.array([100]))
    assert opbytes.partition_rows([stump]) == 0
    assert opbytes.histogram_rows([stump]) == 0


def test_least_seconds_says_which_bound_binds():
    v5e = peaks.load("TPU v5 lite")
    seconds, bound = opbytes.least_seconds(819e9, 0, v5e)
    assert bound == "bytes" and seconds == pytest.approx(1.0)
    seconds, bound = opbytes.least_seconds(819e9, 2 * 197e12, v5e)
    assert bound == "ops" and seconds == pytest.approx(2.0)
    seconds, _ = opbytes.least_seconds(819e9, 0, v5e, chips=4)
    assert seconds == pytest.approx(0.25)
    # Higgs width: 28 x 256 one-hot products a row against 512 B a row
    t = three_leaf_tree()
    _, bound = opbytes.least_seconds(opbytes.histogram_bytes([t], 128),
                                     opbytes.histogram_ops([t], 28, 256), v5e)
    assert bound == "bytes"
    _, bound = opbytes.least_seconds(opbytes.histogram_bytes([t], 128),
                                     opbytes.histogram_ops([t], 67, 256), v5e)
    assert bound == "ops"


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.load("TPU v9 imaginary")
