"""Which child lies first in the parent's range is the caller's choice.

Every partition entry point takes `right_first` and gives one layout: the
rows of the first child at [start, start + n_first), of the other behind
them, each in its original order, the children's values in the value
column, `num_left` returned whichever lies first.  Held here to a stable
partition done in numpy (the Pallas bodies in interpret mode), and the
grower to what it makes of it: the larger child first, the rows of the
smaller counted as staged."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.boosting.grower import GrowerConfig
from lightgbm_tpu.boosting.grower2 import (PayloadCols, WIDE_BITS, _wide_add,
                                           make_partitioned_grower,
                                           wide_count)
from lightgbm_tpu.boosting.gbdt import _feature_meta_device
from lightgbm_tpu.ops import pallas_segment as pseg
from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops.split import MISSING_NAN

from test_grower2 import _make_problem
from test_pallas_segment import _pred

F, B = 5, 16
VALUE_COL = F + 3
N_PAD = 1280
LV, RV = np.float32(-0.25), np.float32(0.75)

#: entry point -> (callable(payload, aux, start, count, pred, lv, rv,
#: right_first), payload lanes): the three Pallas wrappers at the widths
#: their plans take (the column-block one over two 512-lane blocks) and
#: the portable partition at the payload's own ragged width
ENTRY_POINTS = {
    "partition_segment_acc": (lambda *a, rf: pseg.partition_segment_acc(
        *a, VALUE_COL, B, rf, interpret=True), 128),
    "partition_segment_acc_blocks": (
        lambda *a, rf: pseg.partition_segment_acc_blocks(
            *a, VALUE_COL, B, rf, interpret=True), 1024),
    "pallas_segment.partition_segment": (
        lambda *a, rf: pseg.partition_segment(
            *a, VALUE_COL, B, rf, interpret=True), 128),
    "segment.partition_segment": (lambda *a, rf: seg.partition_segment(
        *a, VALUE_COL, rf), F + 4),
}

#: predicate -> what `test_pallas_segment._pred` takes (split column 1,
#: identity decode, B bins)
PREDICATES = {
    "numerical": dict(threshold=6),
    "categorical": dict(is_cat=True, bitset=np.arange(B) % 3 == 1),
    "missing_default_left": dict(threshold=4, missing_type=MISSING_NAN,
                                 default_left=True),
}

#: segment -> (start, count, bin every row of the segment holds or None)
SEGMENTS = {
    "aligned": (256, 700, None),
    "unaligned": (9, 1015, None),
    "empty_side": (100, 300, 1),
    "one_chunk": (7, 100, None),
    "many_chunks": (3, 1200, None),
}


def _payload(width, constant_bin, start, count, seed):
    rng = np.random.default_rng(seed)
    pay = np.zeros((N_PAD + seg.GUARD, width), np.float32)
    pay[:N_PAD, :F] = rng.integers(0, B, size=(N_PAD, F))
    if constant_bin is not None:
        pay[start:start + count, 1] = constant_bin
    pay[:N_PAD, F] = rng.standard_normal(N_PAD)
    pay[:N_PAD, F + 1] = rng.random(N_PAD)
    pay[:N_PAD, F + 2] = 1.0
    return pay


def _go_left(bins, kw):
    """Bin::Split on an identity column, in numpy."""
    bins = bins.astype(np.int64)
    if kw.get("is_cat"):
        return np.asarray(kw["bitset"])[bins]
    missing = (bins == B - 1) if kw.get("missing_type") == MISSING_NAN \
        else np.zeros(len(bins), bool)
    return np.where(missing, kw.get("default_left", False),
                    bins <= kw["threshold"])


@pytest.mark.parametrize("segment", list(SEGMENTS))
@pytest.mark.parametrize("predicate", list(PREDICATES))
@pytest.mark.parametrize("right_first", [False, True])
@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
def test_partition_layout(entry, right_first, predicate, segment):
    fn, width = ENTRY_POINTS[entry]
    start, count, constant_bin = SEGMENTS[segment]
    kw = PREDICATES[predicate]
    host = _payload(width, constant_bin, start, count, seed=start + count)
    rows = host[start:start + count]
    left = _go_left(rows[:, 1], kw)
    first = ~left if right_first else left
    want = host.copy()
    want[start:start + count] = np.concatenate([rows[first], rows[~first]])
    first_value, second_value = (RV, LV) if right_first else (LV, RV)
    want[start:start + count, VALUE_COL] = np.where(
        np.arange(count) < first.sum(), first_value, second_value)
    if constant_bin is not None:
        assert left.all() or not left.any()

    # right_first is DATA: traced, as the grower passes it
    got, _, num_left = jax.jit(
        lambda pay, rf: fn(pay, jnp.zeros_like(pay), jnp.int32(start),
                           jnp.int32(count), _pred(**kw), jnp.float32(LV),
                           jnp.float32(RV), rf=rf)
    )(jnp.asarray(host), jnp.bool_(right_first))

    assert int(num_left) == int(left.sum())
    # rows, value column, and every row outside the segment untouched
    np.testing.assert_array_equal(np.asarray(got), want)


def test_staged_then_committed_is_the_whole_partition():
    """The frontier-batched grower's two halves, with the right child
    first: `partition_segment_stage` returns the FIRST child's count."""
    start, count, kw = 100, 700, PREDICATES["numerical"]
    host = _payload(F + 4, None, start, count, seed=3)
    pay = jnp.asarray(host)
    args = (jnp.int32(start), jnp.int32(count), _pred(**kw))
    want, _, num_left = seg.partition_segment(
        pay, jnp.zeros_like(pay), *args, LV, RV, VALUE_COL, True)
    aux, num_first = seg.partition_segment_stage(
        pay, jnp.zeros_like(pay), *args, True)
    assert int(num_first) == count - int(num_left)
    got = seg.partition_segment_commit(pay, aux, args[0], args[1], num_first,
                                       RV, LV, VALUE_COL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("counts", [[5], [(1 << WIDE_BITS) - 1, 1, 7],
                                    [10_500_000] * 254, [2**31 - 2**21] * 3])
def test_wide_counter_is_exact(counts):
    """A tree's row counters pass int32 and the fetch's float32."""
    acc = jnp.zeros(2, jnp.int32)
    for n in counts:
        acc = _wide_add(acc, jnp.int32(n))
    as_fetched = np.asarray(acc).astype(np.float32)
    assert wide_count(as_fetched) == sum(counts)
    # a pair a device block, stacked
    assert wide_count(np.concatenate([as_fetched] * 4)) == 4 * sum(counts)


# ---------------------------------------------------------------------------
# the grower
# ---------------------------------------------------------------------------

def _raw_child_counts(tree, num_leaves):
    """(left, right) raw row counts of every internal node, from the
    leaves' segment lengths."""
    def rows(child):
        if child < 0:
            return int(tree["seg_cnt"][~child])
        return sum(pairs[child])

    pairs = {}
    for node in reversed(range(num_leaves - 1)):
        pairs[node] = (rows(int(tree["left_child"][node])),
                       rows(int(tree["right_child"][node])))
    return [pairs[node] for node in range(num_leaves - 1)]


@pytest.mark.parametrize("frontier_batch", [1, 4])
@pytest.mark.parametrize("categorical", [(), (2, 4)])
def test_grower_puts_the_larger_child_first(categorical, frontier_batch):
    """Three trees grown one after the other on one payload: after each,
    the leaves' segments tile the rows, the index column is a permutation,
    a row's value column is its leaf's value, and the rows counted as
    staged are the smaller children's."""
    n, f, L = 3072, 6, 15
    X, y = _make_problem(n, f, seed=11, categorical=categorical)
    config = Config({"objective": "binary", "max_bin": 63, "num_leaves": L,
                     "min_data_in_leaf": 20})
    ds = BinnedDataset.from_matrix(X, config,
                                   categorical_feature=list(categorical),
                                   row_chunk=1024)
    assert ds.num_data_padded == n          # raw counts are masked counts
    gcfg = GrowerConfig(num_leaves=L, max_depth=-1, lambda_l1=0.0,
                        lambda_l2=0.1, max_delta_step=0.0,
                        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3,
                        min_gain_to_split=0.0, row_chunk=n,
                        with_categorical=bool(categorical),
                        frontier_batch=frontier_batch)
    cols = PayloadCols(grad=f, hess=f + 1, cnt=f + 2, value=f + 3)
    idx_col = f + 4
    grow = make_partitioned_grower(_feature_meta_device(ds), gcfg,
                                   ds.max_num_bin, cols, f)
    host = np.zeros((n + seg.GUARD, f + 5), np.float32)
    host[:n, :f] = ds.bins.T
    host[:n, cols.hess] = 0.25
    host[:n, cols.cnt] = 1.0
    host[:n, idx_col] = np.arange(n)
    score = np.zeros(n, np.float32)
    aux = jnp.zeros_like(jnp.asarray(host))
    for _ in range(3):
        idx = host[:n, idx_col].astype(np.int64)
        host[:n, cols.grad] = (1 / (1 + np.exp(-score)) - y)[idx]
        tree, payload, aux = grow(jnp.asarray(host), aux, jnp.ones(f, bool))
        tree = jax.device_get(tree)
        host = np.array(payload)
        nl = int(tree["num_leaves"])
        assert nl > 4

        starts, cnts = tree["seg_start"][:nl], tree["seg_cnt"][:nl]
        order = np.argsort(starts)
        assert starts[order][0] == 0
        np.testing.assert_array_equal(starts[order][1:],
                                      (starts + cnts)[order][:-1])
        assert (starts + cnts)[order][-1] == n
        idx = host[:n, idx_col].astype(np.int64)
        np.testing.assert_array_equal(np.sort(idx), np.arange(n))
        for leaf in range(nl):
            s, c = int(starts[leaf]), int(cnts[leaf])
            np.testing.assert_array_equal(
                host[s:s + c, cols.value],
                np.full(c, tree["leaf_value"][leaf], np.float32))

        children = _raw_child_counts(tree, nl)
        partitioned = wide_count(tree["rows_partitioned"])
        staged = wide_count(tree["rows_staged"])
        assert partitioned == sum(l + r for l, r in children)
        assert staged == sum(min(l, r) for l, r in children)
        assert 0 < staged <= partitioned / 2
        # some split had the right child the larger and some the left:
        # both layouts were exercised
        assert len({l <= r for l, r in children}) == 2
        score[idx] += 0.3 * host[:n, cols.value]


TRAIN = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
         "verbose": -1, "seed": 3}


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_booster_counts_the_rows_its_partitions_move(learner):
    """Through `lgb.train`, serial and on the CPU's virtual-device mesh:
    an entry a finished tree in `_FastState.counters`, and the scores the
    payload holds (the value column added a tree at a time, wherever the
    children lay) are the model's own predictions."""
    n, rounds = 4096, 5
    X, y = _make_problem(n, 6, seed=5)
    bst = lgb.train({**TRAIN, "tree_learner": learner},
                    lgb.Dataset(X, label=y), num_boost_round=rounds)
    eng = bst._engine
    assert eng._fast_active and (eng.mesh is not None) == (learner == "data")
    counters = eng._fast.counters
    trees = eng.model.trees
    assert len(counters["rows_staged"]) == len(trees) == rounds
    for tree, partitioned, staged in zip(trees, counters["rows_partitioned"],
                                         counters["rows_staged"]):
        internal = tree.internal_count[:tree.num_leaves - 1]
        # every split's rows, padding rows beside the real ones
        assert partitioned >= internal.sum() and internal[0] == n
        assert 0 < staged < partitioned
        if learner == "serial":
            # no bagging, no padding: the tree's own counts are the raw ones
            assert eng.train_set.num_data_padded == n
            ni = tree.num_leaves - 1
            left, right = (
                np.where(c >= 0, internal[np.maximum(c, 0)],
                         tree.leaf_count[~np.minimum(c, -1)])
                for c in (tree.left_child[:ni], tree.right_child[:ni]))
            assert partitioned == internal.sum()
            assert staged == np.minimum(left, right).sum() <= partitioned / 2
    np.testing.assert_allclose(eng._fast.raw_scores()[0, :n],
                               bst.predict(X, raw_score=True),
                               rtol=1e-5, atol=1e-6)
