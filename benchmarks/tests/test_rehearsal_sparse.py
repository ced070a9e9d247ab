"""A learning task that hands the harness a scipy.sparse CSR table, end to
end on the CPU: the task and its configuration are written into the
rehearsal's copy as a later PR would add them, and nothing else there
changes.  Beside it, `lib/reference.py` held to one walk for both forms:
a table given dense and as CSR reads the same leaves, the same raw
scores to the bit and the same tree-0 check, on trees whose nodes treat
zero or NaN as missing and on rows whose split column is absent, stored
as 0.0 (or -0.0) and stored as NaN; and a CSR walk that never builds the
dense table."""
import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest
from scipy import sparse

from conftest import BENCH, ROOT, run_tiny
from test_rehearsal import check_result

#: the task as a later PR brings it: six categorical columns one-hot coded
#: into 1,200 (the largest 600 categories), four numeric ones beside them,
#: float32 CSR, the label a latent of both
ONEHOT_SPARSE = '''"""One-hot codes and a few numbers as a float32 CSR table."""
import numpy as np
from scipy import sparse

from benchmarks.lib import quality, reference

CARDINALITY = (600, 300, 150, 80, 50, 20)
NUMERIC = 4
#: seed of the task itself (every category's effect), fixed across runs
TASK_SEED = 17


def make(cfg, seed, part):
    rows = cfg["heldout_rows"] if part else cfg["rows"]
    fixed = np.random.default_rng(TASK_SEED)
    rng = np.random.default_rng([seed, part])
    per_row = len(CARDINALITY) + NUMERIC
    cols = np.empty((rows, per_row), np.int32)
    vals = np.ones((rows, per_row), np.float32)
    latent = np.zeros(rows)
    first = 0
    for c, k in enumerate(CARDINALITY):
        share = (np.arange(k) + 1.0) ** -1.0
        code = rng.choice(k, rows, p=share / share.sum())
        cols[:, c] = first + code
        latent += fixed.standard_normal(k)[code]
        first += k
    z = rng.standard_normal((rows, NUMERIC)).astype(np.float32)
    cols[:, len(CARDINALITY):] = first + np.arange(NUMERIC)
    vals[:, len(CARDINALITY):] = z
    latent += z @ fixed.uniform(0.3, 1.0, NUMERIC)
    latent += rng.standard_normal(rows)
    X = sparse.csr_matrix(
        (vals.reshape(-1), cols.reshape(-1),
         np.arange(0, rows * per_row + 1, per_row)),
        shape=(rows, first + NUMERIC))
    return {"X": X, "y": (latent > 1.5).astype(np.float32)}


def dataset_args(data):
    return {}


def first_tree(tree, data, cfg):
    p = cfg["params"]
    return reference.tree0_check(tree, data["X"], data["y"],
                                 p["learning_rate"], p.get("lambda_l2", 0.0))


def heldout(trees, data, cfg):
    raw = reference.predict_raw(trees, data["X"])
    return float(quality.METRICS[cfg["quality_metric"]](data["y"], raw))
'''

CONFIG = {
    "name": "onehot-sparse", "task": "onehot_sparse", "chips": 1,
    "rows": 20000, "heldout_rows": 8000, "features": 1204,
    "params": {"objective": "binary", "num_leaves": 31, "max_bin": 63,
               "learning_rate": 0.1, "verbose": -1},
    "quality_metric": "auc", "quality_at_iter": 4,
    "quality_band": [0.70, 0.85],
    "engines": {"histogram": "lax", "partition": "lax"}}


@pytest.fixture()
def sparse_tree(bench_tree):
    """The rehearsal tree with the task, its configuration and a two-
    iteration `train` mix added as new files; a snapshot of every file the
    copy had before them."""
    root, bench_dir = bench_tree["root"], bench_tree["bench_dir"]
    before = {}
    for folder, _, names in os.walk(bench_dir):
        for name in names:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()
    with open(os.path.join(bench_dir, "tasks", "onehot_sparse.py"),
              "w") as fh:
        fh.write(ONEHOT_SPARSE)
    path = os.path.join(root, "onehot-sparse.json")
    with open(path, "w") as fh:
        json.dump(CONFIG, fh)
    traffic = os.path.join(bench_dir, "traffic")
    with open(os.path.join(traffic, "train.json")) as fh:
        mix = json.load(fh)
    with open(os.path.join(traffic, "train-two.json"), "w") as fh:
        json.dump(dict(mix, warmup_iters=2, min_iters=2, trace_iters=2), fh)
    manifest = bench_tree["manifest"]
    manifest["configs"].append({"name": "onehot-sparse", "file": path})
    manifest["workloads"].append({"name": "onehot-sparse-train",
                                  "config": "onehot-sparse",
                                  "traffic": "train-two", "chips": 1})
    with open(bench_tree["manifest_path"], "w") as fh:
        json.dump(manifest, fh)
    return dict(bench_tree, before=before)


def _detail(tree):
    with open(os.path.join(tree["root"], "chiprun_out", "bench",
                           "onehot-sparse-train.s3.t0.json")) as fh:
        return json.load(fh)["detail"]


def test_a_csr_task_runs_end_to_end_and_reads_correct(sparse_tree):
    result = run_tiny(sparse_tree, "onehot-sparse-train", seconds=0.5)
    check_result(result, trace=False)
    auc, band = result["compared"]["heldout_in_band"]
    assert band == [0.70, 0.85] and 0.70 < auc < 0.85
    assert result["metrics"]["heldout_quality"]["value"] == auc
    assert result["compared"]["tree0_max_count_diff"][0] == 0
    detail = _detail(sparse_tree)
    assert detail["train"][0]["rows"] == 20000
    assert detail["train"][0]["features"] == 1204
    checks = detail["verify"][0]["checks"]
    # the program densifies the table at its boundary and EFB bundles the
    # one-hot columns back: far fewer lanes than 1,204 columns
    assert checks["fast_path"] and checks["payload"]["lanes"] < 64
    tree0 = checks["tree0"]
    assert tree0["counts_ok"] and tree0["rows"] == 20000
    assert tree0["leaves"] == 31
    for path, content in sparse_tree["before"].items():
        assert open(path, "rb").read() == content


def test_a_moved_threshold_on_the_csr_task_reads_not_correct(sparse_tree,
                                                           monkeypatch):
    """An answer altered where it is produced: the first drain hands the
    host tree 0 with its root's threshold moved by 1, so a one-hot root
    sends its category the other way and a numeric one a third of the
    rows."""
    import lightgbm_tpu as lgb
    drain = lgb.Booster.current_iteration

    def moved(self):
        n = drain(self)
        trees = self._engine.model.trees
        if trees and not getattr(self, "_moved", False):
            trees[0].threshold[0] += 1.0
            self._moved = True
        return n

    monkeypatch.setattr(lgb.Booster, "current_iteration", moved)
    result = run_tiny(sparse_tree, "onehot-sparse-train", seconds=0.5)
    assert result["correct"] is False
    value, limit = result["compared"]["tree0_max_count_diff"]
    assert value > limit == 0


# -- one walk for both forms ---------------------------------------------------

def random_tree(rng, leaves, features, thresholds):
    """A tree in the model's arrays, grown as the trainer grows one (a
    leaf split at a time, the new internal node after its parent, the
    right child a new leaf), on random columns and thresholds, every
    node's missing type and default direction drawn: none, zero, NaN."""
    ni = leaves - 1
    left = np.zeros(ni, np.int64)
    right = np.zeros(ni, np.int64)
    holder = {0: None}                      # leaf -> (node, side)
    for k in range(ni):
        leaf = int(rng.integers(0, k + 1))
        if holder[leaf] is not None:
            node, side = holder[leaf]
            (left if side == 0 else right)[node] = k
        left[k], right[k] = ~leaf, ~(k + 1)
        holder[leaf], holder[k + 1] = (k, 0), (k, 1)
    missing = rng.integers(0, 3, ni)
    default_left = rng.random(ni) < 0.5
    return types.SimpleNamespace(
        num_leaves=leaves, left_child=left, right_child=right,
        split_feature=rng.integers(0, features, ni),
        threshold=rng.choice(thresholds, ni),
        decision_type=(missing << 2) | (default_left << 1),
        leaf_value=rng.standard_normal(leaves),
        leaf_count=np.zeros(leaves, np.int64))


def mixed_table(rng, rows, features, dtype):
    """[rows, features] as CSR: a cell absent, stored 0.0, -0.0 or NaN,
    or a value on a small grid round 0 (so thresholds fall between
    values and on them)."""
    kind = rng.choice(5, (rows, features), p=[0.4, 0.1, 0.05, 0.1, 0.35])
    value = rng.choice([-1.0, -0.5, 1e-36, 0.5, 1.0, 2.0], (rows, features))
    value = np.where(kind == 1, 0.0, np.where(kind == 2, -0.0,
                     np.where(kind == 3, np.nan, value)))
    stored = kind > 0
    counts = stored.sum(axis=1)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    cols = np.nonzero(stored)[1]
    return sparse.csr_matrix((value[stored].astype(dtype), cols, indptr),
                             shape=(rows, features))


@pytest.mark.parametrize("form, dtype", [
    (sparse.csr_matrix, np.float32), (sparse.csr_array, np.float64),
    (sparse.csr_array, np.float32), (sparse.csc_matrix, np.float32)])
def test_dense_and_csr_read_the_same(form, dtype):
    from benchmarks.lib import reference
    rng = np.random.default_rng(11)
    csr = mixed_table(rng, 12000, 9, dtype)
    dense = csr.toarray()
    assert dense.dtype == dtype and np.isnan(dense).any()
    assert (csr.data == 0).any() and np.signbit(csr.data[csr.data == 0]).any()
    table = form(csr)
    assert csr.nnz == table.nnz < 0.65 * dense.size
    trees = [random_tree(rng, 31, 9, [-0.5, -1e-35, 0.0, 1e-35, 0.5, 1.0])
             for _ in range(6)]
    kinds = np.concatenate([(np.asarray(t.decision_type) >> 2) & 3
                            for t in trees])
    assert {0, reference.MISSING_ZERO, reference.MISSING_NAN} <= set(kinds)
    y = (rng.random(12000) < 0.3).astype(np.float32)
    for tree in trees:
        leaf = reference.leaf_index(tree, dense)
        assert len(np.unique(leaf)) > 8
        tree.leaf_count[:] = np.bincount(leaf, minlength=31)
        assert (reference.leaf_index(tree, table) == leaf).all()
        check = reference.tree0_check(tree, dense, y, 0.1, 1.0)
        assert check["counts_ok"]
        assert reference.tree0_check(tree, table, y, 0.1, 1.0) == check
    raw = reference.predict_raw(trees, dense)
    assert reference.predict_raw(trees, table).tobytes() == raw.tobytes()
    # a row whose column is absent reads as one that stores 0.0 or -0.0
    node = copy.deepcopy(trees[0])
    node.split_feature[:] = 0
    absent = np.diff(csr[:, [0]].tocsr().indptr) == 0
    zero = dense[:, 0] == 0
    assert absent.any() and (zero & ~absent).any()
    assert (reference.leaf_index(node, table)[zero]
            == reference.leaf_index(node, np.zeros((1, 9), dtype))[0]).all()


#: run in a child process, so that its peak resident memory (VmHWM, kB)
#: is the walk's own: 300,000 x 4,228 at 35 entries a row, 9.5 GiB as
#: dense float64
WALK_ONLY = r'''
import json, sys, types
import numpy as np
from scipy import sparse
sys.path.insert(0, sys.argv[1])
from benchmarks.lib import reference
rows, F, per_row = 300000, 4228, 35
block = F // per_row                    # a row stores one column a block
rng = np.random.default_rng(5)
cols = (np.arange(per_row) * block
        + rng.integers(0, block, (rows, per_row))).astype(np.int32)
X = sparse.csr_array((np.ones(rows * per_row, np.float32), cols.reshape(-1),
                      np.arange(0, rows * per_row + 1, per_row)),
                     shape=(rows, F))
del cols
tree = types.SimpleNamespace(**json.loads(sys.argv[2]))
leaf = reference.leaf_index(tree, X)
raw = reference.predict_raw([tree] * 4, X)
with open("/proc/self/status") as fh:      # this image's peak, not the
    peak = [int(l.split()[1]) for l in fh      # forking parent's
            if l.startswith("VmHWM:")][0]
print(8 * rows * F / 2**30, np.bincount(leaf).max(), len(np.unique(leaf)),
      peak / 2**20)
'''


def test_a_csr_walk_never_builds_the_dense_table():
    rng = np.random.default_rng(3)
    tree = random_tree(rng, 255, 4228, [1e-35, 0.5])
    spec = {k: getattr(tree, k).tolist() for k in (
        "left_child", "right_child", "split_feature", "threshold",
        "decision_type", "leaf_value", "leaf_count")}
    spec["num_leaves"] = 255
    done = subprocess.run(
        [sys.executable, "-c", WALK_ONLY, ROOT, json.dumps(spec)],
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr
    dense_gib, fullest, reached, peak_gib = map(float, done.stdout.split())
    assert dense_gib > 8.0
    assert reached > 1 and fullest < 300000
    assert peak_gib < 1.0, peak_gib
