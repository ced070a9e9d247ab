"""What `correct` has to refuse, shown to be refused, at a rehearsal's
size on the CPU: the control (tree 0 as the nearest precision below the
program's would compute it) and the train driver's run with the timed
path broken underneath.  The chip's readings of the same control at the
cells' own sizes are in PERF.md, section 6."""
import json
import os
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT, run_tiny


def bf16(x):
    """float64 -> the nearest bfloat16 (round to nearest even), as
    float64: what one MXU pass keeps of an f32 operand."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits += 0x7FFF + ((bits >> 16) & 1)
    return (bits >> 16 << 16).astype(np.uint32).view(np.float32) \
        .astype(np.float64)


@pytest.fixture(scope="module")
def epsilon_tree0():
    """Tree 0 of a sound run at `epsilon`'s objective and parameters, cut
    in rows and columns, with the data it was grown on."""
    import lightgbm_tpu as lgb
    from benchmarks.lib import synth
    with open(os.path.join(BENCH, "configs", "epsilon.json")) as fh:
        cfg = json.load(fh)
    cfg.update(rows=20000, features=200)
    cfg["params"] = dict(cfg["params"], verbose=-1)
    X, y = synth.binary_task(cfg["rows"], cfg["features"], (5, 0))
    train = lgb.Dataset(X, label=y, params=cfg["params"]).construct()
    bst = lgb.Booster(cfg["params"], train)
    bst.update()
    bst.current_iteration()
    return cfg, bst._engine.model.trees[0], {"X": X, "y": y}


def test_the_control_in_bf16_fails_the_configurations_tolerance(
        epsilon_tree0):
    """The reference put in the program's place, its sums over gradients
    and hessians rounded to bfloat16 first (the step below the program's
    exact three-part f32): sound leaves pass `epsilon`'s
    `leaf_value_atol`, the control's fail it, and would pass the 5e-4
    that stood before the tolerance was the configuration's."""
    from benchmarks.lib import reference
    from benchmarks.run import load_module
    cfg, tree, data = epsilon_tree0
    task = load_module(os.path.join(BENCH, "tasks", "binary.py"))
    atol = cfg["leaf_value_atol"]
    sound = task.first_tree(tree, data, cfg)
    assert sound["counts_ok"] and sound["max_value_diff"] <= atol / 3

    nl = int(tree.num_leaves)
    leaf = reference.leaf_index(tree, data["X"])
    pos = data["y"] > 0
    p = float(pos.mean())
    grad = bf16(np.where(pos, p - 1.0, p))
    hess = bf16(np.full(len(pos), p * (1.0 - p)))
    g = np.bincount(leaf, weights=grad, minlength=nl)
    h = np.bincount(leaf, weights=hess, minlength=nl)
    control = np.log(p / (1.0 - p)) - g / h * cfg["params"]["learning_rate"]
    kept = np.array(tree.leaf_value[:nl])
    tree.leaf_value[:nl] = control
    try:
        read = task.first_tree(tree, data, cfg)
    finally:
        tree.leaf_value[:nl] = kept
    assert read["counts_ok"]
    assert 3 * atol < read["max_value_diff"] < 5e-4


def half_the_rows_left_out(monkeypatch):
    """The trainer sees every other half of the batch: tree 0 is grown
    on 3,000 of the 6,000 rows the reference routes."""
    import lightgbm_tpu as lgb
    whole = lgb.Dataset

    def half(X, label=None, **kw):
        n = len(X) // 2
        return whole(X[:n], label=label[:n], **kw)

    monkeypatch.setattr(lgb, "Dataset", half)
    return "tiny-train", "tree0_max_count_diff"


def a_leaf_value_altered(monkeypatch):
    """An answer altered where it is produced: the first drain hands the
    host tree 0 with one leaf's value off by 1e-3."""
    import lightgbm_tpu as lgb
    drain = lgb.Booster.current_iteration

    def altered(self):
        n = drain(self)
        trees = self._engine.model.trees
        if trees and not getattr(self, "_altered", False):
            trees[0].leaf_value[0] += 1e-3
            self._altered = True
        return n

    monkeypatch.setattr(lgb.Booster, "current_iteration", altered)
    return "tiny-train", "tree0_max_value_diff"


def the_step_returns_its_state_unchanged(monkeypatch):
    """After the warm-up `update()` comes back at once and grows
    nothing: the window's iterations have no trees."""
    import lightgbm_tpu as lgb
    update, calls = lgb.Booster.update, []

    def stuck(self, *args, **kw):
        calls.append(1)
        if len(calls) <= 4:
            return update(self, *args, **kw)
        time.sleep(0.01)
        return False

    monkeypatch.setattr(lgb.Booster, "update", stuck)
    return "tiny-train", "trees_failed"


@pytest.mark.parametrize("fault", [half_the_rows_left_out,
                                   a_leaf_value_altered,
                                   the_step_returns_its_state_unchanged])
def test_a_broken_timed_path_reads_not_correct(bench_tree, monkeypatch,
                                               fault):
    """The rest of a run driven as the command drives it (no look for a
    chip), the program broken underneath: `correct` comes out false, and
    by the number that is the fault's to catch.  (The fourth fault a
    mesh cell can have, the exchange between chips left out, gives no
    verdict to read: the shards' loops part ways and the process aborts,
    which is no result line and so no pass.)"""
    cell, caught_by = fault(monkeypatch)
    result = run_tiny(bench_tree, cell, seconds=1.0)
    assert result["correct"] is False
    value, limit = result["compared"][caught_by]
    assert value > (limit or 0)
    monkeypatch.undo()
    assert run_tiny(bench_tree, cell, seconds=1.0)["correct"] is True
