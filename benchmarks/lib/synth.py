"""Seeded inputs: the training task and the serving model.

Copies, not imports, of `chip_smoke.synth_higgs_shaped` and
`bench.synth_serving_model` (PERF.md, Open questions, lists the
originals): later PRs may change the program and may not change what it
is measured with.

`binary_task` is `synth_higgs_shaped` generalised to any number of
columns and generated in parallel: rows are cut into fixed chunks, chunk
i draws from `SeedSequence(seed).spawn(...)[i]`, so the data depends on
(seed, rows, columns) and never on the number of threads.  Features are
float64 — what `lgb.Dataset` converts any input to — so construction
copies nothing.
"""
import numpy as np

from benchmarks.lib import parallel

#: rows per generation chunk; part of the data's definition
CHUNK_ROWS = 1 << 19
#: seed of the task itself (the weight vector), fixed across runs: every
#: seed draws new rows of the SAME task, so `heldout_quality` compares
TASK_SEED = 1234
#: chip_smoke's task is defined at this width; the linear term is scaled
#: so that its variance, and so the task's difficulty, is the same at
#: any width
BASE_FEATURES = 28


def binary_task(n_rows, n_features, seed):
    """(X [n_rows, n_features] float64, y [n_rows] float32 in {0, 1}):
    standard-normal features, a label from a linear term, two
    interactions and noise."""
    w = np.random.default_rng(TASK_SEED).standard_normal(n_features)
    w *= 0.5 * np.sqrt(BASE_FEATURES / n_features)
    X = np.empty((n_rows, n_features), np.float64)
    y = np.empty(n_rows, np.float32)
    bounds = parallel.fixed_bounds(n_rows, CHUNK_ROWS)
    seeds = np.random.SeedSequence(seed).spawn(len(bounds) - 1)

    def fill(i, lo, hi):
        rng = np.random.default_rng(seeds[i])
        Xc = X[lo:hi]
        rng.standard_normal(out=Xc)
        logit = Xc @ w
        logit += 0.4 * Xc[:, 0] * Xc[:, 1] + 0.3 * np.abs(Xc[:, 2])
        logit += 0.8 * rng.standard_normal(hi - lo)
        y[lo:hi] = logit > 0

    parallel.for_chunks(bounds, fill)
    return X, y


def feature_rows(n_rows, n_features, seed):
    """[n_rows, n_features] float32 standard-normal rows: the pool a
    predict or serve mix draws its requests from."""
    return np.random.default_rng(seed).standard_normal(
        (n_rows, n_features), dtype=np.float32)


def serving_model(n_trees, num_leaves, n_features, seed):
    """A serving-shape ensemble built directly, with no training: random
    features and thresholds, a random leaf chosen for each split, which
    gives the leaf-wise depth profile (mean depth ~4.3 ln L, largest
    about twice that).  Training 500 trees would cost a quarter of an
    hour of chip in every run.  One departure from the original:
    thresholds are float32 values, so the device predictor's float32
    thresholds and the plain float64 reference route every row alike (a
    trained model's thresholds sit between data values and need no such
    care).  Returns the program's `GBDTModel`."""
    from lightgbm_tpu.models.gbdt_model import GBDTModel
    from lightgbm_tpu.models.tree import Tree
    rng = np.random.default_rng(seed)
    model = GBDTModel()
    model.num_class = 1
    model.num_tree_per_iteration = 1
    model.max_feature_idx = n_features - 1
    model.objective_str = "binary sigmoid:1"
    for _ in range(n_trees):
        t = Tree(num_leaves)
        splits = num_leaves - 1
        # one draw per column of the tree, not five scalar draws a split
        feats = rng.integers(0, n_features, splits)
        thrs = rng.standard_normal(splits, dtype=np.float32)
        vals = rng.standard_normal((splits, 2)) * 0.01
        dleft = rng.integers(0, 2, splits)
        for s in range(splits):
            leaf = int(rng.integers(0, t.num_leaves))
            t.split(leaf, int(feats[s]), 0, float(thrs[s]),
                    float(vals[s, 0]), float(vals[s, 1]),
                    10, 10, 1.0, 2, bool(dleft[s]))
        model.trees.append(t)
    return model
