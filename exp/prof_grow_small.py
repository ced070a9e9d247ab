import sys, time; sys.path.insert(0, '/root/repo')
import numpy as np, jax, jax.numpy as jnp
import lightgbm_tpu as lgb

for n in (4096, 65536, 500_000):
    rng = np.random.default_rng(7)
    X = rng.standard_normal((n, 28)).astype(np.float32)
    y = (X[:, 0] + 0.5*X[:, 1] + rng.standard_normal(n)*0.5 > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 2}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(3):
        bst.update()
    eng = bst._engine
    fs = eng._fast
    fmask = eng._feature_sample()
    def grow():
        global out
        out, fs.payload, fs.aux = fs.grower(fs.payload, fs.aux, fmask)
    grow()
    t0 = time.perf_counter()
    for _ in range(3): grow()
    jax.block_until_ready(fs.payload)
    dt = (time.perf_counter() - t0) / 3 * 1e3
    print("n=%7d  grow: %7.2f ms   (leaves grown: %d)" % (n, dt, int(np.asarray(out["num_leaves"]))), flush=True)

# --- fixed-cost dissection: per-split device overhead vs num_leaves.
# grow() is one jitted program; the slope of time vs (num_leaves-1) at tiny
# N isolates the per-split cost of everything that is NOT row work
# (find_best_split scans, pool bookkeeping, kernel sequencing).  Fetch a
# scalar per rep so the timed region ends when the value has arrived.
import time as _t
n = 4096
rng = np.random.default_rng(7)
X = rng.standard_normal((n, 28)).astype(np.float32)
y = (X[:, 0] + 0.5*X[:, 1] + rng.standard_normal(n)*0.5 > 0).astype(np.float64)
for leaves in (2, 15, 63, 255):
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 255,
              "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 2}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(2):
        bst.update()
    eng = bst._engine
    fs = eng._fast
    fmask = eng._feature_sample()
    def grow_fetch(i):
        out, fs.payload, fs.aux = fs.grower(fs.payload, fs.aux, fmask)
        return int(np.asarray(out["num_leaves"]))
    grow_fetch(0)
    ts = []
    for i in range(5):
        t0 = _t.perf_counter()
        nl = grow_fetch(i)
        ts.append(_t.perf_counter() - t0)
    med = sorted(ts)[2]
    print("leaves=%4d  grow: %7.2f ms  (%.3f ms/split)"
          % (leaves, med * 1e3, med * 1e3 / max(leaves - 1, 1)), flush=True)
