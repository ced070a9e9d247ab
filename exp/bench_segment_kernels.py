"""Microbench: per-call + per-row cost of the Pallas segment kernels on TPU.

Timing protocol: every measurement FETCHES a scalar of the result to the
host, so the timed region ends when the value has arrived, and inputs are
perturbed per rep to defeat any dispatch-level caching.
"""
import sys, os, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import segment as seg
from lightgbm_tpu.ops import pallas_segment as pseg

print("backend:", jax.default_backend(), flush=True)
rng = np.random.default_rng(0)
N = 1 << 20            # 1M rows
F, B = 28, 256
P = 128
GRAD, HESS, CNT, VAL = F, F + 1, F + 2, F + 3

payload = np.zeros((N + seg.GUARD, P), np.float32)
payload[:N, :F] = rng.integers(0, B, (N, F))
payload[:N, GRAD] = rng.standard_normal(N)
payload[:N, HESS] = rng.random(N) + 0.1
payload[:N, CNT] = 1.0
payload = jnp.asarray(payload)

pred = seg.SplitPredicate(
    col=jnp.int32(2), threshold=jnp.int32(100),
    default_left=jnp.bool_(True), is_cat=jnp.bool_(False),
    missing_type=jnp.int32(0), num_bin=jnp.int32(B),
    default_bin=jnp.int32(0), offset=jnp.int32(0),
    identity=jnp.bool_(True), bitset=jnp.zeros(B, jnp.int32))


def timeit_fetch(fn, reps=7):
    """Median seconds per call; fn(i) must RETURN A HOST SCALAR."""
    fn(0)  # warm (compile)
    ts = []
    for i in range(reps):
        t0 = time.perf_counter()
        fn(i + 1)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def hist_call(count):
    def run(i):
        h = pseg.segment_histogram(
            payload, jnp.int32(0), jnp.int32(count - (i % 2)),
            num_features=F, num_bins=B, grad_col=GRAD, hess_col=HESS,
            cnt_col=CNT)
        return float(np.asarray(h)[0, 0, 2])
    return run


def part_call(kernel, count):
    def run(i):
        p_ = jnp.asarray(payload)
        a_ = jnp.zeros_like(p_)
        _ = np.asarray(p_)[0, 0]   # ensure uploaded before the clock
        t0 = time.perf_counter()
        out = kernel(p_, a_, jnp.int32(0), jnp.int32(count - (i % 2)), pred,
                     jnp.float32(1.0), jnp.float32(-1.0), VAL, B)
        nl = int(out[2])
        return time.perf_counter() - t0
    # upload time excluded: run() returns its own measured duration
    run._self_timed = True
    return run


def timeit_self(fn, reps=5):
    fn(0)
    ts = [fn(i + 1) for i in range(reps)]
    return sorted(ts)[len(ts) // 2]


for count in (1 << 15, 1 << 18, 1 << 20):
    t_h = timeit_fetch(hist_call(count))
    t_p = timeit_self(part_call(pseg.partition_segment, count))
    print("count=%8d  hist %8.2f ms (%6.2f ns/row)   part[rmw] %8.2f ms "
          "(%6.2f ns/row)" % (count, t_h * 1e3, t_h / count * 1e9,
                              t_p * 1e3, t_p / count * 1e9), flush=True)

t_p = timeit_self(part_call(pseg.partition_segment_acc, 1 << 20))
print("part[acc] 1M rows: %8.2f ms (%6.2f ns/row)"
      % (t_p * 1e3, t_p / (1 << 20) * 1e9), flush=True)

# dispatch floor: tiny count isolates the fixed per-dispatch cost
t0 = timeit_fetch(hist_call(8))
print("hist count=8 floor: %.2f ms" % (t0 * 1e3), flush=True)
