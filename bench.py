#!/usr/bin/env python
"""Benchmark entry: boosting iters/sec on a Higgs-scale workload.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Baseline (BASELINE.md): reference LightGBM CPU trains Higgs (10.5M x 28,
500 iters, 255 leaves, 2x E5-2670v3) in 238.51 s = 2.096 iters/sec
(docs/Experiments.rst:101-117).  vs_baseline = our_iters_per_sec / 2.096.

The real Higgs dataset cannot be downloaded (no egress), so the workload is
synthesized at the same shape (default 10.5M x 28 like the reference table;
BENCH_ROWS overrides) with learnable nonlinear structure, trained with the
reference config (255 max_bin, 255 leaves, lr 0.1), and evaluated on a
held-out 500K-row test set.  The held-out AUC is reported next to the
reference's published Higgs AUC (0.845154 @500 iters) for orientation only —
the datasets differ, so only iters/sec is comparable.

Per-phase timings (TIMETAG-style, serial_tree_learner.cpp:14-41) cover the
fast path's stages: gradient fill, tree growth (hist+split+partition under
one jit), score update, and host-side tree assembly.

Until ROADMAP A1 replaces this file: it measures the chip or nothing.  A
platform other than a TPU is an error, the run happens at the size asked or
fails, and a section that fails fails the run.  The section functions stay
importable so the CPU tests can check their record shapes at toy scale.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_ITERS_PER_SEC = 500.0 / 238.51  # reference CPU Higgs
REFERENCE_HIGGS_AUC = 0.845154           # @500 iters, real Higgs

def synth_higgs(n_rows: int, n_feat: int = 28, seed: int = 7):
    """Synthetic workload at a configurable shape (default: Higgs 28
    features).  BENCH_FEATURES/BENCH_BINS let a hardware session take
    readings at the other BASELINE.md shapes (MS-LTR 137, Expo 700)."""
    if n_feat < 4:
        raise SystemExit("BENCH_FEATURES must be >= 4 (the synthetic "
                         "signal uses the first four columns)")
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_rows, n_feat)).astype(np.float32)
    w = rng.standard_normal(n_feat)
    logit = (X @ w) * 0.5
    logit += 0.4 * X[:, 0] * X[:, 1] + 0.3 * np.abs(X[:, 2]) - 0.2 * (X[:, 3] > 0.5)
    logit += rng.standard_normal(n_rows).astype(np.float32) * 0.8
    y = (logit > 0).astype(np.float64)
    return X, y


def auc_score(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    npos = y.sum()
    nneg = len(y) - npos
    return (ranks[y > 0].sum() - npos * (npos + 1) / 2) / max(npos * nneg, 1)


def phase_times(bst, reps=3):
    """One piecewise iteration per rep through the fast path's stages; a
    stage that fails raises, and the run fails with it."""
    import jax
    eng = bst._engine
    fs = getattr(eng, "_fast", None)
    if fs is None or not getattr(eng, "_fast_active", False):
        return {}
    # the piecewise stages append trees inline — deferred assemblies from
    # pipelined update() calls must land first (strict ordering), and any
    # open boosting window must settle at the reported iteration (the
    # stages drive fs.payload directly)
    eng.flush(sync_scores=True)
    import jax.numpy as jnp
    fmask = eng._feature_sample()
    lr = jnp.float32(eng.shrinkage_rate)
    quant = bool(getattr(fs, "quant_on", False))
    acc = {"grad_fill_ms": 0.0, "tree_grow_ms": 0.0, "score_update_ms": 0.0,
           "tree_assemble_host_ms": 0.0}
    for _ in range(reps):
        t0 = time.perf_counter()
        if quant:
            fs.payload, qsc = fs._fill_class_quant(fs.payload, k=0,
                                                   qseed=eng._quant_seed(0))
            jax.block_until_ready(fs.payload)
        else:
            fs.payload = jax.block_until_ready(
                fs._fill_class(fs.payload, k=0))
        acc["grad_fill_ms"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        gargs = (fs.payload, fs.aux, fmask, qsc) if quant \
            else (fs.payload, fs.aux, fmask)
        out, fs.payload, fs.aux = fs.grower(*gargs)
        jax.block_until_ready(fs.payload)
        acc["tree_grow_ms"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        tree, _, _ = eng._finish_tree(out, 0.0)
        acc["tree_assemble_host_ms"] += time.perf_counter() - t0
        eng.model.trees.append(tree)

        t0 = time.perf_counter()
        fs.payload = jax.block_until_ready(
            fs._apply_score(fs.payload, lr, k=0))
        acc["score_update_ms"] += time.perf_counter() - t0
        eng.iter += 1
    out = {k: round(v / reps * 1e3, 2) for k, v in acc.items()}
    # self-consistency block (ISSUE 13 satellite): the piecewise
    # absolutes each carry per-dispatch overhead the fused program
    # amortizes, so their SUM can exceed sec_per_iter.  phase_frac
    # normalizes within the piecewise run itself — fractions always sum
    # to 1 and are the number to read for "where does the time go".
    total = sum(acc.values())
    out["piecewise_total_ms"] = round(total / reps * 1e3, 2)
    out["phase_frac"] = {k: (round(v / total, 4) if total > 0 else 0.0)
                         for k, v in acc.items()}
    return out


#: scale the piecewise phase diagnostics run at when the headline scale
#: is above 5M rows (the standalone stage programs hold their own payload
#: temporaries; BENCH_PHASES=1 forces the headline scale)
MID_PHASE_ROWS = 2_000_000


def phase_times_midscale(X, y, params, rows):
    """Piecewise phase telemetry on a FRESH mid-scale booster — runs by
    default when the headline scale skips the piecewise section."""
    import lightgbm_tpu as lgb
    bst = lgb.Booster(dict(params), lgb.Dataset(X[:rows], label=y[:rows]))
    for _ in range(2):
        bst.update()
    out = phase_times(bst)
    out["measured_at_rows"] = rows
    return out


def synth_serving_model(n_trees=500, num_leaves=255, n_feat=28, seed=3):
    """A serving-shape ensemble built directly (no training): random
    features/thresholds, random leaf chosen per split — the leaf-wise
    depth profile (E[depth] ~ 4.3 ln L, max ~2x that) without paying a
    500-iteration training run just to bench prediction."""
    from lightgbm_tpu.models.gbdt_model import GBDTModel
    from lightgbm_tpu.models.tree import Tree
    rng = np.random.default_rng(seed)
    model = GBDTModel()
    model.num_class = 1
    model.num_tree_per_iteration = 1
    model.max_feature_idx = n_feat - 1
    model.objective_str = "binary sigmoid:1"
    for _ in range(n_trees):
        t = Tree(num_leaves)
        while t.num_leaves < num_leaves:
            leaf = int(rng.integers(0, t.num_leaves))
            t.split(leaf, int(rng.integers(0, n_feat)), 0,
                    float(rng.standard_normal()),
                    float(rng.standard_normal() * 0.01),
                    float(rng.standard_normal() * 0.01),
                    10, 10, 1.0, 2, bool(rng.integers(0, 2)))
        model.trees.append(t)
    return model


def bench_predict():
    """BENCH_PREDICT: serving rows/sec at 500 trees x 255 leaves — host
    (f64 numpy) vs the pre-PR scan device engine vs the tree-parallel
    engine.  The two slow reference engines are measured on a subset
    (their per-row cost is row-count-independent once vectorization
    amortizes); the tree-parallel engine runs the full row count through
    its micro-batched streaming path.  Emitted under the bench JSON's
    `predict` key; BENCH_PREDICT_{ROWS,TREES,LEAVES} reshape it."""
    from lightgbm_tpu.models.device_predictor import DevicePredictor

    rows = int(os.environ.get("BENCH_PREDICT_ROWS", 1_000_000))
    n_trees = int(os.environ.get("BENCH_PREDICT_TREES", 500))
    num_leaves = int(os.environ.get("BENCH_PREDICT_LEAVES", 255))
    n_feat = 28
    rng = np.random.default_rng(17)
    model = synth_serving_model(n_trees, num_leaves, n_feat)
    X = rng.standard_normal((rows, n_feat)).astype(np.float32)

    dp = DevicePredictor(model)

    def timed(fn, arg):
        fn(arg)                       # warm-up: compile + caches
        t0 = time.perf_counter()
        out = fn(arg)
        return out, time.perf_counter() - t0

    host_rows = min(rows, 20_000)
    host_out, host_dt = timed(model.predict_raw, X[:host_rows].astype(np.float64))

    scan_rows = min(rows, 65_536)
    _, scan_dt = timed(dp.predict_raw_scan, X[:scan_rows])

    eng_out, eng_dt = timed(dp.predict_raw, X)
    host_vs_eng = float(np.abs(eng_out[:host_rows] - host_out).max())

    eng_rps = rows / eng_dt
    scan_rps = scan_rows / scan_dt
    host_rps = host_rows / host_dt
    return {
        "rows": rows, "n_trees": n_trees, "num_leaves": num_leaves,
        "n_features": n_feat,
        "depth_iters": int(dp.depth_iters),
        "scan_depth_iters": int(dp._scan_depth_iters),
        "engine_rows_per_sec": round(eng_rps, 1),
        "engine_measured_rows": rows,
        "scan_rows_per_sec": round(scan_rps, 1),
        "scan_measured_rows": scan_rows,
        "host_rows_per_sec": round(host_rps, 1),
        "host_measured_rows": host_rows,
        "speedup_vs_scan": round(eng_rps / scan_rps, 2),
        "speedup_vs_host": round(eng_rps / host_rps, 2),
        "max_abs_diff_vs_host_raw": host_vs_eng,
    }


def bench_online():
    """BENCH_ONLINE: the continuous-training service (ISSUE 6) at reduced
    scale, schedule-free (`online_interval=0`) so the numbers measure the
    pipeline, not the clock: cycles/sec, per-cycle publish latency (from
    the service's own stage trail), and subscriber staleness (age of the
    newest resolvable generation, sampled by a 20 Hz poller for the whole
    run).  BENCH_ONLINE_{ROWS,CYCLES,ROUNDS} reshape it."""
    import tempfile
    import threading

    from lightgbm_tpu.runtime import publish as pubmod
    from lightgbm_tpu.runtime.continuous import ContinuousTrainer

    rows = int(os.environ.get("BENCH_ONLINE_ROWS", 8_000))
    cycles = int(os.environ.get("BENCH_ONLINE_CYCLES", 3))
    rounds = int(os.environ.get("BENCH_ONLINE_ROUNDS", 2))
    X, y = synth_higgs(rows)
    with tempfile.TemporaryDirectory(prefix="bench_online_") as d:
        data = os.path.join(d, "train.tsv")
        np.savetxt(data, np.column_stack([y, X]), delimiter="\t",
                   fmt="%.7g")
        out = os.path.join(d, "m.txt")
        staleness = []
        stop = threading.Event()

        def poll():
            sub = pubmod.ModelSubscriber(out + ".pub", attempts=1)
            while not stop.is_set():
                rec = sub.resolve_once()
                if rec is not None:
                    try:
                        staleness.append(
                            time.time() - os.path.getmtime(rec.path))
                    except OSError:
                        pass
                stop.wait(0.05)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        trainer = ContinuousTrainer({
            "data": data, "output_model": out, "objective": "binary",
            "num_leaves": 31, "verbose": -1, "seed": 7,
            "online_cycles": cycles, "online_rounds": rounds,
            "online_interval": 0})
        # stage markers go to stderr: bench stdout is ONE json line
        trainer.wd.stream = sys.stderr
        t0 = time.perf_counter()
        rc = trainer.run()
        dt = time.perf_counter() - t0
        stop.set()
        poller.join(timeout=5)
        if rc != 0:
            raise RuntimeError("online service rc=%d" % rc)
        lat = [s["publish_latency_s"] for s in trainer.wd.stages
               if "publish_latency_s" in s]
        st = np.asarray(staleness) if staleness else np.asarray([0.0])
        return {
            "rows": rows, "cycles": cycles, "rounds_per_cycle": rounds,
            "cycles_per_sec": round(cycles / dt, 3),
            "sec_per_cycle": round(dt / cycles, 3),
            "publish_latency_s": {"mean": round(float(np.mean(lat)), 4),
                                  "max": round(float(np.max(lat)), 4)},
            "staleness_s": {"p50": round(float(np.percentile(st, 50)), 3),
                            "max": round(float(st.max()), 3),
                            "samples": int(st.size)},
            "note": "interval=0: staleness == pipeline lag; a scheduled "
                    "deployment adds its online_interval on top",
        }


def bench_serve():
    """BENCH_SERVE: the fault-tolerant serving runtime (ISSUE 7) under
    concurrent client load — request p50/p99 latency, served rows/sec,
    and hot-swap latency (publish of generation 2 -> first response that
    reports it), with zero drops asserted.  The model is the synthetic
    serving-shape ensemble (no training run needed);
    BENCH_SERVE_{CLIENTS,SECONDS,TREES,LEAVES,BATCH} reshape it."""
    import tempfile
    import threading

    from lightgbm_tpu.runtime import publish as pubmod
    from lightgbm_tpu.runtime.serving import ServeRejected, ServingRuntime

    from lightgbm_tpu.runtime import telemetry

    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 6))
    n_trees = int(os.environ.get("BENCH_SERVE_TREES", 100))
    num_leaves = int(os.environ.get("BENCH_SERVE_LEAVES", 63))
    req_rows = int(os.environ.get("BENCH_SERVE_BATCH", 8))
    n_feat = 28
    rng = np.random.default_rng(23)
    rows = rng.standard_normal((4096, n_feat))
    # the registry's serving-latency histogram drives the reported
    # p50/p99 (ISSUE 9) — scope it to THIS bench run with a state delta
    lat_hist = telemetry.histogram("lgbm_serve_latency_seconds")
    h_before = lat_hist.state()
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as d:
        pub = pubmod.ModelPublisher(os.path.join(d, "pub"), keep_last=0)
        pub.publish(synth_serving_model(n_trees, num_leaves, n_feat,
                                        seed=3).save_model_to_string(),
                    meta={"cycle": 1})
        latencies, shed, errors = [], [0], []
        swap = {"published": None, "seen": None}
        stop = threading.Event()
        with ServingRuntime(publish_dir=os.path.join(d, "pub"),
                            poll_interval_s=0.05,
                            batch_window_s=0.001) as rt:
            def client(seed):
                crng = np.random.default_rng(seed)
                while not stop.is_set():
                    idx = crng.integers(0, len(rows), size=req_rows)
                    t0 = time.perf_counter()
                    try:
                        rec = rt.predict(rows[idx], attempts=1)
                    except ServeRejected:
                        shed[0] += 1
                        continue
                    except Exception as e:   # noqa: BLE001 — ledger
                        errors.append(str(e))
                        continue
                    latencies.append(time.perf_counter() - t0)
                    if rec.generation == 2 and swap["seen"] is None:
                        swap["seen"] = time.monotonic()

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(seconds / 2)
            swap["published"] = time.monotonic()
            pub.publish(synth_serving_model(n_trees, num_leaves, n_feat,
                                            seed=4).save_model_to_string(),
                        meta={"cycle": 2})
            time.sleep(seconds / 2)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            dt = time.perf_counter() - t_start
            st = rt.stats()
        if errors:
            raise RuntimeError("serve bench saw %d hard errors; first: %s"
                               % (len(errors), errors[0]))
        lat = np.asarray(latencies) if latencies else np.asarray([0.0])
        hist_delta = telemetry.state_delta(lat_hist.state(), h_before)

        def _q(q):
            v = telemetry.quantile_from_state(hist_delta, q)
            return round(v * 1e3, 3) if v is not None else None
        return {
            "clients": clients, "request_rows": req_rows,
            "n_trees": n_trees, "num_leaves": num_leaves,
            "requests": len(latencies), "shed": shed[0],
            "rows_per_sec": round(st["rows_served"] / dt, 1),
            # p50/p99 come FROM the metrics registry histogram — the
            # same series a live /metrics scrape exposes (exact to
            # within one bucket of the fixed layout)
            "latency_ms": {
                "p50": _q(0.5), "p99": _q(0.99),
                "max": round(float(lat.max()) * 1e3, 3),
                "source": "registry histogram lgbm_serve_latency_seconds",
                "histogram_count": hist_delta["count"]},
            "client_latency_ms": {
                "p50": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "note": "client-side wall clock, for cross-checking the "
                        "registry quantiles (+- one bucket width)"},
            "swap_latency_s": (round(swap["seen"] - swap["published"], 3)
                               if swap["seen"] else None),
            "batches_device": st["batches_device"],
            "batches_host": st["batches_host"],
            "degradations": st["degradations"],
            "note": "zero-drop asserted: every request completed or was "
                    "shed with an explicit retryable rejection",
        }


def bench_ingest():
    """BENCH_INGEST: dataset-ingest rows/sec (ISSUE 8) — the file-parse
    path vs the zero-copy streaming pushes (dense chunks, CSR chunks)
    vs a binary-cache hit, all producing the SAME binned dataset
    (asserted bit-identical).  BENCH_INGEST_ROWS reshapes it."""
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.stream import StreamingDatasetBuilder

    rows = int(os.environ.get("BENCH_INGEST_ROWS", 120_000))
    n_feat = 28
    X, y = synth_higgs(rows, n_feat)
    X64 = X.astype(np.float64)
    params = {"max_bin": 255, "verbose": -1}
    chunk = max(rows // 8, 1)

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    def construct(data):
        ds = lgb.Dataset(data, params=dict(params))
        ds.construct(Config(dict(params)))
        return ds

    with tempfile.TemporaryDirectory(prefix="bench_ingest_") as d:
        path = os.path.join(d, "train.tsv")
        # %.17g so the text round-trip reproduces the exact doubles the
        # push paths see — the bins_identical assertion depends on it
        np.savetxt(path, np.column_stack([y, X64]), delimiter="\t",
                   fmt="%.17g")
        ds_file, t_file = timed(lambda: construct(path))

        def dense_push():
            b = StreamingDatasetBuilder(params=dict(params))
            for s in range(0, rows, chunk):
                b.push_dense(X64[s:s + chunk], label=y[s:s + chunk])
            return construct(b)
        ds_push, t_push = timed(dense_push)

        def csr_push():
            b = StreamingDatasetBuilder(params=dict(params))
            for s in range(0, rows, chunk):
                Xc = X64[s:s + chunk]
                m = Xc.shape[0]
                # fully-dense CSR: the honest upper bound on marshalling
                indptr = np.arange(m + 1, dtype=np.int64) * n_feat
                indices = np.tile(np.arange(n_feat, dtype=np.int32), m)
                b.push_csr(indptr, indices, Xc.ravel(), n_feat,
                           label=y[s:s + chunk])
            return construct(b)
        ds_csr, t_csr = timed(csr_push)

        bin_path = os.path.join(d, "train.bin")
        ds_file.binned.metadata.set_label(y)
        ds_file.save_binary(bin_path)
        ds_bin, t_bin = timed(lambda: construct(bin_path))

        same = (np.array_equal(ds_file.binned.bins, ds_push.binned.bins)
                and np.array_equal(ds_file.binned.bins, ds_csr.binned.bins)
                and np.array_equal(ds_file.binned.bins, ds_bin.binned.bins))
        if not same:
            raise RuntimeError("ingest paths produced different bins — "
                               "the streaming builder broke parser parity")
        return {
            "rows": rows, "n_features": n_feat,
            "file_parse_rows_per_sec": round(rows / t_file, 1),
            "dense_push_rows_per_sec": round(rows / t_push, 1),
            "csr_push_rows_per_sec": round(rows / t_csr, 1),
            "binary_cache_rows_per_sec": round(rows / t_bin, 1),
            "push_speedup_vs_file_parse": round(t_file / t_push, 2),
            "cache_speedup_vs_file_parse": round(t_file / t_bin, 2),
            "bins_identical_across_paths": True,
            "note": "push paths skip parse entirely; file-parse includes "
                    "the native mmap parser + find-bin + encode",
        }


def bench_telemetry():
    """BENCH_TELEMETRY: observability-overhead A/B (ISSUE 9) — the SAME
    booster (shared compiled programs) measured with the metrics
    registry enabled vs disabled, plus a deterministic microbench of the
    disabled-path instrument cost.  The contract asserted here: with
    telemetry disabled, the instrumentation seam costs <1% of an
    iteration (`disabled_path_overhead_pct`).  The wall-clock on/off
    ratio is recorded too, but timing noise makes the microbench-derived
    bound the honest assertion.  BENCH_TELEMETRY_{ROWS,ITERS} reshape."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.runtime import telemetry, tracing

    rows = int(os.environ.get("BENCH_TELEMETRY_ROWS", 20_000))
    iters = int(os.environ.get("BENCH_TELEMETRY_ITERS", 8))
    X, y = synth_higgs(rows)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 31,
                       "max_bin": 255, "learning_rate": 0.1,
                       "verbose": -1}, lgb.Dataset(X, label=y))
    for _ in range(3):                    # warm-up: compile + caches
        bst.update()
    bst._engine.flush()

    ops0 = telemetry.REGISTRY.ops
    ev0 = tracing.export_chrome()["otherData"]["recorded_total"]
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    bst._engine.flush()
    dt_on = time.perf_counter() - t0
    ops_per_iter = (telemetry.REGISTRY.ops - ops0) / iters
    trace_events_per_iter = \
        (tracing.export_chrome()["otherData"]["recorded_total"] - ev0) / iters

    prev = telemetry.set_enabled(False)
    prev_tr = tracing.set_enabled(False)
    try:
        t0 = time.perf_counter()
        for _ in range(iters):
            bst.update()
        bst._engine.flush()
        dt_off = time.perf_counter() - t0

        # deterministic disabled-path cost: one disabled instrument call
        # is one global read + an early return — measure it directly
        h = telemetry.histogram("lgbm_train_iteration_seconds")
        c = telemetry.counter("lgbm_train_iterations_total")
        n = 20_000
        tm = time.perf_counter()
        for _ in range(n):
            h.observe(0.001)
            c.inc()
        call_cost_s = (time.perf_counter() - tm) / (2 * n)
        # the trace recorder's disabled path rides the same contract
        # (ISSUE 14): one global read + return per site
        tm = time.perf_counter()
        for _ in range(n):
            tracing.instant("bench")
            tracing.record("bench", 0, 0)
        trace_call_cost_s = (time.perf_counter() - tm) / (2 * n)
    finally:
        telemetry.set_enabled(prev)
        tracing.set_enabled(prev_tr)

    sec_per_iter_off = dt_off / iters
    disabled_pct = ((ops_per_iter * call_cost_s
                     + trace_events_per_iter * trace_call_cost_s)
                    / sec_per_iter_off * 100
                    if sec_per_iter_off > 0 else 0.0)
    rec = {
        "rows": rows, "iters": iters,
        "sec_per_iter_on": round(dt_on / iters, 5),
        "sec_per_iter_off": round(sec_per_iter_off, 5),
        "wall_overhead_pct": round((dt_on - dt_off) / dt_off * 100, 3)
        if dt_off > 0 else None,
        "ops_per_iter": round(ops_per_iter, 1),
        "disabled_call_cost_ns": round(call_cost_s * 1e9, 1),
        "trace_events_per_iter": round(trace_events_per_iter, 1),
        "trace_disabled_call_cost_ns": round(trace_call_cost_s * 1e9, 1),
        "disabled_path_overhead_pct": round(disabled_pct, 4),
        "note": "disabled_path_overhead_pct = (metric call sites + trace "
                "event sites) per iteration x disabled per-call cost / "
                "iteration time; asserted < 1%",
    }
    if disabled_pct >= 1.0:
        raise RuntimeError(
            "telemetry+tracing disabled-path overhead %.3f%% >= 1%% of "
            "an iteration — the instrumentation seam regressed"
            % disabled_pct)
    return rec


def bench_attrib(bst, measure_iters):
    """BENCH_ATTRIB: device-time and cost attribution (ISSUE 10) — the
    decomposition `vs_baseline` was missing.  Per iteration on the SAME
    warm booster: dispatch wall (update() returns after the async
    dispatch), device wait (block_until_ready of the training state),
    and the pipeline drain (packed fetch + host assembly, from the PR 9
    drain histogram); plus the compile ledger's verdicts — a
    steady-state zero-retrace pin over the measured window (a violation
    names the site and shape delta) and per-site compile-time totals
    with `cost_analysis()` FLOPs/bytes captured for the window's sites.
    BENCH_ATTRIB_ITERS reshapes it."""
    import jax
    from lightgbm_tpu.runtime import telemetry, xla_obs

    eng = bst._engine
    eng.flush()
    fs = getattr(eng, "_fast", None)
    iters = int(os.environ.get("BENCH_ATTRIB_ITERS",
                               max(min(measure_iters, 6), 2)))
    drain_h = telemetry.histogram("lgbm_pipeline_drain_seconds")
    d0 = drain_h.state()
    c0 = xla_obs.snapshot()
    calls0 = xla_obs.calls_snapshot()
    xla_obs.mark_steady(True)
    dispatch_s = device_s = 0.0
    try:
        for _ in range(iters):
            t0 = time.perf_counter()
            bst.update()
            t1 = time.perf_counter()
            state = fs.payload if fs is not None \
                else getattr(eng, "score", None)
            if state is not None:
                jax.block_until_ready(state)
            t2 = time.perf_counter()
            dispatch_s += t1 - t0
            device_s += t2 - t1
        eng.flush()
    finally:
        xla_obs.mark_steady(False)
    retraces = xla_obs.delta(c0)
    calls_delta = xla_obs.calls_delta(calls0)
    drain = telemetry.state_delta(drain_h.state(), d0)

    # cost capture: ONE extra iteration with lower().compile() capture on
    # (per-site, first unseen signature only) — FLOPs/bytes per program
    prev = xla_obs.set_cost_capture(True)
    try:
        bst.update()
        eng.flush()
    finally:
        xla_obs.set_cost_capture(prev)

    ledger = xla_obs.LEDGER
    sites = []
    for name in ledger.site_names():
        rec = ledger.register(name)
        if rec.compiles == 0 and not rec.cost:
            continue
        entry = {"site": name, "compiles": rec.compiles,
                 "compile_seconds": round(rec.compile_seconds, 4)}
        if rec.cost:
            entry["cost_analysis"] = {
                k: rec.cost[k] for k in ("flops", "bytes accessed")
                if k in rec.cost}
        sites.append(entry)
    sites.sort(key=lambda e: -e["compile_seconds"])
    total = dispatch_s + device_s
    return {
        "iters": iters,
        "per_iter": {
            "dispatch_s": round(dispatch_s / iters, 5),
            "device_wait_s": round(device_s / iters, 5),
            "drain_s": round(drain["sum"] / iters, 5),
            "drains": drain["count"],
            # device-program launches per iteration (xla_obs per-site
            # call ledger; inlined __wrapped__ bodies are part of their
            # outer program) — the ROADMAP item-3 success metric, and
            # what boost_window=J divides by J
            "dispatches_per_iter": round(
                sum(calls_delta.values()) / iters, 3),
        },
        "dispatch_sites": dict(sorted(calls_delta.items(),
                                      key=lambda kv: -kv[1])[:8]),
        "device_share": round(device_s / total, 4) if total > 0 else None,
        "steady_state_retraces": retraces,
        "compile": {
            "total_compiles": ledger.total_compiles(),
            "compile_seconds_total": round(sum(
                e["compile_seconds"] for e in sites), 3),
            "sites": sites[:12],
        },
        "note": "dispatch = update() wall (async dispatch); device_wait "
                "= block_until_ready of the training state after it; "
                "drain = packed fetch + host tree assembly off the "
                "critical path; steady_state_retraces must be {} — a "
                "violation names the site and shape delta",
    }


def bench_window(bst, measure_iters):
    """BENCH_WINDOW: fused-boosting-window on/off A/B on the SAME warm
    booster (ISSUE 13) — compiled per-tree programs are shared, so the
    delta is pure window effect: J iterations per device dispatch vs one
    dispatch per tree, with the stacked [J*K] split records fetched in
    ONE transfer per window.  Reports sec/iter, device-program dispatches
    per iteration (xla_obs call ledger) and blocking fetches per
    iteration (sync audit) for both arms.  BENCH_WINDOW=J sets the
    window (default 4; 0 skips the section), BENCH_WINDOW_ITERS the
    measured span."""
    import jax
    from lightgbm_tpu.runtime import syncs, xla_obs

    eng = bst._engine
    J = int(os.environ.get("BENCH_WINDOW", "4") or 4)
    iters = int(os.environ.get("BENCH_WINDOW_ITERS",
                               max(min(measure_iters, 8), 4)))
    iters = max(2, (iters // J) * J or J)   # whole windows: no truncation
    eng.flush(sync_scores=True)

    def measure():
        c0 = xla_obs.calls_snapshot()
        s0 = syncs.snapshot()
        t0 = time.perf_counter()
        for _ in range(iters):
            bst.update()
        eng.flush(sync_scores=True)
        dt = time.perf_counter() - t0
        cd = xla_obs.calls_delta(c0)
        sd = syncs.delta(s0)
        return {"sec_per_iter": round(dt / iters, 4),
                "dispatches_per_iter": round(sum(cd.values()) / iters, 3),
                "fetches_per_iter": round(sd["total"] / iters, 3)}

    off = measure()
    prev = (eng._boost_window, eng._win_adapt, eng._win_horizon)
    eng._boost_window = J
    eng._win_adapt = J
    eng._win_horizon = None
    try:
        for _ in range(J):            # warm-up: compile the window program
            bst.update()
        eng.flush(sync_scores=True)
        on = measure()
    finally:
        eng.flush(sync_scores=True)
        eng._boost_window, eng._win_adapt, eng._win_horizon = prev
    return {
        "boost_window": J, "iters": iters, "on": on, "off": off,
        "speedup_on_vs_off": (round(off["sec_per_iter"]
                                    / on["sec_per_iter"], 4)
                              if on["sec_per_iter"] > 0 else None),
        "dispatch_reduction": (round(off["dispatches_per_iter"]
                                     / on["dispatches_per_iter"], 2)
                               if on["dispatches_per_iter"] > 0 else None),
        "note": "same booster, shared per-tree programs; ON adds one "
                "compiled scan program per J; what a saved dispatch is "
                "worth on this machine is not measured yet",
    }


def require_tpu():
    """This file measures the chip.  Any other platform is an error, named;
    nothing is re-executed, shrunk or borrowed from an earlier run."""
    from lightgbm_tpu.runtime.doctor import device_report
    device = device_report()
    if device["platform"] != "tpu":
        raise SystemExit("bench: platform is %r (%s), not tpu; refusing to "
                         "measure" % (device["platform"], device["kind"]))
    return device


def main():
    device = require_tpu()
    if os.environ.get("BENCH_PREDICT_ONLY") == "1":
        print(json.dumps({"metric": "predict rows/sec (BENCH_PREDICT_ONLY)",
                          "device": device, "predict": bench_predict()}))
        return
    result = run(int(os.environ.get("BENCH_ROWS", 10_500_000)),
                 int(os.environ.get("BENCH_TEST_ROWS", 500_000)),
                 int(os.environ.get("BENCH_LEAVES", 255)),
                 int(os.environ.get("BENCH_ITERS", 20)),
                 int(os.environ.get("BENCH_FEATURES", 28)),
                 int(os.environ.get("BENCH_BINS", 255)))
    result["device"] = device
    print(json.dumps(result))


def run(n_rows, n_test, num_leaves, measure_iters, n_feat=28, max_bin=255):
    """The headline measurement and every enabled section, at the size
    asked.  A section that fails raises and the run fails: a number that
    survives a failed section is not one this file vouches for."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.runtime import resilience
    from lightgbm_tpu.runtime import syncs
    from lightgbm_tpu.runtime import telemetry as _telemetry

    # batch runs export the registry through the atomic JSON-lines file
    # when $LGBM_TPU_METRICS_FILE is set (ISSUE 9)
    _telemetry.maybe_start_file_export("bench")
    from lightgbm_tpu.runtime import warmup as _warmup
    _warmup.enable_compile_cache()

    # every bench stage runs under a named soft deadline: a hang dies as
    # a StageTimeout naming its stage (with faulthandler tracebacks on
    # stderr) instead of eating the whole wall budget silently.
    # BENCH_STAGE_TIMEOUT=0 disables.
    wd = resilience.Watchdog(
        int(os.environ.get("BENCH_STAGE_TIMEOUT", "1200")),
        hard=False, label="bench stage", stream=sys.stderr)

    def stage(msg):
        # wall-clock-tagged stage marker (stderr: stdout stays the one
        # JSON result line); each marker re-arms the per-stage deadline,
        # so a later hang is blamed on the segment "after <marker>"
        wd("after %r" % msg)
        sys.stderr.write("[%s] bench stage: %s\n"
                         % (resilience.wallclock(), msg))
        sys.stderr.flush()

    wd("synth")
    X, y = synth_higgs(n_rows + n_test, n_feat=n_feat)
    Xte, yte = X[n_rows:], y[n_rows:]
    X, y = X[:n_rows], y[:n_rows]
    stage("synth done (%d rows)" % n_rows)

    params = {"objective": "binary", "metric": "auc",
              "num_leaves": num_leaves, "max_bin": max_bin,
              "learning_rate": 0.1, "verbose": -1}
    # frontier batching (Config.tpu_frontier_batch): BENCH_FRONTIER_BATCH=K
    # lets a session A/B the batched grower (the lax engine's only: with
    # the Pallas histogram the sequential grower runs whatever K says)
    fbatch = int(os.environ.get("BENCH_FRONTIER_BATCH", "1") or 1)
    if fbatch > 1:
        params["tpu_frontier_batch"] = fbatch
    train = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params, train)
    stage("booster built")
    # warm-up: binning + compile + first iterations
    for _ in range(3):
        bst.update()
    bst._engine.flush()
    stage("warmup done")
    # blocking-sync audit over the measured window (ISSUE 5): total and
    # tree->tree-critical-path host fetches per iteration ride the JSON
    sync0 = syncs.snapshot()
    t0 = time.time()
    for _ in range(measure_iters):
        bst.update()
    bst._engine.flush()
    dt = time.time() - t0
    sync_audit = syncs.delta(sync0)
    host_syncs = {
        "per_iter_total": round(sync_audit["total"] / measure_iters, 3),
        "per_iter_critical_path": round(
            sync_audit["critical_path"] / measure_iters, 3),
        "by_label": sync_audit["by_label"],
        "pipeline_depth": bst._engine._pipeline_depth,
    }
    iters_per_sec = measure_iters / dt
    stage("measured %.4f s/iter (%s critical-path syncs/iter)"
          % (dt / measure_iters, host_syncs["per_iter_critical_path"]))

    pred = bst.predict(Xte, device=True)
    test_auc = float(auc_score(yte, pred))
    headline_iters = bst.current_iteration()
    stage("predict+auc done")

    eng = bst._engine
    result = {
        "metric": "boosting iters/sec, Higgs-scale binary (%.1fM x %d, %d leaves, %d bins)"
                  % (n_rows / 1e6, n_feat, num_leaves, max_bin),
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        # the published baseline is the Higgs shape at 10.5M rows; a ratio
        # across workloads or row counts compares nothing
        "vs_baseline": (round(iters_per_sec / BASELINE_ITERS_PER_SEC, 4)
                        if (n_rows, n_feat, max_bin) == (10_500_000, 28, 255)
                        else None),
        "sec_per_iter": round(dt / measure_iters, 4),
        "n_rows": n_rows,
        "host_syncs_per_iter": host_syncs,
        "held_out_auc_at_%d" % headline_iters: round(test_auc, 6),
        "reference_real_higgs_auc_at_500": REFERENCE_HIGGS_AUC,
        "engines": eng.engines,
        "fast_path": bool(getattr(eng, "_fast_active", False)),
        # frontier-batch telemetry: sequential grower rounds per tree
        # (== num_leaves-1 unless the batched grower engaged) and the
        # per-round device dispatch mix the round count multiplies
        "split_rounds_per_tree": getattr(eng, "split_rounds_per_tree",
                                         lambda: None)(),
        "frontier_batch": fbatch,
        "dispatches_per_round": {"partition": fbatch, "histogram": 1,
                                 "split_search": 1},
        "phases_note": "phases are measured PIECEWISE (one dispatch + sync "
                       "per stage), so each absolute value carries the "
                       "per-dispatch overhead the fused programs amortize "
                       "and their SUM may exceed sec_per_iter; the "
                       "normalized phase_frac block is the self-consistent "
                       "split to read, and sec_per_iter is the steady-state "
                       "number",
    }

    # BENCH_PIPELINE A/B (=0 skips): the SAME booster re-measured with the
    # dispatch pipeline off — compiled programs are shared, so the delta
    # is pure pipeline effect (per-tree blocking fetch + host assembly on
    # vs off the critical path)
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        depth_on = eng._pipeline_depth
        eng.flush()
        eng._pipeline_depth = 0
        sync0 = syncs.snapshot()
        tp0 = time.time()
        for _ in range(measure_iters):
            bst.update()
        dt_off = time.time() - tp0
        d_off = syncs.delta(sync0)
        eng._pipeline_depth = depth_on
        result["pipeline"] = {
            "pipeline_depth_on": depth_on,
            "sec_per_iter_on": round(dt / measure_iters, 4),
            "sec_per_iter_off": round(dt_off / measure_iters, 4),
            "speedup_on_vs_off": round(dt_off / dt, 4),
            "host_syncs_per_iter_on": host_syncs["per_iter_total"],
            "host_syncs_per_iter_off": round(
                d_off["total"] / measure_iters, 3),
            "critical_path_syncs_per_iter_on":
                host_syncs["per_iter_critical_path"],
            "critical_path_syncs_per_iter_off": round(
                d_off["critical_path"] / measure_iters, 3),
        }
        stage("pipeline A/B done (%.4f on vs %.4f off s/iter)"
              % (dt / measure_iters, dt_off / measure_iters))

    if n_rows > 5_000_000 and os.environ.get("BENCH_PHASES") != "1":
        phases = phase_times_midscale(X, y, params,
                                      min(MID_PHASE_ROWS, n_rows))
    else:
        phases = phase_times(bst)
    # the sync-audit counters ride the phases output so every bench record
    # carries the blocking-fetch split next to the wall split
    phases["host_sync_audit"] = host_syncs
    result["phases"] = phases
    stage("phases done")

    # compile/device/fetch attribution (BENCH_ATTRIB=0 skips): the ISSUE
    # 10 decomposition + steady-state zero-retrace pin on the warm booster
    if os.environ.get("BENCH_ATTRIB", "1") != "0":
        result["attrib"] = bench_attrib(bst, measure_iters)
        stage("attrib done (device share %s, %s steady retraces)"
              % (result["attrib"]["device_share"],
                 len(result["attrib"]["steady_state_retraces"])))

    # fused-boosting-window A/B (BENCH_WINDOW=0 skips, =J sets the
    # window): one device dispatch per J iterations vs one per tree, on
    # the same warm booster
    if os.environ.get("BENCH_WINDOW", "4") != "0":
        result["window"] = bench_window(bst, measure_iters)
        stage("window A/B done")

    # quantized-gradient A/B (BENCH_HIST_QUANT=int8|int16): same data and
    # config with gradient_quantization on — reports the per-dispatch
    # grad/hess bytes reduction, the quantized-vs-f32 held-out AUC delta
    # and both steady-state timings
    quant_mode = os.environ.get("BENCH_HIST_QUANT", "0")
    if quant_mode not in ("", "0"):
        qdtype = quant_mode if quant_mode in ("int8", "int16") else "int16"
        qparams = dict(params, gradient_quantization=True,
                       gradient_quant_dtype=qdtype)
        bstq = lgb.Booster(qparams, lgb.Dataset(X, label=y))
        for _ in range(3):
            bstq.update()
        tq0 = time.time()
        for _ in range(measure_iters):
            bstq.update()
        bstq._engine.flush()
        dtq = time.time() - tq0
        auc_q = float(auc_score(yte, bstq.predict(Xte, device=True)))
        result["hist_quant"] = dict(bstq._engine.quant_report or {}, **{
            "enabled": bool(bstq._engine._quant_enabled),
            "sec_per_iter_quant": round(dtq / measure_iters, 4),
            "sec_per_iter_f32": round(dt / measure_iters, 4),
            "grow_speedup_vs_f32": round(dt / dtq, 4),
            "held_out_auc_quant": round(auc_q, 6),
            "held_out_auc_f32": round(test_auc, 6),
            "auc_delta_vs_f32": round(auc_q - test_auc, 6),
        })
        stage("hist-quant A/B done (%s)" % qdtype)

    # the service sections, each skipped by BENCH_<SECTION>=0
    for key, section in (("predict", bench_predict),
                         ("online", bench_online),
                         ("serve", bench_serve),
                         ("ingest", bench_ingest),
                         ("telemetry", bench_telemetry)):
        if os.environ.get("BENCH_" + key.upper(), "1") != "0":
            result[key] = section()
            stage("%s section done" % key)

    wd.done()
    _telemetry.write_snapshot_now("bench")
    return result


if __name__ == "__main__":
    main()
