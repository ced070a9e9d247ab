"""Unified observability subsystem (ISSUE 9, runtime/telemetry.py).

Pins the tentpole end to end: the registry semantics (bounded
histograms with bucket-exact quantiles, label-cardinality overflow,
strict table declaration), the span API the stage-trail watchdog now
feeds, all three exporters (Prometheus HTTP, atomic JSON-lines file,
jax.profiler hook), the live wiring through training and serving, and
the two ISSUE acceptance gates:

* a live serving runtime answers GET /metrics with latency histogram
  quantiles that match client-measured wall clocks to within one bucket
  width — and BENCH_SERVE reads its p50/p99 from the same registry;
* a CLI train run with $LGBM_TPU_METRICS_FILE emits snapshots carrying
  per-iteration timing and host_syncs_per_iter consistent with the
  sync-audit pin (0 critical-path fetches at pipeline_depth=1).

Plus the satellites: atomic stage-trail writes (torn-read and
concurrent-reader pins), the metric-catalog <-> docs drift lint, and
the <1% disabled-path overhead assertion at reduced scale.
"""
import json
import math
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime import obs, resilience, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TEST_TABLE = {
    "t_counter_total": {"type": "counter", "labels": ("kind",),
                        "help": "test counter"},
    "t_plain_total": {"type": "counter", "labels": (),
                      "help": "plain test counter"},
    "t_gauge": {"type": "gauge", "labels": (), "help": "test gauge"},
    "t_hist_seconds": {"type": "histogram", "labels": ("who",),
                       "help": "test histogram"},
}


def _registry(**kw):
    return telemetry.MetricsRegistry(table=dict(TEST_TABLE), **kw)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_basics_and_obs_alias():
    assert obs is telemetry
    reg = _registry()
    reg.counter("t_counter_total").inc(kind="a")
    reg.counter("t_counter_total").inc(2.5, kind="a")
    reg.counter("t_counter_total").inc(kind="b")
    assert reg.counter("t_counter_total").value(kind="a") == 3.5
    assert reg.counter("t_counter_total").total() == 4.5
    reg.gauge("t_gauge").set(7)
    reg.gauge("t_gauge").inc(3)
    assert reg.gauge("t_gauge").value() == 10


def test_undeclared_metric_name_raises():
    """Every product metric must be table-declared — otherwise the docs
    drift lint is incomplete by construction."""
    reg = _registry()
    with pytest.raises(KeyError):
        reg.counter("t_not_declared_total")
    with pytest.raises(ValueError):
        reg.gauge("t_counter_total")     # declared, but wrong type


def test_histogram_quantiles_exact_within_bucket():
    """p50/p95/p99 from the fixed layout must sit within one bucket
    width of the true quantile, with sum/count exact."""
    reg = _registry()
    h = reg.histogram("t_hist_seconds")
    rng = np.random.default_rng(7)
    values = rng.uniform(0.0005, 4.0, size=5000)
    for v in values:
        h.observe(float(v), who="x")
    st = h.state(who="x")
    assert st["count"] == 5000
    assert abs(st["sum"] - values.sum()) < 1e-6
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q, who="x")
        true = float(np.quantile(values, q))
        assert abs(est - true) <= h.bucket_width_at(true), (q, est, true)


def test_histogram_empty_and_overflow_tail():
    reg = _registry()
    h = reg.histogram("t_hist_seconds")
    assert h.quantile(0.5, who="x") is None
    h.observe(1e9, who="x")              # beyond the largest finite edge
    q = h.quantile(0.99, who="x")
    assert q == h.buckets[-2]            # reported as the last finite edge


def test_label_cardinality_overflow_bucket():
    """Past max_label_sets, new label sets land in the explicit
    __overflow__ series — bounded memory, visible overload."""
    reg = _registry(max_label_sets=4)
    c = reg.counter("t_counter_total")
    for i in range(10):
        c.inc(kind="k%d" % i)
    keys = {k for k, _ in c.items()}
    assert len(keys) == 5                # 4 real + 1 overflow
    assert (telemetry.OVERFLOW_LABEL,) in keys
    assert c.value(kind=telemetry.OVERFLOW_LABEL) == 6
    assert c.total() == 10               # nothing dropped


def test_prometheus_rendering():
    reg = _registry()
    reg.counter("t_counter_total").inc(kind='we"ird\\')
    reg.histogram("t_hist_seconds").observe(0.003, who="w")
    reg.histogram("t_hist_seconds").observe(0.004, who="w")
    text = reg.render_prometheus()
    assert "# TYPE t_counter_total counter" in text
    assert "# HELP t_hist_seconds test histogram" in text
    assert 't_counter_total{kind="we\\"ird\\\\"} 1' in text
    # buckets are cumulative and end at +Inf == count
    assert 't_hist_seconds_bucket{who="w",le="+Inf"} 2' in text
    assert 't_hist_seconds_bucket{who="w",le="0.005"} 2' in text
    assert 't_hist_seconds_bucket{who="w",le="0.0025"} 0' in text
    assert 't_hist_seconds_count{who="w"} 2' in text


def test_disabled_path_records_nothing():
    reg = _registry()
    prev = telemetry.set_enabled(False)
    try:
        reg.counter("t_plain_total").inc()
        reg.gauge("t_gauge").set(5)
        reg.histogram("t_hist_seconds").observe(1.0, who="x")
    finally:
        telemetry.set_enabled(prev)
    assert reg.counter("t_plain_total").total() == 0
    assert reg.histogram("t_hist_seconds").state()["count"] == 0
    assert reg.ops == 0


def test_snapshot_carries_quantiles_and_json_roundtrips():
    reg = _registry()
    reg.histogram("t_hist_seconds").observe(0.02, who="x")
    snap = reg.snapshot("unit")
    line = json.dumps(snap)
    back = json.loads(line)
    ser = back["metrics"]["t_hist_seconds"]["series"][0]
    assert ser["count"] == 1 and ser["p50"] is not None
    assert back["context"] == "unit" and back["wallclock"]


# ---------------------------------------------------------------------------
# spans + the watchdog as a span client
# ---------------------------------------------------------------------------

def test_span_normalization_and_recording():
    assert telemetry.normalize_span_name("cycle 17: train") == \
        "cycle N: train"
    assert telemetry.normalize_span_name(
        "batch model=default gen=3 rows=512") == \
        "batch model=default gen=N rows=N"
    h = telemetry.histogram("lgbm_span_seconds")
    before = h.state(span="unit span N")
    with telemetry.span("unit span 42"):
        time.sleep(0.01)
    after = h.state(span="unit span N")
    assert after["count"] == before["count"] + 1
    assert after["sum"] - before["sum"] >= 0.009


def test_span_error_status():
    c = telemetry.counter("lgbm_spans_total")
    before = c.value(span="failing span", status="error")
    with pytest.raises(RuntimeError):
        with telemetry.span("failing span"):
            raise RuntimeError("boom")
    assert c.value(span="failing span", status="error") == before + 1


def test_watchdog_stage_closes_record_spans():
    """The stage-trail watchdog is a client of the span API: every
    stage close lands in lgbm_span_seconds under <label>/<stage> with
    digits normalized, status mirroring the trail."""
    h = telemetry.histogram("lgbm_span_seconds")
    key = "unit wd/step N"
    before = h.state(span=key)
    wd = resilience.Watchdog(0, label="unit wd", use_alarm=False)
    wd("step 1")
    time.sleep(0.005)
    wd("step 2")
    wd.done()
    after = h.state(span=key)
    assert after["count"] == before["count"] + 2
    # a thread-mode deadline expiry closes as status=timeout
    c = telemetry.counter("lgbm_spans_total")
    t_before = c.value(span=key, status="timeout")
    wd2 = resilience.Watchdog(0, label="unit wd", use_alarm=False)
    wd2("step 3")
    wd2.record_timeout(note="unit")
    assert c.value(span=key, status="timeout") == t_before + 1


# ---------------------------------------------------------------------------
# exporters: HTTP, file, profiler
# ---------------------------------------------------------------------------

def test_http_server_serves_prometheus_and_json():
    reg = _registry()
    reg.counter("t_plain_total").inc(3)
    srv = telemetry.start_http_server(port=0, registry=reg)
    try:
        base = "http://127.0.0.1:%d" % srv.port
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert "t_plain_total 3" in text
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics.json", timeout=10).read().decode())
        assert snap["metrics"]["t_plain_total"]["series"][0]["value"] == 3
        assert urllib.request.urlopen(
            base + "/healthz", timeout=10).read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        srv.stop()


def test_metrics_file_writer_atomic_lines(tmp_path):
    """Every flush rewrites the file atomically: a concurrent reader
    must ALWAYS see a complete, parseable JSON-lines file (this is the
    torn-read satellite applied to the new exporter)."""
    reg = _registry()
    path = str(tmp_path / "m.jsonl")
    w = telemetry.MetricsFileWriter(path, interval_s=0, context="unit",
                                    registry=reg)
    problems = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                with open(path) as fh:
                    for line in fh.read().splitlines():
                        json.loads(line)
            except FileNotFoundError:
                pass
            except ValueError as e:
                problems.append(str(e))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for i in range(60):
        reg.counter("t_plain_total").inc()
        w.write_now()
    stop.set()
    t.join(timeout=10)
    assert problems == []
    lines = open(path).read().splitlines()
    assert 1 <= len(lines) <= telemetry.SNAPSHOT_KEEP_LAST
    last = json.loads(lines[-1])
    assert last["metrics"]["t_plain_total"]["series"][0]["value"] == 60
    assert last["context"] == "unit"
    w.stop(final_flush=False)


def test_profiler_hook_wraps_n_ticks(tmp_path, monkeypatch):
    """LGBM_TPU_PROFILE=<dir>: the first N ticks land in ONE
    jax.profiler trace under <dir>/<kind>, then the hook closes."""
    import glob
    monkeypatch.setenv(telemetry.PROFILE_ENV, str(tmp_path))
    monkeypatch.setenv(telemetry.PROFILE_ITERS_ENV, "2")
    telemetry._reset_profile_hooks()
    try:
        hook = telemetry.profile_hook("train")
        assert hook.limit == 2
        hook.tick()
        assert hook.active and not hook.done
        hook.tick()
        assert hook.done and not hook.active
        hook.tick()                      # one-shot: further ticks no-op
        files = glob.glob(str(tmp_path / "train") + "/**",
                          recursive=True)
        assert any("xplane" in f or "profile" in f for f in files), files
    finally:
        telemetry._reset_profile_hooks()


# ---------------------------------------------------------------------------
# atomic stage trails (satellite): torn read + concurrent validity
# ---------------------------------------------------------------------------

def test_read_stage_report_tolerates_torn_and_missing(tmp_path):
    torn = tmp_path / "trail.json"
    good = {"stages": [{"name": "s"}], "culprit": None}
    torn.write_text(json.dumps(good)[: len(json.dumps(good)) // 2])
    assert resilience.read_stage_report(str(torn)) is None
    assert resilience.read_stage_report(str(tmp_path / "absent")) is None
    (tmp_path / "notdict.json").write_text("[1, 2]")
    assert resilience.read_stage_report(
        str(tmp_path / "notdict.json")) is None
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(good))
    assert resilience.read_stage_report(str(ok))["stages"][0]["name"] == "s"


def test_stage_trail_writes_are_atomic_under_concurrent_reads(tmp_path):
    """A scraper polling the stage trail while the watchdog rewrites it
    at every transition/annotate must never observe invalid JSON — the
    tmp+fsync+rename discipline, pinned live."""
    path = str(tmp_path / "trail.json")
    wd = resilience.Watchdog(0, label="atomic wd", use_alarm=False,
                             report_path=path)
    problems = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                with open(path) as fh:
                    json.load(fh)
            except FileNotFoundError:
                pass                     # not written yet
            except ValueError as e:
                problems.append("torn read: %s" % e)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for i in range(100):
        wd("stage %d" % i)
        wd.annotate("k", i)
    wd.done()
    stop.set()
    t.join(timeout=10)
    assert problems == []
    rep = resilience.read_stage_report(path)
    assert rep is not None and rep["stages"]


# ---------------------------------------------------------------------------
# metric catalog <-> docs drift lint (satellite)
# ---------------------------------------------------------------------------

def test_metric_catalog_matches_docs():
    """docs/OBSERVABILITY.md's catalog table must equal METRIC_TABLE
    row-for-row (name, type, labels, help) — the FAULT_TABLE pattern:
    the number and meaning in the docs are derived, never hand-waved."""
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read()
    rows = [ln for ln in doc.splitlines()
            if ln.startswith("| `lgbm_")]
    doc_rows = []
    for ln in rows:
        cells = [c.strip() for c in ln.strip("|").split("|")]
        assert len(cells) == 4, ln
        name = cells[0].strip("`")
        labels = () if cells[2] == "—" else tuple(
            s.strip() for s in cells[2].split(","))
        doc_rows.append((name, cells[1], labels, cells[3]))
    table_rows = [
        (name, d["type"], tuple(d["labels"]), d["help"])
        for name, d in sorted(telemetry.METRIC_TABLE.items())]
    doc_names = [r[0] for r in doc_rows]
    table_names = [r[0] for r in table_rows]
    assert doc_names == table_names, (
        "docs/OBSERVABILITY.md catalog drifted from METRIC_TABLE: "
        "docs-only %r, table-only %r"
        % (sorted(set(doc_names) - set(table_names)),
           sorted(set(table_names) - set(doc_names))))
    for drow, trow in zip(doc_rows, table_rows):
        assert drow == trow, "row drift for %s:\n docs:  %r\n table: %r" \
            % (drow[0], drow, trow)


def test_metric_table_help_is_markdown_safe():
    """Pipes in help strings would silently shear the docs table."""
    for name, d in telemetry.METRIC_TABLE.items():
        assert "|" not in d["help"], name
        assert "\n" not in d["help"], name


# ---------------------------------------------------------------------------
# live wiring: training
# ---------------------------------------------------------------------------

def _small_booster(n=3000, rounds=4):
    rng = np.random.default_rng(3)
    X = rng.standard_normal((n, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update()
    bst._drain()
    return bst


def test_training_instruments_and_sync_audit_gauges():
    """Per-iteration timing + iteration counter + the sync-audit gauges
    ride every Booster.update; at the default pipeline_depth=1 the
    critical-path gauge is 0 (the ISSUE-5 pin, now scrapeable)."""
    it_hist = telemetry.histogram("lgbm_train_iteration_seconds")
    it_cnt = telemetry.counter("lgbm_train_iterations_total")
    h_before = it_hist.state()
    c_before = it_cnt.total()
    _small_booster(rounds=5)
    assert it_cnt.total() == c_before + 5
    assert it_hist.state()["count"] == h_before["count"] + 5
    g = telemetry.gauge("lgbm_train_host_syncs_per_iter")
    assert g.value(path="critical") == 0.0
    # the pipeline drain + queue instruments recorded too
    assert telemetry.histogram(
        "lgbm_pipeline_drain_seconds").state()["count"] > 0
    # and the audited sync counters carry the drain label
    assert telemetry.counter("lgbm_host_syncs_total").value(
        label="pipeline_drain") > 0


def test_telemetry_disabled_training_still_works():
    prev = telemetry.set_enabled(False)
    try:
        cnt_before = telemetry.counter(
            "lgbm_train_iterations_total").total()
        bst = _small_booster(n=1500, rounds=2)
        assert bst.current_iteration() == 2
        assert telemetry.counter(
            "lgbm_train_iterations_total").total() == cnt_before
    finally:
        telemetry.set_enabled(prev)


# ---------------------------------------------------------------------------
# ISSUE 35: an iteration out of line gets its account and ONE verdict
# ---------------------------------------------------------------------------

@pytest.fixture()
def stall_log():
    """The warning lines and `train/stall` events of a test, with the
    rule's history and the ring empty at both ends."""
    import gc

    from lightgbm_tpu.runtime import tracing
    from lightgbm_tpu.utils import log
    lines = []
    level = log._level
    telemetry._ITERATION_WALLS.clear()
    tracing.reset()
    log.reset_callback(lines.append)
    log.reset_log_level(log.LogLevel.WARNING)   # an earlier `verbose: -1`

    class Seen:
        warnings = lines

        @staticmethod
        def stalls():
            return [e for e in tracing.export_chrome()["traceEvents"]
                    if e["name"] == "train/stall"]
    try:
        yield Seen
    finally:
        log.reset_callback(None)
        log.reset_log_level(level)
        gc.enable()
        telemetry._ITERATION_WALLS.clear()
        tracing.reset()


def _warmed_booster():
    """A small booster past its compiles, with the rule's history
    full enough to judge the next iteration."""
    from lightgbm_tpu.utils import log
    bst = _small_booster(n=2000, rounds=3)
    log.reset_log_level(log.LogLevel.WARNING)   # `verbose: -1` muted it
    while len(telemetry._ITERATION_WALLS) < telemetry.STALL_MIN_HISTORY + 2:
        bst.update()
    bst._drain()
    return bst


def _inject(cause, bst, monkeypatch, seconds=0.4):
    """Arm ONE stall of the given cause in a coming `update()`."""
    import gc

    import jax

    eng = bst._engine
    real_iter = eng.train_one_iter
    armed = [True]

    def once():
        fire, armed[0] = armed[0], False
        return fire

    def before_the_iteration(what):
        def train_one_iter(*a, **k):
            if once():
                what()
            return real_iter(*a, **k)
        monkeypatch.setattr(eng, "train_one_iter", train_one_iter)

    def spin():
        t_end = time.thread_time() + seconds
        while time.thread_time() < t_end:
            pass

    if cause == "tree_late":
        real_get = jax.device_get
        worker = []

        def slow_get(x):
            name = threading.current_thread().name
            if name == "lgbm-tpu-assembler" and once():
                worker.append(name)
                time.sleep(seconds)
            return real_get(x)
        monkeypatch.setattr(jax, "device_get", slow_get)
    elif cause == "gc":
        gc.disable()                    # the garbage waits for collect()
        heap = []
        for _ in range(400_000):
            a, b = [], []
            a.append(b)
            b.append(a)
            heap.append(a)
        del heap, a, b
        before_the_iteration(gc.collect)
    elif cause == "host_cpu":
        before_the_iteration(spin)
    elif cause == "unnamed":
        before_the_iteration(lambda: time.sleep(seconds))
    elif cause == "launch_blocked":
        step = eng._fast._step
        enqueue = step._jitted

        def slow_enqueue(*a, **k):
            if once():
                time.sleep(seconds)
            return enqueue(*a, **k)
        monkeypatch.setattr(step, "_jitted", slow_enqueue)
    elif cause == "compile":
        eng._fast._step.clear_cache()
    else:
        raise AssertionError(cause)


@pytest.mark.parametrize("cause", ["tree_late", "gc", "host_cpu",
                                   "launch_blocked", "compile", "unnamed"])
def test_a_stalled_iteration_gets_one_verdict(cause, stall_log, monkeypatch):
    from lightgbm_tpu.runtime import tracing
    bst = _warmed_booster()
    assert stall_log.stalls() == []
    _inject(cause, bst, monkeypatch)
    for _ in range(3):
        bst.update()
    bst._drain()
    stalls = [e for e in stall_log.stalls()
              if e["args"]["verdict"] == cause]
    assert len(stalls) == 1, stall_log.stalls()
    a = stalls[0]["args"]
    assert stalls[0]["ph"] == "i"
    assert a["wall_ns"] > telemetry.STALL_RATIO * a["median_ns"]
    # under the iteration it judges, which carries the same number
    [it] = [e for e in tracing.export_chrome()["traceEvents"]
            if e["name"] == "train/iteration"
            and e["args"]["span"] == a["parent"]]
    assert it["args"]["iteration"] == a["iteration"]
    # the two splits of the iteration each add up to its wall
    by_span = sum(a[k] for k in ("launch_ns", "wait_ns", "fetch_ns",
                                 "gc_ns", "self_ns"))
    by_state = a["cpu_ns"] + a["runq_ns"] + a["asleep_ns"]
    assert by_span == pytest.approx(a["wall_ns"], rel=0.01)
    assert by_state == pytest.approx(a["wall_ns"], rel=0.01)
    if cause == "tree_late":
        # and so do the awaited drain's
        drain = sum(a["drain_%s_ns" % k]
                    for k in ("launch", "fetch", "gc", "self"))
        assert drain == pytest.approx(a["drain_ns"], rel=0.01)
        assert a["drain_cpu_ns"] + a["drain_runq_ns"] \
            + a["drain_asleep_ns"] == pytest.approx(a["drain_ns"], rel=0.01)
        assert a["wait_ns"] >= 0.3e9 and a["drain_fetch_ns"] >= 0.3e9
        assert a["what"].startswith("tree ")
    what = {"host_cpu": "train/iteration", "launch_blocked":
            "launch/gbdt.step", "compile": "gbdt.step"}.get(cause)
    if what:
        assert a["what"] == what
    # ONE line for it, with the same fields, through Log
    mine = [ln for ln in stall_log.warnings
            if "train/stall" in ln and "verdict=%s " % cause in ln]
    assert len(mine) == 1 and mine[0].count("\n") == 1
    assert "[Warning] train/stall iteration=%d " % a["iteration"] in mine[0]
    assert "wall_ms=%.3f" % (a["wall_ns"] / 1e6) in mine[0]


def _driven_iterations(seconds):
    for s in seconds:
        with telemetry.train_iteration():
            time.sleep(s)


@pytest.mark.parametrize("history, expect", [
    ([0.02] * 14, 0),                   # nothing out of line
    ([0.005] * 7 + [0.2], 0),           # too little history to judge by
    ([0.005] * 8 + [0.2], 1),           # the same stall, one more before
])
def test_no_stall_no_event_and_no_verdict_on_little_history(
        history, expect, stall_log):
    _driven_iterations(history)
    assert len(stall_log.stalls()) == expect
    assert len([ln for ln in stall_log.warnings
                if "train/stall" in ln]) == expect
    if expect:
        assert stall_log.stalls()[0]["args"]["verdict"] == "unnamed"


def test_a_compile_restarts_the_stall_rules_history(stall_log):
    from lightgbm_tpu.runtime import tracing
    _driven_iterations([0.002] * 9)
    assert len(telemetry._ITERATION_WALLS) == 9
    with telemetry.train_iteration():
        tracing.record("xla compile t.site", time.monotonic_ns(), 1000,
                       track="xla compile", site="t.site")
    assert len(telemetry._ITERATION_WALLS) == 0
    _driven_iterations([0.002])
    assert len(telemetry._ITERATION_WALLS) == 1
    assert stall_log.stalls() == []


# ---------------------------------------------------------------------------
# acceptance gate 1: live serving /metrics quantiles vs client clocks
# ---------------------------------------------------------------------------

def test_serving_metrics_acceptance():
    """A live ServingRuntime with metrics_port= answers GET /metrics
    with the serving latency histogram; its p50 matches the latencies
    the clients measured to within one bucket width, and stats()
    exposes the same quantiles (what BENCH_SERVE reports)."""
    import bench as bench_mod
    from lightgbm_tpu.runtime.serving import ServingRuntime

    model = bench_mod.synth_serving_model(20, 31, 28, seed=3)
    lat_hist = telemetry.histogram("lgbm_serve_latency_seconds")
    before = lat_hist.state()
    client_lat = []
    rng = np.random.default_rng(11)
    with ServingRuntime(model_str=model.save_model_to_string(),
                        metrics_port=0, batch_window_s=0.001) as rt:
        assert rt.metrics_port is not None

        def client(seed):
            crng = np.random.default_rng(seed)
            for _ in range(40):
                X = crng.standard_normal((4, 28))
                t0 = time.perf_counter()
                rt.predict(X)
                client_lat.append(time.perf_counter() - t0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % rt.metrics_port,
            timeout=10).read().decode()
        st = rt.stats()
    assert "lgbm_serve_latency_seconds_bucket" in text
    assert 'lgbm_serve_requests_total{outcome="completed"}' in text
    delta = telemetry.state_delta(lat_hist.state(), before)
    assert delta["count"] == 120
    reg_p50 = telemetry.quantile_from_state(delta, 0.5)
    client_p50 = float(np.percentile(client_lat, 50))
    width = lat_hist.bucket_width_at(client_p50)
    assert abs(reg_p50 - client_p50) <= width, \
        (reg_p50, client_p50, width)
    # stats() exposes the same registry-derived quantiles
    assert st["latency_quantiles_s"]["count"] >= 120
    # batches/rows/queue instruments recorded
    assert telemetry.counter("lgbm_serve_rows_total").total() >= 480


def test_bench_serve_p50_comes_from_registry(monkeypatch):
    """BENCH_SERVE's reported p50/p99 derive from the registry histogram
    (source-tagged), scoped to the run via a state delta."""
    monkeypatch.setenv("BENCH_SERVE_SECONDS", "1.2")
    monkeypatch.setenv("BENCH_SERVE_CLIENTS", "2")
    monkeypatch.setenv("BENCH_SERVE_TREES", "10")
    monkeypatch.setenv("BENCH_SERVE_LEAVES", "15")
    import bench as bench_mod
    rec = bench_mod.bench_serve()
    assert rec["latency_ms"]["source"] == \
        "registry histogram lgbm_serve_latency_seconds"
    assert rec["latency_ms"]["histogram_count"] == rec["requests"]
    if rec["requests"]:
        # registry quantile within one bucket width of the client clock
        h = telemetry.histogram("lgbm_serve_latency_seconds")
        p50_reg = rec["latency_ms"]["p50"] / 1e3
        p50_cli = rec["client_latency_ms"]["p50"] / 1e3
        assert abs(p50_reg - p50_cli) <= h.bucket_width_at(p50_cli)


# ---------------------------------------------------------------------------
# acceptance gate 2: CLI train snapshot file
# ---------------------------------------------------------------------------

def test_cli_train_emits_metrics_snapshot(tmp_path, monkeypatch):
    """task=train with $LGBM_TPU_METRICS_FILE set emits >=1 snapshot
    line carrying per-iteration timing and host_syncs_per_iter gauges
    consistent with the sync-audit pin (critical == 0 at the default
    pipeline_depth=1)."""
    from lightgbm_tpu.application import Application

    rng = np.random.default_rng(9)
    X = rng.standard_normal((1500, 6))
    y = (X[:, 0] > 0).astype(float)
    data = tmp_path / "d.tsv"
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.7g")
    mfile = str(tmp_path / "metrics.jsonl")
    monkeypatch.setenv(telemetry.METRICS_FILE_ENV, mfile)
    monkeypatch.setenv(telemetry.METRICS_INTERVAL_ENV, "0")
    model = tmp_path / "m.txt"
    it_before = telemetry.counter("lgbm_train_iterations_total").total()
    Application(["task=train", "data=%s" % data, "objective=binary",
                 "num_trees=6", "num_leaves=7", "verbose=-1",
                 "output_model=%s" % model]).run()
    assert model.exists()
    lines = open(mfile).read().splitlines()
    assert len(lines) >= 1
    snap = json.loads(lines[-1])
    m = snap["metrics"]
    assert m["lgbm_train_iterations_total"]["series"][0]["value"] \
        == it_before + 6
    hist = m["lgbm_train_iteration_seconds"]["series"][0]
    assert hist["count"] >= 6 and hist["p50"] is not None
    syncs = {s["labels"]["path"]: s["value"]
             for s in m["lgbm_train_host_syncs_per_iter"]["series"]}
    assert syncs["critical"] == 0.0          # the ISSUE-5 pin, exported
    assert "lgbm_span_seconds" in m          # CLI stage closes as spans


# ---------------------------------------------------------------------------
# overhead satellite: <1% disabled path at reduced scale
# ---------------------------------------------------------------------------

def test_bench_telemetry_overhead_pin(monkeypatch):
    monkeypatch.setenv("BENCH_TELEMETRY_ROWS", "2500")
    monkeypatch.setenv("BENCH_TELEMETRY_ITERS", "3")
    import bench as bench_mod
    rec = bench_mod.bench_telemetry()
    assert rec["disabled_path_overhead_pct"] < 1.0, rec
    assert rec["ops_per_iter"] > 0
    assert rec["sec_per_iter_on"] > 0 and rec["sec_per_iter_off"] > 0
    assert telemetry.enabled()               # A/B restored the flag


# ---------------------------------------------------------------------------
# continuous trainer wiring (ingest + cycles through the registry)
# ---------------------------------------------------------------------------

def test_online_trainer_records_ingest_and_cycles(tmp_path):
    from lightgbm_tpu.runtime.continuous import ContinuousTrainer

    rng = np.random.default_rng(5)
    X = rng.standard_normal((800, 5))
    y = (X[:, 0] > 0).astype(float)
    data = tmp_path / "t.tsv"
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.7g")
    rows_before = telemetry.counter("lgbm_ingest_rows_total").total()
    ok_before = telemetry.counter("lgbm_online_cycles_total").value(
        status="ok")
    pub_before = telemetry.histogram(
        "lgbm_online_publish_seconds").state()["count"]
    trainer = ContinuousTrainer({
        "data": str(data), "output_model": str(tmp_path / "m.txt"),
        "objective": "binary", "num_leaves": 7, "verbose": -1,
        "online_cycles": 2, "online_rounds": 1, "online_interval": 0})
    import sys
    trainer.wd.stream = sys.stderr
    assert trainer.run() == 0
    assert telemetry.counter("lgbm_ingest_rows_total").total() \
        == rows_before + 800
    assert telemetry.counter("lgbm_online_cycles_total").value(
        status="ok") == ok_before + 2
    assert telemetry.histogram(
        "lgbm_online_publish_seconds").state()["count"] == pub_before + 2
    assert telemetry.gauge("lgbm_ingest_window_rows").value() == 800


# ---------------------------------------------------------------------------
# mesh-wide aggregation (ISSUE 10): gather/merge/{host} labels + the
# concurrent scrape+flush torn-output pin
# ---------------------------------------------------------------------------

def _two_host_snapshots():
    ra, rb = _registry(), _registry()
    ra.counter("t_plain_total").inc(3)
    ra.histogram("t_hist_seconds").observe(0.02, who="a")
    rb.counter("t_plain_total").inc(5)
    rb.gauge("t_gauge").set(7)
    return {"0": ra.snapshot("hostA"), "1": rb.snapshot("hostB")}


def test_merge_host_snapshots_labels_every_series():
    hosts = _two_host_snapshots()
    merged = telemetry.merge_host_snapshots(hosts)
    assert merged["hosts"] == ["0", "1"]
    series = merged["metrics"]["t_plain_total"]["series"]
    assert [(e["labels"]["host"], e["value"]) for e in series] \
        == [("0", 3.0), ("1", 5.0)]
    h = merged["metrics"]["t_hist_seconds"]["series"][0]
    assert h["labels"] == {"host": "0", "who": "a"}
    # {host} labels STABLE: merging again yields the identical structure
    assert telemetry.merge_host_snapshots(hosts) == merged or \
        telemetry.merge_host_snapshots(hosts)["metrics"] == \
        merged["metrics"]


def test_render_prometheus_from_merged_snapshot():
    merged = telemetry.merge_host_snapshots(_two_host_snapshots())
    text = telemetry.render_prometheus_from_snapshot(
        merged, table=TEST_TABLE)
    assert 't_plain_total{host="0"} 3' in text
    assert 't_plain_total{host="1"} 5' in text
    assert 't_gauge{host="1"} 7' in text
    # histogram rendered with cumulative buckets + the +Inf tail
    assert 't_hist_seconds_bucket{host="0",who="a",le="+Inf"} 1' in text
    assert 't_hist_seconds_count{host="0",who="a"} 1' in text


def test_gather_host_snapshots_single_process_is_host_zero():
    reg = _registry()
    reg.counter("t_plain_total").inc()
    hosts = telemetry.gather_host_snapshots("ctx", registry=reg)
    assert list(hosts) == ["0"]
    assert hosts["0"]["context"] == "ctx"
    merged = telemetry.mesh_snapshot("ctx", registry=reg)
    assert merged["metrics"]["t_plain_total"]["series"][0]["labels"] \
        == {"host": "0"}


def test_metrics_server_snapshot_provider_serves_merged_view():
    merged = telemetry.merge_host_snapshots(_two_host_snapshots())
    srv = telemetry.MetricsServer(
        port=0, registry=_registry(),
        snapshot_provider=lambda: merged)
    try:
        base = "http://127.0.0.1:%d" % srv.port
        with urllib.request.urlopen(base + "/metrics.json", timeout=5) as r:
            snap = json.loads(r.read().decode())
        assert snap["hosts"] == ["0", "1"]
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode()
        assert 't_plain_total{host="1"} 5' in text
    finally:
        srv.stop()


def test_concurrent_scrape_flush_no_torn_output(tmp_path):
    """Writers hammer the registry, the file exporter flushes, and
    scrapers read /metrics throughout: every exposition parses with
    monotone cumulative buckets, every snapshot-file line is valid
    JSON (the ISSUE 10 test-coverage satellite)."""
    reg = _registry()
    srv = telemetry.MetricsServer(port=0, registry=reg)
    writer = telemetry.MetricsFileWriter(str(tmp_path / "m.jsonl"),
                                         interval_s=0.01, registry=reg)
    stop = threading.Event()
    errors = []

    def hammer(seed):
        i = 0
        while not stop.is_set():
            reg.counter("t_counter_total").inc(kind="k%d" % (seed % 3))
            reg.histogram("t_hist_seconds").observe(
                0.001 * ((i % 50) + 1), who="w%d" % seed)
            reg.gauge("t_gauge").set(i)
            i += 1

    def scrape():
        base = "http://127.0.0.1:%d/metrics" % srv.port
        while not stop.is_set():
            try:
                with urllib.request.urlopen(base, timeout=5) as r:
                    text = r.read().decode()
            except OSError as e:            # noqa: PERF203
                errors.append("scrape: %s" % e)
                continue
            if not text.endswith("\n"):
                errors.append("torn exposition (no trailing newline)")
            cum = {}
            for line in text.splitlines():
                if line.startswith("#") or not line:
                    continue
                name_part, _, val = line.rpartition(" ")
                try:
                    v = float(val)
                except ValueError:
                    errors.append("unparseable sample: %r" % line)
                    continue
                if "_bucket{" in name_part:
                    key = name_part.rsplit(',le="', 1)[0]
                    if v < cum.get(key, 0.0):
                        errors.append("non-monotone buckets: %r" % line)
                    cum[key] = v

    threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
               for i in range(3)]
    threads.append(threading.Thread(target=scrape, daemon=True))
    threads.append(threading.Thread(target=scrape, daemon=True))
    for t in threads:
        t.start()
    time.sleep(1.2)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    srv.stop()
    writer.stop()
    assert errors == [], errors[:5]
    # every flushed line is intact JSON (atomic rewrite: never torn)
    lines = (tmp_path / "m.jsonl").read_text().splitlines()
    assert lines
    for ln in lines:
        snap = json.loads(ln)
        assert "metrics" in snap
