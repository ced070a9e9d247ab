"""End-to-end distributed tracing (ISSUE 14): the flight-recorder ring,
context propagation across threads and processes, the Chrome-trace /
merge exporters, the serving stage decomposition pin, and the satellite
fixes (span-name digit normalization, concurrent-writer integrity)."""
import contextlib
import gc
import json
import os
import threading
import time

import numpy as np
import pytest

from lightgbm_tpu.runtime import telemetry, tracing


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.reset()
    yield
    tracing.reset()


# ---------------------------------------------------------------------------
# ids + traceparent
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip_and_malformed():
    t, s = tracing.new_trace_id(), tracing.new_span_id()
    assert len(t) == 32 and len(s) == 16
    assert tracing.parse_traceparent(tracing.make_traceparent(t, s)) == (t, s)
    for bad in (None, "", "garbage", "00-short-short-01", 42,
                "00-" + "0" * 32 + "-" + "0" * 16 + "-01",     # zero ids
                "00-" + "z" * 32 + "-" + "f" * 16 + "-01"):    # non-hex
        assert tracing.parse_traceparent(bad) is None

    ids = {tracing.new_span_id() for _ in range(1000)}
    assert len(ids) == 1000                    # unique id stream


# ---------------------------------------------------------------------------
# spans, context, export
# ---------------------------------------------------------------------------

def test_span_nesting_parent_child_and_export():
    with tracing.span("root", foo=1) as root_ctx:
        assert tracing.current() == root_ctx
        assert tracing.parse_traceparent(
            tracing.current_traceparent()) == root_ctx
        with tracing.span("child"):
            tracing.instant("mark", k="v")
    assert tracing.current() is None           # stack unwound

    doc = tracing.export_chrome()
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e["ph"] in ("X", "i")}
    root, child = by_name["root"], by_name["child"]
    assert child["args"]["trace"] == root["args"]["trace"]
    assert child["args"]["parent"] == root["args"]["span"]
    assert by_name["mark"]["args"]["trace"] == root["args"]["trace"]
    assert root["args"]["foo"] == 1
    # timestamps are ABSOLUTE unix microseconds (the merge contract)
    assert abs(root["ts"] / 1e6 - time.time()) < 300
    assert root["dur"] >= child["dur"] >= 0


def test_span_error_status():
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("x")
    ev = [e for e in tracing.export_chrome()["traceEvents"]
          if e.get("name") == "boom"][0]
    assert ev["args"]["status"] == "error"


def test_attach_and_bind_carry_context_across_threads():
    seen = {}
    with tracing.span("dispatcher") as ctx:
        captured = tracing.context()

        def worker():
            with tracing.attach(captured):
                seen["inside"] = tracing.current()
            seen["outside"] = tracing.current()
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["inside"] == ctx and seen["outside"] is None

    # bind(): the assembler hand-off seam — runs fn under the captured
    # context AND records a span for the invocation
    with tracing.span("iteration") as it_ctx:
        fn = tracing.bind(lambda: tracing.current(), "drain", trees=2)
    out = {}
    t = threading.Thread(target=lambda: out.setdefault("ctx", fn()))
    t.start()
    t.join()
    assert out["ctx"][0] == it_ctx[0]          # same trace id
    drain = [e for e in tracing.export_chrome()["traceEvents"]
             if e.get("name") == "drain"][0]
    assert drain["args"]["trace"] == it_ctx[0]
    assert drain["args"]["parent"] == it_ctx[1]
    assert drain["args"]["trees"] == 2


def test_process_root_from_env(monkeypatch):
    t, s = tracing.new_trace_id(), tracing.new_span_id()
    monkeypatch.setenv(tracing.TRACEPARENT_ENV,
                       tracing.make_traceparent(t, s))
    tracing.reset()                            # re-read the env seed
    assert tracing.process_root() == (t, s)
    with tracing.span("rooted"):
        pass
    ev = [e for e in tracing.export_chrome()["traceEvents"]
          if e.get("name") == "rooted"][0]
    # a root span opened with no explicit context parents under the env
    assert ev["args"]["trace"] == t and ev["args"]["parent"] == s


def test_disabled_path_records_nothing_and_bind_is_identity():
    prev = tracing.set_enabled(False)
    try:
        tracing.instant("x")
        tracing.record("x", 0, 0)
        tracing.flow_start("x", 1)
        with tracing.span("x") as ctx:
            assert ctx is None
        fn = lambda: 1                          # noqa: E731
        assert tracing.bind(fn, "name") is fn
    finally:
        tracing.set_enabled(prev)
    assert tracing.export_chrome()["otherData"]["recorded_total"] == 0


# ---------------------------------------------------------------------------
# satellite: concurrent ring writers never tear or mis-order an export
# ---------------------------------------------------------------------------

def test_concurrent_writers_no_torn_or_out_of_order_events(monkeypatch):
    monkeypatch.setattr(tracing, "_RING", tracing._Ring(1024))
    threads, per = 6, 300

    def work(i):
        for j in range(per):
            with tracing.span("w%d" % i, j=j):
                tracing.instant("m%d" % i)
    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    gc.disable()                    # a collection would be a span of its own
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        doc = tracing.export_chrome()
    finally:
        gc.enable()
    summary = doc["otherData"]
    assert summary["recorded_total"] == threads * per * 2
    evs = [e for e in doc["traceEvents"] if e["ph"] in ("X", "i")]
    # bounded: the ring holds the newest `capacity`, the rest counted
    assert len(evs) == 1024
    assert summary["dropped"] == threads * per * 2 - 1024
    # no torn event: every record is structurally complete
    for e in evs:
        assert e["name"] and isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "span" in e["args"]
    # export order is globally monotonic (sorted on the shared clock)
    stamps = [e["ts"] for e in evs]
    assert stamps == sorted(stamps)


# ---------------------------------------------------------------------------
# merge
# ---------------------------------------------------------------------------

def test_merge_traces_fuses_processes_onto_one_timeline(tmp_path):
    with tracing.span("a"):
        pass
    p1 = str(tmp_path / "one.json")
    tracing.export_chrome(p1, context_name="one")
    tracing.reset()
    with tracing.span("b"):
        pass
    p2 = str(tmp_path / "two.json")
    tracing.export_chrome(p2, context_name="two")

    out = str(tmp_path / "merged.json")
    doc = tracing.merge_traces([p1, p2], out_path=out)
    on_disk = json.load(open(out))
    assert on_disk["otherData"]["merged_from"] == \
        doc["otherData"]["merged_from"]
    # each source landed on its own pid slot with a {host,pid} name
    names = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert {e["pid"] for e in names} == {1, 2}
    assert all("pid=" in e["args"]["name"] for e in names)
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    stamps = [e["ts"] for e in body]
    assert stamps == sorted(stamps)
    # the size bound cuts oldest-first and records the cut
    capped = tracing.merge_traces([p1, p2], max_events=1)
    assert capped["otherData"]["events"] == 1
    assert capped["otherData"]["truncated_oldest"] == len(body) - 1


# ---------------------------------------------------------------------------
# satellite: span-name digit normalization keeps product keys
# ---------------------------------------------------------------------------

def test_normalize_keeps_product_keys_distinguishable():
    n = telemetry.normalize_span_name
    # bounded product parameters survive: J=2 and J=4 are DIFFERENT
    # stages, not two samples of one (the pre-fix rewrite merged them)
    assert n("window dispatch J=4") == "window dispatch J=4"
    assert n("window dispatch J=2") != n("window dispatch J=4")
    assert n("depth=2 drain") == "depth=2 drain"
    # unbounded identifiers still collapse (cardinality stays bounded)
    assert n("cycle 17: train") == n("cycle 991: train") == "cycle N: train"
    assert n("batch model=default gen=12 rows=512") == \
        "batch model=default gen=N rows=N"
    assert n("online stage/cycle 3: publish") == \
        "online stage/cycle N: publish"
    assert n("recover: republish generation 7") == \
        "recover: republish generation N"
    # every registered watchdog-stage shape in the tree stays bounded:
    # a name made only of digits+keys cannot exceed the length cap
    assert len(n("x" * 500)) <= 80


def test_window_dispatch_span_series_distinct_by_J():
    telemetry.record_span("window dispatch J=2", 0.01)
    telemetry.record_span("window dispatch J=4", 0.02)
    snap = telemetry.snapshot()
    spans = {s["labels"]["span"]
             for s in snap["metrics"]["lgbm_span_seconds"]["series"]}
    assert {"window dispatch J=2", "window dispatch J=4"} <= spans


def test_record_span_lands_in_ring_with_raw_name():
    telemetry.record_span("cycle 42: publish", 0.05)
    evs = [e for e in tracing.export_chrome()["traceEvents"]
           if e.get("name") == "cycle 42: publish"]
    assert len(evs) == 1 and evs[0]["dur"] == pytest.approx(50_000, rel=0.1)


# ---------------------------------------------------------------------------
# serving integration: stage decomposition + request/publish links
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def _tiny_model_text():
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.standard_normal((200, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=2)
    return bst._model.save_model_to_string()


def test_serving_stage_sum_pins_to_latency_and_links(tmp_path,
                                                     _tiny_model_text):
    from lightgbm_tpu.runtime import publish
    from lightgbm_tpu.runtime.serving import ServingRuntime

    pub_dir = str(tmp_path / "pub")
    pub = publish.ModelPublisher(pub_dir)
    with tracing.span("cycle 1") as cycle_ctx:
        cycle_tp = tracing.current_traceparent()
        pub.publish(_tiny_model_text, meta={"trace": cycle_tp})

    rng = np.random.default_rng(1)
    rt = ServingRuntime(publish_dir=pub_dir, params={"verbose": -1},
                        batch_window_s=0.001, poll_interval_s=0.05)
    rt.start()
    try:
        deadline = time.monotonic() + 60
        while rt.generation() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert rt.generation() == 1
        ctx = (tracing.new_trace_id(), tracing.new_span_id())
        rec = rt.submit(rng.standard_normal((3, 4)),
                        traceparent=tracing.make_traceparent(*ctx)) \
            .wait(timeout=60)
        # the four stages PARTITION [enqueued, completed]: their sum is
        # the server latency to rounding — the acceptance contract the
        # sim gates at one bucket width against the CLIENT clock
        assert set(rec.stages) == {"queue_wait_s", "batch_gather_s",
                                   "device_s", "drain_s"}
        assert sum(rec.stages.values()) == \
            pytest.approx(rec.latency_s, abs=1e-4)
        # the response links back to the producing cycle's trace
        assert rec.model_trace == cycle_tp
        # an un-traced request still gets its decomposition
        rec2 = rt.submit(rng.standard_normal((1, 4))).wait(timeout=60)
        assert sum(rec2.stages.values()) == \
            pytest.approx(rec2.latency_s, abs=1e-4)
    finally:
        rt.stop()

    evs = tracing.export_chrome()["traceEvents"]
    # server-side stage slices recorded under the CLIENT's trace id
    req_ev = [e for e in evs if str(e.get("name", "")).startswith("req/")
              and e["args"]["trace"] == ctx[0]]
    assert {e["name"] for e in req_ev} == \
        {"req/queue_wait", "req/batch_gather", "req/device", "req/drain"}
    assert all(e["args"]["parent"] == ctx[1] for e in req_ev)
    # publish (flow start) and swap-in (flow end) share one arrow id —
    # the trainer cycle -> publish -> subscriber link of the acceptance
    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    assert starts and ends
    assert {e["id"] for e in starts} & {e["id"] for e in ends}
    # the publish-side event belongs to the cycle's trace
    assert any(e.get("args", {}).get("trace") == cycle_ctx[0]
               for e in starts)
    assert any(e.get("name") == "serve batch" for e in evs)


def test_doctor_bundle_carries_trace_ring(tmp_path):
    from lightgbm_tpu.runtime import doctor
    with tracing.span("pre-crash work"):
        pass
    rec = doctor.collect_debug_bundle(out_dir=str(tmp_path), probe=False)
    names = [m["name"] for m in rec["manifest"]["members"]]
    assert "trace.json" in names
    import tarfile
    with tarfile.open(rec["path"]) as tar:
        member = [m for m in tar.getmembers()
                  if m.name.endswith("trace.json")][0]
        doc = json.loads(tar.extractfile(member).read().decode())
    assert any(e.get("name") == "pre-crash work"
               for e in doc["traceEvents"])


def test_export_to_dir_and_autostart_env(tmp_path, monkeypatch):
    with tracing.span("flushed"):
        pass
    path = tracing.export_to_dir(str(tmp_path / "traces"))
    assert path and os.path.exists(path)
    assert "trace_" in os.path.basename(path)
    doc = json.load(open(path))
    assert any(e.get("name") == "flushed" for e in doc["traceEvents"])
    # autostart only arms when the env var is set
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    monkeypatch.setattr(tracing, "_atexit_armed", False)
    assert tracing.maybe_autostart() is False
    monkeypatch.setenv(tracing.TRACE_DIR_ENV, str(tmp_path / "traces"))
    assert tracing.maybe_autostart() is True


# ---------------------------------------------------------------------------
# satellite: the metric-coverage lint (lint #5)
# ---------------------------------------------------------------------------

def test_metric_coverage_lint_green_and_drift_negative():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "helper"))
    import check_metric_coverage as lint
    assert lint.run() == []
    # drift negative: a fabricated family with no call site IS reported
    table = dict(telemetry.METRIC_TABLE)
    table["lgbm_totally_unarmed_metric"] = {
        "type": "counter", "labels": (), "help": "x"}
    problems = lint.run(table=table)
    assert len(problems) == 1
    assert "lgbm_totally_unarmed_metric" in problems[0]
    # the declaration block itself can never arm a family: the name
    # appears in telemetry.py as a dict key, yet it is still reported
    hits = lint.coverage(table=table)
    assert hits["lgbm_totally_unarmed_metric"] == []


# ---------------------------------------------------------------------------
# ISSUE 24: one span source, two sinks -- the ring and the device
# profiler's trace -- and the catalogue of spans at the seams
# ---------------------------------------------------------------------------

#: span -> its parent after a few iterations of a small booster (None: a
#: root).  docs/OBSERVABILITY.md, "Spans", is the same list in prose.
SPAN_CATALOGUE = {
    "dataset/construct": None,
    "dataset/find_bins": "dataset/construct",
    "dataset/encode": "dataset/construct",
    "dataset/bundle": "dataset/construct",
    "train/iteration": None,
    "booster/payload": "train/iteration",
    "launch/gbdt.payload_build": "booster/payload",
    "launch/gbdt.step": "train/iteration",
    "assembler/wait": "train/iteration",
    "assembler/drain": "train/iteration",
    "launch/gbdt.pack_fetch": "assembler/drain",
    "fetch/pipeline_drain": "assembler/drain",
}
#: the spans of the drain's host half: on the assembler's thread
ON_WORKER = ("assembler/drain", "launch/gbdt.pack_fetch",
             "fetch/pipeline_drain")


def _profiled_iterations(trace_dir, iters=3):
    """A small booster from raw matrix to `iters` drained trees under a
    CPU `jax.profiler` session: (ring events, host-plane events as
    (name, line index))."""
    import glob

    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import gbdt

    rng = np.random.default_rng(5)
    X = rng.standard_normal((2000, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "pipeline_depth": 1}
    # a drain slower than a launch: the next submit() HAS to wait
    real_fetch = gbdt._fetch_packed

    def slow_fetch(out, label="tree_fetch"):
        time.sleep(0.05)
        return real_fetch(out, label=label)

    tracing.reset()
    gbdt._fetch_packed = slow_fetch
    jax.profiler.start_trace(str(trace_dir))
    try:
        bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
        for _ in range(iters):
            bst.update()
        assert bst.current_iteration() == iters
    finally:
        jax.profiler.stop_trace()
        gbdt._fetch_packed = real_fetch
    ring = [e for e in tracing.export_chrome()["traceEvents"]
            if e["ph"] == "X"]
    [path] = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                       recursive=True)
    host = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                host.extend((e.name, i) for e in line.events)
    return ring, host


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    return _profiled_iterations(tmp_path_factory.mktemp("profile"))


@pytest.mark.parametrize("name", sorted(SPAN_CATALOGUE))
def test_span_catalogue_in_the_ring_with_its_parent(profiled, name):
    ring, _ = profiled
    by_id = {e["args"]["span"]: e for e in ring}
    found = [e for e in ring if e["name"] == name]
    assert found, sorted({e["name"] for e in ring})
    parents = {by_id[e["args"]["parent"]]["name"]
               if e["args"].get("parent") in by_id else None for e in found}
    assert SPAN_CATALOGUE[name] in parents, parents
    main_tid = next(e["tid"] for e in ring if e["name"] == "train/iteration")
    for e in found:
        assert (e["tid"] != main_tid) == (name in ON_WORKER)
        parent = by_id.get(e["args"].get("parent"))
        if parent is not None:
            # the hand-off keeps the causal chain: same trace id
            assert e["args"]["trace"] == parent["args"]["trace"]
    # the instant this PR retired: the launch span is the same mark
    assert not [e for e in ring if e["name"] == "tree dispatch"]


@pytest.mark.parametrize("name", sorted(SPAN_CATALOGUE))
def test_span_catalogue_in_the_profilers_host_plane(profiled, name):
    _, host = profiled
    lines = {i for n, i in host if n == "lgbm/" + name}
    assert lines, sorted({n for n, _ in host if n.startswith("lgbm/")})
    main = {i for n, i in host if n == "lgbm/train/iteration"}
    # on the thread that did the work: the drain's spans on another line
    assert lines.isdisjoint(main) == (name in ON_WORKER)


def test_disabled_recorder_writes_no_annotation_either(tmp_path):
    prev = tracing.set_enabled(False)
    try:
        ring, host = _profiled_iterations(tmp_path, iters=2)
    finally:
        tracing.set_enabled(prev)
    assert ring == []
    assert not [n for n, _ in host if n.startswith("lgbm/")]
    assert host                     # the session itself recorded


# ---------------------------------------------------------------------------
# ISSUE 35: a span takes labels until it closes, carries what the host
# did to its thread, and a collection is a span
# ---------------------------------------------------------------------------

def _one_event(name):
    [ev] = [e for e in tracing.export_chrome()["traceEvents"]
            if e["name"] == name]
    return ev


@pytest.mark.parametrize("where", ["same_thread", "bound_thread",
                                   "error_exit"])
def test_labels_given_before_the_close_land_in_the_one_event(where):
    if where == "bound_thread":
        # the span `bind` opens on the other thread takes that thread's
        # account at its close
        with tracing.span("iteration"):
            fn = tracing.bind(lambda: None, "unit", early=1)
        t = threading.Thread(target=fn)
        t.start()
        t.join(10)
        assert not t.is_alive()
    else:
        with pytest.raises(RuntimeError) if where == "error_exit" \
                else contextlib.nullcontext():
            with tracing.span("unit", early=1) as handle:
                handle.labels["late"] = 7
                assert tracing.current() == handle
                assert tracing.ambient("early") == 1
                if where == "error_exit":
                    raise RuntimeError("x")
    ev = _one_event("unit")
    assert ev["ph"] == "X" and ev["args"]["early"] == 1
    if where == "bound_thread":
        assert ev["args"]["cpu_ns"] >= 0
    else:
        assert ev["args"]["late"] == 7
    assert ev["args"].get("status") == \
        ("error" if where == "error_exit" else None)
    assert tracing.current() is None and tracing.ambient("early") is None


def test_late_labels_are_stats_of_the_profilers_event(tmp_path):
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("unit", early=1) as handle:
            handle.account()
            handle.labels["verdict"] = "gc"
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    found = [dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "lgbm/unit"]
    assert len(found) == 1              # the name stays bare: one event
    assert found[0]["early"] == 1 and found[0]["verdict"] == "gc"
    assert found[0]["cpu_ns"] == _one_event("unit")["args"]["cpu_ns"]


@pytest.mark.parametrize("process", [False, True])
def test_account_says_what_the_thread_and_the_process_did(process):
    with tracing.span("busy") as handle:
        handle.account(process=process)
        t_end = time.thread_time() + 0.02
        while time.thread_time() < t_end:
            pass
        time.sleep(0.03)
    args = _one_event("busy")["args"]
    wall_ns = _one_event("busy")["dur"] * 1e3
    assert 19e6 <= args["cpu_ns"] <= wall_ns - 25e6     # it slept too
    if os.path.exists("/proc/thread-self/schedstat"):
        assert 0 <= args["runq_ns"] <= wall_ns
    assert {"sys_ns", "minflt", "majflt", "nivcsw"} <= set(args) \
        if process else "minflt" not in args
    # the thread's file stays open between spans: one descriptor
    stat = tracing._tls.schedstat
    with tracing.span("again") as handle:
        handle.account()
    assert tracing._tls.schedstat is stat


@pytest.mark.parametrize("thread", ["main", "other"])
def test_a_collection_is_a_span_on_the_collecting_thread(thread):
    seen = {}

    def collect():
        with tracing.span("outer") as handle:
            seen["outer"] = handle
            seen["tid"] = threading.get_ident()
            gc.collect(1)
    if thread == "main":
        collect()
    else:
        t = threading.Thread(target=collect)
        t.start()
        t.join(10)
        assert not t.is_alive()
    evs = [e for e in tracing.export_chrome()["traceEvents"]
           if e["name"] == "host/gc"
           and e["args"].get("parent") == seen["outer"][1]]
    assert [e["args"]["generation"] for e in evs] == [1]
    assert evs[0]["tid"] == seen["tid"] and evs[0]["ph"] == "X"
    assert evs[0]["args"]["collected"] >= 0
    assert evs[0]["args"]["trace"] == seen["outer"][0]


def test_no_collection_span_and_no_callback_with_the_recorder_off():
    assert tracing._on_gc in gc.callbacks
    prev = tracing.set_enabled(False)
    try:
        assert tracing._on_gc not in gc.callbacks
        gc.collect()
    finally:
        tracing.set_enabled(prev)
    assert (tracing._on_gc in gc.callbacks) == prev
    assert gc.callbacks.count(tracing._on_gc) <= 1
    assert not [e for e in tracing.export_chrome()["traceEvents"]
                if e["name"] == "host/gc"]


def test_since_a_mark_is_what_was_recorded_after_it():
    with tracing.span("before"):
        pass
    place = tracing.mark()
    assert tracing.since(place) == []
    gc.disable()
    try:
        with tracing.span("a"):
            tracing.instant("b")
        names = [e["name"] for e in tracing.since(place)]
    finally:
        gc.enable()
    assert names == ["b", "a"]          # in the order they were recorded


def test_runtime_package_and_tracing_load_no_jax():
    """`tracing` takes the annotation class from `sys.modules` and does
    without it when jax is not loaded.  The top-level package imports
    jax, so the check stubs it and imports the runtime alone."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('lightgbm_tpu')\n"
        "pkg.__path__ = [%r]\n"
        "sys.modules['lightgbm_tpu'] = pkg\n"
        "import lightgbm_tpu.runtime.tracing as tracing\n"
        "assert 'jax' not in sys.modules, 'import loaded jax'\n"
        "with tracing.span('x'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'span loaded jax'\n"
        "events = tracing.export_chrome()['traceEvents']\n"
        "assert 'x' in [e['name'] for e in events if e['ph'] == 'X']\n"
        % os.path.join(root, "lightgbm_tpu"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr

