"""Fault-tolerant serving runtime (ISSUE 7).

Layers under test:

* runtime/serving.py — admission control + backpressure (bounded queue,
  explicit machine-readable retryable rejections, per-request
  deadlines), micro-batching, the device->host circuit breaker with
  probe-based recovery, zero-drop hot model swap from the PR 6 publish
  seam, multi-model tenancy, and the TCP front end;
* models/device_predictor.py — the micro-batch boundary seam (fault
  injection point + batch-composition invariance, which the chaos
  soak's byte-identity ledger builds on);
* runtime/resilience.py — the serving faults (die_at_predict /
  slow_predict), the thread-mode watchdog, and the FAULT_TABLE <->
  docs/RESILIENCE.md drift pin;
* the ADVERSARIAL pin (exp/chaos_serve.py, shared implementation): the
  tier-1 quick soak plus the slow full soak (the CHAOS_SERVE_r07.json
  acceptance artifact).
"""
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

from lightgbm_tpu.runtime import publish, resilience
from lightgbm_tpu.runtime.serving import (ServeRejected, ServingRuntime,
                                          ServingServer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "exp"))

import chaos_serve  # noqa: E402


def _synth_model(n_trees=16, num_leaves=15, n_feat=6, seed=1):
    """Serving-shape ensemble built directly (no training run)."""
    from bench import synth_serving_model
    return synth_serving_model(n_trees, num_leaves, n_feat,
                               seed=seed).save_model_to_string()


def _booster(text):
    from lightgbm_tpu.basic import Booster
    return Booster(model_str=text)


@pytest.fixture()
def clean_fault_env():
    old = os.environ.pop("LGBM_TPU_FAULT", None)
    yield
    if old is None:
        os.environ.pop("LGBM_TPU_FAULT", None)
    else:
        os.environ["LGBM_TPU_FAULT"] = old


# ---------------------------------------------------------------------------
# the quick serve smoke (tier-1 acceptance): concurrent clients, one hot
# swap, zero drops
# ---------------------------------------------------------------------------

def test_serve_smoke_concurrent_clients_hot_swap_zero_drops(tmp_path):
    """N concurrent clients against a live runtime; generation 2 is
    published mid-load.  Every request must complete or be explicitly
    rejected (zero drops), every response must be byte-identical to
    offline Booster.predict for the generation it reports, and
    post-swap responses must match the NEW generation exactly."""
    pub = publish.ModelPublisher(str(tmp_path / "pub"), keep_last=0)
    t1, t2 = _synth_model(seed=1), _synth_model(seed=2)
    pub.publish(t1, meta={"cycle": 1})
    rng = np.random.default_rng(0)
    probe = rng.standard_normal((48, 6))
    refs = {1: _booster(t1).predict(probe, device=True),
            2: _booster(t2).predict(probe, device=True)}

    outcomes = {"completed": 0, "rejected": 0}
    mismatches, errors, gens = [], [], []
    lock = threading.Lock()
    with ServingRuntime(publish_dir=str(tmp_path / "pub"),
                        poll_interval_s=0.03,
                        batch_window_s=0.002) as rt:
        swap_evt = threading.Event()

        def client(seed):
            crng = np.random.default_rng(seed)
            for k in range(30):
                idx = crng.integers(0, len(probe), size=3)
                try:
                    rec = rt.predict(probe[idx])
                except ServeRejected:
                    with lock:
                        outcomes["rejected"] += 1
                    continue
                except BaseException as e:   # noqa: BLE001 — ledger
                    errors.append(str(e))
                    continue
                with lock:
                    outcomes["completed"] += 1
                    gens.append(rec.generation)
                if not np.array_equal(rec.values,
                                      refs[rec.generation][idx]):
                    mismatches.append(rec.generation)
                if k == 10 and seed == 100:
                    pub.publish(t2, meta={"cycle": 2})
                    swap_evt.set()
                if k > 10:
                    swap_evt.wait(5)

        threads = [threading.Thread(target=client, args=(100 + i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

        # post-swap: responses must report generation 2 and match it
        deadline = time.monotonic() + 10
        while rt.generation() != 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        rec = rt.predict(probe[:5])
        assert rec.generation == 2
        assert np.array_equal(rec.values, refs[2][:5])
        st = rt.stats()

    assert errors == []
    assert mismatches == []
    # zero drops: every admitted request is accounted for
    assert outcomes["completed"] == 4 * 30 - outcomes["rejected"]
    assert st["admitted"] == st["completed"] \
        + sum(st["rejected"].values()) - st["rejected"].get("shutdown", 0)
    assert set(gens) <= {1, 2}
    assert st["swaps"] >= 2          # initial load + the hot swap


def test_multi_model_tenancy(tmp_path):
    """Two lineages served from one runtime: requests carry model_id,
    responses carry the right generation and the right values."""
    pa = publish.ModelPublisher(str(tmp_path / "a"), keep_last=0)
    pb = publish.ModelPublisher(str(tmp_path / "b"), keep_last=0)
    ta, tb = _synth_model(seed=5), _synth_model(seed=6, n_trees=20)
    pa.publish(ta, meta={})
    pb.publish(tb, meta={})
    probe = np.random.default_rng(2).standard_normal((16, 6))
    ra = _booster(ta).predict(probe, device=True)
    rb = _booster(tb).predict(probe, device=True)
    with ServingRuntime(models={"a": str(tmp_path / "a"),
                                "b": str(tmp_path / "b")},
                        poll_interval_s=0.05) as rt:
        got_a = rt.predict(probe, model_id="a")
        got_b = rt.predict(probe, model_id="b")
        assert np.array_equal(got_a.values, ra)
        assert np.array_equal(got_b.values, rb)
        with pytest.raises(ServeRejected) as ei:
            rt.predict(probe, model_id="nope", attempts=1)
        assert ei.value.reason == "no_model" and ei.value.retryable


# ---------------------------------------------------------------------------
# degradation chain
# ---------------------------------------------------------------------------

def test_die_at_predict_degrades_to_host_and_recovers(tmp_path,
                                                      clean_fault_env):
    """Acceptance pin: with die_at_predict armed the server answers
    from the host-predictor fallback (degradation_event in the stage
    trail) instead of erroring out, and recovers to the device path
    when the fault clears."""
    text = _synth_model(seed=3)
    probe = np.random.default_rng(1).standard_normal((8, 6))
    ref_host = _booster(text).predict(probe)
    ref_dev = _booster(text).predict(probe, device=True)
    report = str(tmp_path / "trail.json")
    with ServingRuntime(model_str=text, breaker_cooldown_s=0.2,
                        predict_deadline_s=5.0, batch_window_s=0.0,
                        report_path=report) as rt:
        assert rt.predict(probe).served_by == "device"
        os.environ["LGBM_TPU_FAULT"] = "die_at_predict:1"
        rec = rt.predict(probe)
        assert rec.served_by == "host"
        assert np.array_equal(rec.values, ref_host)
        assert rt.degradation_events \
            and rt.degradation_events[0]["event"] == "serving_degradation"
        # breaker open: no device attempt, still answering
        assert rt.predict(probe).served_by == "host"
        # fault clears -> probe-based recovery after the cooldown
        del os.environ["LGBM_TPU_FAULT"]
        time.sleep(0.3)
        rec = rt.predict(probe)
        assert rec.served_by == "device"
        assert np.array_equal(rec.values, ref_dev)
        assert rt.recovery_events \
            and rt.recovery_events[0]["event"] == "serving_recovery"
    # the degradation event is in the persisted serving stage trail
    trail = json.load(open(report))
    assert any("degradation_event" in st for st in trail["stages"])


def test_slow_predict_times_out_into_trail_and_host_serves(
        clean_fault_env):
    """A HUNG device batch (slow_predict past the predict deadline) is
    abandoned: the stage trail records the timeout with all-thread
    tracebacks, the batch is re-served from the host path, and the
    caller never waits for the stall to finish."""
    text = _synth_model(seed=4)
    probe = np.random.default_rng(3).standard_normal((6, 6))
    ref_host = _booster(text).predict(probe)
    with ServingRuntime(model_str=text, breaker_cooldown_s=10.0,
                        predict_deadline_s=0.3,
                        batch_window_s=0.0) as rt:
        assert rt.predict(probe).served_by == "device"
        os.environ["LGBM_TPU_FAULT"] = "slow_predict:2.5"
        t0 = time.monotonic()
        rec = rt.predict(probe)
        dt = time.monotonic() - t0
        assert rec.served_by == "host"
        assert np.array_equal(rec.values, ref_host)
        assert dt < 2.0, "caller waited for the stalled dispatch (%.2fs)" % dt
        assert any(st.get("status") == "timeout" for st in rt.wd.stages)
        assert rt.wd.tracebacks is not None
        assert isinstance(rt.degradation_events[0]["reason"], str)
        del os.environ["LGBM_TPU_FAULT"]


# ---------------------------------------------------------------------------
# admission control + backpressure
# ---------------------------------------------------------------------------

def test_queue_full_sheds_with_machine_readable_retryable_rejection(
        clean_fault_env):
    """Overload sheds AT ADMISSION with an explicit retryable rejection
    — and the queued requests still complete (zero drops)."""
    text = _synth_model(seed=7)
    probe = np.random.default_rng(4).standard_normal((4, 6))
    os.environ["LGBM_TPU_FAULT"] = "slow_predict:0.8"
    with ServingRuntime(model_str=text, max_queue=2,
                        predict_deadline_s=0.3, breaker_cooldown_s=30.0,
                        batch_window_s=0.0) as rt:
        reqs, rejected = [], []
        for _ in range(8):
            try:
                reqs.append(rt.submit(probe, deadline_s=20.0))
            except ServeRejected as e:
                rejected.append(e)
        assert rejected, "bounded queue never shed"
        for e in rejected:
            assert e.retryable is True
            d = e.to_dict()
            assert d["error"] == "rejected" and d["reason"] == "queue_full"
            assert isinstance(d["queue_depth"], int) and "wallclock" in d
        del os.environ["LGBM_TPU_FAULT"]
        # every ADMITTED request completes — host fallback serves them
        for r in reqs:
            rec = r.wait(timeout=30)
            assert rec.values.shape[0] == probe.shape[0]


def test_expired_requests_are_shed_not_served(clean_fault_env):
    """A request whose deadline passes before its batch forms is shed
    with a deadline rejection — no work is spent on an answer nobody is
    waiting for."""
    text = _synth_model(seed=8)
    probe = np.random.default_rng(5).standard_normal((4, 6))
    os.environ["LGBM_TPU_FAULT"] = "slow_predict:0.6"
    with ServingRuntime(model_str=text, predict_deadline_s=0.25,
                        breaker_cooldown_s=30.0,
                        batch_window_s=0.0) as rt:
        blocker = rt.submit(probe, deadline_s=20.0)   # occupies the batcher
        time.sleep(0.1)       # the blocker's batch is now in flight
        doomed = rt.submit(probe, deadline_s=0.01)
        with pytest.raises(ServeRejected) as ei:
            doomed.wait(timeout=10)
        assert ei.value.reason == "deadline_exceeded"
        assert ei.value.retryable is True
        del os.environ["LGBM_TPU_FAULT"]
        blocker.wait(timeout=30)                      # zero drops


def test_stopped_runtime_rejects_nonretryably(tmp_path):
    text = _synth_model(seed=9)
    rt = ServingRuntime(model_str=text).start()
    rt.stop()
    with pytest.raises(ServeRejected) as ei:
        rt.submit(np.zeros(6))
    assert ei.value.reason == "shutdown" and ei.value.retryable is False


# ---------------------------------------------------------------------------
# device_predictor batch-boundary seam
# ---------------------------------------------------------------------------

def test_device_predictor_batch_hook_fires_per_microbatch():
    from lightgbm_tpu.models.device_predictor import DevicePredictor
    bst = _booster(_synth_model(seed=10))
    dp = DevicePredictor(bst._model, batch_rows=64)
    X = np.random.default_rng(6).standard_normal((200, 6)).astype(np.float32)
    calls = []
    dp.predict_raw(X, batch_hook=lambda i, n: calls.append((i, n)))
    assert calls == [(0, 4), (1, 4), (2, 4), (3, 4)]


def test_device_predict_is_batch_composition_invariant():
    """Per-row device outputs must not depend on which batch a row rides
    in — the invariance the serving runtime's micro-batching and the
    chaos soak's byte-identity ledger are built on."""
    bst = _booster(_synth_model(seed=11, n_trees=24))
    X = np.random.default_rng(7).standard_normal((120, 6))
    full = bst.predict(X, device=True)
    assert np.array_equal(full[:37], bst.predict(X[:37], device=True))
    one = np.concatenate([np.atleast_1d(bst.predict(X[i:i + 1],
                                                    device=True))
                          for i in range(9)])
    assert np.array_equal(full[:9], one)


# ---------------------------------------------------------------------------
# subscriber under concurrent swap + pruning (PR 6 pins, consumer side)
# ---------------------------------------------------------------------------

def test_subscriber_concurrent_publish_prune_never_torn(tmp_path):
    """A reader resolving generation N while keep-last-K pruning and a
    publisher land N+1/N+2 must never observe a torn read: every
    resolution is valid, deep-parses with the real model loader, and
    generations never move backwards."""
    from lightgbm_tpu.models.gbdt_model import GBDTModel
    d = str(tmp_path / "pub")
    texts = {g: _synth_model(seed=g, n_trees=4 + g) for g in range(1, 13)}
    pub = publish.ModelPublisher(d, keep_last=1, grace_s=0.0)
    pub.publish(texts[1], meta={})
    stop = threading.Event()
    seen, problems = [], []

    def reader():
        sub = publish.ModelSubscriber(d, attempts=1)
        last = 0
        while not stop.is_set():
            rec = sub.resolve_once()
            if rec is None:
                continue
            if rec.generation < last:
                problems.append("generation went backwards: %d -> %d"
                                % (last, rec.generation))
            last = rec.generation
            if rec.model_text != texts.get(rec.generation):
                problems.append("gen %d bytes differ" % rec.generation)
            try:
                m = GBDTModel.load_model_from_string(rec.model_text)
                assert m.current_iteration > 0
            except Exception as e:       # noqa: BLE001 — ledger
                problems.append("gen %d torn: %s" % (rec.generation, e))
            seen.append(rec.generation)

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    # keep_last=1 + grace 0: every publish prunes the PREVIOUS newest
    # while readers hammer it — the read-then-validate-in-one-pass
    # contract is what keeps this safe
    for g in range(2, 13):
        pub.publish(texts[g], meta={})
        time.sleep(0.02)
    time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert problems == []
    assert seen and max(seen) == 12


# ---------------------------------------------------------------------------
# fault table <-> docs <-> parser drift pin (satellite)
# ---------------------------------------------------------------------------

def test_fault_table_is_the_single_registry():
    """The parser accepts exactly FAULT_TABLE's names (serving faults
    included), and the docs/RESILIENCE.md injection matrix has exactly
    one row per table entry — the three surfaces cannot drift."""
    assert resilience.FAULT_NAMES == tuple(resilience.FAULT_TABLE)
    for name in ("die_at_predict", "slow_predict"):
        assert name in resilience.FAULT_TABLE
    # parser side: every registered name parses; unknown names raise
    old = os.environ.get("LGBM_TPU_FAULT")
    try:
        for name in resilience.FAULT_TABLE:
            os.environ["LGBM_TPU_FAULT"] = name
            assert resilience.fault_active(name)
        os.environ["LGBM_TPU_FAULT"] = "definitely_not_a_fault"
        with pytest.raises(ValueError):
            resilience.fault_active("die_at_iter")
    finally:
        if old is None:
            os.environ.pop("LGBM_TPU_FAULT", None)
        else:
            os.environ["LGBM_TPU_FAULT"] = old
    # docs side: one matrix row per fault, no undocumented faults, no
    # documented-but-unregistered faults
    doc = open(os.path.join(REPO, "docs", "RESILIENCE.md")).read()
    table_rows = [ln for ln in doc.splitlines()
                  if ln.startswith("| `") and "`" in ln[3:]]
    documented = {ln[3:].split("`", 1)[0].split(":")[0].split("[")[0]
                  for ln in table_rows}
    assert documented == set(resilience.FAULT_TABLE), (
        "docs/RESILIENCE.md injection matrix drifted from "
        "resilience.FAULT_TABLE: docs-only %r, table-only %r"
        % (documented - set(resilience.FAULT_TABLE),
           set(resilience.FAULT_TABLE) - documented))


# ---------------------------------------------------------------------------
# thread-mode watchdog (the serving flight recorder)
# ---------------------------------------------------------------------------

def test_watchdog_thread_mode_keep_last_and_record_timeout(tmp_path):
    report = str(tmp_path / "wd.json")
    wd = resilience.Watchdog(5, use_alarm=False, keep_last=3,
                             report_path=report, stream=sys.stderr)
    out = []

    def worker():
        for i in range(5):
            wd("stage %d" % i)
        wd.record_timeout(note="owner-enforced deadline")
        out.append(wd.report())

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=10)
    rep = out[0]
    assert len(rep["stages"]) == 3 and rep["dropped_stages"] == 2
    assert rep["stages"][-1]["status"] == "timeout"
    assert rep["stages"][-1]["note"] == "owner-enforced deadline"
    assert rep["culprit"] == "stage 4"
    assert "tracebacks" in rep
    assert json.load(open(report))["culprit"] == "stage 4"


# ---------------------------------------------------------------------------
# TCP front end (task=serve)
# ---------------------------------------------------------------------------

def test_serving_server_tcp_roundtrip():
    text = _synth_model(seed=12)
    probe = np.random.default_rng(8).standard_normal((3, 6))
    with ServingRuntime(model_str=text, batch_window_s=0.0) as rt:
        srv = ServingServer(rt)      # port 0 -> ephemeral
        th = threading.Thread(target=srv.serve_forever, daemon=True)
        th.start()
        try:
            with socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=10) as s:
                f = s.makefile("rw")
                f.write(json.dumps({"features": probe.tolist()}) + "\n")
                f.flush()
                resp = json.loads(f.readline())
                assert resp["generation"] == 0
                assert resp["served_by"] in ("device", "host")
                ref = _booster(text).predict(
                    probe, device=resp["served_by"] == "device")
                assert np.allclose(resp["values"], ref, rtol=0, atol=0)
                f.write(json.dumps({"cmd": "stats"}) + "\n")
                f.flush()
                st = json.loads(f.readline())
                assert st["completed"] >= 1 and "breaker" in st
                f.write("not json\n")
                f.flush()
                err = json.loads(f.readline())
                assert err["error"] == "bad_request"
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# chaos soaks (shared implementation with exp/chaos_serve.py)
# ---------------------------------------------------------------------------

def test_quick_chaos_serve_soak(tmp_path, clean_fault_env):
    """Tier-1-sized slice of the acceptance soak: randomized device
    kill/stall + publish churn under concurrent clients -> zero torn or
    wrong-generation responses, every completed response byte-identical
    to offline Booster.predict for its generation."""
    rec = chaos_serve.run_soak(str(tmp_path), generations=4, rounds=2,
                               clients=3, seed=5, step_s=0.25)
    assert rec["ok"], rec
    assert rec["wrong_generation_responses"] == 0
    assert rec["mismatched_responses"] == []
    assert rec["non_machine_readable_rejections"] == 0
    assert rec["requests_completed"] > 0


@pytest.mark.slow
def test_full_chaos_serve_soak(tmp_path, clean_fault_env):
    """The full acceptance soak (the CHAOS_SERVE_r07.json schema)."""
    rec = chaos_serve.run_soak(str(tmp_path), generations=12, clients=6,
                               seed=11)
    assert rec["ok"], rec
    assert rec["degradations"] > 0 and rec["recoveries"] > 0
    assert rec["served_by"]["host"] > 0 and rec["served_by"]["device"] > 0


# ---------------------------------------------------------------------------
# binary wire data plane (ISSUE 16): zero-copy frames over TCP + UDS
# ---------------------------------------------------------------------------

def _wire_pair(rt, tmp_path):
    from lightgbm_tpu.runtime import wire
    srv = wire.WireTCPServer(rt, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    uds_path = str(tmp_path / "wire.sock")
    usrv = wire.WireUnixServer(rt, uds_path)
    threading.Thread(target=usrv.serve_forever, daemon=True).start()
    return srv, usrv, uds_path


def test_wire_roundtrip_matches_json_path_byte_for_byte(tmp_path):
    """The tentpole parity gate: the same probe through the JSON front
    end and through both binary sockets must yield the same float32
    bytes, with generation + stage partitions carried on every path."""
    from lightgbm_tpu.runtime import wire
    text = _synth_model(seed=13)
    probe = np.random.default_rng(9).standard_normal((5, 6)).astype(
        np.float32)
    with ServingRuntime(model_str=text, batch_window_s=0.0,
                        response_dtype="float32") as rt:
        jsrv = ServingServer(rt)
        threading.Thread(target=jsrv.serve_forever, daemon=True).start()
        srv, usrv, uds_path = _wire_pair(rt, tmp_path)
        try:
            with socket.create_connection(("127.0.0.1", jsrv.port),
                                          timeout=10) as s:
                f = s.makefile("rw")
                f.write(json.dumps({"features": probe.tolist()}) + "\n")
                f.flush()
                jresp = json.loads(f.readline())
            jvals = np.asarray(jresp["values"], np.float32)
            for address in (("127.0.0.1", srv.port), uds_path):
                with wire.WireClient(address) as c:
                    out = c.predict(probe)
                assert out["generation"] == jresp["generation"]
                assert out["served_by"] in ("device", "host")
                assert set(out["stages"]) == {"queue_wait_s",
                                              "batch_gather_s",
                                              "device_s", "drain_s"}
                assert out["values"].dtype == np.float32
                got = out["values"].reshape(jvals.shape)
                assert np.array_equal(got, jvals), address
        finally:
            for s2 in (jsrv, srv, usrv):
                s2.shutdown()
                s2.server_close()


def test_wire_torn_frames_reject_machine_readably(tmp_path):
    """Torn input never hangs the server or triggers an unbounded read:
    every malformed frame class yields a machine-readable rejection
    frame, and only an intact-boundary CRC failure keeps the
    connection; the rest close it."""
    import struct
    import zlib
    from lightgbm_tpu.runtime import wire
    text = _synth_model(seed=14)
    with ServingRuntime(model_str=text, batch_window_s=0.0) as rt:
        srv, usrv, uds_path = _wire_pair(rt, tmp_path)

        def raw():
            s = socket.create_connection(("127.0.0.1", srv.port),
                                         timeout=10)
            return s, s.makefile("rb")

        def read_reject(rf):
            frame = wire.read_frame(rf)
            assert frame is not None
            hdr, payload = frame
            rej = wire.unpack_response(hdr, payload)
            assert rej.get("error") == "rejected"
            return rej
        try:
            # truncated header: reject then close
            s, rf = raw()
            s.sendall(wire.pack_request(np.zeros((1, 6), np.float32))[:17])
            s.shutdown(socket.SHUT_WR)
            rej = read_reject(rf)
            assert rej["reason"] == "truncated_header"
            assert rej["retryable"] is True
            assert rf.read(1) == b""      # server closed the connection
            s.close()

            # short payload: reject then close
            s, rf = raw()
            good = wire.pack_request(np.ones((2, 6), np.float32))
            s.sendall(good[:-8])
            s.shutdown(socket.SHUT_WR)
            rej = read_reject(rf)
            assert rej["reason"] == "short_payload"
            assert rf.read(1) == b""
            s.close()

            # bad CRC: frame boundary intact -> reject, connection LIVES
            s, rf = raw()
            bad = bytearray(wire.pack_request(np.ones((2, 6), np.float32)))
            bad[-1] ^= 0xFF
            s.sendall(bytes(bad))
            rej = read_reject(rf)
            assert rej["reason"] == "bad_crc" and rej["retryable"] is True
            s.sendall(good)               # same connection still serves
            frame = wire.read_frame(rf)
            assert frame is not None
            out = wire.unpack_response(*frame)
            assert "values" in out and out["values"].shape == (2, 1)
            s.close()

            # oversized row count: rejected from the header alone,
            # BEFORE any payload-sized read can be provoked
            s, rf = raw()
            hdr = wire.pack_header(wire.MSG_REQUEST, "default",
                                   n_rows=2 ** 31, n_cols=6,
                                   payload=b"\0" * 24)
            s.sendall(hdr + b"\0" * 24)
            rej = read_reject(rf)
            assert rej["reason"] == "oversized"
            assert rej["retryable"] is True
            assert rf.read(1) == b""
            s.close()

            # bad magic: not our protocol, reject + close
            s, rf = raw()
            s.sendall(b"GET / HTTP/1.1\r\n" + b"\0" * 64)
            rej = read_reject(rf)
            assert rej["reason"] == "bad_magic"
            s.close()
        finally:
            for s2 in (srv, usrv):
                s2.shutdown()
                s2.server_close()


def test_wire_reject_frames_carry_backoff_hints():
    """Binary rejections carry the same Retry-After-style hint the JSON
    path reports, and predict()-style retry loops honor it."""
    from lightgbm_tpu.runtime import wire
    from lightgbm_tpu.runtime.serving import retry_delay
    frame = wire.pack_reject("queue_full", retryable=True,
                             retry_after_s=0.25)
    hdr, body = wire.read_frame(__import__("io").BytesIO(frame))
    rej = wire.unpack_response(hdr, body)
    assert rej["reason"] == "queue_full"
    assert rej["retryable"] is True
    assert rej["retry_after_s"] == pytest.approx(0.25)
    # the hint only ever LENGTHENS the client's own schedule
    assert retry_delay(0.05, rej["retry_after_s"]) == pytest.approx(0.25)
    assert retry_delay(0.5, rej["retry_after_s"]) == pytest.approx(0.5)
    assert retry_delay(0.5, None) == pytest.approx(0.5)
    # and the runtime's shed rejections actually carry one
    e = ServeRejected("queue_full", retryable=True, retry_after_s=0.05)
    assert e.to_dict()["retry_after_s"] == pytest.approx(0.05)


def test_submit_view_serves_f32_without_conversion(tmp_path):
    """submit_view() admits a float32 view as-is (no f64 copy) and the
    batcher's gather arena is reused across batches rather than
    reallocated per request."""
    text = _synth_model(seed=15)
    probe = np.random.default_rng(10).standard_normal((4, 6)).astype(
        np.float32)
    with ServingRuntime(model_str=text, batch_window_s=0.0) as rt:
        ref = np.asarray(rt.predict(np.asarray(probe, np.float64)).values)
        rec = rt.submit_view(probe).wait(timeout=30)
        assert np.allclose(np.asarray(rec.values, np.float64), ref,
                           rtol=1e-6, atol=1e-7)
        # arena reuse: same (bucket, cols, dtype) key -> same buffer
        class _Req:
            def __init__(self, X):
                self.X = X
                self.n_rows = X.shape[0]
        b1 = [_Req(probe[:2]), _Req(probe[2:])]
        g1 = rt._gather_batch(b1)
        base1 = g1.base if g1.base is not None else g1
        g2 = rt._gather_batch(b1)
        base2 = g2.base if g2.base is not None else g2
        assert base1 is base2
        assert g1.dtype == np.float32


def test_wire_response_scratch_parity_and_zero_allocation():
    """The ISSUE 17 response-path perf fix: `_ResponseScratch` must emit
    byte-identical frames to module-level `pack_response` (f32 fast
    path, f64 legacy cast, growth, reuse-after-growth) while never
    allocating per response — the SAME bytearray backs every same-bucket
    frame and f64 values cast into a reused per-bucket arena."""
    from lightgbm_tpu.runtime import wire
    rng = np.random.default_rng(21)
    scratch = wire._ResponseScratch()
    stages = {"queue_wait_s": 0.001, "batch_gather_s": 0.0002,
              "device_s": 0.003, "drain_s": 0.0001}
    cases = [
        # (values, generation, model_id, served_by, compiled)
        (rng.standard_normal((4, 1)).astype(np.float32), 3, "default",
         "device", True),                       # f32 fast path (no cast)
        (rng.standard_normal((4, 1)), 3, "default", "device", True),
        (rng.standard_normal((7, 3)), 12, "tenant-042", "host", False),
        (rng.standard_normal(5), 1, "default", "device", False),  # 1-D
        (rng.standard_normal((700, 4)), 2, "big", "device", True),  # grow
        (rng.standard_normal((2, 2)), 9, "default", "host", True),  # after
    ]
    for vals, gen, mid, by, compiled in cases:
        want = wire.pack_response(vals, gen, mid, by, 0.0125, stages,
                                  compiled)
        got = bytes(scratch.pack_response(vals, gen, mid, by, 0.0125,
                                          stages, compiled))
        assert got == want, (vals.shape, vals.dtype)

    # zero per-response allocations, leg 1: once sized, the SAME
    # bytearray backs every same-bucket response (no growth => no alloc)
    buf = scratch._buf
    small = rng.standard_normal((8, 2))
    for _ in range(200):
        scratch.pack_response(small, 5, "default", "device", 0.001,
                              stages, True)
        assert scratch._buf is buf
    # leg 2: f64 values cast into a REUSED per-bucket float32 arena
    arenas = dict(scratch._f32)
    for _ in range(50):
        scratch.pack_response(small, 5, "default", "device", 0.001,
                              stages, True)
    assert dict(scratch._f32) == arenas          # no new arenas...
    for bucket, arr in scratch._f32.items():     # ...same objects
        assert arenas[bucket] is arr
    # leg 3: f32 C-contiguous values bypass the arena entirely
    f32 = np.ascontiguousarray(small, np.float32)
    out = scratch._as_f32(f32)
    assert out is f32
    # growth is power-of-two bucketed (amortized, never per response)
    scratch.pack_response(rng.standard_normal((4096, 8)), 1, "default",
                          "device", 0.0, stages, True)
    grown = scratch._buf
    assert grown is not buf and len(grown) & (len(grown) - 1) == 0
    scratch.pack_response(rng.standard_normal((4096, 8)), 1, "default",
                          "device", 0.0, stages, True)
    assert scratch._buf is grown


def test_wire_server_success_path_allocates_no_response_frames(
        tmp_path, monkeypatch):
    """The live-server pin behind the zero-allocation claim: with
    module-level `pack_response` booby-trapped, every successful wire
    response must still arrive — proving the handler serves success
    frames solely from its per-connection scratch (rejects still use
    `pack_reject`, which is off the per-response hot path)."""
    from lightgbm_tpu.runtime import wire
    text = _synth_model(seed=16)
    probe = np.random.default_rng(11).standard_normal((6, 6)).astype(
        np.float32)

    def _boom(*a, **k):
        raise AssertionError(
            "module-level pack_response reached from the server success "
            "path — the per-connection scratch must own it")
    monkeypatch.setattr(wire, "pack_response", _boom)
    with ServingRuntime(model_str=text, batch_window_s=0.0,
                        response_dtype="float32") as rt:
        ref = np.asarray(rt.predict(np.asarray(probe, np.float64),
                                    ).values)
        srv = wire.WireTCPServer(rt, port=0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            with wire.WireClient(("127.0.0.1", srv.port)) as c:
                for _ in range(8):
                    out = c.predict(probe)
                    assert np.array_equal(
                        out["values"].reshape(ref.shape), ref)
                # and a reject frame still works with the trap armed
                # (pack_reject is off the per-response hot path)
                rej = c.request_once(probe, model_id="no-such-tenant")
                assert rej.get("error") == "rejected"
        finally:
            srv.shutdown()
            srv.server_close()


# ---------------------------------------------------------------------------
# stale UDS path reclamation (ISSUE 20 satellite): kill-and-relaunch
# ---------------------------------------------------------------------------

_UDS_HOLDER = """
import socket, sys
s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
s.bind(sys.argv[1])
s.listen(8)
print("ready", flush=True)
import time; time.sleep(120)
"""


def test_wire_uds_rebinds_over_stale_path_after_kill(tmp_path):
    """A replica SIGKILLed mid-serve leaves its socket FILE behind; the
    relaunch must probe-connect, see nobody listening, unlink the stale
    inode and bind — not die on EADDRINUSE."""
    import signal
    import subprocess
    from lightgbm_tpu.runtime import wire
    path = str(tmp_path / "replica.sock")
    proc = subprocess.Popen([sys.executable, "-c", _UDS_HOLDER, path],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert os.path.exists(path)          # the stale inode SIGKILL left
    text = _synth_model(seed=41)
    probe = np.random.default_rng(12).standard_normal((4, 6)).astype(
        np.float32)
    with ServingRuntime(model_str=text, batch_window_s=0.0,
                        response_dtype="float32") as rt:
        usrv = wire.WireUnixServer(rt, path)     # the relaunch
        threading.Thread(target=usrv.serve_forever, daemon=True).start()
        try:
            ref = np.asarray(rt.predict(
                np.asarray(probe, np.float64)).values)
            with wire.WireClient(path) as c:
                out = c.predict(probe)
            assert np.array_equal(out["values"].reshape(ref.shape), ref)
        finally:
            usrv.shutdown()
            usrv.server_close()


def test_wire_uds_refuses_to_unlink_live_server_path(tmp_path):
    """The other half of the stale-path contract: probe-connect
    SUCCEEDING means a live server owns the path, and the relaunch must
    fail loudly instead of yanking the socket out from under it."""
    from lightgbm_tpu.runtime import wire
    path = str(tmp_path / "live.sock")
    text = _synth_model(seed=42)
    with ServingRuntime(model_str=text, batch_window_s=0.0) as rt:
        usrv = wire.WireUnixServer(rt, path)
        threading.Thread(target=usrv.serve_forever, daemon=True).start()
        try:
            with pytest.raises(OSError, match="LIVE"):
                wire.WireUnixServer(rt, path)
            assert os.path.exists(path)  # the live socket survived
        finally:
            usrv.shutdown()
            usrv.server_close()


def test_start_reports_the_platform_and_never_rewrites_it(monkeypatch):
    """start() binds the platform jax is configured for, records it in
    stats(), and leaves JAX_PLATFORMS alone — no probe child, no
    degradation to another platform behind the caller's back."""
    import jax
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = dict(os.environ)
    with ServingRuntime(model_str=_synth_model(),
                        params={"verbose": -1}) as rt:
        platform = rt.stats()["platform"]
        rec = rt.predict(np.zeros((3, 6)))
    assert platform == {"platform": "cpu", "kind": jax.devices()[0]
                        .device_kind, "count": len(jax.devices())}
    assert rec.served_by == "device"
    assert os.environ["JAX_PLATFORMS"] == before["JAX_PLATFORMS"]
    assert not hasattr(rt, "probe_platform_on_start")
