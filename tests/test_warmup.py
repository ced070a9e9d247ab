"""Warm-start subsystem (ISSUE 15).

Layers under test:

* runtime/warmup.py — the persistent-compile-cache seam (a directory
  placed from outside through $JAX_COMPILATION_CACHE_DIR is left alone;
  otherwise one fixed in-checkout path, swept to a budget) and the
  checksummed shape manifest (merge semantics, torn/stale/mismatch classification);
* runtime/serving.py — prewarm-before-admit: a fresh runtime
  precompiles the manifest's row buckets BEFORE readiness opens, every
  failure mode degrades to the legacy smallest-bucket prewarm with a
  counted ``lgbm_warmup_total{outcome}``, and stop() exports the
  buckets this process actually compiled;
* runtime/telemetry.py — the /healthz readiness gate (503 "warming"
  until the health provider flips);
* runtime/publish.py — the manifest rides the publish dir as its own
  atomic non-generation file: pruning never touches it and concurrent
  readers can never observe it torn (pinned under publish/prune churn).
"""
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from lightgbm_tpu.runtime import publish, telemetry, warmup, xla_obs
from lightgbm_tpu.runtime.serving import ServingRuntime


def _synth_model(n_trees=12, num_leaves=15, n_feat=8, seed=1):
    from bench import synth_serving_model
    return synth_serving_model(n_trees, num_leaves, n_feat,
                               seed=seed).save_model_to_string()


def _warmup_count(kind, outcome):
    return telemetry.counter("lgbm_warmup_total").value(kind=kind,
                                                        outcome=outcome)


# ---------------------------------------------------------------------------
# manifest file semantics
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_and_section_merge(tmp_path):
    d = str(tmp_path)
    warmup.write_manifest(d, "serving",
                          warmup.build_serving_section(8, [64, 16], 3))
    warmup.write_manifest(
        d, "train_online",
        warmup.build_train_section({"num_leaves": 15}, 8, 3))
    sec, reason = warmup.read_manifest(d, "serving")
    assert reason == "ok" and sec["row_buckets"] == [16, 64]
    sec2, reason2 = warmup.read_manifest(d, "train_online")
    assert reason2 == "ok" and sec2["params_sig"]["num_leaves"] == 15
    # the file is checksummed and carries both sections
    doc = json.load(open(warmup.manifest_path(d)))
    assert set(doc["sections"]) == {"serving", "train_online"}
    assert doc["checksum"]


def test_manifest_missing_and_torn(tmp_path):
    d = str(tmp_path)
    sec, reason = warmup.read_manifest(d, "serving")
    assert sec is None and reason == "missing"
    # torn: unparseable bytes
    with open(warmup.manifest_path(d), "w") as fh:
        fh.write('{"schema_version": 1, "sections":')
    sec, reason = warmup.read_manifest(d, "serving")
    assert sec is None and reason == "torn"
    # torn: valid JSON, wrong checksum
    with open(warmup.manifest_path(d), "w") as fh:
        json.dump({"schema_version": 1, "sections": {"serving": {}},
                   "checksum": "0" * 64}, fh)
    sec, reason = warmup.read_manifest(d, "serving")
    assert sec is None and reason == "torn"


def test_classify_serving_outcomes():
    good = warmup.build_serving_section(8, [16, 64], 3)
    assert warmup.classify_serving_section(good, 8, 3) == "ok"
    # an OLD generation's manifest with matching width stays usable
    assert warmup.classify_serving_section(good, 8, 7) == "ok"
    # same generation, wrong width: the manifest itself is suspect
    assert warmup.classify_serving_section(good, 9, 3) == "shape_mismatch"
    # different generation AND wrong width: the lineage moved on
    assert warmup.classify_serving_section(good, 9, 7) == "manifest_stale"
    bad = dict(good, row_buckets=[])
    assert warmup.classify_serving_section(bad, 8, 3) == "manifest_invalid"
    bad = dict(good, row_buckets=[16, "x"])
    assert warmup.classify_serving_section(bad, 8, 3) == "manifest_invalid"


def test_classify_train_outcomes():
    params = {"objective": "binary", "num_leaves": 31}
    sec = warmup.build_train_section(params, 28, 2)
    assert warmup.classify_train_section(sec, params, 28) == "ok"
    assert warmup.classify_train_section(sec, params, 29) \
        == "shape_mismatch"
    assert warmup.classify_train_section(
        sec, {"objective": "binary", "num_leaves": 63}, 28) \
        == "shape_mismatch"
    assert warmup.classify_train_section({"kind": "train_online"},
                                         params, 28) == "manifest_invalid"


def test_concurrent_readers_never_observe_torn_manifest(tmp_path):
    """Readers racing a publisher that publishes + prunes + rewrites the
    manifest every generation must only ever see a valid manifest — the
    atomic-rename discipline, pinned (satellite: concurrent readers
    during publish pruning)."""
    d = str(tmp_path / "pub")
    pub = publish.ModelPublisher(d, keep_last=1, grace_s=0.0)
    text = _synth_model()
    pub.publish(text, meta={"cycle": 1})
    pub.publish_manifest("serving", warmup.build_serving_section(8, [16], 1))
    bad = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            sec, reason = warmup.read_manifest(d, "serving")
            if reason not in ("ok",):
                bad.append(reason)

    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    for gen in range(2, 14):
        pub.publish(text, meta={"cycle": gen})
        pub.publish_manifest(
            "serving", warmup.build_serving_section(8, [16, 64], gen))
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not bad, "readers observed a non-ok manifest: %r" % bad[:5]
    # pruning removed old generations but never the manifest
    assert os.path.exists(warmup.manifest_path(d))
    assert len(publish.generation_paths(d)) <= 2


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

def _restore_suite_cache():
    """Put the seam back the way conftest left it."""
    warmup._reset_for_tests()
    warmup.enable_compile_cache(min_compile_s=1.0)


def _record_config_updates(monkeypatch):
    """Swap jax.config.update for a recorder, so a test can see what the
    seam WOULD set without moving the suite's real cache."""
    import jax
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.__setitem__(key, value))
    return updates


def test_external_cache_dir_is_neither_set_nor_swept(tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR set: jax already reads it, so the seam
    sets no directory, creates nothing under it and never sweeps it."""
    ext = tmp_path / "placed_from_outside"
    ext.mkdir()
    for i, name in enumerate(("a", "b", "c")):
        p = ext / name
        p.write_bytes(b"\0" * (600 * 1024))
        os.utime(p, (1000 + i, 1000 + i))
    monkeypatch.setenv(warmup.JAX_CACHE_ENV, str(ext))
    updates = _record_config_updates(monkeypatch)
    warmup._reset_for_tests()
    try:
        assert warmup.enable_compile_cache() == str(ext)
        assert "jax_compilation_cache_dir" not in updates
        assert warmup.sweep_cache(budget_mb=1) == 0
        assert sorted(os.listdir(ext)) == ["a", "b", "c"]
        st = warmup.cache_status()
        assert st["dir"] == str(ext) and st["owned"] is False
        assert st["files"] == 3 and st["evictions"] == 0
    finally:
        monkeypatch.undo()
        _restore_suite_cache()


_CACHE_DIR_CHILD = (
    "import sys; sys.path.insert(0, %r)\n"
    "from lightgbm_tpu.runtime import warmup\n"
    "import jax\n"
    "d = warmup.enable_compile_cache()\n"
    "assert jax.config.jax_compilation_cache_dir == d\n"
    "print(d)\n")


def test_default_cache_dir_is_fixed_inside_the_checkout():
    """Unset: one fixed path inside the checkout, the same in every
    process — nothing of the host, the pid or the time in its name (the
    path is part of the cache key; a directory that moves never hits)."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert warmup.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    env = {k: v for k, v in os.environ.items() if k != warmup.JAX_CACHE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    seen = [subprocess.run([sys.executable, "-c", _CACHE_DIR_CHILD % repo],
                           env=env, capture_output=True, text=True,
                           timeout=120) for _ in range(2)]
    for r in seen:
        assert r.returncode == 0, r.stderr[-1000:]
    assert [r.stdout.strip() for r in seen] == [warmup.DEFAULT_CACHE_DIR] * 2


def test_owned_cache_sweep_evicts_oldest_past_budget(tmp_path, monkeypatch):
    # a scratch stand-in for the in-checkout directory; conftest already
    # enabled the shared cache, so force a re-enable
    monkeypatch.delenv(warmup.JAX_CACHE_ENV, raising=False)
    monkeypatch.setattr(warmup, "DEFAULT_CACHE_DIR", str(tmp_path / "own"))
    updates = _record_config_updates(monkeypatch)
    warmup._reset_for_tests()
    try:
        cdir = warmup.enable_compile_cache()
        assert cdir == str(tmp_path / "own") and os.path.isdir(cdir)
        assert updates["jax_compilation_cache_dir"] == cdir
        # 3 fake entries of ~0.6 MB: budget 1 MB keeps the newest one
        for i, name in enumerate(("a", "b", "c")):
            p = os.path.join(cdir, name)
            with open(p, "wb") as fh:
                fh.write(b"\0" * (600 * 1024))
            os.utime(p, (1000 + i, 1000 + i))
        assert warmup.sweep_cache(budget_mb=1) == 2
        assert sorted(os.listdir(cdir)) == ["c"]
        st = warmup.cache_status()
        assert st["owned"] and st["evictions"] >= 2 and st["files"] == 1
    finally:
        monkeypatch.undo()
        _restore_suite_cache()


# ---------------------------------------------------------------------------
# /healthz readiness gate
# ---------------------------------------------------------------------------

def test_healthz_warming_until_provider_flips():
    ready = threading.Event()
    srv = telemetry.start_http_server(0, health_provider=ready.is_set)
    try:
        url = "http://127.0.0.1:%d/healthz" % srv.port
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10)
        assert ei.value.code == 503
        assert ei.value.read() == b"warming\n"
        ready.set()
        assert urllib.request.urlopen(url, timeout=10).read() == b"ok\n"
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serving: prewarm-before-admit + export
# ---------------------------------------------------------------------------

def _serving_pub(tmp_path, n_feat=8):
    d = str(tmp_path / "pub")
    pub = publish.ModelPublisher(d, keep_last=0)
    pub.publish(_synth_model(n_feat=n_feat), meta={"cycle": 1})
    return d, pub


def test_prewarm_from_manifest_precompiles_buckets(tmp_path):
    d, pub = _serving_pub(tmp_path)
    pub.publish_manifest("serving",
                         warmup.build_serving_section(8, [16, 64], 1))
    base_ok = _warmup_count("serving", "manifest_ok")
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        poll_interval_s=0.05,
                        batch_window_s=0.001) as rt:
        assert rt.ready
        assert rt.prewarm_events[0]["outcome"] == "manifest_ok"
        assert rt.prewarm_events[0]["buckets"] == [16, 64]
        assert _warmup_count("serving", "manifest_ok") == base_ok + 1
        # the 64-row bucket is already compiled: a 50-row request (pads
        # to 64) is steady-state from request one — the zero-retrace pin
        # under the manifest-prewarm start mode
        before = len(xla_obs.LEDGER.retraces)
        xla_obs.mark_steady(True)
        try:
            rec = rt.predict(np.zeros((50, 8)))
        finally:
            xla_obs.mark_steady(False)
        assert rec.served_by == "device"
        assert len(xla_obs.LEDGER.retraces) == before, (
            "manifest-prewarmed bucket still compiled on first use")


def test_prewarm_degrades_on_torn_manifest(tmp_path):
    d, pub = _serving_pub(tmp_path)
    with open(warmup.manifest_path(d), "w") as fh:
        fh.write("{torn")
    base = _warmup_count("serving", "manifest_torn")
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001) as rt:
        assert rt.ready
        assert rt.prewarm_events[0]["outcome"] == "manifest_torn"
        assert _warmup_count("serving", "manifest_torn") == base + 1
        # legacy prewarm still serves
        rec = rt.predict(np.zeros((3, 8)))
        assert rec.generation == 1


def test_prewarm_degrades_on_shape_mismatch_and_stale(tmp_path):
    d, pub = _serving_pub(tmp_path)
    # same generation, wrong feature width -> shape_mismatch
    pub.publish_manifest("serving",
                         warmup.build_serving_section(9, [16], 1))
    base = _warmup_count("serving", "shape_mismatch")
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001) as rt:
        assert rt.prewarm_events[0]["outcome"] == "shape_mismatch"
        assert rt.predict(np.zeros((2, 8))).generation == 1
    assert _warmup_count("serving", "shape_mismatch") == base + 1
    # different generation AND wrong width -> manifest_stale
    pub.publish_manifest("serving",
                         warmup.build_serving_section(9, [16], 7))
    base = _warmup_count("serving", "manifest_stale")
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001) as rt:
        assert rt.prewarm_events[0]["outcome"] == "manifest_stale"
        assert rt.predict(np.zeros((2, 8))).generation == 1
    assert _warmup_count("serving", "manifest_stale") == base + 1


def test_prewarm_missing_manifest_counts_and_serves(tmp_path):
    d, _pub = _serving_pub(tmp_path)
    base = _warmup_count("serving", "manifest_missing")
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001) as rt:
        assert rt.prewarm_events[0]["outcome"] == "manifest_missing"
        assert rt.predict(np.zeros((2, 8))).generation == 1
    assert _warmup_count("serving", "manifest_missing") == base + 1


def test_stop_exports_observed_buckets(tmp_path):
    d, _pub = _serving_pub(tmp_path)
    rt = ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001)
    rt.start()
    rt.predict(np.zeros((50, 8)))      # bucket 64
    rt.stop()
    sec, reason = warmup.read_manifest(d, "serving")
    assert reason == "ok"
    assert sec["num_features"] == 8
    assert 64 in sec["row_buckets"] and 16 in sec["row_buckets"]
    assert sec["generation"] == 1
    # a second runtime starting from this export prewarms manifest_ok
    with ServingRuntime(publish_dir=d, params={"verbose": -1},
                        batch_window_s=0.001) as rt2:
        assert rt2.prewarm_events[0]["outcome"] == "manifest_ok"
        assert 64 in rt2.prewarm_events[0]["buckets"]


# ---------------------------------------------------------------------------
# continuous trainer: manifest export + relaunch prewarm
# ---------------------------------------------------------------------------

def test_trainer_exports_manifest_and_relaunch_prewarms(tmp_path):
    """Cycle publishes carry the train_online manifest section; a
    relaunch with a matching signature prewarms (manifest_ok) before its
    first slot, and the service still completes its cycles."""
    import sys as _sys

    from lightgbm_tpu.runtime.continuous import ContinuousTrainer
    rng = np.random.default_rng(3)
    X = rng.standard_normal((600, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    data = str(tmp_path / "train.tsv")
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.7g")
    out = str(tmp_path / "m.txt")
    params = {"data": data, "output_model": out, "objective": "binary",
              "num_leaves": 7, "min_data_in_leaf": 5, "verbose": -1,
              "seed": 7, "online_rounds": 1, "online_interval": 0.2}

    t1 = ContinuousTrainer(dict(params, online_cycles=1))
    t1.wd.stream = _sys.stderr
    assert t1.run() == 0
    sec, reason = warmup.read_manifest(out + ".pub", "train_online")
    assert reason == "ok"
    assert sec["params_sig"]["num_leaves"] == 7
    assert sec["params_sig"]["n_features"] == 6

    base_ok = _warmup_count("train_online", "manifest_ok")
    t2 = ContinuousTrainer(dict(params, online_cycles=2))
    t2.wd.stream = _sys.stderr
    assert t2.run() == 0
    assert _warmup_count("train_online", "manifest_ok") == base_ok + 1
    assert any(s.get("prewarm", {}).get("outcome") == "manifest_ok"
               for s in t2.wd.stages if isinstance(s.get("prewarm"), dict))
