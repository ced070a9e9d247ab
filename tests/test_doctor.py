"""Debug-bundle round-trip (ISSUE 10, runtime/doctor.py + task=doctor).

Pins the acceptance gate: one atomic bundle containing probe (opt),
env/config fingerprint, stage trail, metrics snapshot and compile
ledger; create -> untar -> manifest checksums verify; tampering is
detected; the CLI task and the crash path both produce it.
"""
import json
import os
import tarfile

import numpy as np
import pytest

from lightgbm_tpu.application import Application
from lightgbm_tpu.runtime import doctor, resilience, telemetry, xla_obs
from lightgbm_tpu.utils.log import LightGBMError


def _mk_trail(path):
    resilience.atomic_write(path, json.dumps(
        {"label": "t", "stages": [{"stage": "s1", "t": 0.1}],
         "culprit": None}))


def test_bundle_round_trip_checksums_verify(tmp_path, monkeypatch):
    trail = str(tmp_path / "trail.json")
    _mk_trail(trail)
    monkeypatch.setenv("LGBM_TPU_STAGE_REPORT", trail)
    (tmp_path / "BENCH_r99.json").write_text('{"n": 99, "parsed": {}}')
    telemetry.counter("lgbm_train_iterations_total").inc()
    xla_obs.cache_event("t.doctor", "hit")

    rec = doctor.collect_debug_bundle(
        out_dir=str(tmp_path), probe=False, config={"task": "train"},
        artifact_dir=str(tmp_path), note="unit test")
    assert os.path.exists(rec["path"])
    names = {m["name"] for m in rec["manifest"]["members"]}
    assert "env.json" in names
    assert "metrics.json" in names
    assert "xla_ledger.json" in names
    assert any(n.startswith("trails/") for n in names)
    assert "artifacts/BENCH_r99.json" in names
    assert "errors" not in rec["manifest"]

    v = doctor.verify_bundle(rec["path"])
    assert v["ok"], v
    assert v["members"] == len(names)

    # the members actually carry the evidence they claim to
    with tarfile.open(rec["path"]) as tar:
        by = {i.name.split("/", 1)[1]: tar.extractfile(i).read()
              for i in tar.getmembers()}
    env = json.loads(by["env.json"])
    assert env["config"] == {"task": "train"}
    assert "LGBM_TPU_STAGE_REPORT" in env["env"]
    ledger = json.loads(by["xla_ledger.json"])
    assert "t.doctor" in ledger["sites"]
    metrics = json.loads(by["metrics.json"])
    assert "lgbm_train_iterations_total" in metrics["metrics"]
    trail_name = [n for n in by if n.startswith("trails/")][0]
    assert json.loads(by[trail_name])["stages"][0]["stage"] == "s1"


def test_bundle_tamper_detected(tmp_path):
    rec = doctor.collect_debug_bundle(out_dir=str(tmp_path), probe=False,
                                      artifact_dir=str(tmp_path))
    # rewrite the tar with one member's bytes flipped
    tampered = str(tmp_path / "tampered.tar.gz")
    with tarfile.open(rec["path"]) as src, \
            tarfile.open(tampered, "w:gz") as dst:
        for info in src.getmembers():
            data = src.extractfile(info).read()
            if info.name.endswith("env.json"):
                data = data.replace(b"{", b"{ ", 1)
                info.size = len(data)
            import io
            dst.addfile(info, io.BytesIO(data))
    v = doctor.verify_bundle(tampered)
    assert not v["ok"]
    assert any("env.json" in m for m in v["mismatches"])


def test_cli_task_doctor(tmp_path, capsys):
    Application(["task=doctor", "probe=false",
                 "output_dir=%s" % tmp_path,
                 "artifact_dir=%s" % tmp_path]).run()
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines()
            if ln.startswith("doctor bundle ")][0]
    path = line.split(" ", 2)[2]
    assert os.path.exists(path)
    assert doctor.verify_bundle(path)["ok"]


def test_cli_crash_path_ships_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv("LGBM_TPU_DOCTOR_DIR", str(tmp_path))
    monkeypatch.delenv("LGBM_TPU_DOCTOR_ON_CRASH", raising=False)
    with pytest.raises(LightGBMError):
        Application(["task=train"]).run()      # no data= -> Log.fatal
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("lgbm_debug_crash_train")
               and f.endswith(".tar.gz")]
    assert bundles, os.listdir(tmp_path)
    v = doctor.verify_bundle(str(tmp_path / bundles[0]))
    assert v["ok"]
    with tarfile.open(str(tmp_path / bundles[0])) as tar:
        manifest = json.loads([tar.extractfile(i).read()
                               for i in tar.getmembers()
                               if i.name.endswith("manifest.json")][0])
    assert "No training data" in manifest["note"]


def test_cli_crash_path_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("LGBM_TPU_DOCTOR_DIR", str(tmp_path))
    monkeypatch.setenv("LGBM_TPU_DOCTOR_ON_CRASH", "0")
    with pytest.raises(LightGBMError):
        Application(["task=train"]).run()
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("lgbm_debug_")]


def test_collection_failure_degrades_to_manifest_error(tmp_path,
                                                       monkeypatch):
    """A member that cannot be gathered becomes an `errors` entry, never
    an exception out of the crashing process."""
    monkeypatch.setattr(doctor, "_metrics_member",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    rec = doctor.collect_debug_bundle(out_dir=str(tmp_path), probe=False,
                                      artifact_dir=str(tmp_path))
    assert "metrics.json" in rec["manifest"]["errors"]
    assert doctor.verify_bundle(rec["path"])["ok"]


def test_platform_member_reports_what_this_process_binds(tmp_path):
    """task=doctor's platform report is IN-PROCESS (a chip belongs to one
    process: no probe child) and degrades nothing — it names the platform,
    device kind and count jax binds here."""
    import jax
    rec = doctor.collect_debug_bundle(out_dir=str(tmp_path), probe=True,
                                      artifact_dir=str(tmp_path))
    assert "errors" not in rec["manifest"]
    with tarfile.open(rec["path"]) as tar:
        by = {i.name.split("/", 1)[1]: tar.extractfile(i).read()
              for i in tar.getmembers()}
    plat = json.loads(by["platform.json"])
    assert plat["platform"] == jax.devices()[0].platform == "cpu"
    assert plat["count"] == len(jax.devices())
    assert plat["jax"] == jax.__version__
    skipped = doctor.collect_debug_bundle(out_dir=str(tmp_path), probe=False,
                                          artifact_dir=str(tmp_path))
    assert "platform.json" not in {m["name"] for m
                                   in skipped["manifest"]["members"]}
