"""One-command debug bundle (``task=doctor`` / `collect_debug_bundle`).

The artifact a failed run ships home (ISSUE 10).  Ad-hoc evidence
gathering loses exactly the file that mattered; this module packages
EVERYTHING a post-mortem needs into one atomic tar with a checksummed
manifest:

* **platform** — the platform, device kind and device count JAX binds
  IN THIS PROCESS (a chip belongs to one process: no probe child, and
  nothing is degraded — a platform that cannot initialize becomes an
  ``errors`` entry);
* **environment / config fingerprint** — python/jax/numpy versions,
  platform, argv, and every ``LGBM_* / JAX_* / XLA_* / BENCH_*`` env
  var, plus the CLI's resolved parameters when available;
* **stage trails** — ``$LGBM_TPU_STAGE_REPORT`` /
  ``$LGBM_TPU_SERVE_REPORT`` and any explicitly passed trail files
  (read through the tolerant `read_stage_report`, so a torn trail
  degrades to its raw bytes instead of being dropped);
* **metrics snapshot** — the PR 9 registry (the merged {host}-labeled
  mesh view when the process is part of a multi-host run);
* **compile ledger** — `xla_obs.LEDGER.to_json()`: per-site compiles,
  wall time, last shapes, steady-state retraces;
* **trace ring** — the ISSUE 14 flight recorder's bounded event ring as
  Perfetto-loadable Chrome trace JSON (``trace.json``): the causal
  timeline of the last moments before the crash;
* **recent artifacts** — the newest ``BENCH_* / CHAOS* / MULTICHIP*``
  JSONs found next to the repo (size-capped).

The bundle is written tmp+fsync+rename (one atomic file); the manifest
inside it carries a sha256 per member and `verify_bundle` re-checks
them — the round-trip is test-pinned.  Collection must never crash the
crashing process: every member is gathered under its own guard, and a
member that cannot be gathered becomes an ``errors`` entry in the
manifest instead of an exception.
"""
from __future__ import annotations

import glob
import hashlib
import io
import json
import os
import platform
import sys
import tarfile
import time
from typing import Any, Dict, List, Optional

from . import resilience, telemetry, tracing, warmup, xla_obs

__all__ = ["collect_debug_bundle", "verify_bundle", "env_fingerprint",
           "device_report"]

#: newest-first artifact globs bundled from the artifact directory
ARTIFACT_GLOBS = ("BENCH_r*.json", "BENCH_local*.json", "CHAOS*.json",
                  "MULTICHIP*.json")

#: per-member size cap — a bundle must stay shippable over a bad link
MAX_MEMBER_BYTES = 1 << 20

#: artifacts bundled at most (newest by mtime)
MAX_ARTIFACTS = 8


def env_fingerprint(config: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Everything about WHERE this ran that a post-mortem asks first."""
    env_keys = sorted(k for k in os.environ
                      if k.startswith(("LGBM_", "JAX_", "XLA_", "BENCH_",
                                       "NDEV", "TPU_")))
    fp: Dict[str, Any] = {
        "wallclock": resilience.wallclock(),
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "cwd": os.getcwd(),
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "env": {k: os.environ[k] for k in env_keys},
    }
    jax = sys.modules.get("jax")      # never INITIALIZE a platform here
    if jax is not None:
        fp["jax_version"] = getattr(jax, "__version__", "?")
    np = sys.modules.get("numpy")
    if np is not None:
        fp["numpy_version"] = getattr(np, "__version__", "?")
    if config:
        fp["config"] = {str(k): str(v) for k, v in config.items()}
    return fp


def _stage_trail_members(extra: Optional[List[str]]) -> Dict[str, bytes]:
    out: Dict[str, bytes] = {}
    paths: List[str] = []
    for env_key in ("LGBM_TPU_STAGE_REPORT", "LGBM_TPU_SERVE_REPORT"):
        p = os.environ.get(env_key)
        if p:
            paths.append(p)
    paths.extend(extra or [])
    for i, p in enumerate(paths):
        if not os.path.exists(p):
            continue
        name = "trails/%d_%s" % (i, os.path.basename(p))
        rep = resilience.read_stage_report(p)
        if rep is not None:
            out[name] = (json.dumps(rep, indent=1) + "\n").encode("utf-8")
        else:
            with open(p, "rb") as fh:        # torn: raw bytes beat nothing
                out[name] = fh.read(MAX_MEMBER_BYTES)
    return out


def _artifact_members(artifact_dir: str) -> Dict[str, bytes]:
    found: List[str] = []
    for pat in ARTIFACT_GLOBS:
        found.extend(glob.glob(os.path.join(artifact_dir, pat)))
    found = sorted(set(found), key=os.path.getmtime, reverse=True)
    out: Dict[str, bytes] = {}
    for p in found[:MAX_ARTIFACTS]:
        with open(p, "rb") as fh:
            out["artifacts/" + os.path.basename(p)] = \
                fh.read(MAX_MEMBER_BYTES)
    return out


def device_report() -> Dict[str, Any]:
    """The device as JAX reports it IN THIS PROCESS — platform, kind and
    count.  Initializes the backend (and so takes the chip); every entry
    point that states or checks its platform goes through here."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _platform_member() -> Dict[str, Any]:
    import jax
    import jaxlib
    return dict(device_report(), jax=jax.__version__,
                jaxlib=jaxlib.__version__)


def _metrics_member() -> bytes:
    snap: Dict[str, Any]
    try:
        if telemetry.mesh_process_count() > 1:
            snap = telemetry.mesh_snapshot("doctor")
        else:
            snap = telemetry.snapshot("doctor")
    except Exception:    # noqa: BLE001 — platform query may be wedged
        snap = telemetry.snapshot("doctor")
    return (json.dumps(snap) + "\n").encode("utf-8")


def collect_debug_bundle(out_dir: str = ".",
                         tag: Optional[str] = None,
                         config: Optional[Dict[str, Any]] = None,
                         probe: bool = True,
                         stage_reports: Optional[List[str]] = None,
                         artifact_dir: Optional[str] = None,
                         note: Optional[str] = None) -> Dict[str, Any]:
    """Collect everything into ``<out_dir>/lgbm_debug_<stamp>.tar.gz``
    atomically.  Returns ``{"path": ..., "manifest": {...}}``; the same
    manifest (with per-member sha256) rides INSIDE the tar as
    ``manifest.json``."""
    stamp = time.strftime("%Y%m%d_%H%M%S")
    name = "lgbm_debug_%s%s_%d" % (("%s_" % tag) if tag else "", stamp,
                                   os.getpid())
    members: Dict[str, bytes] = {}
    errors: Dict[str, str] = {}

    def gather(member: str, fn) -> None:
        try:
            v = fn()
            if isinstance(v, dict):
                v = (json.dumps(v, indent=1) + "\n").encode("utf-8")
            if v:
                members[member] = v[:MAX_MEMBER_BYTES] \
                    if isinstance(v, bytes) else v
        except Exception as e:   # noqa: BLE001 — collection must not crash
            errors[member] = "%s: %s" % (type(e).__name__, e)

    gather("env.json", lambda: env_fingerprint(config))
    if probe:
        gather("platform.json", _platform_member)
    gather("metrics.json", _metrics_member)
    gather("xla_ledger.json", lambda: xla_obs.LEDGER.to_json())
    # warm-start state (ISSUE 15): persistent compile-cache dir /
    # fingerprint / hit-miss-evict counts — the first question a slow
    # cold start gets asked
    gather("warmup_status.json", warmup.cache_status)
    # the trace flight recorder's ring (ISSUE 14): the causal timeline
    # of the process's last TRACE_RING_EVENTS events, Perfetto-loadable
    # straight out of the bundle
    gather("trace.json", lambda: tracing.export_chrome(
        context_name="doctor"))

    def _trails() -> None:
        members.update(_stage_trail_members(stage_reports))
    try:
        _trails()
    except Exception as e:       # noqa: BLE001
        errors["trails"] = "%s: %s" % (type(e).__name__, e)

    try:
        members.update(_artifact_members(
            artifact_dir if artifact_dir is not None else os.getcwd()))
    except Exception as e:       # noqa: BLE001
        errors["artifacts"] = "%s: %s" % (type(e).__name__, e)

    manifest: Dict[str, Any] = {
        "bundle": name,
        "created": resilience.wallclock(),
        "members": [
            {"name": m, "sha256": hashlib.sha256(members[m]).hexdigest(),
             "bytes": len(members[m])}
            for m in sorted(members)],
    }
    if note:
        manifest["note"] = note
    if errors:
        manifest["errors"] = errors

    out_path = os.path.join(out_dir, name + ".tar.gz")
    tmp = out_path + ".tmp.%d" % os.getpid()
    with tarfile.open(tmp, "w:gz") as tar:
        def add(member_name: str, data: bytes) -> None:
            info = tarfile.TarInfo(name + "/" + member_name)
            info.size = len(data)
            info.mtime = int(time.time())
            tar.addfile(info, io.BytesIO(data))
        add("manifest.json",
            (json.dumps(manifest, indent=1) + "\n").encode("utf-8"))
        for m in sorted(members):
            add(m, members[m])
    with open(tmp, "rb") as fh:           # fsync before the atomic rename
        os.fsync(fh.fileno())
    os.replace(tmp, out_path)
    return {"path": out_path, "manifest": manifest}


def verify_bundle(path: str) -> Dict[str, Any]:
    """Re-read a bundle and re-hash every member against its manifest.
    Returns {"ok": bool, "members": N, "mismatches": [...]}."""
    with tarfile.open(path, "r:gz") as tar:
        by_name = {}
        root = None
        for info in tar.getmembers():
            parts = info.name.split("/", 1)
            if len(parts) != 2:
                continue
            root = parts[0]
            by_name[parts[1]] = tar.extractfile(info).read()
        manifest = json.loads(by_name.pop("manifest.json").decode("utf-8"))
    mismatches: List[str] = []
    for entry in manifest["members"]:
        data = by_name.get(entry["name"])
        if data is None:
            mismatches.append("%s: missing from tar" % entry["name"])
        elif hashlib.sha256(data).hexdigest() != entry["sha256"]:
            mismatches.append("%s: sha256 mismatch" % entry["name"])
    for extra in sorted(set(by_name) - {e["name"]
                                        for e in manifest["members"]}):
        mismatches.append("%s: in tar but not in manifest" % extra)
    return {"ok": not mismatches, "bundle": root,
            "members": len(manifest["members"]), "mismatches": mismatches}
