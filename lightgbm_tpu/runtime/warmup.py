"""Warm-start subsystem: persistent program cache + shape manifests.

Every process used to pay cold XLA compilation on startup: serving
replicas compiled before their first real batch and `train_online`
relaunches recompiled the whole fused-step family after SIGTERM.  This
module makes startup a measured, optimized quantity — LightGBM's own "bin
once, reuse the binary cache" design (PAPER.md §L2) applied to compiled
programs:

* **Persistent compilation cache seam** — `enable_compile_cache()` is
  the one place the program decides where jax's persistent compilation
  cache lives.  When ``JAX_COMPILATION_CACHE_DIR`` is set, jax already
  reads it: the program sets no directory, creates nothing under it and
  never sweeps it — whoever placed the cache owns it.  When it is not
  set, the cache is one fixed path inside the checkout
  (`DEFAULT_CACHE_DIR`, git-ignored) with nothing of the host, process
  or time in its name: the directory is part of the cache's key, so a
  path that moves never hits, and jax's own entry key already covers
  backend, jax version and compile options.  Only that owned directory
  is size-budgeted (`OWNED_BUDGET_MB`, oldest-mtime eviction).
  Per-compile hit/miss classification (did this compile load from disk
  or write a fresh entry?) rides the `xla_obs` compile observer into
  ``lgbm_compile_cache_events_total{event}`` AND the compile ledger
  (site ``warmup.persistent_cache``), against whichever directory is
  active.

* **Shape manifests** — serving and the continuous trainer export the
  shape buckets and jit sites they actually compiled (straight from the
  `xla_obs` ledger) as a checksummed ``warmup.json`` published
  atomically ALONGSIDE model generations in the publish directory
  (`ModelPublisher.publish_manifest` / `ModelSubscriber.read_warmup`
  are the publish.py seam).  The file holds one section per kind
  (``serving`` / ``train_online``) merged read-modify-atomic-write, so
  the trainer and N serving replicas all land without clobbering each
  other; it is not a ``gen_`` file, so retention pruning never touches
  it and concurrent readers can never observe a torn manifest (atomic
  rename — test-pinned under publish/prune churn).

* **Prewarm classification** — `classify_serving_section` /
  `classify_train_section` decide whether a manifest is trustworthy for
  THIS process (torn / stale-generation / shape-mismatched manifests
  degrade to the legacy smallest-bucket prewarm — never block serving),
  and `record_prewarm` counts every prewarm attempt in
  ``lgbm_warmup_total{kind,outcome}`` + ``lgbm_warmup_seconds{kind}``.

No jax at module scope — the CLI entry and platform-free subscribers
import this; jax loads when the cache seam is enabled.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from . import telemetry, xla_obs
from .resilience import atomic_write, wallclock

__all__ = [
    "JAX_CACHE_ENV", "DEFAULT_CACHE_DIR", "MANIFEST_NAME",
    "MANIFEST_SCHEMA_VERSION",
    "enable_compile_cache", "sweep_cache", "cache_status",
    "write_manifest", "read_manifest", "manifest_path",
    "build_serving_section", "build_train_section", "params_sig",
    "classify_serving_section", "classify_train_section",
    "serving_row_buckets", "record_prewarm",
]

#: jax's own variable.  Set: jax reads it and the directory belongs to
#: whoever set it.  Unset: `DEFAULT_CACHE_DIR`.
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the cache directory when `JAX_CACHE_ENV` is unset: fixed, inside the
#: checkout, listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: size budget of the OWNED directory, in MB (oldest-mtime sweep past
#: it); a directory placed from outside is never swept
OWNED_BUDGET_MB = 512

#: the shape manifest published alongside model generations.  Not a
#: ``gen_`` file: `publish.generation_paths` never lists it and
#: `ModelPublisher._prune` never unlinks it.
MANIFEST_NAME = "warmup.json"
MANIFEST_SCHEMA_VERSION = 1

#: serving prewarm never compiles more than this many manifest buckets
#: (a runaway manifest must not stall readiness indefinitely)
MAX_PREWARM_BUCKETS = 8

#: sanity bound on a manifest row bucket (2^22 rows is far past any
#: serving batch); anything outside [1, this] marks the manifest invalid
MAX_BUCKET_ROWS = 1 << 22

_lock = threading.Lock()
_STATE: Dict[str, Any] = {
    "enabled": False, "dir": None, "owned": False,
    "hits": 0, "misses": 0, "evictions": 0, "dir_sig": None,
}


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def enable_compile_cache(min_compile_s: float = 0.0) -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in use.  Every entry point calls this once before its
    first compile (`Application`, `ServingRuntime.start`,
    `ContinuousTrainer.run`, bench.py, chip_smoke.py, tests/conftest.py).

    ``JAX_COMPILATION_CACHE_DIR`` set: that directory, untouched by this
    program beyond the entries jax itself writes.  Unset:
    `DEFAULT_CACHE_DIR`, created if missing and swept to its budget.

    Threshold 0 persists even sub-second programs so a warm start
    recompiles NOTHING.  Idempotent per process."""
    import jax
    external = os.environ.get(JAX_CACHE_ENV)
    cdir = external or DEFAULT_CACHE_DIR
    with _lock:
        if _STATE["enabled"] and _STATE["dir"] == cdir:
            return cdir
    if not external:
        os.makedirs(cdir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cdir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_s))
    with _lock:
        _STATE.update(enabled=True, dir=cdir, owned=not external,
                      dir_sig=_dir_sig(cdir))
    # per-compile hit/miss classification rides the compile ledger's
    # observer seam (xla_obs must not import warmup — the observer is
    # registered, not imported)
    xla_obs.set_compile_observer(_compile_observer)
    sweep_cache()
    return cdir


def _dir_sig(cdir: str) -> Optional[Tuple[int, int]]:
    """O(1) change signature of the cache directory: (mtime_ns, nlink)
    of the dir itself — a new cache entry bumps the dir mtime.  Stat of
    ONE inode, never a listing: the observer runs on every compile and
    the suite-wide cache holds thousands of entries."""
    try:
        st = os.stat(cdir)
        return (st.st_mtime_ns, st.st_nlink)
    except OSError:
        return None


def _compile_observer(site: str, wall_s: float) -> None:
    """Runs after every ledgered compile: a compile that wrote a NEW
    cache entry (the dir signature moved) ran cold (miss); one that did
    not load its executable from disk (hit).  Exact at the service
    default persist-threshold 0, where every fresh compile writes an
    entry; with a higher threshold (the test suite) sub-threshold
    compiles classify as hits — stats, never correctness."""
    with _lock:
        cdir = _STATE["dir"] if _STATE["enabled"] else None
        prev = _STATE["dir_sig"]
    if cdir is None:
        return
    sig = _dir_sig(cdir)
    with _lock:
        event = "miss" if sig != prev else "hit"
        _STATE["dir_sig"] = sig
        _STATE["hits" if event == "hit" else "misses"] += 1
    telemetry.counter("lgbm_compile_cache_events_total").inc(event=event)
    xla_obs.cache_event("warmup.persistent_cache", event)


def sweep_cache(budget_mb: int = OWNED_BUDGET_MB) -> int:
    """LRU sweep of the OWNED cache directory: evict oldest-mtime
    entries until it fits the budget.  Returns the number of entries
    evicted — always 0 for a directory placed from outside through
    ``JAX_COMPILATION_CACHE_DIR``, which is never touched."""
    with _lock:
        cdir = (_STATE["dir"]
                if _STATE["enabled"] and _STATE["owned"] else None)
    if cdir is None or budget_mb <= 0:
        return 0
    entries: List[Tuple[float, int, str]] = []
    try:
        for name in os.listdir(cdir):
            p = os.path.join(cdir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, p))
    except OSError:
        return 0
    total = sum(e[1] for e in entries)
    budget = int(budget_mb) << 20
    evicted = 0
    for mtime, size, p in sorted(entries):
        if total <= budget:
            break
        try:
            os.unlink(p)
        except OSError:
            continue
        total -= size
        evicted += 1
        telemetry.counter("lgbm_compile_cache_events_total").inc(
            event="evict")
    if evicted:
        with _lock:
            _STATE["evictions"] += evicted
            _STATE["dir_sig"] = _dir_sig(cdir)   # re-baseline after unlinks
    return evicted


def cache_status() -> Dict[str, Any]:
    """Machine-readable cache state (the doctor-bundle member)."""
    with _lock:
        st = {k: _STATE[k] for k in ("enabled", "dir", "owned",
                                     "hits", "misses", "evictions")}
    files, total = 0, 0
    if st["dir"]:
        try:
            for name in os.listdir(st["dir"]):
                try:
                    total += os.path.getsize(os.path.join(st["dir"], name))
                    files += 1
                except OSError:
                    continue
        except OSError:
            pass
    st["files"] = files
    st["bytes"] = total
    return st


def _reset_for_tests() -> None:
    """Test seam: forget the enable state (jax config is left as-is)."""
    with _lock:
        _STATE.update(enabled=False, dir=None, owned=False,
                      hits=0, misses=0, evictions=0, dir_sig=None)


# ---------------------------------------------------------------------------
# shape manifests (warmup.json in the publish dir)
# ---------------------------------------------------------------------------

def manifest_path(pub_dir: str) -> str:
    return os.path.join(pub_dir, MANIFEST_NAME)


def _doc_checksum(doc: Dict[str, Any]) -> str:
    payload = json.dumps({"schema_version": doc.get("schema_version"),
                          "sections": doc.get("sections")},
                         sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()


def _read_doc(pub_dir: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """(manifest document, reason): reason is "ok", "missing" (no file)
    or "torn" (unparseable / checksum-invalid / wrong schema).  The
    atomic write discipline means "torn" only ever describes a file
    written by something that is not this seam."""
    try:
        with open(manifest_path(pub_dir), "rb") as fh:
            text = fh.read().decode("utf-8", "replace")
    except OSError:
        return None, "missing"
    try:
        doc = json.loads(text)
    except ValueError:
        return None, "torn"
    if not isinstance(doc, dict) \
            or not isinstance(doc.get("sections"), dict) \
            or doc.get("schema_version") != MANIFEST_SCHEMA_VERSION \
            or doc.get("checksum") != _doc_checksum(doc):
        return None, "torn"
    return doc, "ok"


def write_manifest(pub_dir: str, kind: str,
                   section: Dict[str, Any]) -> str:
    """Merge one kind's section into the publish dir's manifest
    (read-merge-atomic-write, the `mark_rollback` pattern: the trainer
    and N serving replicas can all publish their sections concurrently
    and every writer's section lands).  Returns the path."""
    doc, _ = _read_doc(pub_dir)
    sections = dict((doc or {}).get("sections", {}))
    sections[str(kind)] = dict(section)
    out = {"schema_version": MANIFEST_SCHEMA_VERSION, "sections": sections}
    out["checksum"] = _doc_checksum(out)
    path = manifest_path(pub_dir)
    os.makedirs(pub_dir, exist_ok=True)
    atomic_write(path, json.dumps(out, indent=1) + "\n")
    return path


def read_manifest(pub_dir: str, kind: str
                  ) -> Tuple[Optional[Dict[str, Any]], str]:
    """(section, reason) for one kind: reason "ok", "missing" (no file
    or no such section) or "torn"."""
    doc, reason = _read_doc(pub_dir)
    if doc is None:
        return None, reason
    sec = doc["sections"].get(str(kind))
    if not isinstance(sec, dict):
        return None, "missing"
    return sec, "ok"


def _ledger_sites(limit: int = 32) -> List[str]:
    """Site names the compile ledger saw compile in THIS process — the
    manifest's provenance trail ("what did this role actually build")."""
    snap = xla_obs.snapshot()
    return sorted(name for name, n in snap.items() if n > 0)[:limit]


def build_serving_section(num_features: int, row_buckets: List[int],
                          generation: Optional[int] = None
                          ) -> Dict[str, Any]:
    return {
        "kind": "serving",
        "num_features": int(num_features),
        "row_buckets": sorted({int(b) for b in row_buckets}),
        "generation": int(generation) if generation is not None else None,
        "created": wallclock(),
        "sites": _ledger_sites(),
    }


def params_sig(params: Dict[str, Any], n_features: int) -> Dict[str, Any]:
    """The program-shape-determining parameter subset: two training
    processes with equal signatures compile the same fused-step family
    on a same-width window."""
    p = params or {}
    return {
        "objective": str(p.get("objective", "regression")),
        "num_class": int(p.get("num_class", 1)),
        "num_leaves": int(p.get("num_leaves", 31)),
        "max_bin": int(p.get("max_bin", 255)),
        "boost_window": int(p.get("boost_window", 1)),
        "n_features": int(n_features),
    }


def build_train_section(params: Dict[str, Any], n_features: int,
                        generation: Optional[int] = None
                        ) -> Dict[str, Any]:
    return {
        "kind": "train_online",
        "params_sig": params_sig(params, n_features),
        "generation": int(generation) if generation is not None else None,
        "created": wallclock(),
        "sites": _ledger_sites(),
    }


def classify_serving_section(sec: Dict[str, Any],
                             num_features: Optional[int],
                             newest_generation: Optional[int]) -> str:
    """"ok" when the manifest's buckets can be trusted for this model;
    otherwise the degradation outcome the metrics count:

    * ``manifest_invalid`` — buckets missing/malformed/absurd;
    * ``manifest_stale`` — written for a DIFFERENT generation whose
      shape no longer matches (the lineage moved on; its buckets
      describe a model this replica is not serving);
    * ``shape_mismatch`` — written for this very generation yet the
      feature width disagrees (a corrupt or foreign manifest).

    Buckets are shape-keyed, not generation-keyed, so an old-generation
    manifest whose feature width still matches stays "ok" — that is the
    common steady-state case."""
    buckets = sec.get("row_buckets")
    if not isinstance(buckets, list) or not buckets \
            or not all(isinstance(b, int) and 0 < b <= MAX_BUCKET_ROWS
                       for b in buckets):
        return "manifest_invalid"
    nf = sec.get("num_features")
    if num_features is not None and nf != num_features:
        gen = sec.get("generation")
        if isinstance(gen, int) and newest_generation is not None \
                and gen != newest_generation:
            return "manifest_stale"
        return "shape_mismatch"
    return "ok"


def classify_train_section(sec: Dict[str, Any],
                           params: Dict[str, Any],
                           n_features: int) -> str:
    """"ok" when the manifest was written by a training process whose
    program-shape signature matches THIS one (same fused-step family —
    prewarming pays off); "shape_mismatch" otherwise."""
    sig = sec.get("params_sig")
    if not isinstance(sig, dict):
        return "manifest_invalid"
    return "ok" if sig == params_sig(params, n_features) \
        else "shape_mismatch"


def serving_row_buckets(num_features: Optional[int] = None) -> List[int]:
    """Row buckets the tree-parallel predictor ACTUALLY compiled in this
    process, read straight from the xla_obs ledger (the compile history
    of site ``predictor.tree_parallel`` records each trace's abstract
    shapes — the X argument is ``f32[rows,features]``)."""
    import re
    rec = xla_obs.LEDGER.register("predictor.tree_parallel")
    sigs: List[List[str]] = [list(h.get("signature", []))
                             for h in rec.history]
    if rec.last_sig:
        sigs.append(list(rec.last_sig))
    pat = re.compile(r"^f32\[(\d+),(\d+)\]$")
    buckets = set()
    for sig in sigs:
        for entry in sig:
            m = pat.match(entry)
            if not m:
                continue
            rows, feats = int(m.group(1)), int(m.group(2))
            if num_features is not None and feats != num_features:
                continue
            buckets.add(rows)
    return sorted(buckets)


def record_prewarm(kind: str, outcome: str, seconds: float) -> None:
    """Count one prewarm attempt: every path — manifest-driven, degraded
    to legacy, or errored — lands in ``lgbm_warmup_total{kind,outcome}``
    so the fleet's warm-start behavior is scrapeable."""
    telemetry.counter("lgbm_warmup_total").inc(kind=kind, outcome=outcome)
    telemetry.histogram("lgbm_warmup_seconds").observe(
        max(float(seconds), 0.0), kind=kind)
