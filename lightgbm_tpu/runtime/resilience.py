"""Fault-tolerant execution runtime.

The reference survives multi-hour training through periodic snapshots
(gbdt.cpp:330-334) and bounded socket timeouts (linkers_socket.cpp); this
module is the TPU-native equivalent of that posture:

* **Stage watchdog** (`Watchdog`): every dryrun/bench/ingest stage runs
  under a named deadline.  On expiry the watchdog captures `faulthandler`
  tracebacks of ALL threads, persists the stage trail + culprit into a
  JSON report, and either raises `StageTimeout` (soft mode, host
  processes) or kills the process group with a distinctive exit code
  (hard mode, disposable subprocesses) — a hang can never again surface
  as a bare rc=124.

* **Preemption-safe snapshots** (`write_snapshot`, `find_resume_snapshot`,
  `restore_training_state`, `PreemptionGuard`): snapshot files are model
  files plus a footer carrying the full training state (scores, payload
  row order, RNG streams, variant bookkeeping) and a sha256 checksum;
  writes are atomic (tmp + fsync + rename) with keep-last-K retention;
  SIGTERM/SIGINT write a final snapshot at the next iteration boundary;
  resume scans past corrupt snapshots to the newest valid one and
  continues to a model byte-identical to an uninterrupted run.

* **Non-finite sentinel** (`NonFiniteDetected`, `SentinelGuard`): tree
  outputs fetched from device every iteration are screened for NaN/inf
  under `sentinel_nonfinite=abort|rollback`.

* **Fault injection** (`LGBM_TPU_FAULT`): every behavior above is
  testable through environment-injected faults, e.g.
  ``LGBM_TPU_FAULT=die_at_iter:7,corrupt_snapshot,nan_grad:5``.
  See docs/RESILIENCE.md for the full matrix.

No jax / numpy import at module scope: the CLI entry must be able to use
this module without binding a platform.
"""
from __future__ import annotations

import base64
import contextlib
import datetime
import hashlib
import io
import json
import os
import signal
import sys
import tempfile
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "StageTimeout", "Watchdog", "wallclock",
    "backoff_delays",
    "atomic_write", "read_stage_report", "write_snapshot",
    "validate_snapshot",
    "load_snapshot_state", "find_resume_snapshot", "snapshot_paths",
    "capture_training_state", "restore_training_state",
    "make_resume_callback", "PreemptionGuard", "TrainingPreempted",
    "NonFiniteDetected", "SentinelGuard",
    "fault_arg", "fault_active", "maybe_die_or_preempt",
    "maybe_corrupt_snapshot",
    "maybe_inject_nan", "maybe_slow_stage", "maybe_torn_publish",
    "maybe_die_at_publish", "maybe_die_at_spawn", "maybe_die_at_ring",
    "maybe_fail_predict", "DevicePredictFault",
    "maybe_poison_rows", "maybe_flip_labels", "maybe_regress_model",
    "snapshot_model_text", "FAULT_TABLE", "FAULT_NAMES",
]


def wallclock() -> str:
    """ISO-ish wall-clock tag: every stage line of a red artifact must
    show WHEN it started, so a stall's duration is readable from the
    trail alone."""
    return datetime.datetime.now().strftime("%Y-%m-%dT%H:%M:%S")


# ---------------------------------------------------------------------------
# fault injection (LGBM_TPU_FAULT=name[:arg],name[:arg],...)
# ---------------------------------------------------------------------------

#: THE fault registry: every recognized fault point, with its argument
#: spelling and injection point.  This table is the single source of
#: truth shared by the parser below and the docs/RESILIENCE.md injection
#: matrix (test-pinned against each other, so the table and the parser
#: cannot drift).  Anything else in the spec is rejected loudly — a
#: typoed fault name silently injecting nothing would make a "green
#: under fault" test meaningless.
FAULT_TABLE: Dict[str, Dict[str, str]] = {
    "die_at_iter": {
        "arg": "K",
        "injects_at": "Booster.update entry (maybe_die_or_preempt)"},
    "sigterm_at_iter": {
        "arg": "K",
        "injects_at": "Booster.update entry (SIGTERM to self)"},
    "corrupt_snapshot": {
        "arg": "[K]",
        "injects_at": "write_snapshot, after the atomic rename"},
    "nan_grad": {
        "arg": "K",
        "injects_at": "the _finish_tree host fetch (sentinel_check)"},
    "torn_write": {
        "arg": "[K]",
        "injects_at": "ModelPublisher.publish, before the atomic path"},
    "slow_stage": {
        "arg": "NAME:SECS",
        "injects_at": "stage open in the continuous trainer "
                      "(maybe_slow_stage; one-shot per process)"},
    "die_at_publish": {
        "arg": "K",
        "injects_at": "ModelPublisher.publish, between generation rename "
                      "and manifest write"},
    "die_at_predict": {
        "arg": "K",
        "injects_at": "device-predict micro-batch boundary "
                      "(maybe_fail_predict in DevicePredictor.predict_raw)"},
    "slow_predict": {
        "arg": "SECS",
        "injects_at": "device-predict micro-batch boundary "
                      "(maybe_fail_predict; every batch while armed)"},
    "poison_rows": {
        "arg": "F",
        "injects_at": "online ingest, after parse / before quarantine "
                      "(maybe_poison_rows; fraction F of every chunk)"},
    "label_flip": {
        "arg": "K",
        "injects_at": "online cycle K's training-window labels "
                      "(maybe_flip_labels in the continuous trainer)"},
    "regress_model": {
        "arg": "K",
        "injects_at": "continuous trainer's publish seam, AFTER the "
                      "eval gate (maybe_regress_model on cycle K's "
                      "model text)"},
    "die_at_spawn": {
        "arg": "K",
        "injects_at": "ServingRuntime.start, after the prewarm pass and "
                      "BEFORE /healthz readiness (maybe_die_at_spawn on "
                      "the K-th fleet spawn ordinal)"},
    "die_at_ring": {
        "arg": "K",
        "injects_at": "ShmClient ring produce, right after the K-th "
                      "request frame is published with its response "
                      "unread (maybe_die_at_ring) — the crashed-client "
                      "reclamation path"},
}

FAULT_NAMES = tuple(FAULT_TABLE)


def _fault_spec() -> Dict[str, Optional[str]]:
    """Parse LGBM_TPU_FAULT on every call (cheap, and lets tests flip the
    environment without any cache-busting protocol)."""
    raw = os.environ.get("LGBM_TPU_FAULT", "")
    if not raw:
        return {}
    out: Dict[str, Optional[str]] = {}
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        name, _, arg = tok.partition(":")
        if name not in FAULT_NAMES:
            raise ValueError(
                "unknown fault %r in LGBM_TPU_FAULT=%r (known: %s)"
                % (name, raw, ", ".join(FAULT_NAMES)))
        out[name] = arg if arg != "" else None
    return out


def fault_active(name: str) -> bool:
    return name in _fault_spec()


def fault_arg(name: str, default: Optional[str] = None) -> Optional[str]:
    spec = _fault_spec()
    if name not in spec:
        return default
    return spec[name] if spec[name] is not None else default


def maybe_die_or_preempt(booster) -> None:
    """Training-loop fault hooks, called at every iteration boundary
    (Booster.update entry):

    * ``die_at_iter:K`` — an abrupt, snapshot-less death (power loss /
      OOM-killer model) once K iterations are complete: `os._exit(137)`.
    * ``sigterm_at_iter:K`` — a graceful preemption notice: SIGTERM is
      delivered to this process, which the PreemptionGuard turns into
      write-final-snapshot-then-exit at the iteration boundary.
    """
    spec = _fault_spec()
    if "die_at_iter" not in spec and "sigterm_at_iter" not in spec:
        return
    eng = getattr(booster, "_engine", None)
    if eng is None:
        return
    # an armed fault counts COMPLETED iterations: drain the dispatch
    # pipeline so the count (and the state a die/preempt leaves behind)
    # is the synchronous loop's
    eng.flush()
    done = int(eng.model.current_iteration)
    if "die_at_iter" in spec and done >= int(spec["die_at_iter"] or 0):
        sys.stderr.write("[%s] FAULT die_at_iter: abrupt exit after %d "
                         "iterations\n" % (wallclock(), done))
        sys.stderr.flush()
        os._exit(137)
    if "sigterm_at_iter" in spec and done == int(spec["sigterm_at_iter"] or 0):
        sys.stderr.write("[%s] FAULT sigterm_at_iter: delivering SIGTERM "
                         "after %d iterations\n" % (wallclock(), done))
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGTERM)


def maybe_corrupt_snapshot(path: str, total_iter: int) -> None:
    """`corrupt_snapshot[:K]` truncates the snapshot written at iteration
    K (every snapshot when K is omitted) AFTER the atomic rename —
    modeling a snapshot that landed on disk torn (e.g. the filesystem
    died mid-durability).  Resume must detect it via the checksum and
    fall back to the previous valid snapshot."""
    if not fault_active("corrupt_snapshot"):
        return
    arg = fault_arg("corrupt_snapshot")
    if arg is not None and int(arg) != int(total_iter):
        return
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(max(size // 2, 1))
    sys.stderr.write("[%s] FAULT corrupt_snapshot: truncated %s to %d "
                     "bytes\n" % (wallclock(), path, max(size // 2, 1)))


def maybe_inject_nan(engine, host: Dict) -> None:
    """`nan_grad:K` poisons iteration K's fetched tree outputs the way a
    non-finite gradient burst would (NaN grads -> NaN histogram sums ->
    NaN leaf values) so the sentinel's detection + policy machinery is
    exercised end-to-end."""
    if not fault_active("nan_grad"):
        return
    if int(engine.iter) != int(fault_arg("nan_grad", "0")):
        return
    host["leaf_value"] = host["leaf_value"].copy()
    host["leaf_value"][:] = float("nan")


#: stages already stalled by `slow_stage` this process — the injection is
#: one-shot per process (it models a transient stall, e.g. a filesystem
#: hiccup; a permanent stall would just crash-loop the service and prove
#: nothing about recovery).
_SLOW_STAGES_FIRED: set = set()


def maybe_slow_stage(stage_name: str, defer: bool = False) -> float:
    """`slow_stage:NAME:SECS` stalls the first stage whose name contains
    NAME for SECS seconds — long enough to blow the stage's watchdog
    deadline, which is the point: the service must surface the timeout in
    the stage trail and carry on with the next cycle.  Returns the
    injected stall (0.0 when nothing fired); `defer=True` skips the sleep
    so the caller can record the injection in its stage trail FIRST (the
    watchdog alarm lands mid-sleep, after which nothing else runs)."""
    if not fault_active("slow_stage"):
        return 0.0
    arg = fault_arg("slow_stage", "")
    name, _, secs = (arg or "").partition(":")
    if not name or name not in stage_name or name in _SLOW_STAGES_FIRED:
        return 0.0
    _SLOW_STAGES_FIRED.add(name)
    stall = float(secs or "5")
    sys.stderr.write("[%s] FAULT slow_stage: stalling stage %r for %.1fs\n"
                     % (wallclock(), stage_name, stall))
    sys.stderr.flush()
    if not defer:
        time.sleep(stall)
    return stall


def maybe_torn_publish(path: str, body: str, publish_count: int) -> None:
    """`torn_write[:K]` models a publisher whose K-th publish (1-based;
    every publish when K is omitted) lands TORN on disk and whose process
    dies before it can repair anything: half the body is written straight
    to the FINAL path (no tmp, no fsync, no rename — exactly the
    non-atomic write the real publisher never performs) and the process
    exits abruptly.  Subscribers must reject the torn generation via its
    checksum; the relaunched publisher must republish it."""
    if not fault_active("torn_write"):
        return
    arg = fault_arg("torn_write")
    if arg is not None and int(arg) != int(publish_count):
        return
    with open(path, "w") as fh:
        fh.write(body[: max(len(body) // 2, 1)])
    sys.stderr.write("[%s] FAULT torn_write: tore publish #%d at %s and "
                     "dying\n" % (wallclock(), publish_count, path))
    sys.stderr.flush()
    os._exit(137)


def maybe_die_at_publish(publish_count: int) -> None:
    """`die_at_publish:K` kills the process BETWEEN the generation file's
    atomic rename and the manifest update of the K-th publish (1-based) —
    the window where the newest valid generation on disk is ahead of the
    manifest pointer.  Subscribers must still resolve a valid model and
    the relaunched publisher must reconcile."""
    if not fault_active("die_at_publish"):
        return
    if int(fault_arg("die_at_publish", "1")) != int(publish_count):
        return
    sys.stderr.write("[%s] FAULT die_at_publish: abrupt exit mid-publish "
                     "#%d (generation renamed, manifest stale)\n"
                     % (wallclock(), publish_count))
    sys.stderr.flush()
    os._exit(137)


def maybe_die_at_spawn(spawn_ordinal: Optional[int] = None) -> None:
    """`die_at_spawn:K` kills a serving replica AFTER its prewarm pass and
    BEFORE /healthz flips ready (ISSUE 17) — the window where a fleet
    controller has paid the spawn cost but admitted no traffic.  The
    controller must detect the dead child and relaunch without ever
    routing to it.

    ``spawn_ordinal`` is the fleet-wide 1-based spawn sequence number,
    normally delivered by the spawner through ``LGBM_TPU_SPAWN_ORDINAL``
    (each replica is a fresh process, so a process-local counter could
    never reach K > 1)."""
    if not fault_active("die_at_spawn"):
        return
    if spawn_ordinal is None:
        try:
            spawn_ordinal = int(os.environ.get("LGBM_TPU_SPAWN_ORDINAL",
                                               "1") or 1)
        except ValueError:
            spawn_ordinal = 1
    if int(fault_arg("die_at_spawn", "1")) != int(spawn_ordinal):
        return
    sys.stderr.write("[%s] FAULT die_at_spawn: abrupt exit during spawn "
                     "#%d (prewarmed, never ready)\n"
                     % (wallclock(), spawn_ordinal))
    sys.stderr.flush()
    os._exit(137)


def maybe_die_at_ring(frames_in_flight: int) -> None:
    """`die_at_ring:K` kills an SHM ring client the instant its K-th
    request frame is PUBLISHED with the response still unread (ISSUE 20)
    — the worst reclamation case: the server holds a mapped segment with
    live admissions aliasing it and a peer that will never drain the
    response ring.  The server must detect the death on the control
    socket, drain the in-flight work, unmap with zero leaked mappings
    and keep every other client byte-verified."""
    if not fault_active("die_at_ring"):
        return
    if int(fault_arg("die_at_ring", "1")) != int(frames_in_flight):
        return
    sys.stderr.write("[%s] FAULT die_at_ring: abrupt client exit with "
                     "%d frames in flight in the ring\n"
                     % (wallclock(), frames_in_flight))
    sys.stderr.flush()
    os._exit(137)


#: device-predict fault bookkeeping: batches seen while die_at_predict is
#: armed (the victim is the predict CALL, never the process — a serving
#: runtime must survive device loss, which is the point of the injection)
_PREDICT_FAULT = {"batches": 0}


class DevicePredictFault(RuntimeError):
    """The injected stand-in for an XLA device failure mid-predict
    (`LGBM_TPU_FAULT=die_at_predict`): the serving runtime must catch it,
    trip its circuit breaker, and answer from the host predictor."""


def maybe_fail_predict() -> None:
    """Serving fault seam, consulted at every device-predict micro-batch
    boundary (models/device_predictor.py predict_raw):

    * ``slow_predict:SECS`` — stalls EVERY device batch by SECS while
      armed (a degraded device, cleared by clearing the env var); long
      enough to blow the serving runtime's predict deadline, which is
      the point: the batch must be re-served from the host path and the
      timeout must land in the serving stage trail.
    * ``die_at_predict:K`` — the K-th device batch (1-based, counted
      while armed) and every later one raise `DevicePredictFault`; the
      serving runtime must degrade to the host predictor and recover to
      the device path once the fault clears.
    """
    spec = _fault_spec()
    if "slow_predict" in spec:
        stall = float(spec["slow_predict"] or "5")
        sys.stderr.write("[%s] FAULT slow_predict: stalling device batch "
                         "for %.1fs\n" % (wallclock(), stall))
        sys.stderr.flush()
        time.sleep(stall)
    if "die_at_predict" in spec:
        _PREDICT_FAULT["batches"] += 1
        if _PREDICT_FAULT["batches"] >= int(spec["die_at_predict"] or "1"):
            sys.stderr.write("[%s] FAULT die_at_predict: failing device "
                             "batch #%d\n"
                             % (wallclock(), _PREDICT_FAULT["batches"]))
            sys.stderr.flush()
            raise DevicePredictFault(
                "injected device predict failure "
                "(LGBM_TPU_FAULT=die_at_predict, batch #%d)"
                % _PREDICT_FAULT["batches"])


def maybe_poison_rows(X, y):
    """`poison_rows:F` corrupts fraction F of every parsed ingest chunk
    the way an upstream logging outage would: a deterministic stride of
    rows gets a non-finite label (alternating NaN / +inf so both spellings
    are exercised).  The quarantine (ISSUE 12 stage one) must route every
    poisoned row to the ledger — a single NaN label reaching a histogram
    poisons every split under it.  Returns (y, n_poisoned); X is
    returned untouched (NaN FEATURES are legitimate missing values and
    are deliberately not part of this fault)."""
    if not fault_active("poison_rows") or y is None or len(y) == 0:
        return y, 0
    frac = float(fault_arg("poison_rows", "0.1"))
    if frac <= 0:
        return y, 0
    stride = max(int(round(1.0 / min(frac, 1.0))), 1)
    import numpy as np
    y = np.array(y, dtype=np.float64, copy=True)
    idx = np.arange(0, len(y), stride)
    y[idx[0::2]] = float("nan")
    y[idx[1::2]] = float("inf")
    sys.stderr.write("[%s] FAULT poison_rows: poisoned %d/%d labels\n"
                     % (wallclock(), len(idx), len(y)))
    sys.stderr.flush()
    return y, int(len(idx))


def maybe_flip_labels(y, cycle: int):
    """`label_flip:K` inverts the training labels of cycle K's window —
    valid-looking values carrying wrong information, the data bug the
    ingest quarantine CANNOT catch (every row passes schema validation).
    The pre-publish eval gate (ISSUE 12 stage two) is the defense: the
    model trained on flipped labels regresses on the holdout and must
    not be published.  Returns (y, flipped?)."""
    if not fault_active("label_flip") or y is None or len(y) == 0:
        return y, False
    if int(fault_arg("label_flip", "0")) != int(cycle):
        return y, False
    import numpy as np
    y = np.asarray(y, dtype=np.float64)
    flipped = (float(np.max(y)) + float(np.min(y))) - y
    sys.stderr.write("[%s] FAULT label_flip: inverted cycle %d's %d "
                     "labels\n" % (wallclock(), cycle, len(y)))
    sys.stderr.flush()
    return flipped, True


def maybe_regress_model(model_text: str, cycle: int) -> str:
    """`regress_model:K` sabotages cycle K's model text at the publish
    seam, AFTER the eval gate has judged the (clean) candidate — the
    regression the offline gate cannot see and only the serving canary
    (ISSUE 12 stage three) can catch.  Every `leaf_value=` line is
    rescaled by -2, so the published generation is a VALID, loadable
    model whose live predictions are badly wrong.  The canary must roll
    the fleet back to the prior generation."""
    if not fault_active("regress_model"):
        return model_text
    if int(fault_arg("regress_model", "0")) != int(cycle):
        return model_text
    lines = model_text.split("\n")
    for i, line in enumerate(lines):
        if line.startswith("leaf_value="):
            vals = ["%.17g" % (-2.0 * float(tok))
                    for tok in line[len("leaf_value="):].split()]
            lines[i] = "leaf_value=" + " ".join(vals)
    sys.stderr.write("[%s] FAULT regress_model: sabotaged cycle %d's "
                     "leaf values at the publish seam\n"
                     % (wallclock(), cycle))
    sys.stderr.flush()
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# stage watchdog
# ---------------------------------------------------------------------------

class StageTimeout(RuntimeError):
    """A watchdogged stage exceeded its deadline (soft mode)."""

    def __init__(self, stage: str, seconds: float):
        super().__init__("stage %r exceeded its %ds deadline"
                         % (stage, seconds))
        self.stage = stage
        self.seconds = seconds


#: hard-mode exit code.  Deliberately NOT 124 (the driver's bare-timeout
#: code): rc 73 means "the stage watchdog fired and the diagnostics are in
#: the stage report / stderr", never "something hung silently".
WATCHDOG_EXIT_CODE = 73


def _dump_all_threads() -> str:
    """faulthandler tracebacks of every thread, as text."""
    import faulthandler
    with tempfile.TemporaryFile(mode="w+") as fh:
        faulthandler.dump_traceback(file=fh, all_threads=True)
        fh.seek(0)
        return fh.read()


class Watchdog:
    """Per-stage SIGALRM watchdog with a persistent stage trail.

    ``wd(name)`` (or ``wd.stage(name, seconds)``) opens a named stage
    under a deadline; a hung stage prints its name, dumps faulthandler
    tracebacks of all threads, rewrites the JSON report (when
    ``report_path`` is set) and then either raises `StageTimeout`
    (``hard=False`` — host processes, where killing the interpreter would
    kill the DRIVER) or kills the whole process group with
    `WATCHDOG_EXIT_CODE` (``hard=True`` — disposable subprocesses).

    The report is rewritten at every stage TRANSITION too, so even a
    SIGKILL'd process leaves a trail naming the stage it died in.

    **Thread mode** (`use_alarm=False`, auto-selected off the main
    thread): SIGALRM cannot be armed outside the main thread, so the
    watchdog keeps only the trail bookkeeping and the OWNER enforces
    deadlines itself (e.g. a bounded queue wait), reporting expiries via
    `record_timeout()` — same trail semantics as a fired alarm (stage
    closed as timeout, all-thread tracebacks captured, report persisted)
    but it never raises or exits.  `keep_last=N` bounds the trail for
    long-lived owners (a serving runtime opening one stage per batch
    must not grow its flight recorder without bound); dropped entries
    are counted in the report.
    """

    def __init__(self, seconds: int, hard: bool = False,
                 report_path: Optional[str] = None,
                 kill_process_group: bool = False,
                 label: str = "stage", stream=None,
                 use_alarm: Optional[bool] = None,
                 keep_last: Optional[int] = None):
        self.seconds = int(seconds)
        self.hard = hard
        self.report_path = report_path or os.environ.get(
            "LGBM_TPU_STAGE_REPORT")
        self.kill_process_group = kill_process_group
        self.label = label
        self.stream = stream  # None -> sys.stdout at emit time
        if use_alarm is None:
            use_alarm = (hasattr(signal, "SIGALRM") and threading
                         .current_thread() is threading.main_thread())
        self.use_alarm = bool(use_alarm)
        self.keep_last = keep_last
        self.dropped_stages = 0
        self.stage = "<init>"
        self.stages: List[Dict[str, Any]] = []
        self.tracebacks: Optional[str] = None
        self._t0: Optional[float] = None

    # -- trail bookkeeping ---------------------------------------------------
    def _close_current(self, status: str) -> None:
        if self._t0 is not None and self.stages:
            dur = round(time.monotonic() - self._t0, 3)
            self.stages[-1]["dur_s"] = dur
            self.stages[-1]["status"] = status
            # every stage close is ALSO a span in the metrics registry
            # (ISSUE 9): stages, spans and scraped metrics share one
            # clock and one naming scheme.  Lazy import — telemetry
            # imports helpers from THIS module at its module scope.
            try:
                from . import telemetry
                telemetry.record_span(
                    "%s/%s" % (self.label, self.stages[-1]["name"]),
                    dur, status=status)
            except Exception:            # noqa: BLE001 — never fatal
                pass
        self._t0 = None

    def report(self) -> Dict[str, Any]:
        rep: Dict[str, Any] = {"stages": self.stages, "culprit": None}
        for st in self.stages:
            if st.get("status") in ("timeout", "running", "error"):
                rep["culprit"] = st["name"]
        if self.dropped_stages:
            rep["dropped_stages"] = self.dropped_stages
        if self.tracebacks is not None:
            rep["tracebacks"] = self.tracebacks
        return rep

    def _persist(self) -> None:
        if not self.report_path:
            return
        try:
            atomic_write(self.report_path,
                         json.dumps(self.report(), indent=1))
        except OSError:
            pass  # report persistence must never take the run down

    # -- stage transitions ---------------------------------------------------
    def __call__(self, stage: str, seconds: Optional[int] = None) -> None:
        """Open `stage` under a deadline (default: the watchdog's),
        closing the previous stage as ok."""
        self._close_current("ok")
        budget = int(seconds if seconds is not None else self.seconds)
        self.stage = stage
        self.stages.append({"name": stage, "t_start": wallclock(),
                            "budget_s": budget, "status": "running"})
        if self.keep_last and len(self.stages) > self.keep_last:
            drop = len(self.stages) - self.keep_last
            del self.stages[:drop]
            self.dropped_stages += drop
        self._t0 = time.monotonic()
        out = self.stream if self.stream is not None else sys.stdout
        out.write("[%s] %s: %s (budget %ds)\n"
                  % (wallclock(), self.label, stage, budget))
        out.flush()
        self._persist()
        if self.use_alarm:
            if budget > 0:
                signal.signal(signal.SIGALRM, self._fire)
                signal.alarm(budget)
            else:
                # an UNBOUNDED stage must disarm the previous stage's
                # alarm — otherwise it fires minutes later and blames
                # this stage for the last one's deadline
                signal.alarm(0)

    def annotate(self, key: str, value: Any) -> None:
        """Attach structured evidence (sync-audit deltas, injected-fault
        notes, publish latencies) to the CURRENT stage's trail entry and
        re-persist — the stage trail is the service's flight recorder, so
        per-stage measurements belong in it, not in a side channel."""
        if self.stages:
            self.stages[-1][key] = value
            self._persist()

    @contextlib.contextmanager
    def stage_scope(self, stage: str, seconds: Optional[int] = None):
        """Context-manager spelling; closes the stage on exit.  The alarm
        is disarmed on EVERY exit path — an armed alarm escaping the
        scope would fire minutes later in unrelated code."""
        self(stage, seconds)
        try:
            yield
        except StageTimeout:
            raise
        except BaseException:
            if self.use_alarm:
                signal.alarm(0)
            self._close_current("error")
            self._persist()
            raise
        else:
            self.done(final=False)

    def _fire(self, signum, frame):
        self._close_current("timeout")
        self.tracebacks = _dump_all_threads()
        msg = ("[%s] WATCHDOG: %s %r exceeded its deadline; thread "
               "tracebacks follow\n%s"
               % (wallclock(), self.label, self.stage, self.tracebacks))
        sys.stderr.write(msg)
        sys.stderr.flush()
        self._persist()
        if self.hard:
            if self.kill_process_group:
                try:
                    # children first (the hang may live in a grandchild);
                    # this process dies of its own SIGKILL last
                    os.killpg(os.getpgid(0), signal.SIGKILL)
                except (OSError, PermissionError):
                    pass
            os._exit(WATCHDOG_EXIT_CODE)
        raise StageTimeout(self.stage, self.stages[-1]["budget_s"]
                           if self.stages else self.seconds)

    def record_timeout(self, note: Optional[str] = None) -> None:
        """Thread-mode deadline expiry: the owner enforced the deadline
        itself (a bounded wait on the batch's completion event, say) and
        reports it here — the CURRENT stage closes as ``timeout`` with
        all-thread tracebacks captured and the report persisted, exactly
        like a fired alarm, but nothing raises and nothing exits (the
        owner is a long-lived server that must carry on)."""
        self._close_current("timeout")
        if note and self.stages:
            self.stages[-1]["note"] = note
        self.tracebacks = _dump_all_threads()
        sys.stderr.write("[%s] WATCHDOG: %s %r exceeded its deadline "
                         "(thread mode)%s\n"
                         % (wallclock(), self.label, self.stage,
                            ": " + note if note else ""))
        sys.stderr.flush()
        self._persist()

    def done(self, final: bool = True) -> None:
        """Disarm the alarm (MUST run before the watchdog owner returns:
        an orphaned SIGALRM would hard-kill the host minutes later)."""
        if self.use_alarm:
            signal.alarm(0)
            if final:
                signal.signal(signal.SIGALRM, signal.SIG_DFL)
        self._close_current("ok")
        if final:
            self._persist()


# ---------------------------------------------------------------------------
# retry backoff
# ---------------------------------------------------------------------------

def backoff_delays(attempts: int, base: float = 1.0, cap: float = 8.0,
                   seed: int = 0) -> List[float]:
    """Deterministic jittered exponential backoff (full-jitter flavour,
    but seeded so tests and multi-process ranks are reproducible)."""
    delays = []
    state = (seed * 2654435761 + 12345) & 0xFFFFFFFF
    for a in range(max(attempts - 1, 0)):
        state = (state * 1103515245 + 12345) & 0x7FFFFFFF
        frac = 0.5 + (state / 0x7FFFFFFF) * 0.5          # [0.5, 1.0)
        delays.append(round(min(cap, base * (2 ** a)) * frac, 2))
    return delays


# ---------------------------------------------------------------------------
# atomic snapshot writes + checksum + retention
# ---------------------------------------------------------------------------

def atomic_write(path: str, text: str) -> None:
    """tmp + flush + fsync + rename in the destination directory: a
    crash at any point leaves either the old file or the new one, never
    a torn half-write, and never a stray ``*.snapshot_iter_*`` tmp."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(prefix=".%s.tmp" % os.path.basename(path),
                               dir=d)
    try:
        with os.fdopen(fd, "w") as fh:
            fh.write(text)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def read_stage_report(path: str) -> Optional[Dict[str, Any]]:
    """Tolerant stage-trail reader for scrapers and artifact wrappers:
    returns the report dict, or None for a missing, unreadable, torn or
    non-JSON file.  Writers go through `atomic_write`, so a torn file
    means a non-cooperating writer (or a dying filesystem) — the reader
    must degrade to "no trail", never crash the post-mortem."""
    try:
        with open(path) as fh:
            rep = json.load(fh)
    except (OSError, ValueError):
        return None
    return rep if isinstance(rep, dict) else None


_STATE_PREFIX = "!snapshot_state="
_CHECKSUM_PREFIX = "!snapshot_checksum=sha256:"


def _with_footer(model_text: str, state: Dict[str, Any]) -> str:
    """Model text + state footer + checksum line.  The footer lives past
    'end of trees', where the model parser only greps for the parameters
    block — a snapshot file IS a loadable model file."""
    blob = base64.b64encode(
        zlib.compress(json.dumps(state).encode())).decode()
    body = model_text
    if not body.endswith("\n"):
        body += "\n"
    body += _STATE_PREFIX + blob + "\n"
    digest = hashlib.sha256(body.encode()).hexdigest()
    return body + _CHECKSUM_PREFIX + digest + "\n"


def validate_snapshot(path: str) -> Tuple[bool, str]:
    """(ok, reason).  A snapshot is valid iff it ends with a checksum
    line whose sha256 matches everything before it and its state footer
    decodes — truncated, torn and bit-flipped files all fail."""
    try:
        with open(path, "rb") as fh:
            raw = fh.read()
    except OSError as e:
        return False, "unreadable: %s" % e
    text = raw.decode("utf-8", "replace")
    lines = text.rstrip("\n").split("\n")
    if not lines or not lines[-1].startswith(_CHECKSUM_PREFIX):
        return False, "missing checksum footer (truncated?)"
    digest = lines[-1][len(_CHECKSUM_PREFIX):].strip()
    body = text[: text.rfind(_CHECKSUM_PREFIX)]
    if hashlib.sha256(body.encode()).hexdigest() != digest:
        return False, "checksum mismatch (torn or corrupted write)"
    if load_snapshot_state(path, _prevalidated_text=text) is None:
        return False, "state footer missing or undecodable"
    return True, "ok"


def load_snapshot_state(path: str, _prevalidated_text: Optional[str] = None
                        ) -> Optional[Dict[str, Any]]:
    """The state dict from a snapshot's footer, or None."""
    try:
        if _prevalidated_text is None:
            with open(path) as fh:
                _prevalidated_text = fh.read()
        for line in reversed(_prevalidated_text.rstrip("\n").split("\n")):
            if line.startswith(_STATE_PREFIX):
                blob = line[len(_STATE_PREFIX):].strip()
                return json.loads(zlib.decompress(
                    base64.b64decode(blob)).decode())
    except (OSError, ValueError, zlib.error, json.JSONDecodeError):
        return None
    return None


def snapshot_model_text(path: str) -> Optional[str]:
    """The model-text portion of a snapshot file (everything before the
    state footer) — what `save_model_to_string()` produced at capture
    time, byte-for-byte.  The continuous trainer republishes from this
    after a death between snapshot and publish, so the republished
    generation is byte-identical to what the dead process would have
    published.  None when the file has no footer (not a snapshot)."""
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError:
        return None
    cut = text.find(_STATE_PREFIX)
    if cut < 0:
        return None
    return text[:cut]


def snapshot_paths(output_model: str) -> List[Tuple[int, str]]:
    """Existing ``<output_model>.snapshot_iter_<N>`` files, newest first."""
    d = os.path.dirname(os.path.abspath(output_model)) or "."
    base = os.path.basename(output_model) + ".snapshot_iter_"
    out = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for name in names:
        if name.startswith(base):
            tail = name[len(base):]
            if tail.isdigit():
                out.append((int(tail), os.path.join(d, name)))
    out.sort(reverse=True)
    return out


def find_resume_snapshot(output_model: str, log=None
                         ) -> Tuple[Optional[str], Optional[Dict[str, Any]]]:
    """Newest VALID snapshot for `output_model`, scanning past corrupt /
    truncated ones with a logged warning for each."""
    def warn(msg, *args):
        if log is not None:
            log.warning(msg, *args)
        else:
            sys.stderr.write("resilience: " + (msg % args) + "\n")

    for it, path in snapshot_paths(output_model):
        ok, reason = validate_snapshot(path)
        if ok:
            return path, load_snapshot_state(path)
        warn("snapshot %s is invalid (%s); falling back to the previous "
             "one", path, reason)
    return None, None


# ---------------------------------------------------------------------------
# training-state capture / restore (byte-identical resume)
# ---------------------------------------------------------------------------

def _b64_np(arr) -> str:
    import numpy as np
    a = np.ascontiguousarray(arr)
    return base64.b64encode(zlib.compress(a.tobytes())).decode()


def _np_b64(blob: str, dtype, shape):
    import numpy as np
    raw = zlib.decompress(base64.b64decode(blob))
    return np.frombuffer(raw, dtype=dtype).reshape(shape).copy()


def _rng_state_to_json(rng) -> Dict[str, Any]:
    """numpy Generator (Philox) state -> JSON-able dict."""
    import numpy as np

    def conv(v):
        if isinstance(v, dict):
            return {k: conv(x) for k, x in v.items()}
        if isinstance(v, np.ndarray):
            return {"__nd__": v.dtype.str, "data": v.tolist()}
        if isinstance(v, (np.integer,)):
            return int(v)
        return v

    return conv(rng._rng.bit_generator.state)


def _rng_state_from_json(rng, state: Dict[str, Any]) -> None:
    import numpy as np

    def conv(v):
        if isinstance(v, dict):
            if "__nd__" in v:
                return np.asarray(v["data"], dtype=np.dtype(v["__nd__"]))
            return {k: conv(x) for k, x in v.items()}
        return v

    rng._rng.bit_generator.state = conv(state)


def _params_fingerprint(raw_params: Dict[str, Any]) -> str:
    items = sorted((str(k), str(v)) for k, v in raw_params.items())
    return hashlib.sha256(json.dumps(items).encode()).hexdigest()[:16]


def capture_training_state(booster) -> Dict[str, Any]:
    """Everything a resumed run needs to continue BYTE-IDENTICALLY to an
    uninterrupted one, beyond the trees themselves: the padded raw score
    planes, the fast path's payload row order (histogram accumulation is
    f32 and therefore order-sensitive), the bagging mask + both host RNG
    streams, and the boosting variant's bookkeeping (DART drop RNG /
    tree weights).  Mesh runs skip the row order (rows are reordered per
    shard) — resume still works, but exactness is only guaranteed for
    serial training; the state records which case it captured."""
    import numpy as np
    from . import syncs
    eng = booster._engine
    if eng is None:
        raise RuntimeError("capture_training_state needs a training Booster")
    # snapshots observe the model AND the scores: drain the dispatch
    # pipeline first (flush barrier contract, ISSUE 5) and settle any
    # open boosting window at the reported iteration (ISSUE 13)
    eng.flush(sync_scores=True)
    if eng._fast_active:
        score = eng._fast.raw_scores()                      # [K, n_pad] f32
        perm = (eng._fast.host_idx().astype(np.int32)
                if eng.mesh is None else None)
    else:
        score = np.asarray(syncs.device_get(eng.score, label="snapshot"),
                           np.float32)
        perm = None
    state: Dict[str, Any] = {
        "version": 1,
        "total_iter": int(eng.model.current_iteration),
        "boosting": type(eng).__name__,
        "K": int(eng.num_tree_per_iteration),
        "n_pad": int(eng.train_set.num_data_padded),
        "num_data": int(eng.train_set.num_data),
        "score": _b64_np(score),
        "perm": _b64_np(perm) if perm is not None else None,
        "perm_len": int(perm.size) if perm is not None else 0,
        "bag_mask": _b64_np(np.packbits(eng.bag_mask_host > 0)),
        "bagging_rng": _rng_state_to_json(eng.bagging_rng),
        "feature_rng": _rng_state_to_json(eng.feature_rng),
        "shrinkage_rate": float(eng.shrinkage_rate),
        "boosted_from_average": bool(eng._boosted_from_average),
        "init_score_value": float(eng.init_score_value),
        "params_fingerprint": _params_fingerprint(
            getattr(eng.config, "raw_params", {})),
    }
    if hasattr(eng, "random_for_drop"):                     # DART
        state["dart"] = {
            "drop_rng": _rng_state_to_json(eng.random_for_drop),
            "tree_weight": [float(w) for w in eng.tree_weight],
            "sum_weight": float(eng.sum_weight),
        }
    return state


def restore_training_state(booster, state: Dict[str, Any], log=None) -> None:
    """Surgery on a freshly constructed Booster (init_model = the
    snapshot's trees) that makes its next iteration arithmetically
    identical to the uninterrupted run's:

    * the padded raw scores are installed verbatim (the init replay's
      f32 re-quantization of f64 leaf values is overwritten);
    * the iteration counter moves to the engine-global clock
      (``iter = total, num_init_iteration = 0``) so bagging schedules,
      GOSS warmup/fold-in and DART drop candidates see the same history
      an uninterrupted run would;
    * both host RNG streams (bagging / feature sampling) and the DART
      drop RNG + tree-weight ledger resume mid-stream;
    * on the serial fast path, the payload is rebuilt and then permuted
      into the EXACT row order the snapshot captured — f32 histogram
      accumulation is order-sensitive, so row order is training state.
    """
    import jax.numpy as jnp
    import numpy as np

    def warn(msg, *args):
        if log is not None:
            log.warning(msg, *args)
        else:
            sys.stderr.write("resilience: " + (msg % args) + "\n")

    eng = booster._engine
    if eng is None:
        raise RuntimeError("restore_training_state needs a training Booster")
    K, n_pad = int(state["K"]), int(state["n_pad"])
    if (K != eng.num_tree_per_iteration
            or n_pad != eng.train_set.num_data_padded
            or int(state["num_data"]) != eng.train_set.num_data):
        warn("snapshot shape (K=%d, n_pad=%d) does not match this dataset "
             "(K=%d, n_pad=%d); resuming with plain continued-training "
             "semantics instead", K, n_pad, eng.num_tree_per_iteration,
             eng.train_set.num_data_padded)
        return
    fp = _params_fingerprint(getattr(eng.config, "raw_params", {}))
    if state.get("params_fingerprint") not in (None, fp):
        warn("training parameters differ from the snapshot's; the resumed "
             "model may not be byte-identical to an uninterrupted run")

    eng.score = jnp.asarray(_np_b64(state["score"], np.float32, (K, n_pad)))
    eng.iter = int(state["total_iter"])
    eng.num_init_iteration = 0
    eng.shrinkage_rate = float(state["shrinkage_rate"])
    eng._boosted_from_average = bool(state["boosted_from_average"])
    eng.init_score_value = float(state["init_score_value"])
    bag_bits = _np_b64(state["bag_mask"], np.uint8, (-1,))
    mask = np.unpackbits(bag_bits)[:n_pad].astype(np.float32)
    eng.bag_mask_host = mask
    eng._bag_cmask = jnp.asarray(mask)
    _rng_state_from_json(eng.bagging_rng, state["bagging_rng"])
    _rng_state_from_json(eng.feature_rng, state["feature_rng"])
    if "dart" in state and hasattr(eng, "random_for_drop"):
        _rng_state_from_json(eng.random_for_drop, state["dart"]["drop_rng"])
        eng.tree_weight = [float(w) for w in state["dart"]["tree_weight"]]
        eng.sum_weight = float(state["dart"]["sum_weight"])

    if state.get("perm") and eng.mesh is None and eng._fast_eligible():
        fs = eng._fast_enter()          # identity-ordered fresh payload
        perm = _np_b64(state["perm"], np.int32, (int(state["perm_len"]),))
        if perm.size == fs.n_rows:
            # row j of the uninterrupted payload held original row
            # perm[j]; guard rows (idx == n_pad) all share one dead-slot
            # content, so any guard position sources them
            src = np.where(perm < n_pad, perm, n_pad).astype(np.int32)
            fs.payload = jnp.take(fs.payload, jnp.asarray(src), axis=0)
            fs._bag_dirty = True
        else:
            warn("snapshot payload order length %d does not match the "
                 "rebuilt payload (%d rows); resuming in identity order "
                 "(model may differ in low-order bits)",
                 perm.size, fs.n_rows)


def make_resume_callback(state: Dict[str, Any], log=None):
    """A before_iteration callback that performs the restore exactly once,
    before the first resumed iteration runs (the train() driver owns
    Booster construction, so this is the earliest seam)."""
    done = {"flag": False}

    def _callback(env) -> None:
        if done["flag"]:
            return
        done["flag"] = True
        restore_training_state(env.model, state, log=log)

    _callback.before_iteration = True
    _callback.order = 0
    return _callback


def write_snapshot(booster, output_model: str, total_iter: Optional[int] = None,
                   retention: int = -1, log=None,
                   extra_state: Optional[Dict[str, Any]] = None,
                   retention_grace_s: float = 0.0) -> Optional[str]:
    """Atomic snapshot ``<output_model>.snapshot_iter_<N>`` carrying the
    model plus the resume state footer, with keep-last-`retention`
    cleanup (``<= 0`` keeps everything).  Refuses to snapshot non-finite
    scores (a poisoned snapshot would just re-poison the resume).

    `extra_state` is merged under the footer's ``"service"`` key — the
    continuous trainer records its schedule clock there; resume ignores
    unknown keys, so plain `task=train` snapshots are unaffected.

    `retention_grace_s > 0` hardens keep-last-K against concurrent
    readers: a snapshot beyond the K newest is only unlinked once it is
    also OLDER than the grace window, so a reader that just resolved a
    path (a resume scan racing the trainer, a debugging copy) cannot
    have the file deleted out from under it mid-read.  The default 0
    keeps the historical behavior for batch training, where pruning only
    runs in the single writer process."""
    import numpy as np
    state = capture_training_state(booster)
    if extra_state:
        state["service"] = dict(extra_state)
    if total_iter is None:
        total_iter = state["total_iter"]
    score = _np_b64(state["score"], np.float32,
                    (state["K"], state["n_pad"]))
    if not np.isfinite(score).all():
        if log is not None:
            log.warning("scores are non-finite at iteration %d; snapshot "
                        "NOT written", total_iter)
        return None
    path = "%s.snapshot_iter_%d" % (output_model, total_iter)
    atomic_write(path, _with_footer(
        booster._model.save_model_to_string(), state))
    maybe_corrupt_snapshot(path, total_iter)
    if retention > 0:
        cutoff = time.time() - max(retention_grace_s, 0.0)
        for it, old in snapshot_paths(output_model)[retention:]:
            with contextlib.suppress(OSError):
                if retention_grace_s <= 0 or os.path.getmtime(old) < cutoff:
                    os.unlink(old)
    return path


# ---------------------------------------------------------------------------
# preemption guard (SIGTERM/SIGINT -> final snapshot -> exit)
# ---------------------------------------------------------------------------

class TrainingPreempted(Exception):
    """Raised at the iteration boundary after a preemption signal; the
    final snapshot has already been written when this propagates."""

    def __init__(self, signum: int, iteration: int,
                 snapshot: Optional[str]):
        super().__init__("training preempted by signal %d at iteration %d"
                         % (signum, iteration))
        self.signum = signum
        self.iteration = iteration
        self.snapshot = snapshot


class PreemptionGuard:
    """SIGTERM/SIGINT -> write-final-snapshot-then-exit, at the next
    iteration boundary (Python delivers signals between bytecodes, but
    mid-iteration state — a half-appended multiclass iteration, an
    in-flight device dispatch — is not snapshotable; one iteration is
    the guaranteed preemption latency bound).

    Use as a context manager around the training loop; `callback` goes
    LAST in the after-iteration callback list."""

    def __init__(self, output_model: str, retention: int = -1, log=None):
        self.output_model = output_model
        self.retention = retention
        self.log = log
        self.signum: Optional[int] = None
        self._prev: Dict[int, Any] = {}

        def _callback(env) -> None:
            if self.signum is None:
                return
            total = int(env.model.current_iteration())
            snap = write_snapshot(env.model, self.output_model,
                                  total_iter=total,
                                  retention=self.retention, log=self.log)
            raise TrainingPreempted(self.signum, total, snap)

        _callback.order = 100
        self.callback = _callback

    def _handler(self, signum, frame):
        self.signum = signum
        sys.stderr.write("[%s] preemption signal %d received; writing a "
                         "final snapshot at the next iteration boundary\n"
                         % (wallclock(), signum))
        sys.stderr.flush()

    def __enter__(self) -> "PreemptionGuard":
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev[sig] = signal.signal(sig, self._handler)
            except ValueError:
                pass   # not the main thread: guard inert, training unchanged
        return self

    def __exit__(self, *exc) -> None:
        for sig, prev in self._prev.items():
            with contextlib.suppress(ValueError):
                signal.signal(sig, prev)
        return None


# ---------------------------------------------------------------------------
# non-finite sentinel
# ---------------------------------------------------------------------------

class NonFiniteDetected(ArithmeticError):
    """A freshly grown tree carried NaN/inf outputs (the device-side
    symptom of a non-finite grad/hess/score burst)."""

    def __init__(self, iteration: int, tree_index: int, field: str):
        super().__init__(
            "non-finite %s detected in the tree grown at iteration %d "
            "(tree %d)" % (field, iteration, tree_index))
        self.iteration = iteration
        self.tree_index = tree_index
        self.field = field


def sentinel_check(engine, host: Dict) -> None:
    """Screen the tree outputs fetched from device this iteration (free:
    `_finish_tree` already pulled them to host).  Policy 'off' skips the
    scan entirely; 'abort'/'rollback' raise `NonFiniteDetected` for
    `SentinelGuard` to arbitrate."""
    import numpy as np
    policy = getattr(engine, "_sentinel_policy", "off")
    if policy == "off":
        return
    maybe_inject_nan(engine, host)
    nl = max(int(host["num_leaves"]), 1)
    if not np.isfinite(host["leaf_value"][:nl]).all():
        raise NonFiniteDetected(int(engine.iter),
                                len(engine.model.trees), "leaf values")
    if nl > 1 and not np.isfinite(host["internal_value"][:nl - 1]).all():
        raise NonFiniteDetected(int(engine.iter),
                                len(engine.model.trees), "internal values")


class SentinelGuard:
    """Pre-iteration state for the abort-vs-rollback policy.

    'abort' re-raises as a hard error naming the iteration; 'rollback'
    restores the pre-iteration scores (captured to host when the policy
    is armed — one D2H per iteration, the documented cost of the
    feature), drops the iteration's trees, and STOPS training cleanly
    (the gradient source is producing non-finites; continuing would
    poison every later tree)."""

    def __init__(self, engine):
        from . import syncs
        self.engine = engine
        self.policy = getattr(engine, "_sentinel_policy", "off")
        self.pre_trees = len(engine.model.trees)
        self.pre_iter = int(engine.iter)
        self.score = None
        if self.policy == "rollback":
            if engine._fast_active:
                self.score = engine._fast.raw_scores()
            else:
                self.score = syncs.device_get(engine.score,
                                              label="sentinel")

    def handle(self, err: NonFiniteDetected, log) -> bool:
        """Returns True (= training finished) after a rollback; raises
        for the abort policy.  Mirrors the Booster.update contract."""
        if self.policy != "rollback" or self.score is None:
            raise type(err)(err.iteration, err.tree_index, err.field)
        import jax.numpy as jnp
        eng = self.engine
        del eng.model.trees[self.pre_trees:]
        eng.iter = self.pre_iter
        # discard the poisoned payload outright (a sync-back would copy
        # the NaNs); the next fast entry rebuilds from the restored score
        eng._fast_active = False
        eng.score = jnp.asarray(self.score)
        log.warning(
            "%s; policy=rollback: iteration %d discarded, scores restored, "
            "training stopped", err, err.iteration)
        return True
