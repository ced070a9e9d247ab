"""Blocking host-sync audit seam (ISSUE 5 sync audit).

Every *blocking* device->host observation the training stack performs —
`jax.device_get` of tree outputs, score fetches for metrics/snapshots,
explicit `block_until_ready` barriers — goes through this module so one
instrument can answer "how many times per iteration does the host stall
the device pipeline, and where?".

Two orthogonal dimensions are recorded per event:

* a **label** naming the call site family (``tree_fetch``, ``eval_fetch``,
  ``pipeline_drain``, ...), and
* whether the calling thread currently sits on the **tree->tree critical
  path** (the dispatch loop of ``GBDT._train_one_iter_fast``, marked with
  :func:`critical_path`).  The async pipeline's host halves run on the
  assembler thread, which never carries the marker — so the tier-1 pin
  "0 blocking fetches on the critical path at ``pipeline_depth=1``" is a
  direct counter assertion, not an inference from timings.

The counters are process-global and monotonically increasing; consumers
take a :func:`snapshot` before a region and diff with :func:`delta`
after it (bench reports ``host_syncs_per_iter`` this way).  Each blocking
call is also timed, as a ``fetch/<label>`` span of the flight recorder
(`runtime/tracing.py`): the seconds a thread was blocked on the device.

Implicit syncs (``np.asarray`` on a live jax array, printing a device
array) are outside the seam by construction; the training/boosting code
paths use the explicit helpers only, and the tests pin that property for
the fused fast path.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from . import telemetry, tracing

_lock = threading.Lock()
_counts: Dict[str, int] = {}
_critical_counts: Dict[str, int] = {}
_total = 0
_critical_total = 0

_tls = threading.local()


def _on_critical_path() -> bool:
    return getattr(_tls, "depth", 0) > 0


class critical_path:
    """Context manager marking the current thread as the device critical
    path: blocking syncs recorded while inside count as critical.  The
    marker is thread-local, so work handed to the pipeline assembler
    thread is off-path by construction."""

    def __enter__(self) -> "critical_path":
        _tls.depth = getattr(_tls, "depth", 0) + 1
        return self

    def __exit__(self, *exc) -> None:
        _tls.depth = getattr(_tls, "depth", 1) - 1


def record(label: str) -> None:
    """Count one blocking host sync under `label` (seam-internal; call
    sites should prefer the device_get/block_until_ready wrappers)."""
    global _total, _critical_total
    crit = _on_critical_path()
    with _lock:
        _counts[label] = _counts.get(label, 0) + 1
        _total += 1
        if crit:
            _critical_counts[label] = _critical_counts.get(label, 0) + 1
            _critical_total += 1
    # the same event feeds the process-wide metrics registry (ISSUE 9),
    # so a live /metrics scrape sees the sync profile the bench pins
    telemetry.count_sync(label, crit)


def device_get(x: Any, label: str = "host_fetch") -> Any:
    """Audited `jax.device_get`: ONE recorded blocking fetch, whatever
    the pytree width (jax starts every leaf's D2H copy asynchronously
    before blocking, so a pytree is one round of transfers)."""
    import jax
    record(label)
    with tracing.span("fetch/" + label):
        return jax.device_get(x)


def block_until_ready(x: Any, label: str = "barrier") -> Any:
    """Audited `jax.block_until_ready`."""
    import jax
    record(label)
    with tracing.span("fetch/" + label):
        return jax.block_until_ready(x)


def snapshot() -> Dict[str, Any]:
    """A copyable view of the monotone counters."""
    with _lock:
        return {
            "total": _total,
            "critical_path": _critical_total,
            "by_label": dict(_counts),
            "critical_by_label": dict(_critical_counts),
        }


def delta(before: Dict[str, Any],
          after: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Counter movement since `before` (to `after`, default: now)."""
    if after is None:
        after = snapshot()
    by_label = {k: v - before["by_label"].get(k, 0)
                for k, v in after["by_label"].items()
                if v - before["by_label"].get(k, 0)}
    crit = {k: v - before["critical_by_label"].get(k, 0)
            for k, v in after["critical_by_label"].items()
            if v - before["critical_by_label"].get(k, 0)}
    return {
        "total": after["total"] - before["total"],
        "critical_path": after["critical_path"] - before["critical_path"],
        "by_label": by_label,
        "critical_by_label": crit,
    }


def reset() -> None:
    """Zero the counters (tests and bench sections)."""
    global _total, _critical_total
    with _lock:
        _counts.clear()
        _critical_counts.clear()
        _total = 0
        _critical_total = 0
