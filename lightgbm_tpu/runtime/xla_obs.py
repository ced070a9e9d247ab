"""Compile/retrace ledger: the XLA-side half of observability (ISSUE 10).

PR 9's telemetry sees host wall-clocks and sync counts but is blind to
where device time actually goes — XLA compiles, silent retraces and
program-cache misses are invisible, and a single unplanned retrace of the
tree grower costs seconds, more than a whole training iteration.  This
module is the ONE seam every jit entry point in the codebase registers
through:

* **`xla_obs.jit(fn, site=..., **jax_jit_kwargs)`** — a drop-in
  replacement for ``jax.jit`` (same semantics: ``donate_argnums``,
  ``static_argnames``, ``__wrapped__`` exposing the unjitted function
  for inlining into outer traces).  Every call is classified as a
  program-cache *hit* or a *compile* (a trace of the wrapped function
  fired during the call), and every compile records its wall time, the
  triggering abstract shapes, and — after `mark_steady()` — the shape
  DELTA vs the site's previous trace, so a steady-state retrace names
  both the site and what changed.  ``helper/check_xla_sites.py`` lints
  that no raw ``jax.jit`` bypasses this seam.

* **`cache_event(site, event)`** — the same ledger for the python-side
  program caches (`_PACK_CACHE`, `_GROWER_CACHE`, the predictor's shape
  buckets): hit/miss/evict land in
  ``lgbm_program_cache_events_total{site,event}``.

* **The steady-state zero-retrace pin** — `snapshot()` / `delta()` let
  a test (or BENCH_ATTRIB) assert that after warmup, N further training
  iterations and M further serving batches compile NOTHING; a violation
  is a named `retraces` entry carrying site + shape delta
  (``lgbm_xla_retraces_total{site,delta}``).

* **Cost capture** (`set_cost_capture(True)`, opt-in: it lowers and
  compiles once more per new shape signature) — per-site
  ``cost_analysis()`` (FLOPs / bytes accessed) captured at compile
  time, folded into BENCH_ATTRIB and the doctor bundle.

Metrics ride the PR 9 registry (`lgbm_xla_compiles_total{site}`,
``lgbm_xla_compile_seconds{site}``, the cache/retrace families above);
the ledger itself is pure host bookkeeping — with telemetry disabled
the per-call cost is two clock reads and a list check.  Every call is
also one ``launch/<site>`` span of the flight recorder
(`runtime/tracing.py`; off with ``LGBM_TPU_TRACE=0``).

No jax / numpy at module scope — jax loads lazily inside `jit()`.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import telemetry, tracing
from .resilience import wallclock

__all__ = [
    "jit", "LEDGER", "CompileLedger", "cache_event", "mark_steady",
    "set_cost_capture", "snapshot", "delta", "total_compiles", "reset",
    "calls_snapshot", "calls_delta", "total_calls",
    "set_compile_observer",
]

#: compile-history entries kept per site (bounded: the ledger lives for
#: the whole process)
HISTORY_PER_SITE = 32

#: hard cap on shape-signature / delta strings (they become metric label
#: values and bundle JSON)
SIG_MAX_CHARS = 160


def _aval_str(x: Any) -> str:
    """Compact dtype[shape] of one argument leaf; static/python values
    render as their type name (their CHANGE still shows in the delta)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        short = str(dtype)
        short = {"float32": "f32", "float64": "f64", "int32": "i32",
                 "int64": "i64", "uint8": "u8", "uint16": "u16",
                 "uint32": "u32", "int8": "i8", "int16": "i16",
                 "bool": "b1", "bfloat16": "bf16"}.get(short, short)
        return "%s[%s]" % (short, ",".join(str(d) for d in shape))
    if isinstance(x, (bool, int, float, str)):
        return repr(x)[:24]
    return type(x).__name__


def _signature(args: tuple, kwargs: dict) -> Tuple[str, ...]:
    """Abstract signature of a call: one entry per argument leaf.  Dicts
    (the grower's tree pytrees) are summarized by sorted keys to keep
    signatures short and stable."""
    out: List[str] = []
    for a in args:
        if isinstance(a, dict):
            out.append("{%s}" % ",".join(
                "%s:%s" % (k, _aval_str(a[k])) for k in sorted(a)[:8]))
        elif isinstance(a, (list, tuple)):
            out.append("(%s)" % ",".join(_aval_str(v) for v in a[:8]))
        else:
            out.append(_aval_str(a))
    for k in sorted(kwargs):
        out.append("%s=%s" % (k, _aval_str(kwargs[k])))
    return tuple(out)


def sig_delta(old: Optional[Tuple[str, ...]],
              new: Tuple[str, ...]) -> str:
    """Human-readable diff of two signatures: only the argument slots
    that changed, ``argN:old->new``.  This is what a steady-state
    retrace reports in its metric label."""
    if old is None:
        return "first_trace"
    parts = []
    for i in range(max(len(old), len(new))):
        o = old[i] if i < len(old) else "<absent>"
        n = new[i] if i < len(new) else "<absent>"
        if o != n:
            parts.append("arg%d:%s->%s" % (i, o, n))
    return (";".join(parts) or "identical_signature")[:SIG_MAX_CHARS]


class _Site:
    """Per-site ledger record."""

    __slots__ = ("name", "compiles", "calls", "cache_hits", "cache_misses",
                 "last_sig", "compile_seconds", "history", "cost",
                 "cost_seen")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.calls = 0
        self.cache_hits = 0          # python-side cache hits (cache_event)
        self.cache_misses = 0
        self.last_sig: Optional[Tuple[str, ...]] = None
        self.compile_seconds = 0.0
        self.history: "collections.deque" = collections.deque(
            maxlen=HISTORY_PER_SITE)
        self.cost: Dict[str, Any] = {}          # last cost_analysis()
        self.cost_seen: set = set()

    def to_json(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "compiles": self.compiles, "calls": self.calls,
            "compile_seconds": round(self.compile_seconds, 6),
            "last_signature": list(self.last_sig or ()),
            "history": list(self.history),
        }
        if self.cache_hits or self.cache_misses:
            d["cache_hits"] = self.cache_hits
            d["cache_misses"] = self.cache_misses
        if self.cost:
            d["cost_analysis"] = self.cost
        return d


class CompileLedger:
    """Process-wide compile/retrace ledger (one instance: `LEDGER`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sites: Dict[str, _Site] = {}
        self._tls = threading.local()
        self._steady = False
        self._cost_capture = False
        #: post-compile observer (runtime/warmup.py's persistent-cache
        #: hit/miss classifier registers here — warmup imports this
        #: module, never the reverse)
        self._observer: Optional[Callable[[str, float], None]] = None
        #: steady-state violations: {site, delta, wallclock, wall_s}
        self.retraces: List[Dict[str, Any]] = []

    # -- registration --------------------------------------------------------
    def register(self, site: str) -> _Site:
        rec = self._sites.get(site)
        if rec is None:
            with self._lock:
                rec = self._sites.get(site)
                if rec is None:
                    rec = _Site(site)
                    self._sites[site] = rec
        return rec

    def site_names(self) -> List[str]:
        with self._lock:
            return sorted(self._sites)

    # -- trace plumbing (called from inside jax tracing) ---------------------
    def _notes(self) -> list:
        lst = getattr(self._tls, "notes", None)
        if lst is None:
            lst = self._tls.notes = []
        return lst

    def _note_trace(self, rec: _Site, args: tuple, kwargs: dict) -> None:
        """Runs AT TRACE TIME (host code executed while jax traces the
        wrapped function) — traces are rare, so the signature is computed
        here, never on the cached-call fast path."""
        self._notes().append((rec, _signature(args, kwargs)))

    def _record_compile(self, rec: _Site, wall_s: float,
                        sig: Tuple[str, ...]) -> None:
        prev = rec.last_sig
        with self._lock:
            rec.compiles += 1
            rec.compile_seconds += wall_s
            rec.last_sig = sig
            rec.history.append({
                "wallclock": wallclock(), "wall_s": round(wall_s, 6),
                "signature": list(sig)[:16],
                "delta": sig_delta(prev, sig),
            })
        telemetry.counter("lgbm_xla_compiles_total").inc(site=rec.name)
        telemetry.histogram("lgbm_xla_compile_seconds").observe(
            wall_s, site=rec.name)
        telemetry.counter("lgbm_program_cache_events_total").inc(
            site=rec.name, event="compile")
        # the compile as a slice on its own Perfetto row (ISSUE 14): the
        # flight recorder's merged timeline shows WHICH request/cycle was
        # stalled behind which site's trace+compile
        now_ns = time.monotonic_ns()
        dur_ns = int(wall_s * 1e9)
        tracing.record("xla compile %s" % rec.name, now_ns - dur_ns,
                       dur_ns, track="xla compile", site=rec.name,
                       delta=sig_delta(prev, sig))
        if self._steady:
            delta_s = sig_delta(prev, sig)
            event = {"site": rec.name, "delta": delta_s,
                     "wall_s": round(wall_s, 6), "wallclock": wallclock()}
            with self._lock:
                self.retraces.append(event)
            telemetry.counter("lgbm_xla_retraces_total").inc(
                site=rec.name, delta=delta_s)
            tracing.instant("xla RETRACE %s" % rec.name,
                            track="xla compile", site=rec.name,
                            delta=delta_s)
        obs = self._observer
        if obs is not None:
            try:
                obs(rec.name, wall_s)
            except Exception:    # noqa: BLE001 — never the compile's problem
                pass

    # -- python-side cache events --------------------------------------------
    def cache_event(self, site: str, event: str, n: int = 1) -> None:
        """hit / miss / evict for an explicit program cache (the grower
        caches, `_PACK_CACHE`, the predictor's shape buckets)."""
        rec = self.register(site)
        with self._lock:
            if event == "hit":
                rec.cache_hits += n
            elif event == "miss":
                rec.cache_misses += n
        telemetry.counter("lgbm_program_cache_events_total").inc(
            n, site=site, event=event)

    # -- steady-state pin ----------------------------------------------------
    def mark_steady(self, on: bool = True) -> None:
        """After warmup: any further trace at any site is a RETRACE,
        recorded with the site and the shape delta that triggered it."""
        self._steady = bool(on)

    @property
    def steady(self) -> bool:
        return self._steady

    def set_cost_capture(self, on: bool) -> bool:
        prev = self._cost_capture
        self._cost_capture = bool(on)
        return prev

    def set_compile_observer(self, fn: Optional[Callable[[str, float],
                                                         None]]) -> None:
        """Register the post-compile observer (one per process; None
        unregisters).  Called with (site, wall_s) AFTER each compile is
        recorded; an observer exception is swallowed."""
        self._observer = fn

    # -- read side -----------------------------------------------------------
    def total_compiles(self) -> int:
        with self._lock:
            return sum(s.compiles for s in self._sites.values())

    def snapshot(self) -> Dict[str, int]:
        """{site: compile count} — diff two of these to pin a window."""
        with self._lock:
            return {name: s.compiles for name, s in self._sites.items()}

    def calls_snapshot(self) -> Dict[str, int]:
        """{site: DISPATCH count} — every LedgeredJit invocation is one
        device-program launch (inlined ``__wrapped__`` calls are part of
        their outer program and do not count).  Diff two of these for a
        dispatches-per-iteration attribution (BENCH_ATTRIB)."""
        with self._lock:
            return {name: s.calls for name, s in self._sites.items()}

    def calls_delta(self, before: Dict[str, int]) -> Dict[str, int]:
        """Per-site dispatches since `before` (only non-zero entries)."""
        now = self.calls_snapshot()
        out = {}
        for name, n in now.items():
            d = n - before.get(name, 0)
            if d:
                out[name] = d
        return out

    def total_calls(self) -> int:
        with self._lock:
            return sum(s.calls for s in self._sites.values())

    def delta(self, before: Dict[str, int]) -> Dict[str, int]:
        """Per-site compiles since `before` (only non-zero entries)."""
        now = self.snapshot()
        out = {}
        for name, n in now.items():
            d = n - before.get(name, 0)
            if d:
                out[name] = d
        return out

    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            sites = {name: s.to_json()
                     for name, s in sorted(self._sites.items())}
            retraces = list(self.retraces)
        return {"wallclock": wallclock(), "steady": self._steady,
                "total_compiles": sum(s["compiles"]
                                      for s in sites.values()),
                "sites": sites, "retraces": retraces}

    def reset(self) -> None:
        """Test seam: forget every recorded event (registered wrapper
        objects keep working; their site records are re-created)."""
        with self._lock:
            self._sites.clear()
            self.retraces.clear()
        self._steady = False


#: THE ledger every `xla_obs.jit` site records into
LEDGER = CompileLedger()


class LedgeredJit:
    """`jax.jit` with the compile ledger wired in.  Calls behave exactly
    like the plain jitted function; `__wrapped__` is the traced (but
    unjitted) function so callers that inline into an outer trace (the
    fused-step pattern in gbdt.py) keep working — and their inlined
    traces still note the site."""

    def __init__(self, fn: Callable, site: str, jit_kwargs: Dict[str, Any]):
        import jax
        self.site = site
        self._span = "launch/" + site
        self._rec = LEDGER.register(site)
        rec = self._rec

        @functools.wraps(fn)
        def marked(*a, **k):
            LEDGER._note_trace(rec, a, k)
            return fn(*a, **k)

        self._jitted = jax.jit(marked, **jit_kwargs)
        functools.update_wrapper(self, fn, updated=())
        # AFTER update_wrapper (which points __wrapped__ at fn): inlining
        # callers get the MARKED function, so an inlined trace still
        # notes the site inside the outer program's compile
        self.__wrapped__ = marked

    def __call__(self, *args, **kwargs):
        rec = self._rec
        rec.calls += 1
        notes = LEDGER._notes()
        n0 = len(notes)
        if LEDGER._cost_capture:
            self._maybe_capture_cost(args, kwargs)
        # the launch as a span: the host's time to enqueue one program
        # (or, when the call compiles, to trace, lower and build or load
        # it -- the retro-recorded "xla compile <site>" beside it says so)
        t0 = time.perf_counter()
        with tracing.span(self._span):
            out = self._jitted(*args, **kwargs)
        dt = time.perf_counter() - t0
        if len(notes) > n0:
            mine = [sig for r, sig in notes[n0:] if r is rec]
            del notes[n0:]
            if mine:
                LEDGER._record_compile(rec, dt, mine[-1])
                return out
        telemetry.counter("lgbm_program_cache_events_total").inc(
            site=rec.name, event="hit")
        return out

    def _maybe_capture_cost(self, args, kwargs) -> None:
        """Opt-in FLOPs/bytes capture: lower+compile once per new shape
        signature BEFORE the real call (the real call may donate its
        buffers).  Diagnostics only — any failure is swallowed."""
        try:
            sig = _signature(args, kwargs)
            if sig in self._rec.cost_seen:
                return
            self._rec.cost_seen.add(sig)
            compiled = self._jitted.lower(*args, **kwargs).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost:
                self._rec.cost = {
                    k: (round(float(v), 3)
                        if isinstance(v, (int, float)) else str(v))
                    for k, v in sorted(dict(cost).items())[:24]}
        except Exception:      # noqa: BLE001 — never the hot path's problem
            pass

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def clear_cache(self) -> None:
        self._jitted.clear_cache()


def jit(fn: Optional[Callable] = None, *, site: str,
        **jit_kwargs) -> Any:
    """Ledgered ``jax.jit``.  Usable as a direct call
    (``xla_obs.jit(f, site="x")``) or through functools.partial as a
    decorator (``@functools.partial(xla_obs.jit, site="x",
    static_argnames=(...))``)."""
    if not site:
        raise ValueError("xla_obs.jit needs a non-empty site= name")
    if fn is None:
        return functools.partial(jit, site=site, **jit_kwargs)
    return LedgeredJit(fn, site, jit_kwargs)


# -- module-level conveniences (the names tests and callers use) ------------

def cache_event(site: str, event: str, n: int = 1) -> None:
    LEDGER.cache_event(site, event, n)


def mark_steady(on: bool = True) -> None:
    LEDGER.mark_steady(on)


def set_cost_capture(on: bool) -> bool:
    return LEDGER.set_cost_capture(on)


def snapshot() -> Dict[str, int]:
    return LEDGER.snapshot()


def delta(before: Dict[str, int]) -> Dict[str, int]:
    return LEDGER.delta(before)


def total_compiles() -> int:
    return LEDGER.total_compiles()


def calls_snapshot() -> Dict[str, int]:
    return LEDGER.calls_snapshot()


def calls_delta(before: Dict[str, int]) -> Dict[str, int]:
    return LEDGER.calls_delta(before)


def total_calls() -> int:
    return LEDGER.total_calls()


def reset() -> None:
    LEDGER.reset()


def set_compile_observer(fn: Optional[Callable[[str, float], None]]
                         ) -> None:
    LEDGER.set_compile_observer(fn)
