"""Deferred host-half assembler for the async boosting pipeline (ISSUE 5).

The fused fast path's device step for tree t+1 does not depend on tree
t's host `Tree` object — `_step` consumes only `(payload, aux)`, which
never leave the device.  The only reason the classic loop stalled once
per tree was the synchronous packed fetch inside `_finish_tree`.  This
module provides the bounded FIFO that takes that fetch (and the ~2 ms of
host assembly behind it) off the dispatch path:

* `submit(fn)` enqueues one DRAIN UNIT's host half and applies
  backpressure: at most `depth` units are pending-or-running.  A unit
  is one tree on the per-tree fast path (packed fetch -> `Tree`
  assembly -> `model.trees.append`); the fused boosting window
  (boost_window=J, ISSUE 13) submits MULTI-TREE units — one packed
  fetch draining J*K parked trees — so `trees=` tells the queue how
  many trees a unit carries and `pending_trees` reports how far the
  device is ahead of the host model in TREES, not units.
* the halves run on ONE worker thread in strict submission order —
  `model.trees` grows in exactly the order the trees were dispatched,
  which is what byte-identical model files require.
* `flush()` drains everything, joins the worker, and re-raises the first
  deferred exception.  After `flush()` returns no thread is alive — a
  process with a thousand short-lived boosters never accumulates parked
  workers.

jax is thread-safe for this use: the host half only runs jitted *reads*
of committed output arrays (the packed fetch); nothing in it donates or
mutates device buffers the dispatch thread still owns.
"""
from __future__ import annotations

import collections
import threading
from typing import Callable, Deque, Optional, Tuple

from ..runtime import telemetry, tracing


class TreeAssembler:
    """Bounded, strictly-ordered, single-worker deferred queue."""

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._cv = threading.Condition()
        #: (host half, trees it carries, index of its first tree)
        self._fifo: Deque[Tuple[Callable[[], None], int, int]] = \
            collections.deque()
        self._submitted = 0             # trees handed to submit() so far
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._stopping = False

    @property
    def pending(self) -> int:
        """Drain units submitted but not yet finished."""
        with self._cv:
            return len(self._fifo)

    @property
    def pending_trees(self) -> int:
        """Trees carried by the pending drain units (a boosting-window
        unit counts its whole J*K batch)."""
        with self._cv:
            return sum(n for _, n, _ in self._fifo)

    def submit(self, fn: Callable[[], None], trees: int = 1) -> None:
        """Enqueue one drain unit carrying `trees` parked trees; blocks
        while `depth` units are already pending (the in-flight one
        counts), bounding how far the device runs ahead.  A deferred
        error from an earlier unit re-raises here rather than silently
        dropping trees."""
        # cross-thread trace propagation (ISSUE 14): the host half runs
        # on the worker thread but belongs to the dispatching iteration's
        # causal chain — capture the dispatcher's context here and replay
        # it (plus a drain span) around the deferred fn.  Disabled
        # tracing returns fn unchanged.  The span says which unit it
        # drains (ISSUE 35): the index of its first tree, and the
        # iteration it was dispatched under where the caller's span
        # says one, so an update() that waits can be paired with the
        # tree it waited for by name and not by order.
        trees = max(1, int(trees))
        first = self._submitted
        self._submitted += trees
        if tracing.enabled():
            unit = {"trees": trees, "tree": first}
            iteration = tracing.ambient("iteration")
            if iteration is not None:
                unit["iteration"] = iteration
            fn = tracing.bind(fn, "assembler/drain", **unit)
        with self._cv:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            if len(self._fifo) >= self.depth:
                # back-pressure: the seconds the dispatch thread waits
                # for the device (and the worker) to catch up; the unit
                # in flight is the one whose end lets this one in
                with tracing.span("assembler/wait", pending=len(self._fifo),
                                  awaits=self._fifo[0][2]):
                    while len(self._fifo) >= self.depth:
                        self._cv.wait()
                        if self._error is not None:
                            err, self._error = self._error, None
                            raise err
            self._fifo.append((fn, trees, first))
            # live queue depth (ISSUE 9): how far the device is running
            # ahead of the host model right now
            telemetry.gauge("lgbm_pipeline_queue_depth").set(
                len(self._fifo))
            if self._thread is None:
                self._stopping = False
                self._thread = threading.Thread(
                    target=self._run, name="lgbm-tpu-assembler", daemon=True)
                self._thread.start()
            self._cv.notify_all()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._fifo and not self._stopping:
                    self._cv.wait()
                if not self._fifo:
                    return
                fn = self._fifo[0][0]   # keep queued: in-flight counts
                                        # against the depth bound
            try:
                fn()
            except BaseException as e:  # deferred to submit()/flush()
                with self._cv:
                    if self._error is None:
                        self._error = e
            with self._cv:
                self._fifo.popleft()
                telemetry.gauge("lgbm_pipeline_queue_depth").set(
                    len(self._fifo))
                self._cv.notify_all()

    def flush(self) -> None:
        """Drain every pending half, stop the worker, and re-raise the
        first deferred error.  Idempotent; cheap when already empty."""
        with self._cv:
            if self._fifo:
                with tracing.span("assembler/wait", pending=len(self._fifo),
                                  awaits=self._fifo[-1][2]):
                    while self._fifo:
                        self._cv.wait()
            self._stopping = True
            self._cv.notify_all()
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join()
        with self._cv:
            err, self._error = self._error, None
        if err is not None:
            raise err
