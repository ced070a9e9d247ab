"""Median over completed requests of the time from admission to the
batcher's pop (`ServeResult.stages`, key `queue_wait_s`)."""
import numpy as np

LAYER = "serving"
UNIT = "ms"
MOVES = "serve_p99_ms"
SOURCE = "program_span"
DRIVERS = ("serve",)


def read(run):
    values = [r.stages["queue_wait_s"] for r in run.window.get("results", [])
              if r is not None and r.stages]
    return 1e3 * float(np.median(values)) if values else None
