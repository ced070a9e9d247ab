"""Median over completed requests of the time from the batcher's pop to
the dispatch: the batch window and the gather (`ServeResult.stages`,
key `batch_gather_s`)."""
import numpy as np

LAYER = "serving"
UNIT = "ms"
MOVES = "serve_p99_ms"
SOURCE = "program_span"
DRIVERS = ("serve",)


def read(run):
    values = [r.stages["batch_gather_s"] for r in run.window.get("results", [])
              if r is not None and r.stages]
    return 1e3 * float(np.median(values)) if values else None
