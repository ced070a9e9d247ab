"""Median over completed requests of the time from dispatch to the values
on the host, on the HOST's clock: transfer, queueing and compute
together (`ServeResult.stages`, key `device_s` -- not device time,
which `predictor.device_s_per_mrow` reads from the trace)."""
import numpy as np

LAYER = "serving"
UNIT = "ms"
MOVES = "serve_p99_ms"
SOURCE = "program_span"
DRIVERS = ("serve",)


def read(run):
    values = [r.stages["device_s"] for r in run.window.get("results", [])
              if r is not None and r.stages]
    return 1e3 * float(np.median(values)) if values else None
