"""Device seconds the tree-parallel predictor's program took per million
rows scored in the window (`models/device_predictor.py`)."""
import re

PATTERN = re.compile(r"predict_tree_parallel")
LAYER = "device-predictor"
UNIT = "s"
MOVES = "predict_rows_per_s"
SOURCE = "device_trace"
DRIVERS = ("predict", "serve")


def read(run):
    trace = run.xtrace
    rows = run.window.get("rows")
    if trace is None or not trace.devices or not rows:
        return None
    lo, hi = trace.window_ns()
    ns = sum(d for name, s, d in trace.devices[0].modules
             if PATTERN.search(name) and s >= lo and s + d <= hi)
    return ns / 1e9 / (rows / 1e6)
