"""Rows of the requests completed inside the window, per second of it."""
LAYER = "serving"
UNIT = "rows/s"
MOVES = "serve_p99_ms"
SOURCE = "host_clock"
DRIVERS = ("serve",)


def read(run):
    return run.window.get("summary", {}).get("rows_per_s")
