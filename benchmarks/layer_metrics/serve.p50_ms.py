"""Median over completed requests of completion less the instant the
request was due, on the benchmark's clock."""
LAYER = "serving"
UNIT = "ms"
MOVES = "serve_p99_ms"
SOURCE = "host_clock"
DRIVERS = ("serve",)


def read(run):
    return run.window.get("summary", {}).get("p50_ms")
