"""How late the load generator ran at worst: submission less the due
instant.  A starved generator must not read as a fast server."""
LAYER = "load-generator"
UNIT = "ms"
MOVES = "serve_p99_ms"
SOURCE = "host_clock"
DRIVERS = ("serve",)


def read(run):
    return run.window.get("summary", {}).get("max_lag_ms")
