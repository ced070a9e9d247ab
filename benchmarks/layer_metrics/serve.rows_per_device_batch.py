"""Rows per device batch over the window (`ServingRuntime.stats()`: rows
served over device batches)."""
LAYER = "serving"
UNIT = "rows"
MOVES = "serve_p99_ms"
SOURCE = "program_counter"
DRIVERS = ("serve",)


def read(run):
    now, was = run.window.get("stats"), run.state.get("stats0")
    if not now:
        return None
    batches = now["batches_device"] - was["batches_device"]
    if not batches:
        return None
    return (now["rows_served"] - was["rows_served"]) / batches
