"""Programs built during set-up that the persistent compile cache did not
hold: build requests less cache hits, from JAX's monitoring events
(lib/compiles.py).  0 on every run of a cell after its first."""
LAYER = "compile-cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = None


def read(run):
    n = run.counts.get("setup_compiles")
    return None if n is None else n["requests"] - n["hits"]
