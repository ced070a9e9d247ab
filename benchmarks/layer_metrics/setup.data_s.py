"""Host seconds the benchmark itself spends making the run's inputs from
the seed (its own cost inside `setup_s`, not the program's)."""
LAYER = "benchmark"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"
DRIVERS = None


def read(run):
    return run.setup.get("data_s")
