"""Seconds the program's compile ledger (`runtime/xla_obs.py`) charged to
tracing, lowering and compiling or loading programs during set-up."""
LAYER = "compile-cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = None


def read(run):
    return run.setup.get("compile_s")
