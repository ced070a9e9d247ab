"""Host seconds of the warm-up that runs every shape of the window once:
compilation or cache load, the first transfers and the warm-up work."""
LAYER = "compile-cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"
DRIVERS = None


def read(run):
    return run.setup.get("warmup_s")
