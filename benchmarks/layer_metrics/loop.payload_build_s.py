"""Host seconds the first `update()` spends building the fast path's
payload and its scratch on the device(s) (the program's `booster/payload`
span, from the flight recorder's ring): tracing, compiling or loading,
and launching the build program.  The device's own part of it overlaps
what the host does next and is not in this number."""
from benchmarks.lib import progspans

LAYER = "boosting-loop"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    return progspans.ring_seconds("booster/payload")
