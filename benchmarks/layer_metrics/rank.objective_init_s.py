"""Host seconds the objective spends at set-up building its per-query
tables (the program's `objective/init` span, from the flight recorder's
ring): query buckets, slot maps, the ideal DCG of every query and their
transfer to the device.  None for a program without the span, and for
an objective that has no such tables (no `counters`: the ring is the
process's, and a process may have run another cell before)."""
from benchmarks.lib import progspans

LAYER = "objective"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    engine = getattr(run.state.get("bst"), "_engine", None)
    if not getattr(getattr(engine, "objective", None), "counters", None):
        return None
    return progspans.ring_seconds("objective/init")
