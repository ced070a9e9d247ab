"""Host milliseconds per iteration of the window that the assembler
thread spends turning a fetched tree into the host model: the program's
`assembler/drain` spans of the window's iterations less the `fetch/<label>`
spans under them (the wait for the device), from the flight recorder's
ring.  Off the dispatch path while it stays under the iteration."""
from benchmarks.lib import progspans

LAYER = "boosting-loop"
UNIT = "ms"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    ring = progspans.ring()
    iters = progspans.window_iterations(run, ring)
    ids = {it.id for it in iters}
    drains = [s for s in ring
              if s.name == "assembler/drain" and s.parent in ids]
    fetches = [progspans.children(d, ring, "fetch/") for d in drains]
    if not drains or not any(fetches):
        return None
    assembling = sum(d.dur_ns - sum(f.dur_ns for f in fs)
                     for d, fs in zip(drains, fetches))
    return assembling / 1e6 / len(iters)
