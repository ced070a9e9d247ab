"""Host milliseconds per iteration of the window that the dispatch
thread spends launching device programs: the program's `launch/<site>`
spans (every ledgered jit call) on the thread and within the interval of
the window's `train/iteration` spans, from the flight recorder's ring.
The assembler thread's launches are in `loop.assemble_ms_per_iter`."""
from benchmarks.lib import progspans

LAYER = "boosting-loop"
UNIT = "ms"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    ring = progspans.ring()
    iters = progspans.window_iterations(run, ring)
    if not iters or not any(s.name.startswith("launch/") for s in ring):
        return None
    launched = sum(progspans.inside(it, ring, ("launch/",)) for it in iters)
    return launched / 1e6 / len(iters)
