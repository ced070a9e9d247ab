"""Host milliseconds per iteration of the window that `Booster.update()`
spends doing anything but waiting for the device: the program's
`train/iteration` span less the `assembler/wait` (back-pressure) and
`fetch/<label>` (blocking fetch) spans inside it, from the flight
recorder's ring.  It is what an iteration would cost were the device
infinitely fast: the s/iter below which the host sets the pace."""
from benchmarks.lib import progspans

LAYER = "boosting-loop"
UNIT = "ms"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    ring = progspans.ring()
    iters = progspans.window_iterations(run, ring)
    # a program whose seams record no span would read as all floor
    if not iters or not any(s.name.startswith("launch/") for s in ring):
        return None
    floor = sum(it.dur_ns - progspans.inside(it, ring, ("assembler/wait",
                                                        "fetch/"))
                for it in iters)
    return floor / 1e6 / len(iters)
