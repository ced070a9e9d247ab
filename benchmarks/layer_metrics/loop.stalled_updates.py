"""Iterations of the window that the program's own rule called a stall
(`telemetry.train_iteration`: over three times the median of the 32
before it): its `train/stall` events, each of which names a verdict,
counted by the `iteration` label they share with the span they judge.
None on a program whose iterations carry no number.

The rule judges an iteration only with eight before it in which nothing
compiled (`telemetry.STALL_MIN_HISTORY`).  A `--trace 1` run makes four
warm-up `update()`s and a window of three (`traffic/train.json`), six
walls in hand at the most, so there this reads 0 whatever happened: it
can move only where the window is the untraced run's (some forty
iterations), and `BENCHMARK.json` names it when the harness reads the
ring there (PERF.md, Open questions)."""
from benchmarks.lib import iterspans

LAYER = "boosting-loop"
UNIT = "count"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    evs = iterspans.events()
    iters = iterspans.window(run, evs)
    if not iters or not all("iteration" in it.labels for it in iters):
        return None
    numbers = {it.labels["iteration"] for it in iters}
    return sum(e.name == "train/stall"
               and e.labels.get("iteration") in numbers for e in evs)
