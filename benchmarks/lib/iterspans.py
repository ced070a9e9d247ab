"""An iteration's own account, read from the flight recorder's ring.

Since ISSUE 35 the program's `train/iteration` and `assembler/drain`
spans carry labels: the iteration's number, the unit a drain drains
(`iteration`, `tree`), what the kernel says the thread did meanwhile
(`cpu_ns`, `runq_ns`); a garbage collection is a span (`host/gc`) and an
iteration out of line leaves a `train/stall` event.  `progspans.ring()`
keeps a span's name, thread, times and ids and drops its labels, so the
readers of those labels take the ring from here: every completed span
and every instant with its `args`.

A program without the labels (the parent of that PR) gives events whose
`labels` lack them; each reader built on this says None then.
"""
from collections import namedtuple

from benchmarks.lib import progspans

#: a span ('X') or an instant ('i') of the ring; times in ns on the
#: host's clock, `labels` the event's `args` (ids and status among them)
Event = namedtuple("Event", "name ph tid start_ns dur_ns id parent labels")


def events():
    """Every span and instant the ring holds, oldest first."""
    from lightgbm_tpu.runtime import tracing
    out = []
    for e in tracing.export_chrome()["traceEvents"]:
        if e["ph"] in ("X", "i"):
            args = e.get("args", {})
            out.append(Event(e["name"], e["ph"], e["tid"],
                             int(round(e["ts"] * 1e3)),
                             int(round(e.get("dur", 0.0) * 1e3)),
                             args.get("span"), args.get("parent"), args))
    return out


def window(run, evs):
    """The `train/iteration` spans of the measured window."""
    return progspans.window_iterations(run, [e for e in evs if e.ph == "X"])


def accounted(iters):
    """Whether the iterations carry the host's account at all."""
    return bool(iters) and all("cpu_ns" in it.labels for it in iters)


def drains_of(it, evs):
    """The `assembler/drain` spans of the units dispatched under an
    iteration: by the `iteration` label where the program gives one, by
    the parent id where it does not."""
    if "iteration" in it.labels:
        return [e for e in evs if e.name == "assembler/drain"
                and e.labels.get("iteration") == it.labels["iteration"]]
    return [e for e in evs if e.name == "assembler/drain"
            and e.parent == it.id]


def tree_arrival_ns(it, evs):
    """When the last tree of an iteration was on the host: the close of
    the last `fetch/<label>` span under its drains; None where it has
    none."""
    ids = {d.id for d in drains_of(it, evs)}
    closes = [e.start_ns + e.dur_ns for e in evs
              if e.parent in ids and e.name.startswith("fetch/")]
    return max(closes) if closes else None


def overlap_ns(span, iters):
    """ns of `span` inside the iterations' own intervals."""
    end = span.start_ns + span.dur_ns
    return sum(max(0, min(end, it.start_ns + it.dur_ns)
                   - max(span.start_ns, it.start_ns)) for it in iters)
