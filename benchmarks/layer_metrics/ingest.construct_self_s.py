"""Seconds of `Dataset.construct()` in none of its named parts: the
program's `dataset/construct` span less the spans opened directly under
it (`dataset/find_bins`, `dataset/encode`, `dataset/bundle`).  What is
left is the conversion of the caller's matrix, the allocation of the bin
storage and the metadata."""
from benchmarks.lib import progspans

LAYER = "ingest"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    ring = progspans.ring()
    spans = [s for s in ring if s.name == "dataset/construct"]
    if not spans:
        return None
    return sum(s.dur_ns - sum(c.dur_ns for c in progspans.children(s, ring))
               for s in spans) / 1e9
