"""Seconds of `Dataset.construct()` spent finding the bin boundaries of
every feature from the sampled rows (the program's `dataset/find_bins`
span, from the flight recorder's ring)."""
from benchmarks.lib import progspans

LAYER = "ingest"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    return progspans.ring_seconds("dataset/find_bins")
