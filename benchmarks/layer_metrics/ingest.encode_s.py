"""Seconds of `Dataset.construct()` spent turning the raw matrix into
bin indices (the program's `dataset/encode` spans, labelled with the
path they tried, `native` or `python`; from the flight recorder's
ring)."""
from benchmarks.lib import progspans

LAYER = "ingest"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    return progspans.ring_seconds("dataset/encode")
