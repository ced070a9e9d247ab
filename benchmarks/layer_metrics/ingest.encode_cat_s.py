"""Seconds of `Dataset.construct()` spent turning the CATEGORICAL columns
of the raw matrix into bin indices (the program's
`dataset/encode_categorical` spans, inside `dataset/encode`, labelled
with the columns and the path, `native` or `python`; from the flight
recorder's ring).  None for a program without the span, and for data
without a categorical column."""
from benchmarks.lib import progspans

LAYER = "ingest"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(run):
    return progspans.ring_seconds("dataset/encode_categorical")
