"""The table of device peaks, keyed by `device_kind` as JAX reports it.
A kind that is not in the table is an error, never a default."""
import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def load(kind, path=PATH):
    with open(path) as fh:
        table = json.load(fh)
    if kind not in table["kinds"]:
        raise KeyError("no peaks for device kind %r in %s (known: %s); add "
                       "the kind with its source, do not default"
                       % (kind, path, sorted(table["kinds"])))
    return dict(table["kinds"][kind], source=table["source"])
