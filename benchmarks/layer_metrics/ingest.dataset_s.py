"""Host seconds round `lgb.Dataset(...).construct()`: conversion, bin
finding and the native bin encoding of the whole training matrix."""
LAYER = "ingest"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"
DRIVERS = ("train",)


def read(run):
    return run.setup.get("dataset_s")
