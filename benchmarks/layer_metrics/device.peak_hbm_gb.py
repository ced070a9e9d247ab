"""Peak device memory in use on the fullest chip, in GB
(`memory_stats()`, key `peak_bytes_in_use`)."""
LAYER = "device"
UNIT = "GB"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = None


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
