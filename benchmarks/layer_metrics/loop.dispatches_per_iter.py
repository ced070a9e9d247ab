"""Device programs the boosting loop launched per iteration of the
window (`xla_obs.calls_delta`: every ledgered jit call is one launch)."""
LAYER = "boosting-loop"
UNIT = "count"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(run):
    return sum(run.counts["calls"].values()) / run.window["iters"]
