"""The learning task `binary`: log-loss on a dense numerical table, the
label a function of the columns (`lib/synth.py`, the function the
configuration names in `generator`).

What drivers/train.py asks of a task, and all it knows of one:

    make(cfg, seed, part)         the data of part 0 (train) or 1 (held-out)
    dataset_args(data)            keywords for `lgb.Dataset` beyond data,
                                  label and params
    first_tree(tree, data, cfg)   tree 0 against the plain recomputation
    heldout(trees, data, cfg)     the held-out measure, one float

`data` is the task's own dictionary; the driver reads `X` and `y` of it
and hands the rest back untouched.  `X` is a dense [n, F] array or a
scipy.sparse CSR table (`csr_matrix` or `csr_array`, float32 or
float64) in which an absent entry means 0.0; the driver reads only its
`shape` and hands it to `lgb.Dataset` as it is.  `lib/reference.py`
walks either form: a dense array by `X[rows, cols]`, a CSR table by the
value each row stores, 0.0 where it stores none, a row slice a chunk,
never densified (another sparse format is made CSR once, where a walk
enters).

`first_tree` returns `counts_ok`, `max_value_diff` and detail, and may
return `compared`, {name: [value, limit]}: numbers of the task's own
that the result's `compared` shows after the driver's (a name the
driver uses stays the driver's).  What decides `correct` is still
`counts_ok` and `max_value_diff`: fold into `counts_ok` whatever of
them must hold.
"""
from benchmarks.lib import quality, reference, synth


def make(cfg, seed, part):
    rows = cfg["heldout_rows"] if part else cfg["rows"]
    X, y = getattr(synth, cfg["generator"])(rows, cfg["features"],
                                            (seed, part))
    return {"X": X, "y": y}


def dataset_args(data):
    return {}


def first_tree(tree, data, cfg):
    """Binary log-loss boosted from the average: two distinct gradients
    and one hessian, so the leaves follow from the labels alone
    (`reference.tree0_check`)."""
    p = cfg["params"]
    return reference.tree0_check(tree, data["X"], data["y"],
                                 p["learning_rate"], p.get("lambda_l2", 0.0))


def heldout(trees, data, cfg):
    raw = reference.predict_raw(trees, data["X"])
    return float(quality.METRICS[cfg["quality_metric"]](data["y"], raw))
