"""One general generator of request traffic, driven by a mix's data file.

A copy of what is sound in `runtime/loadgen.py` (PERF.md, Open
questions, lists the original): a seeded Poisson process by thinning,
and a submit loop on an ABSOLUTE clock, so a stall delays nothing that
comes after it and a request's latency counts from the instant it was
due.  What a mix may say (all in its JSON file, none in code):

    "rate_phases": [[seconds, requests_per_s], ...]   repeated cyclically;
                                   one phase is a steady rate, two a burst
    "request_rows": [{"share": 0.4, "rows": [1, 1]}, ...]   size classes,
                                   log-uniform inside each class
    "pool_rows": 65536             rows the requests are cut from

`plan` draws everything from the seed before the window opens: the
window itself draws nothing.
"""
import time

import numpy as np


def rate_at(phases, t):
    """The offered rate at offset t of a cyclic piecewise-constant
    schedule [[seconds, rate], ...]."""
    period = sum(p[0] for p in phases)
    t = t % period
    for seconds, rate in phases:
        if t < seconds:
            return rate
        t -= seconds
    return phases[-1][1]


def poisson_arrivals(phases, duration_s, rng):
    """Sorted arrival offsets in [0, duration_s) of a Poisson process
    with rate `rate_at(phases, t)`, by thinning a homogeneous one at the
    peak rate.  The homogeneous process is CONDITIONED ON ITS COUNT,
    peak x duration to the nearest request: given the count, Poisson
    arrivals are uniform order statistics, so the gaps are the
    process's and every seed offers the same amount of work (a count
    drawn as well would move the offered load by 1/sqrt(n) from seed to
    seed: 92 requests for an expected 60, in PR 22's sweep)."""
    peak = max(p[1] for p in phases)
    n = int(round(peak * duration_s))
    if n <= 0:
        return np.zeros(0)
    t = np.sort(rng.uniform(0.0, duration_s, size=n))
    if len(phases) == 1:
        return t
    keep = rng.uniform(0.0, peak, size=n) < \
        np.array([rate_at(phases, x) for x in t])
    return t[keep]


def request_rows(classes, n, rng):
    """Rows of each of n requests: a class by its share, then
    log-uniform over the class's [lo, hi]."""
    shares = np.array([c["share"] for c in classes], float)
    which = rng.choice(len(classes), size=n, p=shares / shares.sum())
    lo = np.array([c["rows"][0] for c in classes], float)[which]
    hi = np.array([c["rows"][1] for c in classes], float)[which]
    rows = np.floor(np.exp(rng.uniform(np.log(lo), np.log(hi + 1.0))))
    return np.clip(rows, lo, hi).astype(np.int64), which


def plan(traffic, duration_s, seed):
    """{"due": offsets, "rows": rows, "start": first pool row, "class":
    size class} of every request of a window."""
    rng = np.random.default_rng(seed)
    due = poisson_arrivals(traffic["rate_phases"], duration_s, rng)
    rows, which = request_rows(traffic["request_rows"], len(due), rng)
    start = rng.integers(0, traffic["pool_rows"] - rows + 1)
    return {"due": due, "rows": rows, "start": start, "class": which}


def submit_loop(due, submit, clock=time.monotonic, sleep=time.sleep):
    """Call `submit(i)` for request i at `t0 + due[i]` on an absolute
    clock: sleep until the instant, never for an interval, so lateness
    does not accumulate.  Returns (t0, the instant each was submitted);
    lag is `submitted - (t0 + due)`."""
    submitted = np.empty(len(due))
    t0 = clock()
    for i, off in enumerate(due):
        wait = t0 + off - clock()
        if wait > 0:
            sleep(wait)
        submitted[i] = clock()
        submit(i)
    return t0, submitted
