"""The traffic generator: seeded, on an absolute clock, data-driven."""
import json
import os

import numpy as np

from benchmarks.lib import arrivals
from conftest import BENCH


def steady_mix():
    with open(os.path.join(BENCH, "traffic", "serve-steady.json")) as fh:
        return json.load(fh)


def test_plan_is_a_function_of_the_seed():
    mix = steady_mix()
    a, b, c = (arrivals.plan(mix, 10.0, s) for s in (5, 5, 6))
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["due"] == c["due"]).all()
    # every seed offers the same amount of work: rate x seconds requests
    rate = mix["rate_phases"][0][1]
    assert len(a["due"]) == len(c["due"]) == round(rate * 10.0)


def test_rate_and_sizes_follow_the_mix():
    mix = dict(steady_mix(), rate_phases=[[1.0, 400.0]])
    p = arrivals.plan(mix, 50.0, 1)
    assert len(p["due"]) == 20000
    gaps = np.diff(p["due"])
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05    # exponential gaps
    assert (np.diff(p["due"]) >= 0).all() and p["due"][-1] < 50.0
    rows = p["rows"]
    assert rows.min() == 1 and rows.max() <= 4096
    assert abs(np.mean(rows == 1) - 0.40) < 0.02
    assert abs(np.mean(rows > 256) - 0.05) < 0.01
    assert 60 < rows.mean() < 130            # "mean about 90"
    assert (p["start"] + rows <= mix["pool_rows"]).all()


def test_a_burst_mix_is_only_another_data_file():
    phases = [[4.5, 100.0], [0.5, 1000.0]]
    assert arrivals.rate_at(phases, 1.0) == 100.0
    assert arrivals.rate_at(phases, 4.7) == 1000.0
    assert arrivals.rate_at(phases, 5.2) == 100.0
    due = arrivals.poisson_arrivals(phases, 100.0, np.random.default_rng(2))
    in_burst = (due % 5.0) >= 4.5
    assert abs(in_burst.sum() / 10.0 - 1000.0) < 100.0
    assert abs((~in_burst).sum() / 90.0 - 100.0) < 10.0


def test_submit_loop_keeps_an_absolute_clock():
    """A stall delays the stalled request only: the next is submitted at
    its own due instant (or at once, if that has passed), and lateness
    does not pile up."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(seconds):
        now[0] += seconds

    def submit(i):
        now[0] += 0.25 if i == 1 else 0.0      # request 1 stalls

    due = np.array([0.1, 0.2, 0.3, 0.6])
    t0, submitted = arrivals.submit_loop(due, submit, clock=clock, sleep=sleep)
    lag = submitted - (t0 + due)
    assert np.allclose(lag, [0.0, 0.0, 0.15, 0.0])
