"""Share of the first device's idle time in the traced window that no
span of the program accounts for: each idle interval is charged to the
`lgbm/<name>` host span that covers its middle (the innermost one, as
`xplane.idle_gaps` does for the harness's `bench/` annotations), and
what none covers is unattributed.  The seconds by span name go to a
`[bench] idle_by` line."""
from benchmarks.lib import progspans, xplane

LAYER = "device"
UNIT = "%"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = None


def read(run):
    trace, file = run.xtrace, progspans.trace_file(run)
    if trace is None or not trace.devices or file is None:
        return None
    spans = progspans.host_spans(file)
    lo, hi = trace.window_ns()
    charged = progspans.charge_gaps(
        xplane.gaps(trace.devices[0].busy, lo, hi), spans)
    idle = sum(charged.values())
    if not spans or not idle:
        return None
    rows = sorted(charged.items(), key=lambda kv: -kv[1])
    run.say("idle_by", idle_s=idle / 1e9,
            spans=[[name, ns / 1e9] for name, ns in rows])
    return 100.0 * charged.get("unattributed", 0) / idle
