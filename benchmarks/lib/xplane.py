"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the
per-layer metrics read: for each device its operations with self times
and the intervals in which it was busy, and the host's annotations on
the same clock.

`jax.profiler.ProfileData` reads the file with nothing but JAX.  What
the planes and lines of a TPU trace are called, and how today's kernels
and steps are named in it, is recorded in PERF.md ("Names in the
trace"); the patterns that class operations live in each metric's own
file, not here.

    python3 benchmarks/lib/xplane.py <trace-dir-or-file>   # a summary

Times are seconds unless a name ends in `_ns`.
"""
import glob
import gzip
import os
import re
import sys
from collections import defaultdict

#: a device plane, and the chip's ordinal in its name
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a device plane that holds one event per executed HLO
#: operation (nested: a `while` spans its body's operations)
OPS_LINE = "XLA Ops"
#: the line with one event per executed program
MODULES_LINE = "XLA Modules"
#: host events the harness writes round each call it makes
ANNOTATION_PREFIX = "bench/"
#: an instruction's own name at the head of its HLO text
SHORT_NAME = re.compile(r"%?([^\s=(]*)")


class Op:
    """One executed operation on a device.  `name` is what the trace
    calls it: on a TPU the whole HLO instruction, `%<name>.<n> = <shape>
    <opcode>(<operands>), <attributes>`; name patterns match against
    that text.  `short` is the instruction's own name."""
    __slots__ = ("name", "start_ns", "dur_ns", "self_ns")

    def __init__(self, name, start_ns, dur_ns):
        self.name = name
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.self_ns = dur_ns

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns

    @property
    def short(self):
        return SHORT_NAME.match(self.name).group(1)


class Device:
    def __init__(self, ordinal, ops, modules):
        self.ordinal = ordinal
        self.ops = ops                  # sorted by start, self times set
        self.modules = modules          # [(name, start_ns, dur_ns)]
        self.busy = merge([(o.start_ns, o.end_ns) for o in ops])


class Trace:
    def __init__(self, devices, spans):
        self.devices = devices          # [Device], by ordinal
        self.spans = spans              # [(name, start_ns, dur_ns)] host

    def window_ns(self):
        """The traced window: from the first to the last instant covered
        by a harness annotation; without annotations, by any device
        operation."""
        marks = [(s, s + d) for _, s, d in self.spans] \
            or [iv for dev in self.devices for iv in dev.busy]
        if not marks:
            return (0, 0)
        return (min(a for a, _ in marks), max(b for _, b in marks))


# -- intervals ---------------------------------------------------------------

def merge(intervals):
    """Union of [a, b) intervals as a sorted list of disjoint ones."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b):
    """The part of the disjoint sorted intervals `a` not covered by the
    disjoint sorted intervals `b`."""
    out = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(intervals, lo, hi):
    """The idle intervals of [lo, hi) given the busy ones."""
    return subtract([(lo, hi)], clip(intervals, lo, hi))


# -- reading -----------------------------------------------------------------

def find(path):
    """The newest `*.xplane.pb` under a trace directory (or the file)."""
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                      recursive=True)
    if not files:
        raise FileNotFoundError("no *.xplane.pb under %s" % path)
    return max(files, key=os.path.getmtime)


def _profile(path):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    if path.endswith(".txt") or path.endswith(".textproto"):
        with open(path) as fh:
            return ProfileData.from_text_proto(fh.read())
    return ProfileData.from_file(path)


def _set_self_times(ops):
    """Operations of one line nest (a loop spans its body): an
    operation's self time is its duration less its children's."""
    ops.sort(key=lambda o: (o.start_ns, -o.dur_ns))
    stack = []
    for op in ops:
        while stack and stack[-1].end_ns <= op.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= min(op.dur_ns,
                                     stack[-1].end_ns - op.start_ns)
        stack.append(op)
    for op in ops:
        op.self_ns = max(op.self_ns, 0)


def load(path):
    """Read a trace into `Trace`."""
    profile = _profile(find(path))
    devices, spans = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Op(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events)
            _set_self_times(ops)
            devices.append(Device(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(ANNOTATION_PREFIX))
    devices.sort(key=lambda d: d.ordinal)
    spans.sort(key=lambda s: s[1])
    return Trace(devices, spans)


# -- reductions --------------------------------------------------------------

def seconds_matching(device, pattern, lo, hi):
    """Self seconds, inside [lo, hi), of the operations whose name
    matches the compiled pattern."""
    total = 0
    for op in device.ops:
        if op.end_ns <= lo or op.start_ns >= hi:
            continue
        if pattern.search(op.name):
            total += op.self_ns
    return total / 1e9


def mean_seconds_matching(trace, pattern):
    """`seconds_matching` inside the traced window, a chip's average;
    None for a trace with no device plane."""
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window_ns()
    return sum(seconds_matching(d, pattern, lo, hi)
               for d in trace.devices) / len(trace.devices)


def intervals_matching(device, pattern, lo, hi):
    return merge(clip([(o.start_ns, o.end_ns) for o in device.ops
                       if pattern.search(o.name)], lo, hi))


def leaf_intervals(device, lo, hi, exclude=None):
    """Intervals in which an operation with no child ran (a loop's own
    event is not work), leaving out those that match `exclude`."""
    ivs = [(o.start_ns, o.end_ns) for o in device.ops
           if o.self_ns == o.dur_ns
           and not (exclude is not None and exclude.search(o.name))]
    return merge(clip(ivs, lo, hi))


def busy_seconds(trace):
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = trace.window_ns()
    busy = [length(clip(d.busy, lo, hi)) for d in trace.devices] or [0]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def top_ops(trace, n=10):
    """[[name, self seconds]] of the operations that took most device
    time inside the window, summed over devices and occurrences."""
    lo, hi = trace.window_ns()
    total = defaultdict(int)
    for dev in trace.devices:
        for op in dev.ops:
            if op.end_ns > lo and op.start_ns < hi:
                total[label(op)] += op.self_ns
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def label(op):
    """A short, stable name for an operation: the instruction's own
    name without its number (`_partition_segment_acc.11` and `.12` are
    one kernel)."""
    return re.sub(r"\.\d+$", "", op.short) or op.name[:64]


def idle_gaps(trace, n=10):
    """[[what the host was doing, seconds]] for the first device's idle
    time inside the window: each gap is charged to the harness
    annotation that covers its middle (the innermost one), or to
    "unannotated", and the charges are summed."""
    lo, hi = trace.window_ns()
    if not trace.devices or hi <= lo:
        return []
    total = defaultdict(int)
    for a, b in gaps(trace.devices[0].busy, lo, hi):
        mid = (a + b) // 2
        name, best = "unannotated", None
        for sname, s, d in trace.spans:
            if s <= mid < s + d and (best is None or d < best):
                name, best = sname, d
        total[name] += b - a
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


# -- a summary by hand ---------------------------------------------------------

def summary(path, out=sys.stdout, top=40):
    profile = _profile(find(path))
    for plane in profile.planes:
        lines = list(plane.lines)
        print("PLANE %r: %d lines" % (plane.name, len(lines)), file=out)
        for line in lines:
            events = list(line.events)
            print("  LINE %r: %d events" % (line.name, len(events)),
                  file=out)
            by_name = defaultdict(lambda: [0, 0])
            for e in events:
                rec = by_name[e.name]
                rec[0] += 1
                rec[1] += int(e.duration_ns)
            rows = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
            for name, (cnt, ns) in rows:
                print("    %10.6f s %7d x  %s" % (ns / 1e9, cnt, name[:160]),
                      file=out)
            shown = set()
            for e in events:
                if e.name in shown or len(shown) >= top:
                    continue
                shown.add(e.name)
                print("    e.g. %r start=%d dur=%d stats=%r"
                      % (e.name[:80], e.start_ns, e.duration_ns,
                         {k: (v[:300] if isinstance(v, str) else v)
                          for k, v in dict(e.stats).items()}), file=out)
    trace = load(path)
    busy, window = busy_seconds(trace)
    print("window %.6f s, busy %.6f s" % (window, busy), file=out)
    for row in top_ops(trace, 25):
        print("  op %-60s %.6f" % tuple(row), file=out)
    for row in idle_gaps(trace):
        print("  gap %-59s %.6f" % tuple(row), file=out)


if __name__ == "__main__":
    summary(sys.argv[1])
