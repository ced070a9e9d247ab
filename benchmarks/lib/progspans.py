"""What the program says about itself, read from its two sinks.

The program has ONE span source, `lightgbm_tpu/runtime/tracing.py`: a
live span goes into the flight recorder's ring (host clock, parent ids,
thread) and, while a profiler session is on, into the trace's host plane
as `lgbm/<name>` (the device's clock, the thread's line).  The phases of
the fused step are `jax.named_scope("lgbm.<phase>")`: in a v5e trace
they sit in the `tf_op` stat of each operation's METADATA
(`XPlane.event_metadata`), which `jax.profiler.ProfileData` does not
hand out, so a small reader of the protobuf wire format fetches them
(PERF.md, "Names in the trace").

`lib/xplane.py` keeps only the harness's own `bench/` annotations, so
the readers here open the same file again.  A program without these
spans or scopes (the parent of the PR that added them) gives empty
lists, and every metric built on them reads None.

    python3 benchmarks/lib/progspans.py <trace-dir-or-file>   # a summary
"""
import functools
import gzip
import os
import re
import sys
from collections import defaultdict, namedtuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.lib import xplane

#: what `tracing.span` puts before a span's name in the profiler's trace
TRACE_PREFIX = "lgbm/"
#: a phase scope in an operation's `tf_op` (`jit(step)/while/body/
#: lgbm.tree_update/lgbm.hist/jit(_segment_histogram)/pallas_call:`);
#: scopes nest and the innermost, the last one, names the phase
SCOPE = re.compile(r"lgbm\.([a-z_]+)")

#: a completed span of the ring; times in ns on the host's clock
Span = namedtuple("Span", "name tid start_ns dur_ns id parent")
#: a program span in the trace; `thread` tells the host plane's lines
#: apart (their names do not: every Python thread's line is `python3`)
HostSpan = namedtuple("HostSpan", "name thread start_ns dur_ns")


# -- the ring ----------------------------------------------------------------

def ring():
    """Every completed span the flight recorder holds, oldest first (a
    process makes one run, so the ring is the run's)."""
    from lightgbm_tpu.runtime import tracing
    return [Span(e["name"], e["tid"], int(round(e["ts"] * 1e3)),
                 int(round(e["dur"] * 1e3)), e["args"].get("span"),
                 e["args"].get("parent"))
            for e in tracing.export_chrome()["traceEvents"] if e["ph"] == "X"]


def ring_seconds(name):
    """Seconds in the ring's spans of that name; None if it has none."""
    spans = [s for s in ring() if s.name == name]
    return sum(s.dur_ns for s in spans) / 1e9 if spans else None


def children(span, spans, prefix=""):
    """The spans opened directly under `span`, on any thread."""
    return [s for s in spans if s.parent == span.id
            and s.name.startswith(prefix)]


def inside(span, spans, prefixes):
    """Union, in ns, of the spans with one of the name prefixes that ran
    on `span`'s thread within its interval."""
    end = span.start_ns + span.dur_ns
    return xplane.length(xplane.merge(
        (s.start_ns, min(s.start_ns + s.dur_ns, end)) for s in spans
        if s.tid == span.tid and span.start_ns <= s.start_ns < end
        and s is not span and s.name.startswith(tuple(prefixes))))


def window_iterations(run, spans):
    """The `train/iteration` spans of the measured window: the last
    `iters` of the ring (`verify` calls no `update()`)."""
    iters = [s for s in spans if s.name == "train/iteration"]
    n = int(run.window.get("iters", 0))
    return iters[-n:] if n and len(iters) >= n else []


# -- the trace's host plane --------------------------------------------------

def trace_file(run):
    """The traced window's `xplane.pb`, where `run.py` wrote it (it
    removes the directory only after the metrics are read); None for an
    untraced run."""
    path = os.path.join(os.path.dirname(run.bench_dir), "chiprun_out",
                        "bench_trace", run.cell["name"])
    return xplane.find(path) if run.trace and os.path.isdir(path) else None


@functools.lru_cache(maxsize=2)     # several readers open one file
def host_spans(file):
    """The program's spans in a trace file, sorted by start."""
    out = []
    for plane in xplane._profile(file).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend(HostSpan(e.name[len(TRACE_PREFIX):],
                                "%s#%d" % (plane.name, i),
                                int(e.start_ns), int(e.duration_ns))
                       for e in line.events
                       if e.name.startswith(TRACE_PREFIX))
    return sorted(out, key=lambda s: s.start_ns)


def charge_gaps(gaps, spans):
    """{span name: ns} for idle intervals of a device: each is charged to
    the program span that covers its middle (the innermost, that is the
    shortest, on whatever thread) or to "unattributed"."""
    total = defaultdict(int)
    for a, b in gaps:
        mid = (a + b) // 2
        covering = [s for s in spans
                    if s.start_ns <= mid < s.start_ns + s.dur_ns]
        name = min(covering, key=lambda s: s.dur_ns).name if covering \
            else "unattributed"
        total[name] += b - a
    return dict(total)


# -- the phases of the fused step, from the operations' metadata -------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[kind]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _xspace_bytes(path):
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return fh.read()
    if path.endswith((".txt", ".textproto")):
        from jax.profiler import ProfileData
        with open(path) as fh:
            return ProfileData.text_proto_to_serialized_xspace(fh.read())
    with open(path, "rb") as fh:
        return fh.read()


@functools.lru_cache(maxsize=2)
def op_phases(file):
    """{device plane name: {operation name: phase}} for the operations
    of a trace file whose metadata carries a `tf_op` with an
    `lgbm.<phase>` scope.
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5
    (maps: key=1, value=2); XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .str_value=5; XStatMetadata.name=2."""
    out = {}
    for field, plane in _fields(_xspace_bytes(file)):
        if field != 1:
            continue
        name, events, tf_op = "", [], None
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                entry = dict(_fields(v))
                if dict(_fields(entry.get(2, b""))).get(2) == b"tf_op":
                    tf_op = entry.get(1)
        if tf_op is None or not xplane.DEVICE_PLANE.match(name):
            continue
        phases = out[name] = {}
        for meta in events:
            op_name = None
            for f, v in _fields(meta):
                if f == 2:
                    op_name = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op:
                        found = SCOPE.findall(stat.get(5, b"").decode())
                        if found:
                            phases[op_name] = found[-1]
    return out


def phase_seconds(run):
    """{phase: self seconds of its operations inside the traced window,
    a chip's average}; None where the trace has no device plane or no
    operation with a phase (another platform, an older program)."""
    trace, file = run.xtrace, trace_file(run)
    if trace is None or not trace.devices or file is None:
        return None
    by_plane = op_phases(file)
    lo, hi = trace.window_ns()
    total = defaultdict(int)
    for dev in trace.devices:
        phases = by_plane.get("/device:TPU:%d" % dev.ordinal, {})
        for op in dev.ops:
            if op.end_ns > lo and op.start_ns < hi and op.name in phases:
                total[phases[op.name]] += op.self_ns
    if not total:
        return None
    return {p: ns / 1e9 / len(trace.devices) for p, ns in total.items()}


def phase_s_per_iter(run, *phases):
    """Seconds per iteration in the given phases; None as above."""
    found = phase_seconds(run)
    if found is None:
        return None
    return sum(found.get(p, 0.0) for p in phases) / run.window["iters"]


# -- a summary by hand -------------------------------------------------------

def summary(path, out=sys.stdout):
    path = xplane.find(path)
    by_line = defaultdict(lambda: defaultdict(lambda: [0, 0]))
    for s in host_spans(path):
        rec = by_line[s.thread][s.name]
        rec[0] += 1
        rec[1] += s.dur_ns
    for thread, names in sorted(by_line.items()):
        print("LINE %s" % thread, file=out)
        for name, (n, ns) in sorted(names.items(), key=lambda kv: -kv[1][1]):
            print("  %10.6f s %6d x  %s%s" % (ns / 1e9, n, TRACE_PREFIX, name),
                  file=out)
    for plane, phases in sorted(op_phases(path).items()):
        count = defaultdict(int)
        for phase in phases.values():
            count[phase] += 1
        print("PLANE %s: operations by phase %s" % (plane, dict(count)),
              file=out)


if __name__ == "__main__":
    summary(sys.argv[1])
