"""Hunt the `update()` that stalls (ROADMAP A6): run a train cell's
untraced window again and again and print, for every window, each
iteration's `update()` wall, the interval between two trees' arrivals
on the host, and the account of any iteration the program's own rule
called a stall (`telemetry.train_iteration`: the `train/stall` event and
its warning line).  An experiment, not the benchmark: it reads the
program's flight recorder through `benchmarks/lib/iterspans.py` and
drives the cell through the harness's own `open_cell` / driver.

    python exp/stall_hunt.py --workload higgs-train --seed 3000000901 \
        --runs 12 --windows 1

`--runs R` makes R runs one after the other, EACH IN A PROCESS OF ITS
OWN (a child per run, seed + i; this process stays off JAX, so the chip
is the child's): set-up and `--windows` windows of `--seconds` each, as
`benchmarks/run.py` makes one.  The stalls on record were seen one run a
process, so that is the default; `--windows` > 1 keeps a process for
longer where the question is whether its age matters.

`--profile` wraps chunks of `--chunk` iterations in a profiler session
and reads a chunk's trace only when a stall fell inside it: for that
iteration, on the device's clock, how busy the chip was, its longest
operation, its idle gaps and the programs it ran, beside the program's
own `lgbm/` spans (a device busy for seconds inside one operation, a
device idle with nothing enqueued, or a device done and the fetch not
delivered).  The other chunks' traces are deleted unread.

`--inject fetch` / `--inject gc` makes ONE stall of a known cause in
the first window (a 2 s sleep inside the assembler thread's blocking
fetch; a collection of some millions of objects on the dispatch
thread): the rule's verdict has to be `tree_late` / `gc`.  The patch is
made from here, the program has no switch for it.

`--ratio 1.5` lowers the bar of the program's rule for the hunt (3
times the median, and 50 ms, in the program), so that an `update()` of
twice the usual gets its account too.  A window's line also lists the
iterations whose fetch was enqueued behind the NEXT iteration's step
(`fetch_behind_next_step`): such a tree arrives an iteration late and
the next `update()` returns at once; nothing is lost.

A heartbeat thread (sleep 50 ms, note how late it woke) runs beside the
windows: a host that stood still shows as a late beat where a tree that
came late does not (a beat needs the interpreter: a thread that holds
it for seconds delays the beat too).
"""
import argparse
import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BEAT_S = 0.05
TRACE_DIR = os.path.join(REPO, "chiprun_out", "stall_hunt_trace")


def say(kind, **fields):
    print("[hunt] %-8s %s" % (kind, json.dumps(fields, default=str)),
          flush=True)


class Heartbeat(threading.Thread):
    """Wakes every `BEAT_S` and keeps the beats that came late."""

    def __init__(self):
        super().__init__(name="stall-hunt-heartbeat", daemon=True)
        self.late = []                  # (monotonic ns of the beat, late s)
        self.beats = 0
        self._halt = threading.Event()

    def run(self):
        due = time.monotonic() + BEAT_S
        while not self._halt.wait(max(due - time.monotonic(), 0.0)):
            now = time.monotonic()
            self.beats += 1
            if now - due > BEAT_S:
                self.late.append((time.monotonic_ns(), now - due))
            due = max(due + BEAT_S, now)

    def take(self):
        late, self.late = self.late, []
        beats, self.beats = self.beats, 0
        return beats, late

    def stop(self):
        self._halt.set()
        self.join(5)


def arm(inject, bst, at_call, seconds=2.0, objects=16_000_000):
    """ONE stall of a known cause at the `at_call`-th iteration from
    here (patched from outside: the program has no switch for it): a
    sleep of `seconds` in the assembler thread's fetch, or a collection
    of `objects` objects on the dispatch thread."""
    import jax
    eng = bst._engine
    real_iter = eng.train_one_iter
    calls = [0]
    armed = [False]
    heap = []
    if inject == "gc":
        gc.disable()                    # the garbage waits for its turn
        for _ in range(objects // 2):
            a = []
            heap.append([a])
            a.append(heap[-1])

    def train_one_iter(*a, **k):
        calls[0] += 1
        if calls[0] == at_call:
            if inject == "gc":
                heap.clear()
                gc.enable()
                gc.collect()
            else:
                armed[0] = True
        return real_iter(*a, **k)
    eng.train_one_iter = train_one_iter

    if inject == "fetch":
        real_get = jax.device_get

        def slow_get(x):
            if armed[0] and threading.current_thread().name \
                    == "lgbm-tpu-assembler":
                armed[0] = False
                time.sleep(seconds)
            return real_get(x)
        jax.device_get = slow_get


def fetches_behind_the_next_step(iters, evs):
    """Indices of the window's iterations whose unit's fetch program
    (`launch/gbdt.pack_fetch`, assembler thread) was enqueued AFTER the
    next iteration's step (`launch/gbdt.step*`, dispatch thread): the
    device runs its programs in order, so that tree reaches the host
    only when the next one is done, an iteration late, and the
    `update()` after it finds both and returns at once."""
    from benchmarks.lib import iterspans

    def first(parent_ids, prefix):
        starts = [e.start_ns for e in evs
                  if e.parent in parent_ids and e.name.startswith(prefix)]
        return min(starts, default=None)
    out = []
    for k, (it, nxt) in enumerate(zip(iters, iters[1:])):
        fetch = first({d.id for d in iterspans.drains_of(it, evs)},
                      "launch/gbdt.pack_fetch")
        step = first({nxt.id}, "launch/gbdt.step")
        if fetch is not None and step is not None and step < fetch:
            out.append(k)
    return out


def window_report(run, index, heart, t_open_ns):
    """One window from the ring: the walls, the arrivals, the stalls."""
    from benchmarks.lib import iterspans
    evs = iterspans.events()
    iters = iterspans.window(run, evs)
    arrivals = [iterspans.tree_arrival_ns(it, evs) for it in iters]
    steps = [None if a is None or b is None else round((b - a) / 1e6, 1)
             for a, b in zip(arrivals, arrivals[1:])]
    numbers = {it.labels.get("iteration") for it in iters}
    stalls = [dict(e.labels) for e in evs if e.name == "train/stall"
              and e.labels.get("iteration") in numbers]
    walls = [round(it.dur_ns / 1e6, 1) for it in iters]
    beats, late = heart.take()
    say("window", index=index, iters=len(iters),
        s_per_iter=run.window.get("seconds", 0) / max(len(iters), 1),
        first_iteration=min(numbers, default=None),
        update_ms=walls, arrival_step_ms=steps,
        gc_ms=round(sum(iterspans.overlap_ns(e, iters) for e in evs
                        if e.name == "host/gc") / 1e6, 3),
        runq_ms=[round(it.labels.get("runq_ns", 0) / 1e6, 2)
                 for it in iters],
        majflt=sum(it.labels.get("majflt", 0) for it in iters),
        fetch_behind_next_step=fetches_behind_the_next_step(iters, evs),
        beats=beats,
        late_beats=[[round((t - t_open_ns) / 1e9, 3), round(s, 3)]
                    for t, s in late])
    for stall in stalls:
        say("stall", window=index, **stall)
    return iters, stalls


def profiled_window(run, seconds, chunk):
    """The driver's window in chunks of `chunk` iterations, each under a
    profiler session whose trace is read only if a stall fell inside."""
    import jax
    from benchmarks.lib import iterspans
    bst = run.state["bst"]
    t0 = time.perf_counter()
    iters = 0
    while time.perf_counter() - t0 < seconds:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        try:
            for _ in range(chunk):
                bst.update()
            bst.current_iteration()
        finally:
            jax.profiler.stop_trace()
        iters += chunk
        evs = iterspans.events()
        last = [e for e in evs if e.name == "train/iteration"][-chunk:]
        numbers = {it.labels.get("iteration") for it in last}
        for e in evs:
            if e.name == "train/stall" \
                    and e.labels.get("iteration") in numbers:
                say("device", **device_lines(TRACE_DIR,
                                             e.labels["iteration"]))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run.window.update(iters=iters, seconds=time.perf_counter() - t0)


def device_lines(trace_dir, iteration):
    """What the first chip did during the stalled iteration, which the
    trace finds by the `iteration` stat of `lgbm/train/iteration`."""
    from benchmarks.lib import xplane
    path = xplane.find(trace_dir)
    spans = []                          # (name, line, start, dur, stats)
    for plane in xplane._profile(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend((e.name, i, int(e.start_ns), int(e.duration_ns),
                              dict(e.stats)) for e in line.events
                             if e.name.startswith("lgbm/"))
    mine = [s for s in spans if s[0] == "lgbm/train/iteration"
            and s[4].get("iteration") == iteration]
    if not mine:
        return {"iteration": iteration, "found": False}
    _, _, lo, dur, _ = mine[0]
    hi = lo + dur
    devices = xplane.load(path).devices
    if not devices:                     # a rehearsal off the chip
        return {"iteration": iteration, "found": True, "wall_s": dur / 1e9,
                "device_busy_s": None}
    dev = devices[0]
    busy = xplane.clip(dev.busy, lo, hi)
    idle = sorted(xplane.gaps(dev.busy, lo, hi), key=lambda g: g[0] - g[1])
    ops = [o for o in dev.ops if o.end_ns > lo and o.start_ns < hi]
    longest = max(ops, key=lambda o: o.self_ns, default=None)
    return {
        "iteration": iteration, "found": True, "wall_s": dur / 1e9,
        "device_busy_s": xplane.length(busy) / 1e9,
        "idle_gaps_s": [[round((a - lo) / 1e9, 4), round((b - a) / 1e9, 4)]
                        for a, b in idle[:5]],
        "longest_op": None if longest is None else
        [longest.short, round(longest.self_ns / 1e9, 4),
         round((longest.start_ns - lo) / 1e9, 4)],
        "programs": [[name[:40], round((s - lo) / 1e9, 4), round(d / 1e9, 4)]
                     for name, s, d in dev.modules if s + d > lo and s < hi],
        "spans": [[name, line, round((s - lo) / 1e9, 4), round(d / 1e9, 4)]
                  for name, line, s, d, _ in spans
                  if s + d > lo and s < hi and d > 1e6],
    }


def one_run(args, **cell):
    """This process is the run: set-up once, then the windows.  `cell`
    goes to the harness's `open_cell` (a rehearsal's manifest)."""
    from benchmarks import run as brun
    from lightgbm_tpu.utils import log
    from lightgbm_tpu.runtime import telemetry
    _, run, driver, _ = brun.open_cell(args.workload, args.seed, False,
                                       **cell)
    driver.setup(run)
    telemetry.STALL_RATIO = args.ratio
    log.reset_log_level(log.LogLevel.WARNING)   # the stall line is one
    say("setup", seed=args.seed, **run.setup)
    if args.inject != "none":
        arm(args.inject, run.state["bst"], at_call=12,
            seconds=args.inject_seconds, objects=args.inject_objects)
    heart = Heartbeat()
    heart.start()
    caught = 0
    try:
        for index in range(args.windows):
            t_open_ns = time.monotonic_ns()
            heart.take()
            if args.profile:
                profiled_window(run, args.seconds, args.chunk)
            else:
                driver.window(run, args.seconds)
            _, stalls = window_report(run, index, heart, t_open_ns)
            caught += len(stalls)
    finally:
        heart.stop()
    say("run", seed=args.seed, windows=args.windows, stalls=caught,
        device=run.device)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="higgs-train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--windows", type=int, default=1)
    ap.add_argument("--runs", type=int, default=0,
                    help="so many runs, each in a child process (0: this "
                         "process is the one run)")
    ap.add_argument("--until-stalls", type=int, default=0,
                    help="with --runs: stop once so many were caught")
    ap.add_argument("--ratio", type=float, default=3.0,
                    help="call an iteration a stall from so many times "
                         "the median on (the program's rule says 3: set "
                         "from here, it has no switch for it)")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--inject", choices=("none", "fetch", "gc"),
                    default="none")
    ap.add_argument("--inject-seconds", type=float, default=2.0)
    ap.add_argument("--inject-objects", type=int, default=16_000_000)
    args = ap.parse_args(argv)
    if not args.runs:
        return one_run(args)

    caught = 0
    for i in range(args.runs):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--workload", args.workload, "--seed", str(args.seed + i),
               "--seconds", str(args.seconds), "--windows", str(args.windows),
               "--chunk", str(args.chunk), "--ratio", str(args.ratio),
               "--inject", args.inject,
               "--inject-seconds", str(args.inject_seconds),
               "--inject-objects", str(args.inject_objects)]
        if args.profile:
            cmd.append("--profile")
        out = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        kept = [ln for ln in out.stdout.splitlines()
                if ln.startswith("[hunt]") or "train/stall" in ln]
        print("\n".join(kept), flush=True)
        if out.returncode:
            print(out.stdout[-3000:], flush=True)
            say("failed", run=i, rc=out.returncode)
            return out.returncode
        caught += sum(ln.startswith("[hunt] stall") for ln in kept)
        if args.until_stalls and caught >= args.until_stalls:
            break
    say("hunt", runs=i + 1, stalls=caught)
    return 0


if __name__ == "__main__":
    sys.exit(main())
