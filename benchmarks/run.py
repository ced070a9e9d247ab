#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
under a traffic mix.  Everything that belongs to one of them is found by
its NAME, so a later PR adds files and one manifest entry and edits
nothing here (benchmarks/README.md):

    configs/<config>.json        sizes, parameters, source, cuts
    traffic/<mix>.json           the mix's parameters and its driver
    drivers/<driver>.py          setup(run), window(run, seconds), verify(run)
    layer_metrics/<metric>.py    read(run) -> float | None

A run refuses any platform but a TPU and fewer chips than the cell asks
for (exit 2, no result line), turns on the persistent compile cache
through the program's own seam (`warmup.enable_compile_cache`:
$JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache/), sets up
and warms this cell's shapes, measures for `--seconds`, verifies, and
prints one JSON object as its LAST line: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` with `--trace 1`), then
`compared`, each number the verdict rests on beside its limit, which are
also the last lines on standard error.  With
`--trace 0` the metrics are the cell's end-to-end ones, taken on the
host's clock with the profiler off; with `--trace 1` a short window is
traced and the metrics are the cell's per-layer ones.  Human-readable
detail goes to earlier `[bench]` lines and to chiprun_out/bench/.
"""
import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time


def _process_age_s():
    """Seconds since the kernel started this process: set-up counts the
    interpreter's start and the imports too."""
    with open("/proc/self/stat") as fh:
        start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as fh:
        uptime = float(fh.read().split()[0])
    return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)


#: the instant the process started, on `time.perf_counter`'s clock
T_PROCESS = time.perf_counter() - _process_age_s()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_REFUSED = 2
OUT_DIR = os.path.join("chiprun_out", "bench")
TRACE_DIR = os.path.join("chiprun_out", "bench_trace")


class Refused(Exception):
    """The run cannot be a measurement (no TPU, too few chips, no
    program): exit 2 and no result line."""


def load_module(path):
    """A driver or a metric reader, by file: names may hold dots."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def applies(entry, cell_name):
    return "workloads" not in entry or cell_name in entry["workloads"]


class Run:
    """What a driver fills and a metric reader reads."""

    def __init__(self, cell, config, traffic, seed, trace, bench_dir):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.trace = bool(trace)        # a short traced window
        self.bench_dir = bench_dir
        self.setup = {}                 # seconds of each part of set-up
        self.window = {}                # what the driver measured
        self.state = {}                 # the driver's own objects
        self.counts = {}                # counter movements over the window
        self.trees = []                 # trees the kernels' least work is of
        self.xtrace = None              # lib.xplane.Trace of a traced window
        self.device = {}
        self.peaks = None
        self.detail = {}
        self._readers = {}
        self._values = {}

    def say(self, stage, **fields):
        print("[bench] %-8s %s" % (stage, json.dumps(fields, default=str)),
              flush=True)
        self.detail.setdefault(stage, []).append(fields)

    def span(self, name):
        """A host annotation in the profiler's trace (next to nothing
        when no trace is being taken)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def timed(self, part):
        t0 = time.perf_counter()
        yield
        self.setup[part] = self.setup.get(part, 0.0) \
            + time.perf_counter() - t0

    def reader(self, name):
        if name not in self._readers:
            path = os.path.join(self.bench_dir, "layer_metrics", name + ".py")
            self._readers[name] = load_module(path)
        return self._readers[name]

    def metric(self, name):
        """A per-layer metric's value, or None where its reader finds
        nothing to read (readers may build on one another)."""
        if name not in self._values:
            reader = self.reader(name)
            drivers = getattr(reader, "DRIVERS", None)
            if drivers is not None and self.traffic["driver"] not in drivers:
                self._values[name] = None
            else:
                value = reader.read(self)
                self._values[name] = None if value is None else float(value)
        return self._values[name]


def device_report(jax, chips, require_tpu):
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise Refused("platform is %r (%s), not tpu: the benchmark measures "
                      "the chip and does not fall back"
                      % (platform, devices[0].device_kind))
    if len(devices) < chips:
        raise Refused("the cell asks for %d chips and JAX finds %d"
                      % (chips, len(devices)))
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def ledger_compile_seconds(xla_obs):
    return sum(site["compile_seconds"]
               for site in xla_obs.LEDGER.to_json()["sites"].values())


def memory_peak_bytes(jax):
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def open_cell(workload, seed, trace, *, manifest_path=None,
              bench_dir=BENCH_DIR, root=ROOT, require_tpu=True):
    """Find the cell's files by name, check the device, turn on the
    compile cache: (manifest, run, driver, compile counter)."""
    manifest = load_json(manifest_path or os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused("no workload %r in the manifest (has: %s)"
                      % (workload, sorted(cells)))
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      traffic["driver"] + ".py"))
    if importlib.util.find_spec("lightgbm_tpu") is None:
        raise Refused("no program beside the benchmark (%s/lightgbm_tpu)"
                      % ROOT)

    import jax
    from benchmarks.lib import compiles, peaks
    from lightgbm_tpu.runtime import warmup

    run = Run(cell, config, traffic, seed, trace, bench_dir)
    run.device = device_report(jax, cell["chips"], require_tpu)
    run.setup["startup_s"] = time.perf_counter() - T_PROCESS
    if run.device["platform"] == "tpu":
        run.peaks = peaks.load(run.device["kind"])
    cache_dir = warmup.enable_compile_cache()
    counter = compiles.CompileCounter().install()
    run.say("device", **run.device, jax=jax.__version__,
            compile_cache_dir=cache_dir, workload=workload, seed=run.seed,
            trace=int(trace))
    return manifest, run, driver, counter


def run_cell(workload, seed, seconds, trace, *, manifest_path=None,
             bench_dir=BENCH_DIR, root=ROOT, require_tpu=True,
             keep_trace=False):
    """One run of one cell; returns the result object (the last line)."""
    manifest, run, driver, counter = open_cell(
        workload, seed, trace, manifest_path=manifest_path,
        bench_dir=bench_dir, root=root, require_tpu=require_tpu)
    import jax
    from benchmarks.lib import xplane
    from lightgbm_tpu.runtime import syncs, xla_obs

    compile_s0 = ledger_compile_seconds(xla_obs)
    driver.setup(run)
    run.setup["compile_s"] = ledger_compile_seconds(xla_obs) - compile_s0
    run.counts["setup_compiles"] = counter.snapshot()

    trace_dir = os.path.join(root, TRACE_DIR, workload)
    before = {"compiles": xla_obs.snapshot(), "calls": xla_obs.calls_snapshot(),
              "syncs": syncs.snapshot(), "jax": counter.snapshot()}
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # our annotations, not every call
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_window = time.perf_counter()
    try:
        driver.window(run, float(seconds))
    finally:
        if trace:
            jax.profiler.stop_trace()
    run.window["wall_s"] = time.perf_counter() - t_window
    run.counts.update(
        compiles=xla_obs.delta(before["compiles"]),
        calls=xla_obs.calls_delta(before["calls"]),
        syncs=syncs.delta(before["syncs"]),
        jax=counter.delta(before["jax"]))
    run.setup["total_s"] = t_window - T_PROCESS
    compiled = sum(run.counts["compiles"].values()) \
        + run.counts["jax"]["requests"]

    verdict = driver.verify(run)
    if hasattr(driver, "teardown"):
        driver.teardown(run)
    if compiled:
        verdict["checks"]["compiled_in_window"] = {
            "ledger": run.counts["compiles"], "jax": run.counts["jax"]}
        verdict["correct"] = False
    #: each number the verdict rests on beside its limit: [value, limit]
    compared = dict(verdict.pop("compared", {}),
                    compiled_in_window=[compiled, 0])
    run.say("verify", **verdict)

    run.device["memory_peak_bytes"] = memory_peak_bytes(jax)
    device = dict(run.device)
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(verdict["attempted"]),
              "failed": int(verdict["failed"])}
    if trace:
        run.xtrace = xplane.load(trace_dir)
        busy_s, window_s = xplane.busy_seconds(run.xtrace)
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = {"device_ops": xplane.top_ops(run.xtrace),
                               "idle_gaps": xplane.idle_gaps(run.xtrace)}
        wanted = manifest["per_layer"]
        values = {m["name"]: run.metric(m["name"]) for m in wanted
                  if applies(m, workload)}
        if keep_trace:
            kept = os.path.join(root, OUT_DIR, "%s.s%d.xplane.pb"
                                % (workload, run.seed))
            os.makedirs(os.path.dirname(kept), exist_ok=True)
            shutil.copyfile(xplane.find(trace_dir), kept)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        wanted = manifest["end_to_end"]
        measured = dict(run.window.get("metrics", {}),
                        setup_s=run.setup["total_s"])
        values = {m["name"]: measured.get(m["name"]) for m in wanted
                  if applies(m, workload)}
    units = {m["name"]: m["unit"] for m in wanted}
    result["metrics"] = {name: {"value": value, "unit": units[name]}
                         for name, value in values.items()
                         if value is not None}
    result["device"] = device
    result["compared"] = compared           # last on the line

    run.say("setup", **run.setup)
    run.say("counts", **run.counts)
    out_path = os.path.join(root, OUT_DIR, "%s.s%d.t%d.json"
                            % (workload, run.seed, int(trace)))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump({"result": result, "detail": run.detail}, fh, indent=1,
                  default=str)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (the cells "
                         "held back in benchmarks/held/)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the traced window's xplane.pb to %s" % OUT_DIR)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), manifest_path=args.manifest,
                          keep_trace=args.keep_trace)
    except Refused as e:
        print("benchmarks/run.py: refused: %s" % e, file=sys.stderr)
        return EXIT_REFUSED
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["compared"].items():
        print("[bench] compared %s %s limit %s"
              % (name, json.dumps(value), json.dumps(limit)),
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
