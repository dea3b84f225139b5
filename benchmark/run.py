#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, loads its configuration and traffic files,
imports the driver the traffic file names and, in a traced run, the reader of
each per-layer metric that lists the cell. Holds no cell's, configuration's or
metric's name. Prints one JSON object as the last line of standard output.
`--rehearsal 1` runs the cell's `rehearsal` sizes on whatever backend JAX has
and reports no metric: it proves the control flow, never a speed.
"""

import time
T_START = time.perf_counter()

import argparse                                              # noqa: E402
import glob                                                  # noqa: E402
import importlib                                             # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import threading                                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check_manifest                         # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_AFTER_S, TRACE_FOR_S = 2.0, 3.0


class Clock:
    """Compile and cache-load seconds as JAX reports them (after
    chip_smoke.Clock), split where set-up ends; programs compiled while the
    window is open are counted apart."""

    def __init__(self):
        import jax.monitoring
        self.compile_s = self.setup_compile_s = 0.0
        self.programs = self.cache_hits = self.window_compiles = 0
        self.setup_s = None
        self.window_open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event in (BACKEND_COMPILE_EVENT, CACHE_RETRIEVAL_EVENT):
            self.compile_s += secs
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1
            self.window_compiles += self.window_open

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def close_setup(self):
        self.setup_s = time.perf_counter() - T_START
        self.setup_compile_s = self.compile_s
        self.window_open = True

    def close_window(self):
        self.window_open = False


class Tracer:
    """Traces TRACE_FOR_S seconds of the window, from TRACE_AFTER_S in. The
    profiler is started and stopped on a thread of its own so that the feed
    is not held up; host and Python tracing are off (the device planes are
    all that is read, and a host trace of four seconds is 400 MB)."""

    def __init__(self, on, seconds):
        self.on, self.state = on, "idle"
        self.after = min(TRACE_AFTER_S, seconds / 4)
        self.length = min(TRACE_FOR_S, seconds / 2)
        self.thread = None

    def tick(self, elapsed, counted):
        if self.on and self.state == "idle" and elapsed >= self.after:
            self.state = "tracing"
            self.thread = threading.Thread(target=self._trace, daemon=True)
            self.thread.start()

    def _trace(self):
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        time.sleep(self.length)
        jax.profiler.stop_trace()

    def finish(self):
        if self.thread is not None:
            self.thread.join()
        files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return max(files, key=os.path.getmtime) if files else None


def fail(msg):
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(1)


def load_cell(manifest, name):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(check_manifest.find_traffic(ROOT, manifest["paths"],
                                          cell["traffic"])) as f:
        traffic = json.load(f)
    return cell, config, traffic


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def metrics_for(manifest, kind, cell_name):
    return [m for m in manifest[kind]
            if cell_name in check_manifest.cells_of(manifest, m)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest_path = os.path.join(ROOT, "BENCHMARK.json")
    faults = check_manifest.check(manifest_path)
    if faults:
        fail("manifest: " + "; ".join(faults))
    with open(manifest_path) as f:
        manifest = json.load(f)
    cell, config, traffic = load_cell(manifest, args.workload)
    if args.rehearsal:
        config = merge(config, config.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    if args.trace:
        # the program's own switch; its counters are read in the traced run
        os.environ["MMLSPARK_TPU_TELEMETRY"] = "1"

    import jax
    clock = Clock()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks_table = json.load(f)
    if not args.rehearsal:
        if platform != "tpu" or len(devices) < cell["chips"]:
            fail(f"the cell needs {cell['chips']} TPU chip(s); JAX found "
                 f"{len(devices)} {platform} device(s)")
        if kind not in peaks_table:
            fail(f"device kind {kind!r} is not in peaks.json")
    devices = devices[:cell["chips"]]

    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    tracer = Tracer(bool(args.trace), args.seconds)
    counters_before = program_counters() if args.trace else {}
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": args.seed, "seconds": args.seconds, "clock": clock,
           "tracer": tracer, "devices": devices,
           "rehearsal": bool(args.rehearsal)}
    result = driver.run(ctx)
    clock.close_window()
    trace_file = tracer.finish()
    if clock.window_compiles:
        fail(f"{clock.window_compiles} program(s) compiled inside the window")

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    values = dict(result["metrics"])
    values["setup_s"] = clock.setup_s
    values["peak_hbm_gib"] = peak_bytes / 2 ** 30
    out = {"correct": False, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": {}, "device": device}

    if args.trace and not args.rehearsal:
        if trace_file is None:
            fail("the traced run left no trace file")
        from benchmark import trace_reduce
        trace = trace_reduce.summarise(trace_reduce.load_events(trace_file))
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        out["breakdown"] = trace["breakdown"]
        counters = dict(result["counters"])
        counters.update({"setup_compile_s": clock.setup_compile_s,
                         "programs": clock.programs,
                         "cache_hits": clock.cache_hits})
        after = program_counters()
        counters["program"] = {k: after[k] - counters_before.get(k, 0.0)
                               for k in after}
        cell_info = {"cell": cell, "config": config, "traffic": traffic,
                     "peaks": peaks_table[kind], "chips": len(devices)}
        for m in metrics_for(manifest, "per_layer", cell["name"]):
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}")
            value = reader.read(trace, counters, cell_info)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    elif not args.rehearsal:
        for m in metrics_for(manifest, "end_to_end", cell["name"]):
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the check comes last: the peak is read and the program's state is gone
    t_check = time.perf_counter()
    correct, compared, notes = result.pop("check")()
    out["correct"] = bool(correct and result["failed"] == 0)
    out["setup"] = {"setup_s": clock.setup_s,
                    "setup_compile_s": clock.setup_compile_s,
                    "programs": clock.programs,
                    "cache_hits": clock.cache_hits}
    out["window"] = result["counters"]
    out["check_s"] = time.perf_counter() - t_check
    out["notes"] = notes
    if args.rehearsal:
        out["rehearsal"] = True
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def program_counters():
    """The program's counters as its telemetry registry gives them, summed
    over label sets: {family name: value}."""
    from mmlspark_tpu import telemetry
    out = {}
    for name, fam in telemetry.snapshot().items():
        if fam["type"] != "counter":
            continue
        out[name] = float(sum(s.get("value", 0.0) for s in fam["series"]))
    return out


if __name__ == "__main__":
    sys.exit(main())
