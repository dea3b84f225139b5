#!/usr/bin/env python3
"""Check the ring's clock against the profiler's, on the chip.

    python3 tools/check_span_clock.py --workload joyai_train_stream --seed 7

The tracer's spans reach a profiler capture two ways (telemetry/tracer.py,
"Two clocks, two routes"): as annotations, which the capture keeps at
`host_tracer_level` >= 1, and through the ring's `clock/anchor` events, which
need no host tracing. This runs one benchmark cell's traffic as
`benchmark/run.py` does, with telemetry on and ONE capture at level 1, and
compares the two: every `fit/dispatch` of the ring against the event its
annotation left in the plane `/host:CPU`, placed both ways the repo places
one: the annotation's event onto the ring's clock by
`benchmark.host_timeline`'s `Timeline.ring_ns` (the mapping the six
`idle_in_*` and `step_host_interval_*` metrics go through), and the ring's
span onto the capture's by the program's `Tracer.to_unix_ns` less the
capture's `profile_start_time`. Prints one JSON line: the steps compared and
the largest distance of a start and of an end each way, in microseconds (the
ring keeps whole microseconds, and a span opens its annotation before it
reads its clock, so a microsecond or two is the floor). Exit 1 where a
distance passes 100 us or nothing could be compared. Outside the benchmark:
no metric reads it, and it checks nothing of the model. It needs the chip,
as `benchmark/run.py` does: on the CPU both routes read the same host clock
and agree whatever the chip's trace would say. `--rehearsal 1` runs the
cell's rehearsal sizes on whatever backend JAX has, to prove the control
flow and nothing else.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ["MMLSPARK_TPU_TELEMETRY"] = "1"

from benchmark import host_timeline, run as bench       # noqa: E402

SPAN, LIMIT_US = "fit/dispatch", 100.0


class Capture(bench.Tracer):
    """The benchmark's capture, with the host's TraceMe events kept."""

    def _trace(self):
        import jax
        shutil.rmtree(bench.TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(bench.TRACE_DIR, profiler_options=options)
        time.sleep(self.length)
        jax.profiler.stop_trace()


def annotations(path):
    """{step: (start_ns, end_ns)} of the capture's `fit/dispatch` events."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN:
                    step = dict(ev.stats).get("step")
                    out[int(step)] = (ev.start_ns, ev.start_ns
                                      + ev.duration_ns)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, config, traffic = bench.load_cell(manifest, args.workload)
    if args.rehearsal:      # the cell's small sizes, on whatever JAX has
        config = bench.merge(config, config.get("rehearsal", {}))
        traffic = bench.merge(traffic, traffic.get("rehearsal", {}))

    import jax
    from mmlspark_tpu import telemetry
    devices = jax.devices()[:cell["chips"]]
    if not args.rehearsal and (devices[0].platform != "tpu"
                               or len(devices) < cell["chips"]):
        sys.exit(f"needs {cell['chips']} TPU chip(s), found {len(devices)} "
                 f"{devices[0].platform} device(s); --rehearsal 1 proves "
                 "the control flow without one")
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    capture = Capture(True, args.seconds)
    driver.run({"cell": cell, "config": config, "traffic": traffic,
                "seed": args.seed, "seconds": args.seconds,
                "clock": bench.Clock(), "tracer": capture,
                "devices": devices, "rehearsal": bool(args.rehearsal)})
    path = capture.finish()
    start, _ = host_timeline.profile_times_ns(path)
    planes, line = host_timeline.load(path, telemetry.trace.events())
    seen = annotations(path)
    far = {"start": [], "end": [], "program_start": [], "program_end": []}
    for e in telemetry.trace.events() if line is not None else ():
        if e["name"] == SPAN and e["args"]["step"] in seen:
            a, b = seen[e["args"]["step"]]
            lo, hi = e["ts"] * 1000, (e["ts"] + e["dur"]) * 1000
            far["start"].append(abs(line.ring_ns(a) - lo) / 1e3)
            far["end"].append(abs(line.ring_ns(b) - hi) / 1e3)
            at = telemetry.trace.to_unix_ns(e["ts"]) - start
            far["program_start"].append(abs(at - a) / 1e3)
            far["program_end"].append(abs(at + hi - lo - b) / 1e3)
    size = os.path.getsize(path)
    shutil.rmtree(bench.TRACE_DIR, ignore_errors=True)
    out = {"workload": cell["name"], "platform": devices[0].platform,
           "device": devices[0].device_kind, "capture_bytes": size,
           "capture_s": capture.length, "steps_compared": len(far["start"]),
           **{f"{k}_us_max": max(v, default=None) for k, v in far.items()},
           "anchors": len(telemetry.trace.anchors()),
           "slack_ns_max": max(a["slack_ns"]
                               for a in telemetry.trace.anchors()),
           "idle_ms_a_step": host_timeline.idle_ms_a_step(planes, line)}
    print(json.dumps(out), flush=True)
    ok = far["start"] and max(sum(far.values(), [])) <= LIMIT_US
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
