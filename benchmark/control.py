#!/usr/bin/env python3
"""The readings the limits are set from, taken on the chip at the cell's own
size (or, with --rehearsal 1, at its rehearsal size anywhere).

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 \\
        [--program-seeds 11 12 ...] [--stand-ins control witness] \
        [--rehearsal 1]

For each of --seeds: the control (the plain reference in the nearest precision
below the one the configuration states, put in the program's place) and the
planted faults (half of every batch left out; the state returned unchanged),
each judged against the float32 reference through the driver's own `compare`
and `judge`, at the traffic file's limits: every one of them has to come out
`correct: false` (`witness`, the reference in the stated precision, `true`).
For each of --program-seeds: the program itself, driven as a run drives it
with a window of one second, all in this one process. One JSON line each. Not
part of a benchmark run; exits 1 if a reading is not as expected.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


class NoClock:
    def close_setup(self):
        pass


class NoTracer:
    def tick(self, elapsed, counted):
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--stand-ins", nargs="*", default=None,
                    help="default: control half_batch state_unchanged; "
                         "`witness` is the reference in the stated precision")
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from benchmark import run as brun
    from benchmark.drivers import train_stream as ts
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, config, traffic = brun.load_cell(manifest, args.workload)
    if args.rehearsal:
        config = brun.merge(config, config.get("rehearsal", {}))
        traffic = brun.merge(traffic, traffic.get("rehearsal", {}))
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    as_expected = True

    def emit(line):
        line.update(workload=args.workload,
                    device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)

    for seed in args.program_seeds:
        t0 = time.perf_counter()
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "seed": seed, "seconds": 1.0, "clock": NoClock(),
               "tracer": NoTracer(), "rehearsal": bool(args.rehearsal)}
        try:
            result = ts.run(ctx)
            t1 = time.perf_counter()
            ok, compared, notes = result.pop("check")()
        except Exception as e:      # one seed lost, the others still read
            as_expected = False
            emit({"seed": seed, "program": {"error": repr(e)[:2000]}})
            gc.collect()
            continue
        as_expected &= ok
        emit({"seed": seed, "program": {"correct": ok, "compared": compared},
              "notes": notes, "run_s": t1 - t0,
              "check_s": time.perf_counter() - t1})
        del result, ctx
        gc.collect()
    for seed in args.seeds:
        t0 = time.perf_counter()
        judged = ts.judge_stand_ins(config, traffic, seed,
                                    args.stand_ins or ts.MUST_FAIL)
        as_expected &= all(j["correct"] == (name == "witness")
                           for name, j in judged.items())
        emit({"seed": seed, "seconds": time.perf_counter() - t0, **judged})
    print(f"control: every reading as expected: {as_expected}",
          file=sys.stderr)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
