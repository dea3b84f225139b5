"""The ``mmlspark-tpu-perf`` command line (also
``python -m mmlspark_tpu.perf``).

Exit codes mirror graftlint: 0 — no regression, 1 — at least one metric
regressed past its noise band (the failure names each metric and its
delta), 2 — usage error. ``--format json`` emits the full
:class:`~.gate.GateReport` document for CI annotations.

    # gate a fresh bench capture against the committed trajectory
    python bench.py --all > run.json && mmlspark-tpu-perf --check run.json

    # re-validate a committed round against the rounds before it
    mmlspark-tpu-perf --check BENCH_rNN.json
"""

from __future__ import annotations

import argparse
import json
import sys

from .gate import DEFAULT_K_MAD, DEFAULT_MIN_REL, check_run
from .history import (find_history_dir, load_history, load_record,
                      metric_series)


def _fmt_value(v: float) -> str:
    return f"{v:.4g}" if abs(v) < 1000 else f"{v:,.1f}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mmlspark-tpu-perf",
        description="statistical bench-regression gate: a run's metrics "
                    "vs the BENCH_r*.json history (median-of-N with a "
                    "MAD noise band)")
    ap.add_argument("--check", metavar="FILE",
                    help="run to gate: bench.py [--all] JSON output or a "
                         "BENCH_rNN.json round record (a round checks "
                         "against the rounds before it)")
    ap.add_argument("--history", metavar="DIR", default=None,
                    help="directory holding BENCH_r*.json (default: "
                         "search cwd, its parents, then the checkout)")
    ap.add_argument("--min-rel", type=float, default=DEFAULT_MIN_REL,
                    help="noise-band floor as a fraction of the median "
                         f"(default {DEFAULT_MIN_REL})")
    ap.add_argument("--k-mad", type=float, default=DEFAULT_K_MAD,
                    help="noise-band width in robust sigmas "
                         f"(1.4826*MAD; default {DEFAULT_K_MAD})")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--list", action="store_true",
                    help="print the discovered history per metric and "
                         "exit")
    args = ap.parse_args(argv)

    history_dir = args.history or find_history_dir()
    history = load_history(history_dir) if history_dir else []

    if args.list:
        if not history:
            print("no BENCH_r*.json history found")
            return 0
        names = sorted({m for r in history for m in r["metrics"]})
        print(f"history: {history_dir} ({len(history)} round(s))")
        for name in names:
            vals = metric_series(history, name)
            print(f"  {name}: " + " -> ".join(_fmt_value(v)
                                              for v in vals))
        return 0

    if not args.check:
        ap.error("--check FILE is required (or --list)")
    try:
        run = load_record(args.check)
    except ValueError as e:
        print(f"mmlspark-tpu-perf: {e}", file=sys.stderr)
        return 2
    if not run["metrics"]:
        print(f"mmlspark-tpu-perf: {args.check}: no metrics found",
              file=sys.stderr)
        return 2
    # a round record inside the history gates against the rounds BEFORE
    # it — never against itself, and not against later rounds either
    if history and run["round"] is not None:
        history = [r for r in history
                   if r["source"] != run["source"]
                   and (r["round"] is None or r["round"] < run["round"])]

    report = check_run(run, history, min_rel=args.min_rel,
                       k_mad=args.k_mad, history_dir=history_dir)

    if args.format == "json":
        print(json.dumps(report.to_json(), indent=2))
    else:
        for e in report.entries:
            if e["status"] == "no-history":
                print(f"  new      {e['metric']}: "
                      f"{_fmt_value(e['value'])} (no history — recorded, "
                      f"not gated)")
                continue
            arrow = {"regression": "REGRESSION", "improvement": "faster ",
                     "ok": "ok      "}[e["status"]]
            print(f"  {arrow} {e['metric']}: {_fmt_value(e['value'])} vs "
                  f"median {_fmt_value(e['median'])} over "
                  f"{e['history_n']} round(s) "
                  f"({e['rel_delta']:+.1%}, band "
                  f"±{e['band'] / abs(e['median']):.1%}, "
                  f"{e['direction']})")
        n_reg = len(report.regressions)
        if n_reg:
            print(f"mmlspark-tpu-perf: {n_reg} regression(s) — FAIL")
        else:
            print("mmlspark-tpu-perf: no regressions")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
