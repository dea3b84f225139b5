"""Discovery and parsing of the benchmark history (``BENCH_r*.json``).

Every bench round the driver records lands as a ``BENCH_rNN.json`` at
the repo root. Two shapes exist in the wild and both are first-class:

* the **harness round record** — ``{"n": 5, "cmd": ..., "tail": ...,
  "parsed": {"metric": ..., "value": ..., "unit": ...}}`` where
  ``parsed`` is the last JSON line of the bench run (historically one
  metric; with ``bench.py --all`` it is the schema document below);
* the **bench schema document** (``mmlspark-bench/v1``) — what
  ``bench.py --all`` prints: ``{"schema": "mmlspark-bench/v1",
  "backend": ..., "metrics": [{"metric", "value", "unit", ...}, ...]}``.

A bare one-metric line (``{"metric": ..., "value": ...}``) also parses,
so ``--check`` accepts a raw ``python bench.py`` capture.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Optional

SCHEMA = "mmlspark-bench/v1"

#: the round-record filename pattern at the repo root
BENCH_GLOB = "BENCH_r*.json"
_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def find_history_dir(start: Optional[str] = None) -> Optional[str]:
    """The directory holding the ``BENCH_r*.json`` trajectory.

    Searches ``start`` (default: cwd) and each parent up to the
    filesystem root, then the checkout this package lives in. Returns
    None when no round file exists anywhere — the caller treats that as
    "no history", never an error (a fresh clone has no trajectory yet).

    This is the fix for the long-standing ``vs_baseline: null``: the
    bench harness runs from its own cwd, where a look-next-to-the-script
    search finds nothing.
    """
    seen = set()
    d = os.path.abspath(start or os.getcwd())
    while d not in seen:
        seen.add(d)
        if glob.glob(os.path.join(d, BENCH_GLOB)):
            return d
        d = os.path.dirname(d)
    # the checkout the installed package lives in (repo root is two
    # levels above this file: mmlspark_tpu/perf/history.py)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if pkg_root not in seen and glob.glob(os.path.join(pkg_root,
                                                       BENCH_GLOB)):
        return pkg_root
    return None


def _metric_entries(doc: dict):
    """Yield ``{"metric", "value", "unit", ...}`` dicts from any
    recognized document shape (round record, schema doc, bare line)."""
    if not isinstance(doc, dict):
        return
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        yield from _metric_entries(parsed)
        return
    if isinstance(doc.get("metrics"), list):    # mmlspark-bench/v1
        for m in doc["metrics"]:
            if isinstance(m, dict):
                yield m
        return
    if "metric" in doc:
        yield doc


def load_record(path: str) -> dict:
    """One history/run file -> ``{"source", "round", "metrics"}`` where
    ``metrics`` maps metric name to ``{"value": float, "unit": str}``.
    Entries without a numeric value (skipped scenarios, nulls) are
    dropped. Raises ``ValueError`` on unreadable/unparseable files —
    a gate must not silently pass on garbage input."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        raise ValueError(f"cannot read {path}: {e}") from e
    try:
        doc = json.loads(text)
    except ValueError:
        # tolerate a multi-line capture: the last parseable JSON line
        doc = None
        for line in reversed(text.strip().splitlines()):
            try:
                doc = json.loads(line)
                break
            except ValueError:
                continue
        if doc is None:
            raise ValueError(f"{path}: no parseable JSON document")
    metrics: dict[str, dict] = {}
    for m in _metric_entries(doc):
        name, value = m.get("metric"), m.get("value")
        if not name or not isinstance(value, (int, float)):
            continue
        metrics[str(name)] = {"value": float(value),
                              "unit": str(m.get("unit", ""))}
    rnd = None
    if isinstance(doc, dict) and isinstance(doc.get("n"), int):
        rnd = doc["n"]
    else:
        m = _ROUND_RE.search(os.path.basename(path))
        if m:
            rnd = int(m.group(1))
    return {"source": os.path.abspath(path), "round": rnd,
            "metrics": metrics}


def load_history(directory: str,
                 exclude: Optional[str] = None) -> list:
    """Every parseable round record in ``directory``, oldest first
    (by round number, then filename). ``exclude`` drops one file by
    path — checking ``BENCH_r05.json`` (say) must not compare it against
    itself."""
    out = []
    skip = os.path.abspath(exclude) if exclude else None
    for path in sorted(glob.glob(os.path.join(directory, BENCH_GLOB))):
        if skip and os.path.abspath(path) == skip:
            continue
        try:
            out.append(load_record(path))
        except ValueError:
            continue    # one corrupt round must not hide the others
    out.sort(key=lambda r: (r["round"] is None, r["round"] or 0,
                            r["source"]))
    return out


def metric_series(history: list, metric: str) -> list:
    """The metric's values across the history, oldest first."""
    return [r["metrics"][metric]["value"] for r in history
            if metric in r["metrics"]]


def latest_value(history: list, metric: str) -> Optional[float]:
    """Most recent recorded value of ``metric`` (None when never
    recorded) — what ``bench.py`` prints its ``vs_baseline`` ratio
    against."""
    series = metric_series(history, metric)
    return series[-1] if series else None
