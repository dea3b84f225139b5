#!/usr/bin/env python3
"""Check BENCHMARK.json against the rules that can be checked without JAX.

    python3 benchmark/check_manifest.py [path/to/BENCHMARK.json]

Exit 0 and print "manifest ok" where every rule holds, else print each fault
and exit 1. `run.py` calls `check()` before anything else. Stdlib only.
"""

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _line(text, what, faults):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        faults.append(f"{what}: must be 1 to 200 characters on one line")


def _keys(entry, required, optional, what, faults):
    extra = set(entry) - set(required) - set(optional)
    missing = set(required) - set(entry)
    if extra or missing:
        faults.append(f"{what}: keys missing {sorted(missing)}, "
                      f"not allowed {sorted(extra)}")


def _under(path, roots):
    return any(path == r or path.startswith(r.rstrip("/") + "/")
               for r in roots)


def find_traffic(root, paths, traffic):
    """The data file of a traffic mix, by its name, under any of `paths`."""
    for p in paths:
        for suffix in TRAFFIC_SUFFIXES:
            f = os.path.join(root, p, "traffic", traffic + suffix)
            if os.path.isfile(f):
                return f
    return None


def cells_of(manifest, metric):
    """The cells a metric is reported in: its `workloads`, else every cell."""
    return list(metric.get("workloads")
                or [w["name"] for w in manifest["workloads"]])


def check(manifest_path):
    """Return the list of faults (empty: the manifest is sound)."""
    faults = []
    root = os.path.dirname(os.path.abspath(manifest_path))
    if os.path.getsize(manifest_path) > 64 * 1024:
        faults.append("manifest is over 64 KiB")
    with open(manifest_path) as f:
        m = json.load(f)
    if set(m) != TOP_KEYS:
        return faults + [f"top-level keys must be exactly {sorted(TOP_KEYS)},"
                         f" got {sorted(m)}"]

    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"paths entry {p!r}: relative, letters digits _ . - /")
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        faults.append("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command word {word!r} leaves the repo")
        if "/" in word and not _under(word, paths):
            faults.append(f"command word {word!r} is not under paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")

    configs = {}
    files = set()
    if not 1 <= len(m["configs"]) <= 24:
        faults.append("configs: 1 to 24")
    for c in m["configs"]:
        what = f"config {c.get('name')!r}"
        _keys(c, ("name", "source", "file", "reduced", "why"), (), what,
              faults)
        if not NAME.match(str(c.get("name", ""))):
            faults.append(f"{what}: name is not a token")
        if c.get("name") in configs:
            faults.append(f"{what}: name used twice")
        configs[c.get("name")] = c
        _line(c.get("source"), f"{what} source", faults)
        _line(c.get("why"), f"{what} why", faults)
        f = c.get("file", "")
        if not PATH.match(f) or not _under(f, paths):
            faults.append(f"{what}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            faults.append(f"{what}: file {f!r} does not exist")
        if f in files:
            faults.append(f"{what}: file {f!r} is another configuration's")
        files.add(f)
        red = c.get("reduced", [])
        if not (isinstance(red, list) and len(red) <= 16):
            faults.append(f"{what}: reduced is a list of at most 16 keys")
        for k in red if isinstance(red, list) else ():
            if not NAME.match(str(k)):
                faults.append(f"{what}: reduced key {k!r} is not a token")

    cells = {}
    pairs = set()
    if not 1 <= len(m["workloads"]) <= 24:
        faults.append("workloads: 1 to 24")
    for w in m["workloads"]:
        what = f"workload {w.get('name')!r}"
        _keys(w, ("name", "config", "traffic", "chips", "why"), (), what,
              faults)
        for k in ("name", "config", "traffic"):
            if not NAME.match(str(w.get(k, ""))):
                faults.append(f"{what}: {k} {w.get(k)!r} is not a token")
        if w.get("name") in cells:
            faults.append(f"{what}: name used twice")
        cells[w.get("name")] = w
        if w.get("config") not in configs:
            faults.append(f"{what}: unknown config {w.get('config')!r}")
        if (w.get("config"), w.get("traffic")) in pairs:
            faults.append(f"{what}: config and traffic pair appears twice")
        pairs.add((w.get("config"), w.get("traffic")))
        if w.get("chips") not in (1, 4):
            faults.append(f"{what}: chips is 1 or 4")
        _line(w.get("why"), f"{what} why", faults)
        if find_traffic(root, paths, str(w.get("traffic"))) is None:
            faults.append(f"{what}: no traffic file for {w.get('traffic')!r}")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        faults.append(f"{four} cells ask for 4 chips: at most a quarter, "
                      "and one always may")
    used = {w.get("config") for w in m["workloads"]}
    for name in configs:
        if name not in used:
            faults.append(f"config {name!r} is used by no cell")

    metric_names = set()
    e2e = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        faults.append("end_to_end: 1 to 16 metrics")
    for e in m["end_to_end"]:
        what = f"end_to_end metric {e.get('name')!r}"
        _keys(e, ("name", "unit", "better", "bound", "source"),
              ("workloads",), what, faults)
        _metric_common(e, what, metric_names, cells, faults)
        if e.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"{what}: source is host_clock or device_trace")
        b = e.get("bound")
        if not (isinstance(b, (int, float)) and 0 < b <= 0.1):
            faults.append(f"{what}: bound must be over 0 and at most 0.1")
        e2e[e.get("name")] = e
    if "setup_s" not in e2e:
        faults.append("end_to_end lacks setup_s")
    elif "workloads" in e2e["setup_s"]:
        faults.append("setup_s is reported by every cell")

    layer_cells = set()
    if not 1 <= len(m["per_layer"]) <= 128:
        faults.append("per_layer: 1 to 128 metrics")
    for p in m["per_layer"]:
        what = f"per_layer metric {p.get('name')!r}"
        _keys(p, ("name", "unit", "better", "source", "layer", "moves"),
              ("workloads",), what, faults)
        _metric_common(p, what, metric_names, cells, faults)
        if p.get("source") not in SOURCES:
            faults.append(f"{what}: source is one of {SOURCES}")
        if not NAME.match(str(p.get("layer", ""))):
            faults.append(
                f"{what}: layer must be 1 to 64 characters from letters, "
                "digits, '_', '.' and '-', starting with a letter, digit "
                "or '_'")
        moved = e2e.get(p.get("moves"))
        if moved is None:
            faults.append(f"{what}: moves {p.get('moves')!r} is no "
                          "end_to_end metric")
            continue
        moved_cells = set(cells_of(m, moved))
        for cell in cells_of(m, p):
            layer_cells.add(cell)
            if cell not in moved_cells:
                faults.append(f"{what}: cell {cell!r} does not report "
                              f"{p.get('moves')!r}")
        reader = [os.path.join(root, d, "layer_metrics", p["name"] + ".py")
                  for d in paths]
        if not any(os.path.isfile(r) for r in reader):
            faults.append(f"{what}: no reader layer_metrics/{p['name']}.py")

    for cell in cells:
        others = [e for e in m["end_to_end"] if e.get("name") != "setup_s"
                  and cell in cells_of(m, e)]
        if not others:
            faults.append(f"workload {cell!r} reports no end_to_end metric "
                          "besides setup_s")
        if cell not in layer_cells:
            faults.append(f"workload {cell!r} reports no per_layer metric")
    return faults


def _metric_common(e, what, metric_names, cells, faults):
    if not NAME.match(str(e.get("name", ""))):
        faults.append(f"{what}: name is not a token")
    if e.get("name") in metric_names:
        faults.append(f"{what}: name used twice")
    metric_names.add(e.get("name"))
    if not UNIT.match(str(e.get("unit", ""))):
        faults.append(f"{what}: unit {e.get('unit')!r} must be 1 to 16 of "
                      "letters, digits, _ / % . -")
    if e.get("better") not in ("lower", "higher"):
        faults.append(f"{what}: better is lower or higher")
    for cell in e.get("workloads", ()):
        if cell not in cells:
            faults.append(f"{what}: unknown workload {cell!r}")
    if "workloads" in e and not e["workloads"]:
        faults.append(f"{what}: workloads is empty")


def main(argv):
    here = os.path.dirname(os.path.abspath(__file__))
    path = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(here), "BENCHMARK.json")
    faults = check(path)
    for f in faults:
        print("manifest fault:", f, file=sys.stderr)
    if faults:
        return 1
    print("manifest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
