"""From a profiler trace to the numbers the per-layer metrics read.

`load_events` reads an `.xplane.pb` with `jax.profiler.ProfileData` into plain
lists; `summarise` reduces those lists. The split lets the self-test check the
reduction against a small recorded fixture with hand-checked values, on the
CPU, with no profiler.

    python3 -m benchmark.trace_reduce <file.xplane.pb> [--describe] [--cut out.json N]

What a TPU trace looks like (TPU v5 lite, jax 0.9.0): one plane per chip named
`/device:TPU:<n>`; its line `XLA Modules` has one event per run of a compiled
program, named `jit_<function>(<fingerprint>)`; its line `XLA Ops` has one
event per operation of the program, in order and not overlapping, named as in
the HLO. An operation's event is named by the whole text of its HLO
instruction (`%fusion.12 = bf16[128,56,56,64]{...} fusion(...), kind=...`)
and carries no other statistic that names it: `load_events` keeps the
instruction's name (`fusion.12`) as the event's name and, as its label, the
start of the text (the result's type and the opcode) with the custom call's
target. A Pallas kernel has no name of its own there (the program gives
`pallas_call` none): it is a `tpu_custom_call` under the name of the scope
that called it, told from its siblings by the types it returns.
"""

import gzip
import json
import re
import statistics
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
LABEL_CHARS = 240
TARGET = re.compile(r'custom_call_target="[^"]*"')


def load_events(path):
    """{plane: {line: [[name, start_ns, dur_ns, label], ...]}} of the device
    planes. `label` is the start of the operation's HLO text."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = []
            for ev in line.events:
                name, label = split_name(ev.name)
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), label])
            events.sort(key=lambda e: e[1])
            lines[line.name] = events
        out[plane.name] = lines
    return out


def split_name(text):
    """An operation's event name is its HLO instruction: (name, text)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    target = TARGET.search(rest)
    return head.lstrip("%"), rest[:LABEL_CHARS] + (
        " " + target.group(0) if target else "")


def read_fixture(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def union_ns(intervals):
    """Total length of the union of [start, end) intervals, and the gaps
    between its pieces as (start, length)."""
    total, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def quantile(values, q):
    """The q-quantile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def program_name(event_name):
    return re.sub(r"\(\d+\)$", "", event_name)


def step_program(modules):
    """The program that took most device time: the step."""
    total = {}
    for name, _, dur, _ in modules:
        total[program_name(name)] = total.get(program_name(name), 0.0) + dur
    return max(total, key=total.get) if total else None


def summarise_plane(lines):
    modules, ops = lines.get(MODULES_LINE, []), lines.get(OPS_LINE, [])
    step = step_program(modules)
    steps = [(s, d) for n, s, d, _ in modules if program_name(n) == step]
    if len(steps) >= 4:
        # whole steps only: the first step's event is cut by the start of
        # the trace (it opens where the trace does), the last by its end
        t0, t1 = steps[1][0], steps[-1][0]
        steps = steps[1:-1]
    elif ops:
        t0 = min(s for _, s, _, _ in ops)
        t1 = max(s + d for _, s, d, _ in ops)
    else:
        return None
    inside = [(n, s, d, lab) for n, s, d, lab in ops if t0 <= s < t1]
    busy, gaps = union_ns([(s, min(s + d, t1)) for _, s, d, _ in inside])
    if inside:
        first = min(s for _, s, _, _ in inside)
        if first > t0:
            gaps.append((t0, first - t0))
        last = max(min(s + d, t1) for _, s, d, _ in inside)
        if last < t1:
            gaps.append((last, t1 - last))
    op_ns, op_n, op_label = {}, {}, {}
    for n, s, d, lab in inside:
        op_ns[n] = op_ns.get(n, 0.0) + d
        op_n[n] = op_n.get(n, 0) + 1
        op_label[n] = lab
    starts = [s for s, _ in steps] + [t1] if len(steps) >= 2 else []
    intervals = [b - a for a, b in zip(starts, starts[1:])]
    step_gaps = [max(0.0, b - (a + d)) for (a, d), b in
                 zip(steps, starts[1:])] if starts else []
    return {"step_program": step, "steps": len(steps) if starts else 0,
            "window_ns": t1 - t0, "busy_ns": busy, "gaps": gaps,
            "op_ns": op_ns, "op_n": op_n, "op_label": op_label,
            "step_busy_ns": [d for _, d in steps] if starts else [],
            "intervals_ns": intervals, "step_gaps_ns": step_gaps}


def summarise(events):
    """Numbers over the device planes: seconds averaged over the chips, step
    lists joined. None of it is rounded."""
    planes = [p for p in (summarise_plane(events[k]) for k in sorted(events))
              if p is not None]
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    n = len(planes)
    op_s, op_n, op_label = {}, {}, {}
    for p in planes:
        for name, ns in p["op_ns"].items():
            op_s[name] = op_s.get(name, 0.0) + ns / 1e9 / n
            op_n[name] = op_n.get(name, 0) + p["op_n"][name] / n
        op_label.update(p["op_label"])
    gaps = sorted((g for p in planes for g in p["gaps"]),
                  key=lambda g: -g[1])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips": n,
        "step_program": planes[0]["step_program"],
        "steps": sum(p["steps"] for p in planes) / n,
        "window_s": sum(p["window_ns"] for p in planes) / 1e9 / n,
        "busy_s": sum(p["busy_ns"] for p in planes) / 1e9 / n,
        "op_s": op_s, "op_n": op_n, "op_label": op_label,
        "intervals_ms": [x / 1e6 for p in planes for x in p["intervals_ns"]],
        "step_gaps_ms": [x / 1e6 for p in planes for x in p["step_gaps_ns"]],
        "step_busy_ms": [x / 1e6 for p in planes for x in p["step_busy_ns"]],
        "breakdown": {
            "device_ops": [[name, s] for name, s in top_ops],
            "idle_gaps": [["unattributed", g[1] / 1e9] for g in gaps[:5]],
        },
    }


def kernel_time(trace, pattern):
    """(seconds, calls) per chip of the operations whose name or label
    matches the pattern."""
    rx = re.compile(pattern)
    hit = [name for name in trace["op_s"]
           if rx.search(name) or rx.search(trace["op_label"].get(name, ""))]
    return (sum(trace["op_s"][h] for h in hit),
            sum(trace["op_n"][h] for h in hit))


def median(values):
    return statistics.median(values) if values else None


def describe(path):
    """Print what a trace holds: planes, lines, the biggest events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if not DEVICE_PLANE.match(plane.name):
                continue
            total = {}
            for ev in evs:
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {ns / 1e6:12.3f} ms  {name}")
            seen = set()
            for ev in evs:
                short = split_name(ev.name)[0]
                if "tpu_custom_call" in ev.name and short not in seen \
                        and len(seen) < 8:
                    seen.add(short)
                    print("    kernel", ev.name[:1500])


def main(argv):
    if "--describe" in argv:
        describe(argv[1])
        return 0
    events = load_events(argv[1])
    if "--cut" in argv:
        out, n = argv[argv.index("--cut") + 1], int(argv[argv.index("--cut") + 2])
        cut = {}
        for plane, lines in events.items():
            mods = [e for e in lines.get(MODULES_LINE, [])]
            step = step_program(mods)
            starts = [e[1] for e in mods if program_name(e[0]) == step]
            t0, t1 = starts[0], starts[min(n + 1, len(starts) - 1)]
            cut[plane] = {ln: [e for e in evs if t0 <= e[1] <= t1]
                          for ln, evs in lines.items()}
        opener = gzip.open if out.endswith(".gz") else open
        with opener(out, "wt") as f:
            json.dump(cut, f)
        return 0
    s = summarise(events)
    s.pop("op_label")
    s["op_s"] = dict(sorted(s["op_s"].items(), key=lambda kv: -kv[1])[:30])
    print(json.dumps(s, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
