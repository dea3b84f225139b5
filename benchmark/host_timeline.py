"""One timeline for a traced run: the step loop's spans under the device's
idle gaps.

    python3 -m benchmark.host_timeline <file.xplane.pb> <ring.jsonl>

`trace_reduce.summarise_plane` gives each chip's idle gaps as (start, length)
in nanoseconds of the capture's clock, inside the whole steps of the trace.
The program's ring (`span_reduce.ring()`, or the file `MMLSPARK_TPU_TRACE`
exports) gives the loop thread's spans in microseconds of
`time.perf_counter_ns`. Two records tie the clocks, so that a capture at
`host_tracer_level` 0 (which keeps no annotation of a span) is enough:

- the capture's plane `Task Environment` carries `profile_start_time` and
  `profile_stop_time` in Unix nanoseconds, and the device planes' events
  count from that start;
- the ring's `clock/anchor` events carry `perf_ns`, `unix_ns` and `slack_ns`,
  one reading of each clock taken together, about one a second.

A moment `t` of the capture is `profile_start_time + t` on the Unix clock and,
through the anchor nearest to it, `perf_ns + (profile_start_time + t -
unix_ns)` on the ring's. Each gap is then cut by what the loop thread (the
thread of `fit/dispatch`) was inside:

- `fit/feed_wait`: the chip waited for a batch                    -> feed
- `fit/dispatch`: the call was inside the runtime, held, allocating
  or enqueueing                                                   -> runtime
- anything else (`fit/step_stats`, `ckpt/write`, the loop's own
  lines) or no span at all                                        -> loop

`timeline()` returns None, never 0 and never an exception, where the clocks
cannot be tied: no `profile_start_time`, no anchor with a `slack_ns` of at
most 100 us within two seconds of the capture, or no `fit/dispatch` (the
ring of a program older than its anchors: every reader here then gives None).
`load()` reads the files; the rest works on plain lists, so that the tests
check it against `fixtures/host_timeline.json`, by hand, and against
`fixtures/host_timeline_lfm2moe.json.gz`, recorded on the chip, on the CPU.

The same tie places the capture's end on the ring. The steps dispatched
after `jax.profiler.stop_trace` run in its wake (PERF.md section 6, PR 36:
ResNet's at less than half their pace), so `step_host_intervals_ms` keeps
the window's steps up to `profile_stop_time` and no later one.
"""

import bisect
import functools
import json
import sys

from benchmark import scope_ops, span_reduce, trace_reduce

ANCHOR_EVENT, TASK_PLANE = "clock/anchor", "Task Environment"
START_STAT, STOP_STAT = "profile_start_time", "profile_stop_time"
#: the loop thread's spans that name a cut; the rest of its time is the loop's
CUT_OF = {"fit/feed_wait": "feed", "fit/dispatch": "runtime"}
CUTS = ("feed", "runtime", "loop")
MAX_SLACK_NS, MAX_ANCHOR_AWAY_NS = 100_000, 2_000_000_000
PRINT_OVER_NS = 100_000


def profile_times_ns(path):
    """(`profile_start_time`, `profile_stop_time`) of the capture in Unix
    ns, None for one the file does not state."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if plane.name == TASK_PLANE:
            stats = dict(plane.stats)
            return stats.get(START_STAT), stats.get(STOP_STAT)
    return None, None


def anchors(events):
    """(unix_ns, perf_ns) of the ring's sound anchors, sorted."""
    return sorted((a["unix_ns"], a["perf_ns"]) for a in
                  (e.get("args", {}) for e in events
                   if e.get("name") == ANCHOR_EVENT)
                  if a.get("slack_ns", MAX_SLACK_NS + 1) <= MAX_SLACK_NS)


def loop_spans(events):
    """The complete events of the loop thread, the thread of the ring's last
    `fit/dispatch`, as (start_ns, end_ns, event) sorted by start."""
    tid = next((e["tid"] for e in reversed(events)
                if e.get("name") == "fit/dispatch"), None)
    return sorted(((e["ts"] * 1000, (e["ts"] + e["dur"]) * 1000, e)
                   for e in events if e.get("ph") == "X"
                   and e.get("tid") == tid), key=lambda s: s[:2])


class Timeline:
    """The loop thread's spans on the capture's clock."""

    def __init__(self, start_unix_ns, anchored, spans, stop_unix_ns=None):
        # each anchor as (its moment in the capture, ring clock less
        # capture clock): whole numbers, since a Unix time in ns is past
        # what a float holds to the microsecond
        self.anchored = [(unix - start_unix_ns, perf - (unix - start_unix_ns))
                         for unix, perf in anchored]
        self.spans = spans
        # one thread's spans of one name follow each other: starts and ends
        # are both sorted, and a gap's share is found by bisection (a traced
        # window of resnet holds over a hundred thousand gaps)
        self.named = {cut: ([s for s, _, ev in spans if ev["name"] == name],
                            [e for _, e, ev in spans if ev["name"] == name])
                      for name, cut in CUT_OF.items()}
        #: where the capture stopped, on the ring's clock; None if not known
        self.stopped_ring_ns = None if stop_unix_ns is None else self.ring_ns(
            int(stop_unix_ns) - start_unix_ns)

    def ring_ns(self, trace_ns):
        """A moment of the capture on the ring's clock, through the anchor
        nearest to it; None where that one is over two seconds away."""
        at = bisect.bisect(self.anchored, (trace_ns,))
        near = min(self.anchored[max(0, at - 1):at + 1],
                   key=lambda a: abs(a[0] - trace_ns))
        if abs(near[0] - trace_ns) > MAX_ANCHOR_AWAY_NS:
            return None
        return trace_ns + near[1]

    def cut(self, gap):
        """{cut: ns} of one gap (start, length); the three add up to its
        length. None where no anchor is near."""
        lo = self.ring_ns(gap[0])
        if lo is None:
            return None
        hi = lo + gap[1]
        out = {}
        for cut, (starts, ends) in self.named.items():
            first = bisect.bisect_right(ends, lo)
            last = bisect.bisect_left(starts, hi)
            out[cut] = float(sum(min(hi, ends[k]) - max(lo, starts[k])
                                 for k in range(first, last)))
        out["loop"] = gap[1] - out["feed"] - out["runtime"]
        return out

    def covering(self, gap):
        """The innermost span that holds at least half of the gap (where
        none does, the one that holds most); None where none meets it."""
        lo = self.ring_ns(gap[0])
        if lo is None:
            return None
        hi = lo + gap[1]
        met = [(min(min(hi, e) - max(lo, s), gap[1] / 2), s - e, ev)
               for s, e, ev in self.spans if s < hi and e > lo]
        return max(met, key=lambda m: m[:2], default=(0, 0, None))[2]


def timeline(start_unix_ns, events, stop_unix_ns=None):
    anchored, spans = anchors(events), loop_spans(events)
    if start_unix_ns is None or not anchored or not spans:
        return None
    return Timeline(int(start_unix_ns), anchored, spans, stop_unix_ns)


def name_gap(gap, line=None):
    """The cut that holds most of one idle gap (start, length) of the capture,
    or "unattributed": what `trace_reduce.summarise` can write in
    `breakdown.idle_gaps`. `line` defaults to the traced run in progress."""
    line = traced_run()[1] if line is None else line
    cut = line.cut(gap) if line is not None else None
    return max(CUTS, key=cut.get) if cut else "unattributed"


def idle_ms_a_step(planes, line):
    """{cut: idle device ms a step}, mean over the chips and the whole steps
    of the trace; None where the clocks cannot be tied or no step is whole.
    `planes` are `trace_reduce.summarise_plane`'s."""
    steps = sum(p["steps"] for p in planes)
    if line is None or not steps:
        return None
    total = dict.fromkeys(CUTS, 0.0)
    for p in planes:
        for gap in p["gaps"]:
            cut = line.cut(gap)
            if cut is None:
                return None
            for name in CUTS:
                total[name] += cut[name]
    return {name: total[name] / 1e6 / steps for name in CUTS}


def device_planes(events):
    return [p for p in (trace_reduce.summarise_plane(events[k])
                        for k in sorted(events)) if p is not None]


def load(path, events):
    """(the planes of the capture at `path`, its Timeline or None)."""
    start, stop = profile_times_ns(path)
    return (device_planes(trace_reduce.load_events(path)),
            timeline(start, events, stop))


@functools.lru_cache(maxsize=1)
def _load_with_ring(path):
    return load(path, span_reduce.ring())


def traced_run():
    """(planes, Timeline or None) of the traced run in progress: the file
    `run.py` had the profiler write, and the ring of the run's process."""
    path = scope_ops.traced_run_file()
    return _load_with_ring(path) if path else ([], None)


def read_idle(cut):
    """What the reader of an `idle_in_*_ms` returns."""
    idle = idle_ms_a_step(*traced_run())
    return None if idle is None else float(idle[cut])


def step_host_intervals_ms(counters):
    """The differences of consecutive window steps' `fit/dispatch` ends, from
    the ring, over `span_reduce.window`'s cut up to the moment the capture
    stopped: in a loop paced by the dispatch call, the device's step interval
    for every step of the window that the profiler's wake did not slow (in a
    loop that runs ahead, the host's pace while its queue fills, then the
    device's). None where that moment cannot be put on the ring's clock: no
    `profile_stop_time`, or clocks that cannot be tied (a ring without
    anchors: a program older than this reading)."""
    line = traced_run()[1]
    cut = span_reduce.window(span_reduce.ring(), counters.get("window_steps"))
    if not cut or line is None or line.stopped_ring_ns is None:
        return None
    ends = sorted(e["ts"] + e["dur"] for e in cut.get("fit/dispatch", []))
    ends = [t for t in ends if t * 1000 <= line.stopped_ring_ns]
    return [(b - a) / 1e3 for a, b in zip(ends, ends[1:])] or None


def read_interval(counters, reduce):
    values = step_host_intervals_ms(counters)
    return None if values is None else float(reduce(values))


def main(argv):
    from mmlspark_tpu.telemetry import merge_traces
    planes, line = load(argv[1], merge_traces([argv[2]]))
    if line is None:
        print(f"the clocks cannot be tied: the capture has no {START_STAT}, "
              f"or the ring no sound {ANCHOR_EVENT} or no fit/dispatch")
        return 1
    for chip, plane in enumerate(planes):
        for gap in sorted(plane["gaps"]):
            cut = line.cut(gap) if gap[1] >= PRINT_OVER_NS else None
            if cut is None:
                continue
            ev = line.covering(gap) or {"name": "-"}
            args = ev.get("args", {})
            print(f"chip {chip} idle from {gap[0] / 1e6:.3f} ms for "
                  f"{gap[1] / 1e6:.3f} ms: "
                  + " ".join(f"{name} {cut[name] / 1e6:.3f}" for name in CUTS)
                  + f"; in {ev['name']} step {args.get('step', '-')} "
                  f"in_flight {args.get('in_flight', '-')}")
    print(json.dumps({"idle_ms_a_step": idle_ms_a_step(planes, line),
                      "steps": sum(p["steps"] for p in planes)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
