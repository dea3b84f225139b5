"""From the program's own spans to the numbers five per-layer metrics read.

The program's tracer (`mmlspark_tpu.telemetry.trace`) keeps its spans in a
ring in the run's own process, as Chrome-trace events: `name`, `ts` and `dur`
in whole microseconds on `time.perf_counter_ns`, `tid`, and the span's
attributes under `args`. `ring()` takes them as `run.py:program_counters`
takes the registry; `window()` keeps what belongs to the measured window.
The split lets the tests check the cut and the readers against a small
recorded ring with hand-checked values, on the CPU.

Both step loops of the trainer emit, per step, `fit/step` > `fit/feed_wait`,
`fit/dispatch` (loop thread, attribute `step`; `fit/dispatch` also
`in_flight`) and `fit/prefetch` (producer thread, attribute `item` = the step
that consumes the batch), and once per fit `fit/init`.

**The cut.** The run's fit is what follows the ring's last `fit/init`. Its
window's steps are the last `counters["window_steps"]` steps dispatched: the
driver counts the batches it hands over after the mark, and every one of them
is dispatched before `fitStream` returns. Set-up's hand-fed steps, whose
waits on the driver's host reads last seconds, come before them. The window
opens when its first batch is placed, at the end of that item's
`fit/prefetch` (with no producer thread, at prefetch depth 0: of the loop's
wait for it): the production of that batch, and the loop's wait for it, began
while the driver drained the device before the mark, so a span of a window
step is kept only if it began at or after that moment. A program without
these spans (the parent of the PR that added them) gives `None`, and every
reader then returns `None`.
"""

import statistics

ITEM_SPAN, INIT_SPAN = "fit/prefetch", "fit/init"
#: the attribute that numbers each per-step span
NUMBERED_BY = {"fit/step": "step", "fit/feed_wait": "step",
               "fit/dispatch": "step", ITEM_SPAN: "item"}
median, mean = statistics.median, statistics.fmean


def ring():
    """The program's spans so far, oldest first."""
    from mmlspark_tpu import telemetry
    return telemetry.trace.events()


def last_fit(events):
    """(the last `fit/init` event or None, the events recorded after it)."""
    for k in range(len(events) - 1, -1, -1):
        if events[k].get("name") == INIT_SPAN:
            return events[k], events[k + 1:]
    return None, list(events)


def window(events, window_steps):
    """{span name: [events of the window]} of the ring's last fit, or None
    where the fit recorded no numbered `fit/dispatch`."""
    _, fit = last_fit(events)
    by_name = {}
    for e in fit:
        key = NUMBERED_BY.get(e.get("name"))
        if key in e.get("args", {}):
            by_name.setdefault(e["name"], []).append((e["args"][key], e))
    dispatched = sorted(n for n, _ in by_name.get("fit/dispatch", []))
    if not dispatched or not window_steps:
        return None
    steps = set(dispatched[-int(window_steps):])
    first = min(steps)
    placed = [e["ts"] + e["dur"] for name in (ITEM_SPAN, "fit/feed_wait")
              for n, e in by_name.get(name, []) if n == first]
    t_open = min(placed, default=0)
    return {name: [e for n, e in pairs if n in steps and e["ts"] >= t_open]
            for name, pairs in by_name.items()}


def durations_ms(spans):
    return [e["dur"] / 1e3 for e in spans]


def read(counters, name, reduce, value=durations_ms):
    """`reduce` over `value` of the window's spans called `name`, from the
    program's ring; None where there are none."""
    cut = window(ring(), counters.get("window_steps"))
    values = value((cut or {}).get(name, []))
    return float(reduce(values)) if values else None


def init_seconds(events):
    """Duration of the last fit's `fit/init` span."""
    init, _ = last_fit(events)
    return None if init is None else init["dur"] / 1e6
