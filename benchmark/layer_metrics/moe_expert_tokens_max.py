"""The fullest held expert's load: the median over the window's steps of the
largest number of assignments (token, expert) any one held expert of any
expert layer got in the step. The program counts it on the device, returns
it from the step program beside the loss and, once a step has finished,
records it numbered by `step` on its ring as the event `fit/step_stats`
(attribute `moe_expert_tokens_max`; `moe_tokens_dropped` beside it has to
read 0). Uniform routing gives 16,384 x 8 / 256 = 512 a held expert, and the
fullest of 8 x 4 somewhat more. None where the program records no such
event."""

from benchmark import span_reduce

EVENT, VALUE, DROPPED = ("fit/step_stats", "moe_expert_tokens_max",
                         "moe_tokens_dropped")


def window_stats(counters):
    """The `fit/step_stats` events of the window's steps."""
    events = span_reduce.ring()
    cut = span_reduce.window(events, counters.get("window_steps"))
    if not cut:
        return []
    steps = {e["args"]["step"] for e in cut.get("fit/dispatch", [])}
    _, fit = span_reduce.last_fit(events)
    return [e["args"] for e in fit if e.get("name") == EVENT
            and e.get("args", {}).get("step") in steps]


def read(trace, counters, cell):
    stats = window_stats(counters)
    if not stats:
        return None
    if any(s[DROPPED] for s in stats):
        raise RuntimeError("the dropless expert layer dropped tokens: "
                           f"{[s[DROPPED] for s in stats if s[DROPPED]]}")
    return float(span_reduce.median([s[VALUE] for s in stats]))
