"""How far the loop runs ahead of the device: the median over the window's
steps of `fit/dispatch`'s `in_flight`, the number of steps dispatched before
this one whose loss was not ready when it was dispatched. Every step in
flight holds its outputs on the device, so this is what `peak_hbm_gib`
follows where the step's state is not donated."""

from benchmark import span_reduce


def in_flight(spans):
    return [e["args"]["in_flight"] for e in spans
            if "in_flight" in e["args"]]


def read(trace, counters, cell):
    return span_reduce.read(counters, "fit/dispatch", span_reduce.median,
                            value=in_flight)
