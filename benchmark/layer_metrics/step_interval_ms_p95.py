"""95th percentile of the start-to-start intervals of consecutive step
programs on the device over the traced window."""

from benchmark import trace_reduce


def read(trace, counters, cell):
    return trace_reduce.quantile(trace["intervals_ms"], 0.95)
