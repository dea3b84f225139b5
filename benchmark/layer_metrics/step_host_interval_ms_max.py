"""The longest host step-to-step interval of the window up to the capture's
stop: what a single stall of a slow run shows
(`step_host_interval_ms_median` says of what)."""

from benchmark import host_timeline


def read(trace, counters, cell):
    return host_timeline.read_interval(counters, max)
