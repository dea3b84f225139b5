"""The tail of the host's step-to-step interval: the 95th percentile of the
differences of consecutive window steps' `fit/dispatch` ends up to the
capture's stop (`step_host_interval_ms_median` says of what)."""

from benchmark import host_timeline, trace_reduce


def read(trace, counters, cell):
    return host_timeline.read_interval(
        counters, lambda v: trace_reduce.quantile(v, 0.95))
