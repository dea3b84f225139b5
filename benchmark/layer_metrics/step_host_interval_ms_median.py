"""The host's step-to-step interval: the median difference of consecutive
window steps' `fit/dispatch` ends, from the ring, over the window's steps
dispatched before the capture stopped (the later ones run in the profiler's
wake: `host_timeline.step_host_intervals_ms`). In a loop paced by the
dispatch call this is the device's step interval over three or more times
the steps that `step_interval_ms_p95` sees (None on a ring without
`clock/anchor`, or a capture without `profile_stop_time`)."""

from benchmark import host_timeline, span_reduce


def read(trace, counters, cell):
    return host_timeline.read_interval(counters, span_reduce.median)
