"""Host time to enqueue one step: the median duration of the loop's
`fit/dispatch` span over the window's steps. It includes any time the
runtime holds the call back (memory full, its own limit of steps in flight),
which is what paces a loop that never waits for a step itself."""

from benchmark import span_reduce


def read(trace, counters, cell):
    return span_reduce.read(counters, "fit/dispatch", span_reduce.median)
