"""Idle device time a step that fell while the loop was inside
`fit/dispatch`: the call was in the runtime, held back (memory full, its own
limit of steps in flight), allocating or enqueueing. What donating the state
or bounding the run-ahead can recover (`host_timeline`; None where the
clocks cannot be tied)."""

from benchmark import host_timeline


def read(trace, counters, cell):
    return host_timeline.read_idle("runtime")
