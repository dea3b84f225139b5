"""Idle device time a step that fell while the loop thread was in neither
`fit/feed_wait` nor `fit/dispatch`: its own work between two calls (the read
of a finished step's counts, `fit/step_stats`; a checkpoint, `ckpt/write`;
the lines between), or launch latency after the call returned
(`host_timeline`; None where the clocks cannot be tied)."""

from benchmark import host_timeline


def read(trace, counters, cell):
    return host_timeline.read_idle("loop")
