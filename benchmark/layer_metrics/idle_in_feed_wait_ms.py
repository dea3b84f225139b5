"""Idle device time a step that fell while the loop stood in `fit/feed_wait`:
the chip waited for a batch. Mean over the chips and the whole steps of the
trace, in ms; with `idle_in_dispatch_ms` and `idle_in_loop_ms` it adds up to
(`window_s` - `busy_s`) / steps (`host_timeline`, which ties the two clocks
through the ring's `clock/anchor`; None where it cannot)."""

from benchmark import host_timeline


def read(trace, counters, cell):
    return host_timeline.read_idle("feed")
