"""What the feed's shortfall cost the loop: the mean duration a step of the
loop's `fit/feed_wait` span (its `next()` on the prefetcher) over the
window's steps. A mean, so that times the window's steps it is the time the
loop stood waiting for a placed batch."""

from benchmark import span_reduce


def read(trace, counters, cell):
    return span_reduce.read(counters, "fit/feed_wait", span_reduce.mean)
