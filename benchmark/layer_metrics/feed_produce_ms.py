"""What one batch costs the host: the median duration of the producer's
`fit/prefetch` span (the driver's generator, `_stream_batch`, pad,
`put_global_batch`) over the window's items. Beside `step_interval_ms_p95`
it is the feed's headroom: a feed whose batch costs more than a step's
interval cannot keep up at any prefetch depth."""

from benchmark import span_reduce


def read(trace, counters, cell):
    return span_reduce.read(counters, "fit/prefetch", span_reduce.median)
