"""Device time of one step program, mean over the traced window's steps."""


def read(trace, counters, cell):
    busy = trace["step_busy_ms"]
    return sum(busy) / len(busy) if busy else None
