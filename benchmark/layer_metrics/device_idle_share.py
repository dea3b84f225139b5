"""1 - the union of the device's busy intervals over the traced window."""


def read(trace, counters, cell):
    if not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
