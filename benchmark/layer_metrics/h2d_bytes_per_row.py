"""Host bytes handed to device placement per row of the whole fit, from the
program's `mmlspark_mesh_put_bytes` over every row the driver handed over.
A count: it repeats exactly, and it guards the uint8 wire contract."""

COUNTER = "mmlspark_mesh_put_bytes"


def read(trace, counters, cell):
    put = counters.get("program", {}).get(COUNTER)
    rows = counters.get("rows_handed")
    if not put or not rows:
        return None
    return put / rows
