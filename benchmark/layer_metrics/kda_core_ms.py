"""Device milliseconds a step inside the chunked delta-rule scans (the KDA
cores): the forward scan, its recomputation under `remat` and the backward
scan, of every KDA layer.

The core is plain XLA under `lax.scan`, so the trace shows it as `while`
operations (one event a loop run, the body's operations inside it) and not as
a kernel with a name. A `while` is told apart by what it carries, which the
start of its HLO text gives: the scan's loops carry, after the counter, the
float32 state of the recurrence, one head_dim x head_dim matrix a row and
head: `(s32[], f32[rows, heads, K, K], ...`. Where the trace has no such
operation (another program, or a kernel in the scan's place) the reader
finds nothing and returns None."""

import re

LAYOUT = re.compile(r"\{[^}]*\}")


def loop_seconds(trace, carried):
    """(seconds, runs) per chip of the `while` operations whose carried
    tuple starts with the counter and then `carried` (a type without its
    layout, as `f32[8,8,128,128]`)."""
    head = f"(s32[], {carried},"
    hit = [name for name in trace["op_s"]
           if name.split(".")[0] == "while"
           and LAYOUT.sub("", trace["op_label"].get(name, "")).startswith(
               head)]
    return (sum(trace["op_s"][h] for h in hit),
            sum(trace["op_n"][h] for h in hit))


def ms_a_step(seconds, trace):
    if not seconds or not trace["steps"]:
        return None
    return 1e3 * seconds / trace["steps"]


def read(trace, counters, cell):
    lin = cell["config"].get("linear_attn_config")
    if not lin or "batch_rows" not in counters:
        return None
    rows = counters["batch_rows"] // cell["chips"]
    K = lin["head_dim"]
    seconds, _ = loop_seconds(
        trace, f"f32[{rows},{lin['num_heads']},{K},{K}]")
    return ms_a_step(seconds, trace)
