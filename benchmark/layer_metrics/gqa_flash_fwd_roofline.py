"""The grouped-query flash attention forward kernel's share of its roofline:
the least time the chip could take for the calls made (the larger of
operations over peak and bytes over peak, from benchmark/flops/lfm2_moe.py:
every query head's two products, q and o moved at the query heads held, k
and v at the key/value heads held), over the kernel's measured device time
in the traced window. The attention blocks keep the forward kernel's
residuals under `remat`, so it runs once a block a step; every call made is
counted on both sides. The kernels are told by the names the program gives
its `pallas_call`s. None where the configuration is of another family or
the trace holds no such kernel: never 0."""

from benchmark import trace_reduce
from benchmark.flops import attention, lfm2_moe

FWD = r"^flash_fwd(\.\d+)?$"


def share(trace, counters, cell, patterns, shape_fn):
    """Least seconds for the calls of the kernels matching `patterns` (as
    many calls of each) over their measured seconds, in percent."""
    cfg = cell["config"]
    if cfg.get("type") != "lfm2_moe" or "batch_rows" not in counters:
        return None
    timed = [trace_reduce.kernel_time(trace, p) for p in patterns]
    seconds, calls = sum(s for s, _ in timed), {n for _, n in timed}
    if not seconds or len(calls) != 1 or not min(calls):
        return None
    ops, nbytes = shape_fn(cfg, counters["batch_rows"] // cell["chips"])
    least, _ = attention.least_seconds(ops, nbytes, cell["peaks"])
    return 100.0 * calls.pop() * least / seconds


def read(trace, counters, cell):
    return share(trace, counters, cell, (FWD,), lfm2_moe.gqa_flash_fwd)
