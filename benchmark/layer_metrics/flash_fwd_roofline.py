"""The flash attention forward kernel's share of its roofline: the least time
the chip could take for the calls made (the larger of operations over peak
and bytes over peak, from benchmark/flops/attention.py), over the kernel's
measured device time in the traced window. Under `remat` the forward kernel
runs twice a layer a step; every call made is counted on both sides.

The program gives its Pallas calls no name, so the trace shows each as a
`tpu_custom_call` named after the scope that called it. The three kernels are
told apart by what they return: forward (out bf16, logsumexp f32), dq (one
bf16), dkv (two bf16)."""

from benchmark import trace_reduce
from benchmark.flops import attention

_T = r"\[[\d,]+\]\{[^}]*\}"
PALLAS = r" custom-call\(.*tpu_custom_call"
KERNEL = r"^\(bf16" + _T + r", f32" + _T + r"\)" + PALLAS


def share(seconds, calls, counters, cell, shape_fn):
    """Least seconds for `calls` calls of the kernel over measured seconds."""
    if not seconds or not calls:
        return None
    cfg = cell["config"]
    ops, nbytes = shape_fn(counters["batch_rows"] // cell["chips"],
                           cfg["heads"], cfg["input"]["seq_len"],
                           cfg["d_model"] // cfg["heads"],
                           cfg.get("causal", False))
    least, _ = attention.least_seconds(ops, nbytes, cell["peaks"])
    return 100.0 * calls * least / seconds


def read(trace, counters, cell):
    seconds, calls = trace_reduce.kernel_time(trace, KERNEL)
    return share(seconds, calls, counters, cell, attention.flash_fwd)
