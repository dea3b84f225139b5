"""The two flash attention backward kernels' (dq, dkv) share of their
roofline, taken together: 2.5 times the forward's operations a layer a step,
over the two kernels' measured device time in the traced window."""

from benchmark import trace_reduce
from benchmark.flops import attention
from benchmark.layer_metrics import flash_fwd_roofline as fwd

KERNEL_DQ = r"^bf16" + fwd._T + fwd.PALLAS
KERNEL_DKV = r"^\(bf16" + fwd._T + r", bf16" + fwd._T + r"\)" + fwd.PALLAS


def read(trace, counters, cell):
    s_dq, n_dq = trace_reduce.kernel_time(trace, KERNEL_DQ)
    s_dkv, n_dkv = trace_reduce.kernel_time(trace, KERNEL_DKV)
    if not s_dq or not s_dkv or n_dq != n_dkv:
        return None
    return fwd.share(s_dq + s_dkv, n_dq, counters, cell, attention.flash_bwd)
