"""The two grouped-query flash attention backward kernels' (dq, dkv) share
of their roofline, taken together: five products a query head where the
forward has two, q, o, dO and dq moved at the query heads held and k, v, dk
and dv at the key/value heads held (benchmark/flops/lfm2_moe.py), over the
two kernels' measured device time in the traced window. None where the
configuration is of another family, the trace holds no such kernel or the
two were not called equally often: never 0."""

from benchmark.flops import lfm2_moe
from benchmark.layer_metrics.gqa_flash_fwd_roofline import share

DQ, DKV = r"^flash_dq(\.\d+)?$", r"^flash_dkv(\.\d+)?$"


def read(trace, counters, cell):
    return share(trace, counters, cell, (DQ, DKV), lfm2_moe.gqa_flash_bwd)
