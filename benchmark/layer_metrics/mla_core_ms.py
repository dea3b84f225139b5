"""Device milliseconds a step in the latent-attention cores: the three flash
attention kernels (`flash_fwd`, `flash_dq`, `flash_dkv`, named by the
program's `pallas_call`s) that the latent layers call on their zero-padded
q, k, v; forward, recomputation under `remat` and backward. The padding and
the layout copies around the calls are not in it. None where the trace holds
no such kernel."""

from benchmark import trace_reduce
from benchmark.layer_metrics.kda_core_ms import ms_a_step

KERNELS = r"^flash_(fwd|dq|dkv)(\.\d+)?$"


def read(trace, counters, cell):
    if "kv_lora_rank" not in cell["config"]:
        return None
    seconds, _ = trace_reduce.kernel_time(trace, KERNELS)
    return ms_a_step(seconds, trace)
