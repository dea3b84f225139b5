"""Device milliseconds a step in the cores of the rotary latent-attention
blocks: the three flash attention kernels (`flash_fwd`, `flash_dq`,
`flash_dkv`, named by the program's `pallas_call`s) that every latent block,
the prediction module's included, calls on its rotated, zero-padded q, k, v;
forward, recomputation under `remat` and backward. The rotation, the padding
and the layout copies around the calls are not in it. The accepted
`mla_core_ms` reads the same kernels for the NoPE configuration and lists its
own cell; a `benchmark` PR may fold the two. None where the configuration
has no rotary latent attention or the trace holds no such kernel."""

from benchmark import trace_reduce
from benchmark.layer_metrics.kda_core_ms import ms_a_step
from benchmark.layer_metrics.mla_core_ms import KERNELS


def read(trace, counters, cell):
    cfg = cell["config"]
    if "kv_lora_rank" not in cfg or not cfg.get("rope_interleave"):
        return None
    seconds, _ = trace_reduce.kernel_time(trace, KERNELS)
    return ms_a_step(seconds, trace)
