"""The fullest held expert's load in the `lfm2_moe` cell: the median over
the window's steps of the largest number of assignments (token, expert) any
one held expert of any expert layer got in the step, from the program's
`fit/step_stats` events as `moe_expert_tokens_max` reads them for its own
cell. Because the deployment's four chips share the batch, uniform routing
gives a held expert the deployment's own 32,768 x 4 / 32 = 4,096 a step.
Raises if a step's `moe_tokens_dropped` is not 0. None where the
configuration is of another family or the program records no such event."""

from benchmark.layer_metrics import moe_expert_tokens_max


def read(trace, counters, cell):
    if cell["config"].get("type") != "lfm2_moe":
        return None
    return moe_expert_tokens_max.read(trace, counters, cell)
