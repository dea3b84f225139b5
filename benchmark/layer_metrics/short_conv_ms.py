"""Device milliseconds a step inside the gated short-convolution mixers'
cores: the gate, the depthwise causal convolution and the gate between a
conv mixer's two projections, forward, recomputation under `remat` and
backward, of every conv mixer.

The core is plain XLA, a handful of loop fusions a mixer a pass, and the
program traces it under the scope `short_conv`
(`mmlspark_tpu/models/lfm2_moe.py`). The trace's operations are named by
their HLO instructions, which do not show a scope; the profile's own table
of event metadata does (`benchmark/scope_ops.py`), so the reader takes from
the traced run's file the instructions whose `op_name` lies under the scope
and adds up their device time in the window. A fusion counts by its root:
what the compiler folds of the core into a projection's matrix product (the
output gate into the out-projection, forward, where nothing else reads it)
is the product's time and not in here, and the two projections never are.
None where the configuration has no conv mixer, the run left no trace file
or no operation of it lies under the scope."""

from benchmark import scope_ops
from benchmark.layer_metrics.kda_core_ms import ms_a_step

SCOPE = "short_conv"


def read(trace, counters, cell):
    if "conv" not in cell["config"].get("layer_types", ()):
        return None
    path = scope_ops.traced_run_file()
    if path is None:
        return None
    names = scope_ops.under_scope(path, SCOPE)
    return ms_a_step(sum(s for name, s in trace["op_s"].items()
                         if name in names), trace)
