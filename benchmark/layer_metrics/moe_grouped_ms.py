"""Device milliseconds a step in the grouped expert products of the dropless
expert layers: the `while` over tiles of routed tokens (a tile's gather, its
three products, its scatter-add), forward, recomputation under `remat` and
backward, and the sort of the assignments before it.

Plain XLA, so told apart as `kda_core_ms` tells its loops: the tile loops
carry, after the counter, the float32 (tokens, hidden) accumulator of the
layer's result or of its input's gradient. The sort is the `sort` operation
over one entry an assignment (tokens x experts per token). None where the
trace has neither."""

import re

from benchmark.layer_metrics.kda_core_ms import loop_seconds, ms_a_step


def read(trace, counters, cell):
    cfg = cell["config"]
    if "moe_intermediate_size" not in cfg or "batch_rows" not in counters:
        return None
    tokens = (counters["batch_rows"] // cell["chips"]
              * cfg["input"]["seq_len"])
    seconds, runs = loop_seconds(trace, f"f32[{tokens},{cfg['hidden_size']}]")
    if not runs:
        return None
    entries = tokens * cfg["num_experts_per_token"]
    sort = re.compile(rf"\[{entries}\]")
    seconds += sum(s for name, s in trace["op_s"].items()
                   if name.split(".")[0] == "sort"
                   and sort.search(trace["op_label"].get(name, "")))
    return ms_a_step(seconds, trace)
