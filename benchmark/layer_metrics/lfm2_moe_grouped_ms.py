"""Device milliseconds a step in the grouped expert products of the
`lfm2_moe` cell's four dropless expert layers: the `while` over tiles of
routed tokens (a tile's gather, its three products, its scatter-add),
forward and backward (`remat` does not run the forward loop again: the
layer's residuals are its inputs), and the sort of the assignments before
it.

`moe_grouped_ms`'s own rule (that entry lists kimilinear's cell alone and
reads its key `num_experts_per_token`; this family's file says
`num_experts_per_tok`): the tile loops carry, after the counter, the float32
(tokens, hidden) accumulator of the layer's result or of its input's
gradient, `f32[32768,2048]` here, whatever the rows of a tile, so one rule
reads a program that walks 256-row tiles and one that walks 1,024-row tiles.
The sort is the `sort` operation over one entry an assignment (tokens x
experts a token = 131,072). None where the configuration is of another
family or the trace has no such loop."""

from benchmark.layer_metrics import moe_grouped_ms


def read(trace, counters, cell):
    cfg = cell["config"]
    if cfg.get("type") != "lfm2_moe":
        return None
    as_kimi = dict(cfg, num_experts_per_token=cfg["num_experts_per_tok"])
    return moe_grouped_ms.read(trace, counters, dict(cell, config=as_kimi))
