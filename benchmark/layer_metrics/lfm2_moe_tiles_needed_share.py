"""How far the tile floor stands above the load in the `lfm2_moe` cell: the
median over the window's steps of the tiles the routing filled, whole or in
part, over the tiles the expert layers walked (those or the floor of two
uniform shares, the greater), in per cent, summed over the four expert
layers. The program counts both on the device and records them in its
`fit/step_stats` events (`moe_tiles_needed`, `moe_tiles_walked`), which
`moe_expert_tokens_max.window_stats` cuts to the window. 100 says the floor
is not met or exactly filled; uniform routing at 1,024 rows a tile needs
32-40 of the 64 tiles a layer walks. None where the configuration is of
another family or the program records neither number (a program from before
the counts)."""

from benchmark import span_reduce
from benchmark.layer_metrics import moe_expert_tokens_max

NEEDED, WALKED = "moe_tiles_needed", "moe_tiles_walked"


def read(trace, counters, cell):
    if cell["config"].get("type") != "lfm2_moe":
        return None
    shares = [100.0 * s[NEEDED] / s[WALKED]
              for s in moe_expert_tokens_max.window_stats(counters)
              if s.get(WALKED)]
    return float(span_reduce.median(shares)) if shares else None
