"""The whole step's share of the chip's peak: forward and backward operations
per row from the configuration's shapes (benchmark/flops/<type>.py, nothing
recomputed counted), times the rows per second of the traced window's whole
steps, over chips x peak bf16. Never from XLA's cost analysis."""

import importlib


def read(trace, counters, cell):
    if not trace["steps"] or not trace["window_s"]:
        return None
    flops = importlib.import_module(
        f"benchmark.flops.{cell['config']['type']}")
    rows_per_s = trace["steps"] * counters["batch_rows"] / trace["window_s"]
    peak = cell["chips"] * cell["peaks"]["flops_bf16"]
    return 100.0 * flops.train_flops_per_row(cell["config"]) * rows_per_s / peak
