"""Positions scored a step by the per-token losses: the median over the
window's steps of `lm_tokens_scored`, which the step program counts on the
device (real rows x the positions that entered the main head's and the
prediction module's means), returns beside the loss and, once a step has
finished, records numbered by `step` on its ring in the event
`fit/step_stats`. A row of T ids scores T - 1 positions in the main head and
T - 2 in each prediction module: the reader raises if a step read anything
else, so a loss that silently scores fewer positions cannot read as a faster
step. It raises too if the same event says the expert layers left an
assignment out (`moe_tokens_dropped`, which `moe_expert_tokens_max` checks
in the cells it lists): a step that computes fewer tokens cannot read as a
faster one either. None where the program records no such value."""

from benchmark import span_reduce
from benchmark.layer_metrics.moe_expert_tokens_max import (DROPPED,
                                                           window_stats)

VALUE = "lm_tokens_scored"


def read(trace, counters, cell):
    steps = [s for s in window_stats(counters) if VALUE in s]
    if not steps:
        return None
    cfg = cell["config"]
    T = cfg["input"]["seq_len"]
    want = counters["batch_rows"] * (
        T - 1 + cfg.get("num_nextn_predict_layers", 0) * (T - 2))
    stats = [s[VALUE] for s in steps]
    if any(s != want for s in stats):
        raise RuntimeError(f"a step scored {sorted(set(stats))} positions; "
                           f"the rows handed over hold {want}")
    dropped = [s[DROPPED] for s in steps if s.get(DROPPED)]
    if dropped:
        raise RuntimeError("the dropless expert layer dropped tokens: "
                           f"{dropped}")
    return float(span_reduce.median(stats))
