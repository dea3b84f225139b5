"""Device milliseconds a step in the vocabulary heads' loss walks: for the
main head and the prediction module's, the forward walk over chunks of
positions (final norm, projection onto the vocabulary rows held,
log-sum-exp, the target's logit) and the backward walk, which computes a
chunk's logits again before their gradient.

Plain XLA under `lax.scan`, so told apart as `kda_core_ms` tells its loops:
a `while` operation of the step program (one event a loop run, the body's
operations inside it) by what it carries, which the start of its HLO text
gives. The walks are the only loops that carry the hidden states cut into
chunks, `[n, rows, chunk, hidden]` (the forward walk after the counter and
its float32 `[rows]` sums; the backward walk after the counter and the two
float32 gradients it adds up, the norm's `[hidden]` and the head's
`[hidden, vocabulary]`). The copies that cut the hidden states into chunks
and join their gradient again are outside the loops and not in it. None
where the configuration has no vocabulary head or the trace no such loop."""

import re

from benchmark.layer_metrics.kda_core_ms import LAYOUT, ms_a_step

DEFAULT_CHUNK = 512     # the module's `lm_loss_chunk` where the file has none


def read(trace, counters, cell):
    cfg = cell["config"]
    if cfg.get("learner", {}).get("loss") != "next_token" \
            or "batch_rows" not in counters:
        return None
    rows = counters["batch_rows"] // cell["chips"]
    T, d = cfg["input"]["seq_len"], cfg["hidden_size"]
    chunk = min(cfg.get("lm_loss_chunk", DEFAULT_CHUNK), T)
    carried = re.compile(rf"\[{-(-T // chunk)},{rows},{chunk},{d}\]")
    hit = [name for name in trace["op_s"]
           if name.split(".")[0] == "while"
           and carried.search(LAYOUT.sub("", trace["op_label"].get(name, "")))]
    return ms_a_step(sum(trace["op_s"][h] for h in hit), trace)
