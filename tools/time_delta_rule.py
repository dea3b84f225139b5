"""Time the chunked delta-rule scan alone on the chip.

    python3 tools/time_delta_rule.py                    # the block as shipped
    python3 tools/time_delta_rule.py --block 8,16,32 --ops 12

`chunked_delta_rule` at the shapes of `kimilinear_train_stream` (B 8,
T 2,048, H 8, K = V = 128, chunk 64), once forward only and once forward +
backward (the gradient of a weighted sum of the outputs in all five inputs).
Each line is one traced program: milliseconds a call of the whole program and
of each of its scan loops, from the device trace's `XLA Ops` line, read with
the benchmark's own reduction and told apart as `kda_core_ms` tells them (a
`while` whose carry starts with the float32 state), so a reading here and
`kda_core_ms` of the cell are the same quantity a layer a pass. `--block`
puts candidates in `SOLVE_BLOCK`'s place; `--ops N` adds the N longest
operations, a loop's body among them. No cell runs this; it fails where JAX
finds no TPU.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import trace_reduce
from benchmark.layer_metrics import kda_core_ms
from mmlspark_tpu.ops import delta_rule

B, T, H, K, CHUNK = 8, 2048, 8, 128, 64     # kimilinear_train_stream's
CALLS = 6


def inputs(seed=0):
    """q, k unit rows, v normal, a decay of 0.2-0.999 a token a channel (the
    module's init), steps in (0, 1): the values do not move the time."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(rng.normal(size=(B, T, H, K))) for _ in range(2))
    v = rng.normal(size=(B, T, H, K))
    g = np.log(rng.uniform(0.2, 0.999, size=(B, T, H, K)))
    beta = rng.uniform(0.05, 0.95, size=(B, T, H))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def programs(scan):
    """{pass: jitted program} of a scan with `chunked_delta_rule`'s
    signature."""
    weights = jnp.asarray(
        np.random.default_rng(1).normal(size=(B, T, H, K)), jnp.float32)

    def loss(*args):
        return jnp.sum(scan(*args, chunk=CHUNK) * weights)

    return {"fwd": jax.jit(lambda *args: scan(*args, chunk=CHUNK)),
            "fwd_bwd": jax.jit(jax.grad(loss, argnums=range(5)))}


def trace_of(fn, args):
    """The benchmark's reduction of a traced run of CALLS calls of `fn`."""
    jax.block_until_ready(fn(*args))                  # compile, warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(CALLS):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        return trace_reduce.summarise(trace_reduce.load_events(path))


def report(trace, ops=0, **tags):
    """One line: the program's and its scan loops' milliseconds a call."""
    calls = trace["steps"]
    ms = lambda name: 1e3 * trace["op_s"][name] / calls
    scan_s, _ = kda_core_ms.loop_seconds(trace, f"f32[{B},{H},{K},{K}]")
    line = dict(tags, program_ms=sum(trace["step_busy_ms"]) / calls,
                scan_ms=1e3 * scan_s / calls,
                loops={n: ms(n) for n in sorted(trace["op_s"])
                       if n.split(".")[0] == "while"})
    if ops:
        top = sorted(trace["op_s"], key=ms, reverse=True)[:ops]
        line["ops"] = [[n, ms(n), trace["op_label"].get(n, "")[:100]]
                       for n in top]
    print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--block", default="", help="candidates for SOLVE_BLOCK")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    print(json.dumps({"device": dev.device_kind, "calls": CALLS,
                      "shape": [B, T, H, K], "chunk": CHUNK}), flush=True)
    shipped = delta_rule.SOLVE_BLOCK
    data = inputs()
    for block in [int(b) for b in args.block.split(",") if b] or [shipped]:
        delta_rule.SOLVE_BLOCK = block
        try:
            for name, fn in programs(delta_rule.chunked_delta_rule).items():
                report(trace_of(fn, data), args.ops, block=block,
                       **{"pass": name})
        finally:
            delta_rule.SOLVE_BLOCK = shipped


if __name__ == "__main__":
    main()
