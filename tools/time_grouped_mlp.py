"""Time one dropless expert layer's tile walk alone on the chip.

    python3 tools/time_grouped_mlp.py                       # lfm2moe's layer
    python3 tools/time_grouped_mlp.py --cell joyai --rows 256,512
    python3 tools/time_grouped_mlp.py --shape 32768,2048,1792,8,4,32 --ops 8

`moe.grouped_expert_mlp` at a cell's (N tokens a step, d hidden, f expert
width, E experts held, k experts a token, W router outputs), over seeded
uniform routing (every token takes k of the W experts; the assignments to
the first E, sorted by expert, as `DroplessMoE` lists them) and under the
cell's own floor of tiles (its family's uniform shares: the
`EXPERT_FLOOR_SHARES` of `lfm2_moe` in lfm2moe and of `laguna` in laguna,
`moe.GROUP_FLOOR_SHARES` in the other two; `--shape` keeps `--cell`'s
floor), so a loop walks what the cell's walks, once forward only and once
forward + backward (the gradient of a weighted sum of the result in x, the
three weight stacks and the combine weights). Each line is one traced
program at one tile size (`--rows`; `shipped` marks what `moe.tile_rows`
gives the shape): milliseconds a call of the whole program and of its tile
loops, from the device trace's `XLA Ops` line, read with the benchmark's own
reduction and told apart as `moe_grouped_ms` tells them (a `while` whose
carry starts with the float32 (tokens, hidden) accumulator), so a reading
here and a layer's loops in the cell's trace are the same quantity;
`tile_us` is a loop's time over the tiles it walked. `--ops N` adds the N
longest operations, a loop's body among them. No cell runs this; it fails
where JAX finds no TPU.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tools")]

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.layer_metrics import kda_core_ms
from mmlspark_tpu.models import laguna, lfm2_moe, moe
from time_delta_rule import trace_of

CELLS = {   # a manifest cell's configuration and traffic files, its floor
    "kimilinear": ("kimi_linear_48b_a3b", "stream_b8_t2048_kimi",
                   moe.GROUP_FLOOR_SHARES),
    "joyai": ("joyai_llm_flash_48b_a3b", "stream_b8_t4096_joyai",
              moe.GROUP_FLOOR_SHARES),
    "lfm2moe": ("lfm2_8b_a1b", "stream_b8_t4096_lfm2",
                lfm2_moe.EXPERT_FLOOR_SHARES),
    "laguna": ("laguna_s_2p1", "stream_b8_t4096_laguna",
               laguna.EXPERT_FLOOR_SHARES),
}
ROWS = (256, 512, 1024, 2048)


def cell_shape(name):
    """(N, d, f, E, k, W) of an expert layer of the cell `name`."""
    config, traffic, _ = CELLS[name]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        batch = json.load(f)["batch_rows"]
    held = cfg.get("num_experts", cfg.get("n_routed_experts"))
    k = cfg.get("num_experts_per_token", cfg.get("num_experts_per_tok"))
    return (batch * cfg["input"]["seq_len"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], held, k, cfg["router_width"])


def parse(argv):
    """(shape, floor in uniform shares, tile sizes, ops) from the command
    line."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), default="lfm2moe")
    ap.add_argument("--shape", default="", help="N,d,f,E,k,W in --cell's "
                    "place")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)),
                    help="tile sizes to time")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args(argv)
    shape = (tuple(int(v) for v in args.shape.split(","))
             if args.shape else cell_shape(args.cell))
    if len(shape) != 6 or min(shape) < 1 or not shape[3] <= shape[5] \
            or not shape[4] <= shape[5]:
        ap.error(f"--shape is N,d,f,E,k,W with E, k <= W: {shape}")
    rows = tuple(int(r) for r in args.rows.split(",") if r)
    if not rows or min(rows) < 8 or any(r % 8 for r in rows):
        ap.error(f"--rows are multiples of 8: {args.rows}")
    return shape, CELLS[args.cell][2], rows, args.ops


def inputs(shape, seed=0):
    """x, the three weight stacks in bfloat16 and the assignment lists of a
    seeded uniform routing: (x, w_gate, w_up, w_down, token, weight,
    counts)."""
    N, d, f, E, k, W = shape
    rng = np.random.default_rng(seed)
    chosen = np.argsort(rng.random((N, W)), axis=-1)[:, :k]
    local = np.where(chosen < E, chosen, E).reshape(-1)
    order = np.argsort(local, kind="stable")
    stack = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                                   jnp.bfloat16)
    return (jnp.asarray(rng.normal(size=(N, d)), jnp.bfloat16),
            stack(E, d, f), stack(E, d, f), stack(E, f, d),
            jnp.asarray(order // k, jnp.int32),
            jnp.asarray(rng.uniform(0.1, 0.4, N * k), jnp.float32),
            jnp.asarray(np.bincount(local, minlength=E + 1)[:E], jnp.int32))


def programs(shape, shares, rows):
    """{pass: jitted program of `inputs`} at `rows` a tile, and the tiles a
    call's loop walks at the least (the floor of `shares` uniform shares)."""
    N, d, f, E, k, W = shape
    floor = moe.floor_tiles(N, k, E, W, rows, shares)
    ct = jnp.asarray(np.random.default_rng(1).normal(size=(N, d)),
                     jnp.float32)

    def fwd(x, wg, wu, wd, token, weight, counts):
        return moe.grouped_expert_mlp(x, wg, wu, wd, token, weight, counts,
                                      floor, rows)[0]

    def loss(*args):
        return jnp.sum(fwd(*args) * ct)

    return {"fwd": jax.jit(fwd),
            "fwd_bwd": jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 5)))
            }, floor


def report(trace, shape, walked, ops=0, **tags):
    """One line: the program's and its tile loops' milliseconds a call."""
    calls = trace["steps"]
    ms = lambda name: 1e3 * trace["op_s"][name] / calls
    loop_s, _ = kda_core_ms.loop_seconds(trace, f"f32[{shape[0]},{shape[1]}]")
    loops = {n: ms(n) for n in sorted(trace["op_s"])
             if n.split(".")[0] == "while"}
    line = dict(tags, tiles=walked,
                program_ms=sum(trace["step_busy_ms"]) / calls,
                loops_ms=1e3 * loop_s / calls, loops=loops,
                tile_us={n: 1e3 * v / walked for n, v in loops.items()})
    if ops:
        top = sorted(trace["op_s"], key=ms, reverse=True)[:ops]
        line["ops"] = [[n, ms(n), trace["op_label"].get(n, "")[:100]]
                       for n in top]
    print(json.dumps(line), flush=True)


def main():
    shape, shares, sizes, ops = parse(sys.argv[1:])
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    N, d, f, E, k, W = shape
    data = inputs(shape)
    counts = np.asarray(data[-1])
    print(json.dumps({"device": dev.device_kind, "shape": shape,
                      "share": N * k // W, "counts": counts.tolist(),
                      "shipped": moe.tile_rows(N * k // W),
                      "floor_shares": str(shares)}), flush=True)
    for rows in sizes:
        fns, floor = programs(shape, shares, rows)
        walked = max(floor, int(np.sum(-(-counts // rows))))
        for name, fn in fns.items():
            report(trace_of(fn, data), shape, walked, ops, rows=rows,
                   **{"pass": name})


if __name__ == "__main__":
    main()
