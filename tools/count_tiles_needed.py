#!/usr/bin/env python3
"""Count what a cell's routing asks of the expert layers' tile walk, seed by
seed, at the model's fresh init.

    python3 tools/count_tiles_needed.py --workload lfm2moe_train_stream \
        --seeds 2147493001-2147493012
    python3 tools/count_tiles_needed.py --workload lfm2moe_train_stream \
        --seeds 1-2 --rehearsal 1

For each seed the cell's model is built and initialised as `fitStream`
initialises it in the benchmark's run (`train_stream.build_learner`, the
seeded init on the first row), the seed's pool of batches is made as
`train_stream.make_pool` makes it, and each batch of the pool (the window
cycles them) goes once through the model's forward pass. Every expert layer's counts
(`moe.MOE_STEP_STATS`) are read layer by layer. One JSON line a seed: per
batch, per expert layer, the fullest held expert's assignments and the tiles
the routing fills at the layer's tile rows. A last line sums up over every
(seed, batch, layer): the uniform share, the fullest expert read (in
assignments and in shares) and, for each candidate floor in uniform shares,
its tiles a layer and the layer-steps whose need passed it, which would have
walked more than the floor (the step's time then follows the routing).

Outside the benchmark: no metric reads it. It needs the chip for a cell's
own sizes (the forward pass at a step's batch); `--rehearsal 1` runs the
cell's rehearsal sizes on whatever backend JAX has, to prove the control
flow and nothing else.
"""

import argparse
import json
import os
import sys
from fractions import Fraction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench                          # noqa: E402
from benchmark.drivers import train_stream                  # noqa: E402

FLOORS = (Fraction(1), Fraction(5, 4), Fraction(3, 2), Fraction(2))


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, inclusive")
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    first, _, last = args.seeds.partition("-")
    seeds = range(int(first), int(last or first) + 1)
    if not seeds:
        ap.error(f"--seeds {args.seeds} names no seed")
    return args.workload, seeds, bool(args.rehearsal)


def cell_files(workload, rehearsal):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _, config, traffic = bench.load_cell(json.load(f), workload)
    if rehearsal:
        config = bench.merge(config, config.get("rehearsal", {}))
        traffic = bench.merge(traffic, traffic.get("rehearsal", {}))
    return config, traffic


def layer_counts(config, traffic, seeds):
    """Yield (seed, [[(layer, fullest, tiles needed), ...] a batch]) at each
    seed's fresh init, over its pool of batches."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.models.moe import DroplessMoE

    def is_expert_layer(module, method):
        return isinstance(module, DroplessMoE) and method == "__call__"

    # the seed moves the init and the batches, never the model's shapes
    module = build_model(dict(train_stream.build_learner(
        config, traffic, seeds[0]).getModelConfig()))
    forward = jax.jit(lambda p, x: module.apply(
        p, x, step_stats=True, row_losses=True,
        capture_intermediates=is_expert_layer,
        mutable=["intermediates"])[1]["intermediates"])
    for seed in seeds:
        pool = train_stream.make_pool(config, traffic, seed)
        params = module.init(jax.random.PRNGKey(train_stream.build_learner(
            config, traffic, seed).getSeed()), jnp.asarray(pool[0][0][:1]))
        batches = []
        for x, _ in pool:
            seen = jax.device_get(forward(params, jnp.asarray(x)))
            flat = jax.tree_util.tree_flatten_with_path(
                seen, is_leaf=lambda v: isinstance(v, tuple))[0]
            batches.append([
                ("/".join(str(getattr(k, "key", k)) for k in path[:-1]),
                 int(out[0][1][1]), int(out[0][1][3]))
                for path, out in flat])
        del params
        yield seed, batches


def summary(config, traffic, records):
    """The uniform share, the fullest expert read, and for each candidate
    floor the layer-steps whose need passed it."""
    from mmlspark_tpu.models import moe
    N = traffic["batch_rows"] * config["input"]["seq_len"]
    k = config.get("num_experts_per_tok", config.get("num_experts_per_token"))
    E = config.get("num_experts", config.get("n_routed_experts"))
    W = config["router_width"]
    share, rows = N * k // W, moe.tile_rows(N * k // W)
    layers = [c for _, batches in records for b in batches for c in b]
    fullest = max(c[1] for c in layers)
    floors = {}
    for shares in FLOORS:
        floor = moe.floor_tiles(N, k, E, W, rows, shares)
        floors[str(shares)] = {
            "tiles": floor,
            "experts_past": sum(c[1] > shares * share for c in layers),
            "layer_steps_past": sum(c[2] > floor for c in layers)}
    return {"share": share, "rows": rows, "layer_steps": len(layers),
            "fullest": fullest, "fullest_shares": fullest / share,
            "needed_max": max(c[2] for c in layers),
            "needed_min": min(c[2] for c in layers),
            "floors": floors}


def main(argv=None):
    workload, seeds, rehearsal = parse(argv)
    import jax
    if not rehearsal and jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU for the cell's sizes, found "
                 f"{jax.devices()[0].platform}; --rehearsal 1 runs small")
    config, traffic = cell_files(workload, rehearsal)
    records = []
    for seed, batches in layer_counts(config, traffic, seeds):
        records.append((seed, batches))
        print(json.dumps({"seed": seed, "layers": [c[0] for c in batches[0]],
                          "fullest": [[c[1] for c in b] for b in batches],
                          "needed": [[c[2] for c in b] for b in batches]}),
              flush=True)
    print(json.dumps(dict(summary(config, traffic, records),
                          device=jax.devices()[0].device_kind,
                          workload=workload, seeds=len(seeds))), flush=True)


if __name__ == "__main__":
    main()
