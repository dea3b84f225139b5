"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.7: data parallelism
only). TPU-first design: the pipeline is ONE jitted program under
``shard_map`` — each device along the ``pipe`` axis holds one stage's
parameters (a stacked pytree sharded on its leading axis), microbatch
activations hop stage-to-stage with ``lax.ppermute`` (neighbor-only ICI
traffic), and the whole schedule is a ``lax.scan`` over
``n_microbatches + n_stages - 1`` ticks. Differentiable end-to-end
(``ppermute``/``scan`` have transposes), so the same primitive serves
training — no hand-written backward schedule.

Composes with data parallelism: put ``pipe`` after ``data`` in the mesh and
shard the batch over ``data`` as usual.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(stage_params: list):
    """Stack per-stage pytrees (identical treedefs) along a new leading axis —
    the axis the ``pipe`` mesh dimension shards."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params)


def shard_pipeline_params(stacked, mesh: Mesh, axis_name: str = "pipe"):
    """Place stacked stage params with leading axis sharded over ``pipe``."""
    sh = NamedSharding(mesh, P(axis_name))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), stacked)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   axis_name: str = "pipe", n_microbatches: int = None,
                   batch_axis: str = None):
    """Run ``n_stages`` chained applications of ``stage_fn`` as a pipeline.

    stage_fn(params_i, h) -> h'   one stage; h and h' share a shape.
    stacked_params: pytree with leading axis n_stages (= mesh[axis_name]).
    x: global batch (N, ...); split into ``n_microbatches`` equal microbatches.
    batch_axis: optional mesh axis to also shard the batch over (DP x PP).

    Returns f(x) with shape (N, ...), equivalent to sequentially applying all
    stages. Tick t: stage 0 injects microbatch t; stage s processes what
    stage s-1 produced at t-1; the last stage's outputs are collected and
    replicated back via a masked psum.
    """
    n_stages = mesh.shape[axis_name]
    N = x.shape[0]
    M = n_microbatches or n_stages
    if N % M != 0:
        raise ValueError(f"batch {N} not divisible by n_microbatches {M}")
    mb = N // M
    x_mb = x.reshape(M, mb, *x.shape[1:])

    b = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    x_spec = P(None, b)                       # (M, mb, ...): mb over data
    p_spec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    out_spec = P(None, b)

    def local(params, xm):
        # params leaves: (1, ...) local stage slice; xm: (M, mb_local, ...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        s_idx = lax.axis_index(axis_name)
        last = n_stages - 1
        zero = jnp.zeros_like(xm[0])

        def tick(carry, t):
            state, outbuf = carry
            inject = xm[jnp.clip(t, 0, M - 1)]
            h_in = jnp.where(s_idx == 0, inject, state)
            y = stage_fn(params, h_in)
            # rotate activations one stage forward around the ring
            state_next = lax.ppermute(
                y, axis_name, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # the last stage finished microbatch t-last at tick t
            slot = jnp.clip(t - last, 0, M - 1)
            write = jnp.logical_and(s_idx == last, t >= last)
            cur = lax.dynamic_index_in_dim(outbuf, slot, keepdims=False)
            upd = jnp.where(write, y.astype(outbuf.dtype), cur)
            outbuf = lax.dynamic_update_index_in_dim(outbuf, upd, slot, 0)
            return (state_next, outbuf), None

        outbuf0 = jnp.zeros((M,) + zero.shape, xm.dtype)
        (_, outbuf), _ = lax.scan(tick, (zero, outbuf0),
                                  jnp.arange(M + n_stages - 1))
        # only the last stage holds real outputs; replicate over the pipe axis
        outbuf = jnp.where(s_idx == last, outbuf, jnp.zeros_like(outbuf))
        return lax.psum(outbuf, axis_name)

    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(p_spec, x_spec), out_specs=out_spec,
                        check_vma=False)(stacked_params, x_mb)
    return out.reshape(N, *out.shape[2:])


def transformer_pp_forward(cfg: dict, params, tokens, mesh: Mesh,
                           n_microbatches: int = None,
                           axis_name: str = "pipe",
                           batch_axis: str = "data"):
    """Forward pass of the transformer family with its encoder-block stack
    run as a GPipe pipeline over the ``pipe`` mesh axis.

    This is how ``TpuLearner.setPipelineParallel(k)`` trains: the embed and
    head (a few % of the FLOPs) run replicated across the pipe axis, the L
    encoder blocks split into ``k`` stages of L/k blocks each, and
    microbatch activations hop stage-to-stage over ``ppermute`` — one
    differentiable jitted program, so ``jax.grad`` of a loss on these
    logits yields the full pipelined backward with no hand-written
    schedule. ``params`` keeps the ORIGINAL flax layout (block subtrees are
    stacked inside the trace), so the optimizer, checkpoints, and TpuModel
    inference reuse the fitted tree unchanged.
    """
    import flax.linen as nn

    from ..models.modules import build_model

    enc = build_model(cfg)          # field access only (dtype, dims, attn)
    L, pp = enc.layers, mesh.shape[axis_name]
    if L % pp != 0:
        raise ValueError(f"layers ({L}) must divide by the pipe axis ({pp})")
    p = params["params"] if "params" in params else params
    B, T = tokens.shape
    emb = nn.Embed(enc.vocab_size, enc.d_model, dtype=enc.dtype).apply(
        {"params": p["Embed_0"]}, tokens)
    pos = nn.Embed(enc.max_len, enc.d_model, dtype=enc.dtype).apply(
        {"params": p["Embed_1"]}, jnp.arange(T)[None, :])
    h = (emb + pos).astype(enc.dtype)

    # stage j = blocks [j*k, (j+1)*k): leaf shapes (pp, k, ...)
    k = L // pp
    stages = [stack_stage_params([p[f"block{j * k + i}"] for i in range(k)])
              for j in range(pp)]
    stacked = stack_stage_params(stages)

    from ..models.modules import _EncoderBlock
    Block = nn.remat(_EncoderBlock) if enc.remat else _EncoderBlock
    block = Block(d_model=enc.d_model, heads=enc.heads,
                  mlp_ratio=enc.mlp_ratio, dtype=enc.dtype,
                  attention=enc._attention)

    def stage_fn(stage_params, hm):
        def body(hc, blk_p):
            return block.apply({"params": blk_p}, hc), None
        out, _ = lax.scan(body, hm, stage_params)
        return out

    h = pipeline_apply(stage_fn, stacked, h, mesh, axis_name=axis_name,
                       n_microbatches=n_microbatches or pp,
                       batch_axis=batch_axis)
    h = nn.LayerNorm(dtype=enc.dtype).apply(
        {"params": p["LayerNorm_0"]}, h)
    if enc.pool == "mean":
        h = jnp.mean(h, axis=1)
    logits = nn.Dense(enc.num_classes, dtype=enc.dtype).apply(
        {"params": p["Dense_0"]}, h)
    return logits.astype(jnp.float32)
