"""Round-5 probe: MXU node-histogram kernel prototype vs existing backends.

Timing: chained lax.fori_loop with data-dependent iterations + one scalar
fetch, so a build's time cannot hide in the dispatch queue.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _split3(a):
    """Exact-ish 3-way bf16 split of f32: a ~= hi + mid + lo."""
    hi = a.astype(jnp.bfloat16)
    r1 = a - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    r2 = r1 - mid.astype(jnp.float32)
    lo = r2.astype(jnp.bfloat16)
    return hi, mid, lo


def _nh_kernel(bins_ref, node_ref, g_ref, h_ref, hg_ref, hh_ref, *,
               n_nodes: int, n_feat: int, width: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        hg_ref[:] = jnp.zeros_like(hg_ref)
        hh_ref[:] = jnp.zeros_like(hh_ref)

    node = node_ref[:]                       # (bn, 1) int32
    g = g_ref[:]                             # (bn, 1) f32
    h = h_ref[:]
    node1h = (node == jax.lax.broadcasted_iota(
        jnp.int32, (node.shape[0], n_nodes), 1))
    ag = jnp.where(node1h, g, 0.0)           # (bn, n_nodes) f32
    ah = jnp.where(node1h, h, 0.0)
    a = jnp.concatenate([ag, ah], axis=1)    # (bn, 2*n_nodes)
    hi, mid, lo = _split3(a)
    A = jnp.concatenate([hi, mid, lo], axis=1)   # (bn, 6*n_nodes) bf16

    for f in range(n_feat):
        bf = bins_ref[:, f][:, None]         # (bn, 1) int32
        B = (bf == jax.lax.broadcasted_iota(
            jnp.int32, (bf.shape[0], width), 1)).astype(jnp.bfloat16)
        out = jax.lax.dot_general(
            A, B, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # (6n, width)
        out = out.reshape(3, 2 * n_nodes, width).sum(axis=0)
        hg_ref[f * n_nodes:(f + 1) * n_nodes, :] += out[:n_nodes]
        hh_ref[f * n_nodes:(f + 1) * n_nodes, :] += out[n_nodes:]


def node_histogram(bins, node, g, h, *, n_nodes: int, n_bins: int = 256,
                   block_n: int = 2048, interpret=False):
    """bins (N,F) int32, node (N,) int32, g/h (N,) f32 ->
    (hg, hh) each (F, n_nodes, n_bins) f32."""
    N, F = bins.shape
    width = max(128, -(-n_bins // 128) * 128)
    pad = (-N) % block_n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        node = jnp.pad(node, (0, pad), constant_values=n_nodes)  # no-op slot
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
    nblk = bins.shape[0] // block_n
    kernel = functools.partial(_nh_kernel, n_nodes=n_nodes, n_feat=F,
                               width=width)
    hg, hh = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((block_n, F), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((F * n_nodes, width), lambda i: (0, 0)),
                   pl.BlockSpec((F * n_nodes, width), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((F * n_nodes, width), jnp.float32),
                   jax.ShapeDtypeStruct((F * n_nodes, width), jnp.float32)),
        interpret=interpret,
    )(bins.astype(jnp.int32), node.astype(jnp.int32)[:, None],
      g.astype(jnp.float32)[:, None], h.astype(jnp.float32)[:, None])
    return (hg.reshape(F, n_nodes, width)[..., :n_bins],
            hh.reshape(F, n_nodes, width)[..., :n_bins])


def timed(fn, *args, iters=10, label=""):
    """Chained fori_loop: data-dependent iterations, one scalar sync."""
    @jax.jit
    def loop(args_, salt):
        def body(i, carry):
            s, = carry
            # salt the grad so no iteration can be CSE'd away
            out = fn(*args_[:-1], args_[-1] + s * 1e-30)
            s2 = jax.tree_util.tree_reduce(
                lambda acc, x: acc + x.astype(jnp.float32).sum(), out, 0.0)
            return (s2 * 1e-30,)
        return jax.lax.fori_loop(0, iters, body, (salt,))[0]

    r = float(loop(args, jnp.float32(0.0)))  # compile+warm
    t0 = time.perf_counter()
    r = float(loop(args, jnp.float32(r)))
    dt = (time.perf_counter() - t0) / iters
    print(f"{label:48s} {dt*1e3:9.2f} ms/call")
    return dt


def main():
    import os
    aux_only = os.environ.get("PROBE_AUX_ONLY") == "1"
    N, F = 1_000_000, 28
    rng = np.random.default_rng(0)
    bins_np = rng.integers(0, 256, (N, F), dtype=np.uint8)
    g_np = rng.normal(size=N).astype(np.float32)
    h_np = rng.random(N).astype(np.float32)

    bins_u8 = jnp.asarray(bins_np)
    bins_i32 = jnp.asarray(bins_np.astype(np.int32))
    g = jnp.asarray(g_np)
    h = jnp.asarray(h_np)

    import sys
    sys.path.insert(0, "/root/repo")
    from mmlspark_tpu.ops.pallas_kernels import (compare_reduce_histogram,
                                                 segment_histogram)

    for n_nodes in () if aux_only else (1, 2, 16):
        node_np = rng.integers(0, n_nodes, N, dtype=np.int32)
        node = jnp.asarray(node_np)

        # correctness vs segment (reference)
        comb = node[:, None] * 256 + bins_i32
        ref_g, ref_h = segment_histogram(comb, g, h, n_bins=n_nodes * 256)
        ref_g = ref_g.reshape(F, n_nodes, 256)
        hg, hh = node_histogram(bins_i32, node, g, h, n_nodes=n_nodes)
        err = float(jnp.max(jnp.abs(hg - ref_g)))
        rel = err / float(jnp.max(jnp.abs(ref_g)))
        print(f"n_nodes={n_nodes}: max abs err {err:.3e} rel {rel:.3e}")

        timed(lambda b, nd, gg: node_histogram(b, nd, gg, h,
                                               n_nodes=n_nodes),
              bins_i32, node, g,
              label=f"mxu node_histogram n_nodes={n_nodes}")
        timed(lambda c, gg: segment_histogram(c, gg, h,
                                              n_bins=n_nodes * 256),
              comb, g, label=f"segment_sum ids={n_nodes*256}")
        if n_nodes == 1:
            timed(lambda b, gg: compare_reduce_histogram(b, gg, h,
                                                         n_bins=256),
                  bins_u8, g, label="compare_reduce ids=256")

    # block sweep for the best n_nodes=16 config
    node = jnp.asarray(rng.integers(0, 16, N, dtype=np.int32))
    for bn in () if aux_only else (1024, 2048, 4096, 8192):
        try:
            timed(lambda b, nd, gg: node_histogram(b, nd, gg, h, n_nodes=16,
                                                   block_n=bn),
                  bins_i32, node, g, label=f"mxu n=16 block_n={bn}")
        except Exception as e:
            print(f"block_n={bn}: {type(e).__name__}: {str(e)[:120]}")

    # aux op costs at 1M (last arg is the salted f32 array)
    timed(lambda b, nd, gg: (jnp.take_along_axis(
        b, (nd + gg[:1].astype(jnp.int32))[:, None] % F, axis=1)[:, 0]
        > 128,),
          bins_i32, node, g, label="routing gather take_along_axis")

    def route_cols(b, nd, gg):
        # per-node column compare: (n, n_nodes) matrix then select by node
        cols = jnp.stack([b[:, k % F] for k in range(16)], axis=1)
        thr = gg[:16].astype(jnp.int32)
        m = cols > thr[None, :]
        return (jnp.take_along_axis(m, nd[:, None] % 16, axis=1)[:, 0],)
    timed(route_cols, bins_i32, node, g,
          label="routing via 16 column compares")
    leaf_tbl = jnp.asarray(rng.normal(size=32).astype(np.float32))

    def leaf_sums_onehot(nd, gg):
        oh = (nd[:, None] == jnp.arange(32)).astype(jnp.float32)
        return (oh.T @ gg[:, None],)
    timed(leaf_sums_onehot, node, g, label="leaf sums one-hot matmul (32)")
    timed(lambda nd, gg: (jax.ops.segment_sum(gg, nd, num_segments=32),),
          node, g, label="leaf sums segment_sum (32)")
    timed(lambda nd, gg: (leaf_tbl[nd] * gg,), node, g,
          label="leaf gather leaf[node]")
    timed(lambda nd, gg: (jnp.nonzero(nd < 8, size=N // 2,
                                      fill_value=N)[0].astype(jnp.float32)
                          + gg[0],),
          node, g, label="nonzero(size=n/2) compaction index")
    # 10M-scale check of the kernel (linearity)
    N2 = 10_000_000
    bins2 = jnp.asarray(rng.integers(0, 256, (N2, F), dtype=np.uint8)
                        .astype(np.int32))
    node2 = jnp.asarray(rng.integers(0, 16, N2, dtype=np.int32))
    g2 = jnp.asarray(rng.normal(size=N2).astype(np.float32))
    h2 = jnp.asarray(rng.random(N2).astype(np.float32))
    timed(lambda b, nd, gg: node_histogram(b, nd, gg, h2, n_nodes=16),
          bins2, node2, g2, iters=5, label="mxu n_nodes=16 @ 10M")
    timed(lambda c, gg: segment_histogram(c, gg, h2, n_bins=16 * 256),
          node2[:, None] * 256 + bins2, g2, iters=3,
          label="segment ids=4096 @ 10M")


if __name__ == "__main__":
    main()
