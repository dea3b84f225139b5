"""Sequence / context parallelism: ring attention, Ulysses, blockwise attention.

The reference has NO long-context story (SURVEY.md §5: "Long-context /
sequence parallelism: absent" — its only sequence model is a pre-trained
BiLSTM evaluated via CNTKModel, notebook 304, and sequence length never
exceeds one host). This module designs it in from the start, TPU-first, so
the attention path scales past single-chip HBM:

  * ``blockwise_attention`` — single-device memory-efficient attention:
    online-softmax over KV blocks via ``lax.scan`` (FlashAttention recurrence)
    so the (T, T) score matrix is never materialized. O(T) memory in sequence
    length instead of O(T^2).
  * ``ring_attention`` — context parallelism over a mesh axis: Q/K/V are
    sequence-sharded; KV shards rotate around the ICI ring via
    ``lax.ppermute`` while each device accumulates online-softmax partial
    results for its resident queries. Compute overlaps the neighbor exchange;
    memory per chip stays O(T / sp).
  * ``ulysses_attention`` — all-to-all sequence parallelism: two
    ``lax.all_to_all`` collectives re-shard (seq-sharded, all heads) ->
    (head-sharded, full seq), run dense local attention per head group, and
    re-shard back. Cheaper than ring when head count >= sp and ICI all-to-all
    bandwidth is plentiful.
  * ``make_sp_attention`` — wraps either collective form in ``shard_map`` over
    a named mesh axis, yielding a plain ``(q, k, v) -> o`` callable usable
    inside any flax module under ``jit``.

All collective math runs in float32 for the softmax statistics with bfloat16
matmul inputs (MXU-native). Shapes are static; the scan carries are
fixed-shape — everything XLA needs to pipeline DMA against compute.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _attend_block(q, k, v, qpos, kpos, causal: bool, scale: float,
                  kv_valid_below=None):
    """One (Q-resident, KV-block) attention step: returns (out_unnorm, m, l).

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); qpos: (Tq,), kpos: (Tk,) global
    positions for causal masking; kv_valid_below masks padded KV rows
    (kpos >= bound). Scores in float32.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]          # (Tq, Tk)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_valid_below is not None:
        scores = jnp.where((kpos < kv_valid_below)[None, None, None, :],
                           scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                        # (B, H, Tq)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)  # all-masked row -> 0
    l = jnp.sum(p, axis=-1)                             # (B, H, Tq)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, m, l


def _online_merge(acc, m_acc, l_acc, out, m, l):
    """Merge a new block's (out, m, l) into the running (acc, m_acc, l_acc)
    via the numerically-stable online-softmax recurrence."""
    m_new = jnp.maximum(m_acc, m)
    corr_old = jnp.exp(m_acc - m_new)
    corr_new = jnp.exp(m - m_new)
    corr_old = jnp.where(m_acc <= NEG_INF / 2, 0.0, corr_old)
    corr_new = jnp.where(m <= NEG_INF / 2, 0.0, corr_new)
    l_new = l_acc * corr_old + l * corr_new
    acc_new = (acc * corr_old[..., None].transpose(0, 2, 1, 3)
               + out * corr_new[..., None].transpose(0, 2, 1, 3))
    return acc_new, m_new, l_new


def _finalize(acc, l):
    """acc: (B, Tq, H, D) unnormalized, l: (B, H, Tq) -> normalized output."""
    denom = l[..., None].transpose(0, 2, 1, 3)          # (B, Tq, H, 1)
    return acc / jnp.maximum(denom, 1e-30)


def blockwise_attention(q, k, v, block_size: int = 512,
                        causal: bool = False,
                        scale: Optional[float] = None):
    """Memory-efficient single-device attention (FlashAttention recurrence).

    q/k/v: (B, T, H, D). Scans over KV blocks with an online softmax so peak
    memory is O(B*H*Tq*block) instead of O(B*H*Tq*Tk). This is also the local
    kernel both SP forms call per shard.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_size = min(block_size, Tk)
    if Tk % block_size != 0:         # pad KV to a block multiple, mask pads
        pad = block_size - Tk % block_size
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = k.shape[1] // block_size
    qpos = jnp.arange(Tq)
    kb = k.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)

    def step(carry, blk):
        acc, m_acc, l_acc = carry
        k_i, v_i, i = blk
        kpos = i * block_size + jnp.arange(block_size)
        out, m, l = _attend_block(q, k_i, v_i, qpos, kpos, causal=causal,
                                  scale=scale, kv_valid_below=Tk)
        return _online_merge(acc, m_acc, l_acc, out, m, l), None

    init = (jnp.zeros((B, Tq, H, D), jnp.float32),
            jnp.full((B, H, Tq), NEG_INF, jnp.float32),
            jnp.zeros((B, H, Tq), jnp.float32))
    (acc, m_acc, l_acc), _ = lax.scan(step, init,
                                      (kb, vb, jnp.arange(n_blocks)))
    return _finalize(acc, l_acc).astype(q.dtype)


# --------------------------------------------------------------- ring

def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Context-parallel attention over a mesh axis (call inside shard_map).

    Per device: q/k/v are the LOCAL sequence shard (B, T/sp, H, D). KV shards
    rotate around the ring with ``lax.ppermute`` (neighbor-only traffic —
    rides ICI links); each device folds every visiting KV block into its
    queries' online softmax. Global positions derived from the axis index
    keep causal masking exact across shards.

    Design: Ring Attention (Liu et al.) re-expressed as an XLA-collective
    scan — no NCCL/MPI analog needed (the reference's only rings are the
    LightGBM socket ring TrainUtils.scala:141 and the MPI ring
    CommandBuilders.scala:241, both CPU-side; here the ring IS the compute).
    """
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qpos = idx * Tq + jnp.arange(Tq)
    perm = [(i, (i - 1) % sp) for i in range(sp)]   # shard s visits blocks
                                                    # s, s+1, ... (mod sp)

    # fold the resident block first, then sp-1 exchange+fold rounds — the
    # last round must not pay a ppermute whose result nobody reads
    out0, m0, l0 = _attend_block(q, k, v, qpos, idx * Tk + jnp.arange(Tk),
                                 causal=causal, scale=scale)
    acc0, macc0, lacc0 = _online_merge(
        jnp.zeros((B, Tq, H, D), jnp.float32),
        jnp.full((B, H, Tq), NEG_INF, jnp.float32),
        jnp.zeros((B, H, Tq), jnp.float32), out0, m0, l0)

    def step(carry, s):
        acc, m_acc, l_acc, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        src = (idx + s) % sp                        # owner of the block we hold
        kpos = src * Tk + jnp.arange(Tk)
        out, m, l = _attend_block(q, k_cur, v_cur, qpos, kpos,
                                  causal=causal, scale=scale)
        acc, m_acc, l_acc = _online_merge(acc, m_acc, l_acc, out, m, l)
        return (acc, m_acc, l_acc, k_cur, v_cur), None

    if sp > 1:
        (acc, m_acc, l_acc, _, _), _ = lax.scan(
            step, (acc0, macc0, lacc0, k, v), jnp.arange(1, sp))
    else:
        acc, l_acc = acc0, lacc0
    return _finalize(acc, l_acc).astype(q.dtype)


# --------------------------------------------------------------- ulysses

def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None,
                      block_size: int = 512):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses form), inside
    shard_map. Inputs are sequence-sharded (B, T/sp, H, D) with full heads;
    two ``lax.all_to_all`` re-shard to (B, T, H/sp, D) — full sequence,
    head-sharded — where dense local attention runs, then back. Requires
    H % sp == 0."""
    sp = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % sp != 0:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp ({sp})")
    # (B, T/sp, H, D) -> (B, T, H/sp, D): split heads, concat sequence
    def fwd(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    def bwd(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
    qg, kg, vg = fwd(q), fwd(k), fwd(v)
    out = blockwise_attention(qg, kg, vg, block_size=block_size,
                              causal=causal, scale=scale)
    return bwd(out)


# --------------------------------------------------------------- shard_map

def make_sp_attention(mesh: Mesh, axis_name: str = "seq",
                      mode: str = "ring", causal: bool = False,
                      batch_axis: Optional[str] = "data"):
    """Build a plain ``(q, k, v) -> o`` attention callable that is sequence-
    parallel over ``axis_name`` (and batch-parallel over ``batch_axis`` when
    present in the mesh). Usable directly inside flax modules under jit —
    shard_map handles the collective placement; XLA overlaps the ppermutes
    with the per-block einsums.

    Inputs/outputs are GLOBAL (B, T, H, D); the sequence dim is sharded over
    ``axis_name`` inside."""
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}")
    b = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    spec = P(b, axis_name, None, None)

    if mode == "ring":
        local = functools.partial(ring_attention, axis_name=axis_name,
                                  causal=causal)
    elif mode == "ulysses":
        local = functools.partial(ulysses_attention, axis_name=axis_name,
                                  causal=causal)
    else:
        raise ValueError(f"unknown sp mode {mode!r} (ring|ulysses)")

    def attn(q, k, v):
        return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    return attn


def plain_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Dense reference attention (for tests and tiny sequences)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
