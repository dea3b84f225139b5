"""Chunked scan of the gated delta rule with a decay per channel.

Per head the state S (K x V) follows, token by token,

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = scale * S_t^T q_t

with a decay a_t in (0, 1]^K per channel and a step b_t in [0, 1] per head
(Kimi Delta Attention, arXiv:2510.26692; with a_t one number a head it is
the gated delta rule). `chunked_delta_rule` computes the same outputs a chunk
of C tokens at a time, in plain `jax.numpy` under one `lax.scan`:

Write g_t = log a_t, G_t the running sum of g inside the chunk and
u_t = b_t (v_t - S_{t-1}^T (a_t * k_t)), so that S_t = Diag(a_t) S_{t-1} +
k_t u_t^T. Unrolled from the chunk's first state S_0,

    A_ts = sum_c k_tc k_sc exp(G_tc - G_sc)        (s < t)
    P_ts = sum_c q_tc k_sc exp(G_tc - G_sc)        (s <= t)
    (I + Diag(b) A) U = Diag(b) (V - (K * exp G) S_0)     unit lower triangular,
                                                          solved by blocks
    O    = scale * ((Q * exp G) S_0 + P U)
    S_C  = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

Every exponent is a difference G_t - G_s with s <= t, so it is <= 0 whatever
the decay: nothing is divided by a decayed quantity (the usual k / exp(G)
overflows float32 within one chunk once a channel decays by more than e^-88).
Taken literally that is a (C, C, K) tensor of exponentials a chunk a head,
vector work. It is built only inside the diagonal blocks of SOLVE_BLOCK rows.
For a row block i and every row s before it, with R_i the cumulative
log-decay at the last token before the block,

    exp(G_t - G_s) = exp(G_t - R_i) * exp(R_i - G_s)     G_t <= R_i <= G_s

so both exponents are still <= 0, a factor underflows only where the product
is below float32's range anyway, and A and P left of the diagonal blocks are
(k_i * exp(G_i - R_i)) (k_<i * exp(R_i - G_<i))^T and the same with q_i:
products over the channels, in float32 (`highest`) as the sums they replace.
At C 64 that leaves four (16, 16, K) tensors of the (64, 64, K) one. A chunk
that SOLVE_BLOCK does not divide, or no longer than it, is one diagonal
block. Each chunk's tensors are rebuilt in the backward pass
(`jax.checkpoint` on the scan's body), so a call keeps the chunk's inputs
and one state a chunk.

The arithmetic is float32 whatever the inputs' dtype; the products with the
state and P U take the backend's default precision (one bfloat16 pass on a
TPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

_m_chunks = telemetry.registry.counter(
    "mmlspark_kda_chunks_total",
    "chunks scanned by the chunked delta rule, summed over rows and heads, "
    "of the calls built (static in the shapes: counted at trace time)",
    labels=("layer",))
_m_decay_exps = telemetry.registry.counter(
    "mmlspark_kda_decay_exps_total",
    "exponentials exp(G_t - G_s) of the explicit (row, row, channel) decay "
    "tensors, those of the diagonal blocks, of the calls built (static in "
    "the shapes: counted at trace time)",
    labels=("layer",))


#: rows of a diagonal block of the intra-chunk system and of its decay
#: products
SOLVE_BLOCK = 16


def _block_rows(C):
    """Rows of a diagonal block of a chunk of C tokens: SOLVE_BLOCK where it
    divides the chunk, else the whole chunk as one block."""
    return SOLVE_BLOCK if C % SOLVE_BLOCK == 0 else C


def _solve_unit_lower(L, rhs):
    """(I + L)^-1 rhs for strictly lower triangular L (..., C, C), by blocks:
    a diagonal block I + N of SOLVE_BLOCK rows is inverted as the product of
    (I + (-N)^(2^i)), exact because N is nilpotent, and the blocks below it
    are eliminated by forward substitution, block row after block row. All
    of it small batched products in float32 (`highest`: they are a
    thousandth of the chunk's work): XLA's own TriangularSolve took 0.3 ms a
    chunk on the v5e, half the scan's time. The powers stay within
    SOLVE_BLOCK, so their entries are bounded by C(15, 7) however alike the
    keys are; a whole chunk's powers would not be."""
    C = L.shape[-1]
    s = _block_rows(C)
    n = C // s
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    batch = L.shape[:-2]
    Lb = L.reshape(batch + (n, s, n, s))
    eye = jnp.eye(s, dtype=L.dtype)
    P = -jnp.stack([Lb[..., i, :, i, :] for i in range(n)], axis=-3)
    inv = eye + P
    for _ in range(max(s - 1, 1).bit_length() - 1):
        P = mm(P, P)
        inv = mm(inv, eye + P)
    R = rhs.reshape(batch + (n, s, rhs.shape[-1]))
    out = []
    for i in range(n):
        r = R[..., i, :, :]
        for j in range(i):
            r = r - mm(Lb[..., i, :, j, :], out[j])
        out.append(mm(inv[..., i, :, :], r))
    return jnp.concatenate(out, axis=-2)


def _decay_products(q, k, G):
    """Within one block of rows, (..., s, K) each: A_ts = sum_c k_tc k_sc
    exp(G_tc - G_sc) and P_ts the same with q_t, for s <= t and 0 above the
    diagonal, (..., s, s) each, through the explicit (..., s, s, K) tensor
    of exponentials."""
    s = q.shape[-2]
    lower = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(lower[:, :, None],
                              G[..., :, None, :] - G[..., None, :, :],
                              -jnp.inf))
    kd = k[..., None, :, :] * decay
    return (jnp.sum(k[..., :, None, :] * kd, axis=-1),
            jnp.sum(q[..., :, None, :] * kd, axis=-1))


def _blocked_decay_products(q, k, G, s):
    """`_decay_products` over a chunk of n = C / s blocks of s rows: the
    explicit tensor only inside the n diagonal blocks; the blocks left of
    the diagonal as products over the channels, each row block's two
    factors taken about R, the cumulative log-decay at the last token
    before it, so that both exponents are <= 0. A row block is put together
    by `concatenate` with its zeros: `jnp.pad` in their place took 0.5 ms
    more a forward loop on the v5e."""
    B, H, C, K = q.shape
    n = C // s
    mm = functools.partial(jnp.einsum, "bhtc,bhsc->bhts",
                           precision=lax.Precision.HIGHEST)
    qb, kb, Gb = (a.reshape(B, H, n, s, K) for a in (q, k, G))
    Ad, Pd = _decay_products(qb, kb, Gb)
    A_rows, P_rows = [], []
    for i in range(n):
        left = []
        if i:
            R = Gb[:, :, i - 1, -1:, :]
            w = jnp.exp(Gb[:, :, i] - R)
            qk = jnp.concatenate([qb[:, :, i] * w, kb[:, :, i] * w], axis=2)
            left = [mm(qk, k[:, :, :i * s] * jnp.exp(R - G[:, :, :i * s]))]
        right = [jnp.zeros((B, H, s, C - (i + 1) * s), q.dtype)]
        P_rows.append(jnp.concatenate(
            [a[:, :, :s] for a in left] + [Pd[:, :, i]] + right, axis=-1))
        A_rows.append(jnp.concatenate(
            [a[:, :, s:] for a in left] + [Ad[:, :, i]] + right, axis=-1))
    return (jnp.concatenate(A_rows, axis=2), jnp.concatenate(P_rows, axis=2))


@functools.partial(jax.jit, static_argnames="scale")
def _chunk_step(S, xs, *, scale):
    """One chunk: (state, (q, k, v, g, b)) -> (new state, outputs).
    q, k, g: (B, H, C, K); v: (B, H, C, V); b: (B, H, C); S: (B, H, K, V).
    Under `jit` so that a step program traces the body once for all its
    layers and passes: the unrolled block rows otherwise cost a second and
    a half of Python a step program (XLA inlines the call)."""
    q, k, v, g, b = xs
    C = q.shape[2]
    G = jnp.cumsum(g, axis=2)
    s = _block_rows(C)
    A, P = (_decay_products(q, k, G) if s == C
            else _blocked_decay_products(q, k, G, s))
    A = A * jnp.tril(jnp.ones((C, C), A.dtype), -1)
    eG = jnp.exp(G)
    rhs = b[..., None] * (v - jnp.einsum("bhck,bhkv->bhcv", k * eG, S))
    U = _solve_unit_lower(b[..., None] * A, rhs)
    o = scale * (jnp.einsum("bhck,bhkv->bhcv", q * eG, S)
                 + jnp.einsum("bhts,bhsv->bhtv", P, U))
    G_end = G[:, :, -1:, :]
    S = (jnp.exp(G_end).transpose(0, 1, 3, 2) * S
         + jnp.einsum("bhck,bhcv->bhkv", k * jnp.exp(G_end - G), U))
    return S, o


def chunked_delta_rule(q, k, v, g, beta, chunk: int = 64,
                       scale: Optional[float] = None,
                       layer: str = ""):
    """q, k, g: (B, T, H, K); v: (B, T, H, V); beta: (B, T, H) -> (B, T, H, V)
    in float32. `g` is the log of the decay (<= 0). T is padded to a multiple
    of `chunk` with tokens that leave the state as it is (k = v = 0, b = 0,
    g = 0); `layer` labels the counters."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    scale = K ** -0.5 if scale is None else scale
    C = min(chunk, T)
    pad = -T % C
    nc = (T + pad) // C
    _m_chunks.labels(layer=layer).inc(B * H * nc)
    _m_decay_exps.labels(layer=layer).inc(B * H * nc * C * _block_rows(C) * K)

    def chunks(a):
        a = a.astype(jnp.float32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, nc, C) + a.shape[2:])
        # (nc, B, H, C, ...): the scan walks the chunks
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    step = jax.checkpoint(lambda S, xs: _chunk_step(S, xs, scale=scale))
    S0 = jnp.zeros((B, H, K, V), jnp.float32)
    _, o = lax.scan(step, S0, tuple(chunks(a) for a in (q, k, v, g, beta)))
    # (nc, B, H, C, V) -> (B, T, H, V)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)
    return o.reshape(B, nc * C, H, V)[:, :T]
