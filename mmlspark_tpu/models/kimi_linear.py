"""The `kimi_linear` token-model family: a hybrid of delta-rule linear
attention and NoPE latent attention over SwiGLU / sparse-expert MLPs.

Pre-RMSNorm residual blocks. A block's mixer is Kimi Delta Attention
(`KDALayer`, arXiv:2510.26692) or multi-head latent attention without
rotary (`MLALayer`), by the published lists of layer numbers; its MLP is a
dense SwiGLU in the leading layers and a dropless, share-aware expert layer
(`moe.DroplessMoE`) after them. No position embedding anywhere: KDA's decay
carries position and the latent layers are NoPE. The configuration's keys
are those of the model's public `config.json`; the counts of heads, routed
experts and vocabulary rows are what is held *here* (one rank's share of a
layer), while `router_width` stays the deployment's expert count.

`MLALayer`, `_Block`, `remat_block` and `causal_attention` also serve the
`joyai_llm_flash` family (models/joyai_llm_flash.py), whose latent layers
rotate the split part of q and k (`rotate_pairs`) behind a query bottleneck:
two static fields of `MLALayer` that this family's build leaves unset.

Not here (the sibling family has them): the vocabulary projection with a
per-token loss (this family's head is the mean over positions, a final
RMSNorm and a linear classifier, as the `transformer` family's), rotary
(``mla_use_nope`` must be true) and a query bottleneck (``q_lora_rank`` must
be null). In neither: a learning-rate schedule.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..ops.delta_rule import chunked_delta_rule
from .moe import MOE_STEP_STATS, DroplessMoE, SwiGLU

_m_rotary_layers = telemetry.registry.counter(
    "mmlspark_mla_rotary_layers_total",
    "latent attention layers built with rotary on the split part of q and k "
    "(static in the configuration: counted at trace time)")


def _dense(features, dtype, name):
    """A projection without bias."""
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def _a_log_init(key, shape, dtype=jnp.float32):
    # log of a decay rate drawn uniformly from [1, 16): the public
    # implementation's (flash-linear-attention's KDA)
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # the inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(key, shape, dtype=jnp.float32):
    # uniform in +-1/sqrt(kernel size), a depthwise Conv1d's default
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class _ShortConv(nn.Module):
    """Causal depthwise convolution over time, then `activation` (SiLU, as
    KDA's q, k, v take it; None for none, as the `lfm2_moe` family's gated
    mixer takes it): y_t = act(sum_i w_i x_{t-(n-1)+i})."""
    kernel_size: int
    activation: Optional[Callable] = nn.silu

    @nn.compact
    def __call__(self, x):
        n, T = self.kernel_size, x.shape[1]
        w = self.param("kernel", _conv_init, (n, x.shape[-1]), jnp.float32)
        xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
        y = sum(xp[:, i:i + T] * w[i].astype(x.dtype) for i in range(n))
        return y if self.activation is None else self.activation(y)


class KDALayer(nn.Module):
    """Kimi Delta Attention over the heads held here: (B, T, d) -> (B, T, d).

    q, k, v: a projection to heads x head_dim, a short causal convolution
    and SiLU each; q and k L2-normalised per head. A decay per channel,
    a_t = exp(-exp(A_log) * softplus(W_f2 W_f1 x + dt_bias)), through a
    bottleneck of head_dim; a step per head, b_t = sigmoid(W_b x). The state
    recurrence runs chunked (`ops.delta_rule`). The output is RMS-normalised
    per head, gated by sigmoid(W_g2 W_g1 x) and projected back."""
    heads: int
    head_dim: int
    conv_size: int = 4
    chunk: int = 64
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        H, D = self.heads, self.head_dim
        dense = functools.partial(_dense, dtype=self.dtype)

        def mixed(name):
            a = dense(H * D, name=f"{name}_proj")(x)
            a = _ShortConv(self.conv_size, name=f"{name}_conv")(a)
            return a.reshape(B, T, H, D).astype(jnp.float32)

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)

        q, k, v = unit(mixed("q")), unit(mixed("k")), mixed("v")
        f = dense(H * D, name="f_b_proj")(dense(D, name="f_a_proj")(x))
        a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H * D,), jnp.float32)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (f.astype(jnp.float32) + dt_bias).reshape(B, T, H, D))
        beta = jax.nn.sigmoid(
            dense(H, name="b_proj")(x).astype(jnp.float32))
        o = chunked_delta_rule(q, k, v, g, beta, chunk=self.chunk,
                               scale=D ** -0.5, layer="/".join(self.path))
        o = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="o_norm")(o)
        gate = dense(H * D, name="g_b_proj")(dense(D, name="g_a_proj")(x))
        o = o.reshape(B, T, H * D) * nn.sigmoid(gate)
        return dense(d, name="o_proj")(o)


def rotate_pairs(x, theta):
    """Rotary position embedding on interleaved pairs: x is (B, T, ..., D),
    position t = 0..T-1 along axis 1; the pair (x[2i], x[2i+1]) is turned by
    the angle t * theta^(-2i / D). Angles, sines and the product are float32;
    the result has x's dtype. The pair's partner comes from a product with a
    constant D x D matrix of 0 and +-1 (exact: one term a column), so no
    operand is ever viewed as (..., D / 2, 2), which the chip would tile 64
    times over."""
    T, D = x.shape[1], x.shape[-1]
    f32 = jnp.float32
    # lanes 2i and 2i + 1 share the pair's frequency theta^(-2i / D)
    inv = theta ** (-(jnp.arange(D) // 2 * 2).astype(f32) / D)
    ang = (jnp.arange(T, dtype=f32)[:, None] * inv).reshape(
        (T,) + (1,) * (x.ndim - 3) + (D,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # (x P)[2i] = -x[2i + 1], (x P)[2i + 1] = x[2i]
    i = np.arange(0, D, 2)
    swap = np.zeros((D, D), np.float32)
    swap[i + 1, i], swap[i, i + 1] = -1.0, 1.0
    x32 = x.astype(f32)
    partner = jnp.matmul(x32, swap, precision=jax.lax.Precision.HIGHEST)
    return (x32 * cos + partner * sin).astype(x.dtype)


def _lane_padded(a, width):
    """`a` with zeros after its last dimension, up to `width`."""
    if a.shape[-1] == width:
        return a
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, width - a.shape[-1]),))


class MLALayer(nn.Module):
    """Multi-head latent attention over the heads held here.

    q = W_q x (heads x (nope + rope)) or, with a query bottleneck `q_rank`,
    q = W_qb RMSNorm(W_qa x); [c, k_r] = W_kva x (kv_rank + rope), c
    RMS-normalised; [k_n, v] = W_kvb c per head; k = [k_n, k_r] with k_r
    shared by the heads. With `rope_theta` the rope-wide parts of q (per
    head) and k_r are rotated by position (`rotate_pairs`); without, nothing
    is (NoPE). Causal softmax(q k^T / sqrt(nope + rope)) v; W_o from heads x
    v_dim. `attention` takes two widths: q and k are handed over zero-padded
    to a multiple of 128 lanes (192 -> 256; zeros add nothing to a score,
    and on the chip a 192-deep product costs the passes of a 256-deep one),
    v as it is, and the result comes back v_dim wide with nothing to cut."""
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    attention: Any       # (q, k (B, T, H, Dqk), v (B, T, H, Dv), scale) -> o
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    q_rank: Optional[int] = None          # the query bottleneck's width
    rope_theta: Optional[float] = None    # None: no rotation

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        H, qk = self.heads, self.nope_dim + self.rope_dim
        dense = functools.partial(_dense, dtype=self.dtype)
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        if self.q_rank is None:
            q = dense(H * qk, name="q_proj")(x)
        else:
            c_q = norm(name="q_a_norm")(dense(self.q_rank, name="q_a_proj")(x))
            q = dense(H * qk, name="q_b_proj")(c_q)
        q = q.reshape(B, T, H, qk)
        kva = dense(self.kv_rank + self.rope_dim, name="kv_a_proj")(x)
        c = norm(name="kv_a_norm")(kva[..., :self.kv_rank])
        k_r = kva[..., self.kv_rank:]
        if self.rope_theta is not None:
            _m_rotary_layers.inc()
            q = jnp.concatenate(
                [q[..., :self.nope_dim],
                 rotate_pairs(q[..., self.nope_dim:], self.rope_theta)],
                axis=-1)
            k_r = rotate_pairs(k_r, self.rope_theta)
        kv = dense(H * (self.nope_dim + self.v_dim), name="kv_b_proj")(c)
        kv = kv.reshape(B, T, H, self.nope_dim + self.v_dim)
        k = jnp.concatenate(
            [kv[..., :self.nope_dim],
             jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, self.rope_dim))],
            axis=-1)
        v = kv[..., self.nope_dim:]
        width = -(-qk // 128) * 128
        o = self.attention(_lane_padded(q, width), _lane_padded(k, width), v,
                           qk ** -0.5)
        return dense(d, name="o_proj")(o.reshape(B, T, H * self.v_dim))


def causal_attention(attn_impl: str, block_size: int):
    """(q, k, v, scale) -> o, causal, over q (B, T, H, Dqk), k (B, T, Hkv,
    Dqk) and v (B, T, Hkv, Dv), o as wide as v with q's heads: the Pallas
    flash kernel (``flash``; ``auto`` on a TPU), which takes the two widths
    and the two head counts as they are, or the single-device blockwise
    recurrence (``blockwise``; ``auto`` elsewhere), which takes one of
    each: v is zero-padded to q's width for it (the padded columns of its
    result are cut off) and grouped k and v are repeated to q's heads."""
    def attention(q, k, v, scale):
        impl = attn_impl
        if impl == "auto":
            from ..core.env import on_tpu
            impl = "flash" if on_tpu() else "blockwise"
        if impl == "flash":
            from ..ops.pallas_kernels import flash_attention
            return flash_attention(q, k, v, causal=True, scale=scale)
        from ..parallel.sequence import blockwise_attention
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        o = blockwise_attention(q, k, _lane_padded(v, q.shape[-1]),
                                block_size=block_size, causal=True,
                                scale=scale)
        return o[..., :v.shape[-1]]
    return attention


def remat_block():
    """`_Block` rematerialised in its backward pass, all but the two
    residuals only a flash forward kernel can make (its result and the rows'
    log-sum-exp, `pallas_kernels.FLASH_RESIDUALS`): those are kept, 65 MB a
    latent block at 8 rows of 4,096 positions and 8 heads of 128, which is
    about what v, O and dO no longer padded to 256 lanes gave back, and the
    backward pass does not run `flash_fwd` a second time. On the blockwise
    path nothing carries the names and the whole block is recomputed."""
    from ..ops.pallas_kernels import FLASH_RESIDUALS
    return nn.remat(_Block, policy=jax.checkpoint_policies
                    .save_only_these_names(*FLASH_RESIDUALS))


class _Block(nn.Module):
    """x + mixer(norm(x)), then x + mlp(norm(x)): -> (x, expert stats).
    `mixer` and `mlp` build the two sub-layers (name -> module)."""
    mixer: Any
    mlp: Any
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, row_mask=None):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = x + self.mixer(name="mixer")(norm(name="input_norm")(x))
        h = norm(name="post_attention_norm")(x)
        mlp = self.mlp(name="mlp")
        if isinstance(mlp, DroplessMoE):
            h, stats = mlp(h, row_mask)
        else:
            h, stats = mlp(h), jnp.zeros((len(MOE_STEP_STATS),), jnp.int32)
        return x + h, stats


def block_mlp(model, dense: bool):
    """name -> a block's MLP, from the fields both families' models carry:
    a dense SwiGLU or the dropless expert layer."""
    if dense:
        return functools.partial(SwiGLU, model.intermediate_size, model.dtype)
    return functools.partial(
        DroplessMoE, num_experts=model.num_experts,
        router_width=model.router_width,
        d_hidden=model.moe_intermediate_size, top_k=model.top_k,
        first_expert=model.first_expert, num_shared=model.num_shared,
        renormalize=model.renormalize, routed_scale=model.routed_scale,
        dtype=model.dtype)


def expert_step_stats(stats):
    """The blocks' `MOE_STEP_STATS` rows as {name: scalar}: sums over the
    layers; the fullest expert is the maximum."""
    s = jnp.stack(stats)
    reduce = {"moe_expert_tokens_max": jnp.max}
    return {n: reduce.get(n, jnp.sum)(s[:, j])
            for j, n in enumerate(MOE_STEP_STATS)}


class KimiLinearModel(nn.Module):
    """Token ids (B, T) -> (B, num_classes) (``pool='mean'``) or per-token
    (B, T, num_classes). ``step_stats=True`` also returns the expert layers'
    per-step counts, {name: int32 scalar} over `moe.MOE_STEP_STATS` (sums
    over the layers; the fullest expert is the maximum)."""
    vocab_size: int
    hidden_size: int
    layer_kinds: Sequence[str]            # "kda" | "mla", one a layer
    dense_layers: int                     # leading layers with a dense MLP
    heads: int
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_chunk: int = 64
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 8
    first_expert: int = 0
    router_width: int = 256
    top_k: int = 8
    num_shared: int = 1
    renormalize: bool = True
    routed_scale: float = 1.0
    eps: float = 1e-5
    num_classes: int = 2
    pool: str = "mean"
    remat: bool = False
    attn_impl: str = "auto"        # auto | blockwise | flash (Pallas kernel)
    block_size: int = 512
    dtype: Any = jnp.bfloat16

    #: what ``step_stats=True`` returns beside the logits (the trainer asks
    #: for them in a fit that started with telemetry on)
    step_stat_names = MOE_STEP_STATS

    def layer_names(self):
        return (["embed"] + [f"block{i}" for i in range(len(self.layer_kinds))]
                + ["logits"])

    def _mixer(self, kind):
        if kind == "kda":
            return functools.partial(
                KDALayer, self.heads, self.kda_head_dim, self.conv_size,
                self.kda_chunk, self.eps, self.dtype)
        if kind == "mla":
            return functools.partial(
                MLALayer, self.heads, self.kv_rank, self.nope_dim,
                self.rope_dim, self.v_dim,
                causal_attention(self.attn_impl, self.block_size), self.eps,
                self.dtype)
        raise ValueError(f"layer kind must be 'kda' or 'mla', got {kind!r}")

    @nn.compact
    def __call__(self, tokens, output_layer: Optional[str] = None,
                 row_mask=None, step_stats: bool = False):
        from .modules import _LayerTap
        tap = _LayerTap(output_layer)
        x = tap.tap("embed", nn.Embed(self.vocab_size, self.hidden_size,
                                      dtype=self.dtype,
                                      name="embed")(tokens))
        if tap.done:
            return tap.result.astype(jnp.float32)
        Block = remat_block() if self.remat else _Block
        stats = []
        for i, kind in enumerate(self.layer_kinds):
            blk = Block(self._mixer(kind),
                        block_mlp(self, i < self.dense_layers), self.eps,
                        self.dtype, name=f"block{i}")
            x, s = blk(x, row_mask)
            stats.append(s)
            x = tap.tap(f"block{i}", x)
            if tap.done:
                return tap.result.astype(jnp.float32)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        if self.pool not in ("mean", "none"):
            raise ValueError(f"pool must be 'mean' or 'none', got "
                             f"{self.pool!r}")
        if self.pool == "mean":
            x = jnp.mean(x, axis=1)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype,
                                       name="head")(x))
        logits = x.astype(jnp.float32)
        if not step_stats:
            return logits
        return logits, expert_step_stats(stats)


def build(cfg: dict) -> KimiLinearModel:
    """The model from the keys of the public `config.json` (counts are what
    is held here; see the module's docstring)."""
    if not cfg.get("mla_use_nope", True):
        raise ValueError("kimi_linear: this family's latent layers are NoPE; "
                         "mla_use_nope must be true (MLALayer rotates the "
                         "split part for the joyai_llm_flash family)")
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("kimi_linear: this family has no query bottleneck; "
                         "q_lora_rank must be null (MLALayer takes one for "
                         "the joyai_llm_flash family)")
    if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid" \
            or cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("kimi_linear: the router scores by sigmoid and the "
                         "MLPs gate by silu")
    lin = cfg.get("linear_attn_config", {})
    layers = cfg.get("num_hidden_layers", 2)
    kda = set(lin.get("kda_layers", range(1, layers + 1)))
    mla = set(lin.get("full_attn_layers", ()))
    # the published lists number the layers from 1
    if kda & mla or kda | mla != set(range(1, layers + 1)):
        raise ValueError(f"kda_layers {sorted(kda)} and full_attn_layers "
                         f"{sorted(mla)} must split layers 1..{layers}")
    heads = cfg.get("num_attention_heads", 2)
    if lin.get("num_heads", heads) != heads:
        raise ValueError("kimi_linear: one count of heads held for both "
                         "kinds of layer")
    return KimiLinearModel(
        vocab_size=cfg.get("vocab_size", 1024),
        hidden_size=cfg.get("hidden_size", 64),
        layer_kinds=tuple("kda" if i in kda else "mla"
                          for i in range(1, layers + 1)),
        dense_layers=cfg.get("first_k_dense_replace", 1),
        heads=heads,
        kda_head_dim=lin.get("head_dim", 128),
        conv_size=lin.get("short_conv_kernel_size", 4),
        kda_chunk=cfg.get("kda_chunk_size", 64),
        kv_rank=cfg.get("kv_lora_rank", 512),
        nope_dim=cfg.get("qk_nope_head_dim", 128),
        rope_dim=cfg.get("qk_rope_head_dim", 64),
        v_dim=cfg.get("v_head_dim", 128),
        intermediate_size=cfg.get("intermediate_size", 256),
        moe_intermediate_size=cfg.get("moe_intermediate_size", 64),
        num_experts=cfg.get("num_experts", 8),
        first_expert=cfg.get("first_expert_held", 0),
        router_width=cfg.get("router_width", cfg.get("num_experts", 8)),
        top_k=cfg.get("num_experts_per_token", 8),
        num_shared=cfg.get("num_shared_experts", 1),
        renormalize=cfg.get("moe_renormalize", True),
        routed_scale=cfg.get("routed_scaling_factor", 1.0),
        eps=cfg.get("rms_norm_eps", 1e-5),
        num_classes=cfg.get("num_classes", 2),
        pool=cfg.get("pool", "mean"),
        remat=cfg.get("remat", False),
        attn_impl=cfg.get("attn_impl", "auto"),
        block_size=cfg.get("block_size", 512),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)))
