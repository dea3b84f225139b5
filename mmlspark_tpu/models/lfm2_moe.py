"""The `lfm2_moe` token-model family: gated short convolutions and
grouped-query attention over SwiGLU / sparse-expert MLPs, with a vocabulary
head tied to the embedding (LFM2-8B-A1B's layers, transformers' `Lfm2Moe*`).

Pre-RMSNorm residual blocks (`kimi_linear._Block`). A block's mixer follows
the published ``layer_types``, one entry a layer:

- ``conv``, the gated short convolution (`ShortConvMixer`): [B, C, u] =
  W_in z, three lane blocks of one projection; v = B * u; c_t = sum_i w_i *
  v_{t-(L-1)+i}, depthwise and causal over ``conv_L_cache`` taps with no
  activation (`kimi_linear._ShortConv`'s shifted sum without its SiLU);
  out = W_out (C * c).
- ``full_attention``, grouped-query attention (`GQALayer`): H query heads
  and Hkv key/value heads of ``head_dim``; RMSNorm on each head of q and of
  k (one scale of head_dim each); rotary over the whole head in the
  half-split form (`rotate_half_split`); causal softmax(q k^T /
  sqrt(head_dim)) v with query head h against key/value head h // (H / Hkv),
  which `flash_attention` takes as it is: K and V are handed over, copied
  and read at Hkv heads.

Its MLP is a dense SwiGLU in the ``num_dense_layers`` leading layers and
`moe.DroplessMoE` after them, with no shared expert, the family's 1e-6 in
the renormalisation and its floor of tiles (`EXPERT_FLOOR_SHARES`). The
configuration's keys are those of the model's public `config.json`; the
counts of heads, key/value heads, routed experts and vocabulary rows are
what is held *here* (one chip's share of a layer), while `router_width` stays the deployment's expert count and ``head_dim``
the published hidden_size / num_attention_heads.

Two results, as `joyai_llm_flash` has them. Without ``row_losses`` the model
returns per-token logits (B, T, V) float32 over the embedding's own rows.
With ``row_losses=True`` (`TpuLearner`'s ``loss="next_token"``) it returns
one loss a row: the mean over t = 0..T-2 of
-log softmax(E RMSNorm(h_t))[id_{t+1}], walked in chunks of
``lm_loss_chunk`` positions (`joyai_llm_flash.chunked_token_losses` on the
embedding's transpose), so the embedding's gradient is the sum of its two
uses, the look-up's and the head's.

Not here: a learning-rate schedule, the expert bias's update rule (it stays
0), sliding windows, packing with segment ids, decode with a convolution
and key/value cache, experts across chips with their all-to-all.
"""

from __future__ import annotations

import functools
from fractions import Fraction
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import telemetry
from .joyai_llm_flash import (_Leaf, _m_vocab_rows, _rms_norm,
                              chunked_token_losses)
from .kimi_linear import (_Block, _dense, _ShortConv, block_mlp,
                          causal_attention, expert_step_stats, remat_block)
from .moe import MOE_STEP_STATS

#: what the model reports a step beside the expert layers' counts: the
#: batch's weighted mean loss (float32) and the positions that entered it
LM_STEP_STATS = ("lm_loss_main", "lm_tokens_scored")

#: the scope the conv mixers' gate, convolution and gate are traced under
SHORT_CONV_SCOPE = "short_conv"

#: the expert layers' floor of tiles, in uniform shares (`moe.DroplessMoE`'s
#: `floor_shares`; the field's default, `moe.GROUP_FLOOR_SHARES` = 2, is set
#: for routers of 256 outputs whose held experts swing 0.5-1.9 shares at a
#: fresh init). This family's router of 32 outputs, top 4, hardly swings: at
#: 32,768 tokens a step, 4,096 assignments a held expert, the fullest held
#: expert read 4,161-4,952 (at most 1.21 shares) and a layer's need 33-40
#: tiles of 1,024 over 12 seeds x 8 batches x 4 layers at the fresh init
#: (`tools/count_tiles_needed.py`, TPU v5e; PERF.md section 6), 4,479-4,704
#: over the cell's traced training windows. 5/4 shares, 5 tiles of 1,024 a
#: held expert and 40 a layer, stand above every expert up to 5,120
#: assignments, so the walk's length stays the same from step to step; the
#: result does not depend on it
EXPERT_FLOOR_SHARES = Fraction(5, 4)

_m_conv_mixers = telemetry.registry.counter(
    "mmlspark_short_conv_mixers_total",
    "gated short-convolution mixers built, by the taps of their depthwise "
    "kernel (static in the configuration: counted at trace time)",
    labels=("kernel_size",))


def rotate_half_split(x, theta):
    """Rotary position embedding over the whole last dimension in the
    half-split form: x is (B, T, ..., D), position t = 0..T-1 along axis 1;
    lane i < D/2 and lane i + D/2 are the pair turned by the angle
    t * theta^(-2i / D): x cos + rotate_half(x) sin with rotate_half(x) =
    [-x[D/2:], x[:D/2]]. Angles, sines and the product are float32; the
    result has x's dtype."""
    T, D = x.shape[1], x.shape[-1]
    f32 = jnp.float32
    inv = theta ** (-(jnp.arange(D) % (D // 2) * 2).astype(f32) / D)
    ang = (jnp.arange(T, dtype=f32)[:, None] * inv).reshape(
        (T,) + (1,) * (x.ndim - 3) + (D,))
    # rotate_half as a roll by half the width under a sign
    sign = jnp.where(jnp.arange(D) < D // 2, -1.0, 1.0).astype(f32)
    x32 = x.astype(f32)
    return (x32 * jnp.cos(ang) + jnp.roll(x32, D // 2, axis=-1)
            * (sign * jnp.sin(ang))).astype(x.dtype)


class ShortConvMixer(nn.Module):
    """The gated short convolution: (B, T, d) -> (B, T, d) (module
    docstring). What lies between the two projections runs under the scope
    `SHORT_CONV_SCOPE`."""
    conv_size: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        _m_conv_mixers.labels(kernel_size=str(self.conv_size)).inc()
        bcu = _dense(3 * d, self.dtype, "in_proj")(x)
        with jax.named_scope(SHORT_CONV_SCOPE):
            gate_in, gate_out, u = (bcu[..., i * d:(i + 1) * d]
                                    for i in range(3))
            y = gate_out * _ShortConv(self.conv_size, None,
                                      name="conv")(gate_in * u)
        return _dense(d, self.dtype, "out_proj")(y)


class GQALayer(nn.Module):
    """Grouped-query attention over the heads held here: (B, T, d) ->
    (B, T, d) (module docstring). `attention` takes q (B, T, H, D) and k, v
    (B, T, Hkv, D) as they are."""
    heads: int
    kv_heads: int
    head_dim: int
    attention: Any          # (q, k, v, scale) -> o (B, T, H, D)
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        H, Hkv, D = self.heads, self.kv_heads, self.head_dim
        if H % Hkv:
            raise ValueError(f"{H} query heads do not share {Hkv} key/value "
                             "heads evenly")

        def heads(name, n):
            return _dense(n * D, self.dtype, f"{name}_proj")(x).reshape(
                B, T, n, D)

        def normed_rotated(a, name):
            a = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                           name=f"{name}_norm")(a)
            return rotate_half_split(a, self.rope_theta)

        q = normed_rotated(heads("q", H), "q")
        k = normed_rotated(heads("k", Hkv), "k")
        o = self.attention(q, k, heads("v", Hkv), D ** -0.5)
        return _dense(d, self.dtype, "o_proj")(o.reshape(B, T, H * D))


class Lfm2MoeModel(nn.Module):
    """Token ids (B, T) -> per-token logits (B, T, V) float32, or with
    ``row_losses=True`` the rows' losses (B,) (module docstring).
    ``step_stats=True`` also returns {name: scalar}: the expert layers'
    counts (`moe.MOE_STEP_STATS`) and, with ``row_losses``,
    `LM_STEP_STATS`."""
    vocab_size: int
    hidden_size: int
    layer_kinds: Sequence[str]       # "conv" | "full_attention", one a layer
    dense_layers: int                # leading layers with a dense MLP
    heads: int
    kv_heads: int
    head_dim: int = 64
    conv_size: int = 3
    rope_theta: float = 1e6
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 8
    first_expert: int = 0
    router_width: int = 32
    top_k: int = 4
    num_shared: int = 0
    renormalize: bool = True
    routed_scale: float = 1.0
    renorm_eps: float = 1e-6
    eps: float = 1e-5
    lm_loss_chunk: int = 512
    remat: bool = False
    attn_impl: str = "auto"        # auto | blockwise | flash (Pallas kernel)
    block_size: int = 512
    dtype: Any = jnp.bfloat16

    #: what ``step_stats=True`` returns beside the losses (the trainer asks
    #: for them in a fit that started with telemetry on)
    step_stat_names = MOE_STEP_STATS + LM_STEP_STATS
    #: the family computes ``loss="next_token"``'s row losses itself
    has_lm_head = True

    def layer_names(self):
        return (["embed"] + [f"block{i}" for i in range(len(self.layer_kinds))]
                + ["logits"])

    def _mixer(self, kind):
        if kind == "conv":
            return functools.partial(ShortConvMixer, self.conv_size,
                                     self.dtype)
        if kind == "full_attention":
            return functools.partial(
                GQALayer, self.heads, self.kv_heads, self.head_dim,
                causal_attention(self.attn_impl, self.block_size),
                self.rope_theta, self.eps, self.dtype)
        raise ValueError("layer kind must be 'conv' or 'full_attention', "
                         f"got {kind!r}")

    def _mlp(self, dense):
        mlp = block_mlp(self, dense)
        return mlp if dense else functools.partial(
            mlp, renorm_eps=self.renorm_eps,
            floor_shares=EXPERT_FLOOR_SHARES)

    @nn.compact
    def __call__(self, tokens, output_layer: Optional[str] = None,
                 row_mask=None, step_stats: bool = False,
                 row_losses: bool = False):
        from .modules import _LayerTap
        tap = _LayerTap(output_layer)
        B, T = tokens.shape
        V, d = self.vocab_size, self.hidden_size
        embed = nn.Embed(V, d, dtype=self.dtype, name="embed")
        x = tap.tap("embed", embed(tokens))
        if tap.done:
            return tap.result.astype(jnp.float32)
        Block = remat_block() if self.remat else _Block
        stats = []
        for i, kind in enumerate(self.layer_kinds):
            blk = Block(self._mixer(kind), self._mlp(i < self.dense_layers),
                        self.eps, self.dtype, name=f"block{i}")
            x, s = blk(x, row_mask)
            stats.append(s)
            x = tap.tap(f"block{i}", x)
            if tap.done:
                return tap.result.astype(jnp.float32)
        scale = _Leaf((d,), nn.initializers.ones, "scale", name="norm")()
        # the tied head: the embedding's own rows, (V, d) -> (d, V)
        kernel = embed.embedding.T
        _m_vocab_rows.inc(V)

        def with_stats(result, **more):
            if not step_stats:
                return result
            return result, dict(expert_step_stats(stats), **more)

        if not row_losses:
            z = jnp.dot(_rms_norm(x, scale, self.eps, self.dtype),
                        kernel.astype(self.dtype),
                        preferred_element_type=jnp.float32)
            return with_stats(tap.tap("logits", z))

        if T < 2:
            raise ValueError(f"rows of {T} ids leave no position to score")
        main = chunked_token_losses(
            x, scale, kernel, jnp.roll(tokens, -1, axis=1),
            jnp.arange(T) < T - 1, eps=self.eps, chunk=self.lm_loss_chunk,
            dtype=self.dtype, head="main") / (T - 1)
        w = (jnp.ones((B,), jnp.float32) if row_mask is None
             else row_mask.astype(jnp.float32))
        return with_stats(
            main,
            lm_loss_main=jnp.sum(main * w) / jnp.maximum(jnp.sum(w), 1.0),
            lm_tokens_scored=jnp.sum(w > 0, dtype=jnp.int32) * (T - 1))


def build(cfg: dict) -> Lfm2MoeModel:
    """The model from the keys of the public `config.json` (counts are what
    is held here; see the module's docstring)."""
    want = {"conv_bias": False, "use_expert_bias": True,
            "tie_word_embeddings": True}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"lfm2_moe: {key} must be {value!r}, got "
                             f"{cfg[key]!r}")
    layers = cfg.get("num_hidden_layers", 2)
    kinds = tuple(cfg.get("layer_types",
                          ("conv",) * (layers - 1) + ("full_attention",)))
    if len(kinds) != layers:
        raise ValueError(f"lfm2_moe: layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers {layers}")
    heads = cfg.get("num_attention_heads", 4)
    hidden = cfg.get("hidden_size", 64)
    experts = cfg.get("num_experts", 8)
    return Lfm2MoeModel(
        vocab_size=cfg.get("vocab_size", 1024),
        hidden_size=hidden,
        layer_kinds=kinds,
        dense_layers=cfg.get("num_dense_layers", 1),
        heads=heads,
        kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim", hidden // heads),
        conv_size=cfg.get("conv_L_cache", 3),
        rope_theta=float(cfg.get("rope_theta", 1e6)),
        intermediate_size=cfg.get("intermediate_size", 256),
        moe_intermediate_size=cfg.get("moe_intermediate_size", 64),
        num_experts=experts,
        first_expert=cfg.get("first_expert_held", 0),
        router_width=cfg.get("router_width", experts),
        top_k=cfg.get("num_experts_per_tok", 4),
        renormalize=cfg.get("norm_topk_prob", True),
        routed_scale=cfg.get("routed_scaling_factor", 1.0),
        eps=cfg.get("norm_eps", 1e-5),
        lm_loss_chunk=cfg.get("lm_loss_chunk", 512),
        remat=cfg.get("remat", False),
        attn_impl=cfg.get("attn_impl", "auto"),
        block_size=cfg.get("block_size", 512),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)))
