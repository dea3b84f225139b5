"""The `joyai_llm_flash` token-model family: rotary latent attention behind a
query bottleneck in every layer, SwiGLU / sparse-expert MLPs, an untied
vocabulary head with a per-token loss, and one multi-token-prediction module
(the layers DeepSeek-V3's report defines, arXiv:2412.19437 sections 2.1.1,
2.1.2 and 2.2).

Pre-RMSNorm residual blocks (`kimi_linear._Block`), every mixer an
`MLALayer` with ``q_rank`` and ``rope_theta`` set; a dense SwiGLU in the
leading layers and `moe.DroplessMoE` after them. The configuration's keys are
those of the model's public `config.json`; the counts of heads, routed
experts and vocabulary rows are what is held *here* (one rank's share of a
layer), while `router_width` stays the deployment's expert count.

Two results. Without ``row_losses`` the model returns per-token logits
(B, T, V) float32 over the vocabulary rows held. With ``row_losses=True``
(what `TpuLearner`'s ``loss="next_token"`` asks for) it returns one loss a
row, computed from the row's own ids: the mean over t = 0..T-2 of
-log softmax(W_head RMSNorm(h_t))[id_{t+1}], plus ``mtp_loss_weight`` times
the prediction module's mean over t = 0..T-3 of
-log softmax(W_head RMSNorm'(h'_t))[id_{t+2}], where h' is one more block on
W_eh [RMSNorm_e(Emb(id_{t+1})); RMSNorm_h(h_t)] with the embedding and the
head shared. Both heads walk chunks of ``lm_loss_chunk`` positions through
norm, projection and log-sum-exp under `remat` (`chunked_token_losses`), so
no (B * T, V) array exists in the step program. The module runs the
prediction block over all T positions with the ids rolled; the block is
causal, so a scored position never sees the wrapped one.

Not here: a learning-rate schedule, the selection bias's update rule (it
stays 0), grouped-query latent heads (the flash kernels take a key/value
head count since the `lfm2_moe` family, models/lfm2_moe.py; latent
attention has as many of either), decode with a latent cache, experts
across chips with their all-to-all, the head and loss as one kernel,
packing with segment ids. `chunked_token_losses`, `_Leaf` and `_rms_norm`
also serve the `lfm2_moe` family, whose head is its embedding's transpose.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from .kimi_linear import (MLALayer, _Block, _dense, block_mlp,
                          causal_attention, expert_step_stats, remat_block)
from .moe import MOE_STEP_STATS

#: what the model reports a step beside the expert layers' counts: the
#: batch's weighted means of the two loss terms (float32) and the positions
#: that entered the two means (int32)
LM_STEP_STATS = ("lm_loss_main", "lm_loss_mtp", "lm_tokens_scored")

_m_loss_chunks = telemetry.registry.counter(
    "mmlspark_lm_loss_chunks_total",
    "chunks of positions a vocabulary head's loss walk is built with "
    "(static in the shapes: counted at trace time)", labels=("head",))
_m_vocab_rows = telemetry.registry.counter(
    "mmlspark_lm_vocab_rows",
    "vocabulary rows held by the heads built (counted at trace time)")


class _Leaf(nn.Module):
    """One parameter as a module of its own (`name`/`leaf` in the tree, as a
    Dense's kernel or a norm's scale would be), handed back as an array: a
    walk under `lax.scan` cannot call a flax module."""
    shape: tuple
    initializer: Any
    leaf: str

    @nn.compact
    def __call__(self):
        return self.param(self.leaf, self.initializer, self.shape,
                          jnp.float32)


def _rms_norm(x, scale, eps, dtype):
    """`nn.RMSNorm`'s arithmetic on an array: statistics in float32."""
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def chunked_token_losses(h, scale, kernel, targets, scored, *, eps, chunk,
                         dtype, head="main"):
    """Per row, the sum over the scored positions of
    -log softmax(rmsnorm(h_t) @ kernel)[target_t], in float32: (B,).

    h (B, T, d); scale (d,) and kernel (d, V) float32 masters; targets
    (B, T) int32; scored (T,) or (B, T), 1 where a position counts. The walk
    is a `lax.scan` over chunks of `chunk` positions whose body (norm,
    projection, log-sum-exp, the target's logit) is rematerialised: a
    (B, chunk, V) float32 array is the most that exists, forward or backward.
    The body takes the float32 kernel and casts it, so its gradient adds up
    over the chunks in float32."""
    B, T, d = h.shape
    C = min(chunk, T)
    n = -(-T // C)
    _m_loss_chunks.labels(head=head).inc(n)
    scored = jnp.broadcast_to(scored, (B, T)).astype(jnp.float32)

    def by_chunk(a):
        a = jnp.pad(a, ((0, 0), (0, n * C - T)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, C) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def chunk_loss(hc, tc, mc, scale, kernel):
        z = jnp.dot(_rms_norm(hc, scale, eps, dtype), kernel.astype(dtype),
                    preferred_element_type=jnp.float32)         # (B, C, V)
        lse = jax.nn.logsumexp(z, axis=-1)
        hit = lax.broadcasted_iota(jnp.int32, z.shape, 2) == tc[..., None]
        picked = jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
        return jnp.sum((lse - picked) * mc, axis=1)

    def step(total, xs):
        return total + chunk_loss(*xs, scale, kernel), None

    total, _ = lax.scan(step, jnp.zeros((B,), jnp.float32),
                        (by_chunk(h), by_chunk(targets), by_chunk(scored)))
    return total


class _PredictionModule(nn.Module):
    """Multi-token prediction, depth 1: (h, e_next) -> (h', its final norm's
    scale, expert stats). h is the main stack's last block output before the
    final norm, e_next the shared embedding of the next token."""
    block: Any                     # name -> _Block
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h, e_next, row_mask=None):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = jnp.concatenate([norm(name="enorm")(e_next),
                             norm(name="hnorm")(h)], axis=-1)
        x = _dense(h.shape[-1], self.dtype, "eh_proj")(x)
        x, stats = self.block(name="block")(x, row_mask)
        scale = _Leaf((h.shape[-1],), nn.initializers.ones, "scale",
                      name="norm")()
        return x, scale, stats


class JoyAIFlashModel(nn.Module):
    """Token ids (B, T) -> per-token logits (B, T, V) float32, or with
    ``row_losses=True`` the rows' losses (B,) (module docstring).
    ``step_stats=True`` also returns {name: scalar}: the expert layers'
    counts (`moe.MOE_STEP_STATS`: sums over the layers; the fullest expert is
    the maximum) and, with ``row_losses``, `LM_STEP_STATS`."""
    vocab_size: int
    hidden_size: int
    layers: int
    dense_layers: int                     # leading layers with a dense MLP
    heads: int
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 32e6
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_experts: int = 8
    first_expert: int = 0
    router_width: int = 256
    top_k: int = 8
    num_shared: int = 1
    renormalize: bool = True
    routed_scale: float = 2.5
    eps: float = 1e-6
    mtp_layers: int = 1                   # 0 | 1 prediction modules
    mtp_loss_weight: float = 0.3
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
        return (["embed"] + [f"block{i}" for i in range(self.layers)]
                + ["logits"])

    def _mixer(self):
        return functools.partial(
            MLALayer, self.heads, self.kv_rank, self.nope_dim, self.rope_dim,
            self.v_dim, causal_attention(self.attn_impl, self.block_size),
            self.eps, self.dtype, self.q_rank, self.rope_theta)

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

        def block(dense):
            return functools.partial(Block, self._mixer(), block_mlp(self, dense),
                                     self.eps, self.dtype)

        stats = []
        for i in range(self.layers):
            x, s = block(i < self.dense_layers)(name=f"block{i}")(x, row_mask)
            stats.append(s)
            x = tap.tap(f"block{i}", x)
            if tap.done:
                return tap.result.astype(jnp.float32)
        scale = _Leaf((d,), nn.initializers.ones, "scale", name="norm")()
        kernel = _Leaf((d, V), nn.initializers.lecun_normal(), "kernel",
                       name="head")()
        _m_vocab_rows.inc(V)
        x2 = None
        if self.mtp_layers and (row_losses or self.is_initializing()):
            # position t is fed id_{t+1}; the last position wraps to id_0 and
            # is never scored
            x2, scale2, s = _PredictionModule(
                block(False), self.eps, self.dtype, name="mtp")(
                    x, embed(jnp.roll(tokens, -1, axis=1)), row_mask)
            stats.append(s)
        def with_stats(result, **more):
            if not step_stats:
                return result
            return result, dict(expert_step_stats(stats), **more)

        if not row_losses:
            z = jnp.dot(_rms_norm(x, scale, self.eps, self.dtype),
                        kernel.astype(self.dtype),
                        preferred_element_type=jnp.float32)
            return with_stats(tap.tap("logits", z))

        if T < 2 + self.mtp_layers:
            raise ValueError(f"rows of {T} ids leave no position to score")
        walk = functools.partial(chunked_token_losses, eps=self.eps,
                                 chunk=self.lm_loss_chunk, dtype=self.dtype)
        at = jnp.arange(T)
        main = walk(x, scale, kernel, jnp.roll(tokens, -1, axis=1),
                    at < T - 1, head="main") / (T - 1)
        extra, scored = jnp.zeros_like(main), T - 1
        if x2 is not None:
            extra = walk(x2, scale2, kernel, jnp.roll(tokens, -2, axis=1),
                         at < T - 2, head="mtp") / (T - 2)
            scored += T - 2
        w = (jnp.ones((B,), jnp.float32) if row_mask is None
             else row_mask.astype(jnp.float32))

        def mean(a):
            return jnp.sum(a * w) / jnp.maximum(jnp.sum(w), 1.0)

        return with_stats(
            main + self.mtp_loss_weight * extra, lm_loss_main=mean(main),
            lm_loss_mtp=mean(extra),
            lm_tokens_scored=jnp.sum(w > 0, dtype=jnp.int32) * scored)


def build(cfg: dict) -> JoyAIFlashModel:
    """The model from the keys of the public `config.json` (counts are what
    is held here; see the module's docstring)."""
    want = {"scoring_func": "sigmoid", "hidden_act": "silu",
            "rope_interleave": True, "rope_scaling": None,
            "tie_word_embeddings": False, "attention_bias": False,
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"joyai_llm_flash: {key} must be {value!r}, got "
                             f"{cfg[key]!r}")
    heads = cfg.get("num_attention_heads", 2)
    if cfg.get("num_key_value_heads", heads) != heads:
        raise ValueError("joyai_llm_flash: latent attention has as many "
                         "key/value heads as query heads")
    if cfg.get("num_nextn_predict_layers", 1) not in (0, 1):
        raise ValueError("joyai_llm_flash: num_nextn_predict_layers must be "
                         "0 or 1")
    return JoyAIFlashModel(
        vocab_size=cfg.get("vocab_size", 1024),
        hidden_size=cfg.get("hidden_size", 64),
        layers=cfg.get("num_hidden_layers", 2),
        dense_layers=cfg.get("first_k_dense_replace", 1),
        heads=heads,
        q_rank=cfg.get("q_lora_rank", 48),
        kv_rank=cfg.get("kv_lora_rank", 32),
        nope_dim=cfg.get("qk_nope_head_dim", 32),
        rope_dim=cfg.get("qk_rope_head_dim", 16),
        v_dim=cfg.get("v_head_dim", 32),
        rope_theta=float(cfg.get("rope_theta", 32e6)),
        intermediate_size=cfg.get("intermediate_size", 256),
        moe_intermediate_size=cfg.get("moe_intermediate_size", 64),
        num_experts=cfg.get("n_routed_experts", 8),
        first_expert=cfg.get("first_expert_held", 0),
        router_width=cfg.get("router_width", cfg.get("n_routed_experts", 8)),
        top_k=cfg.get("num_experts_per_tok", 8),
        num_shared=cfg.get("n_shared_experts", 1),
        renormalize=cfg.get("norm_topk_prob", True),
        routed_scale=cfg.get("routed_scaling_factor", 2.5),
        eps=cfg.get("rms_norm_eps", 1e-6),
        mtp_layers=cfg.get("num_nextn_predict_layers", 1),
        mtp_loss_weight=cfg.get("mtp_loss_weight", 0.3),
        lm_loss_chunk=cfg.get("lm_loss_chunk", 512),
        remat=cfg.get("remat", False),
        attn_impl=cfg.get("attn_impl", "auto"),
        block_size=cfg.get("block_size", 512),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)))
