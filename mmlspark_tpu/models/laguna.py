"""The `laguna` token-model family: grouped-query attention with a per-head
output gate, sliding-window and full layers mixed by the published
``layer_types`` with a head count of their own each, over a dense SwiGLU in
the leading layers and softmax-routed experts beside one shared expert
after them, and an untied vocabulary head (poolside's Laguna, `model_type`
``laguna``).

Pre-RMSNorm residual blocks (`kimi_linear._Block`). A block's mixer is
`GatedGQALayer` over the heads its layer holds:

- q = W_q z (H_l heads of ``head_dim``), k = W_k z and v = W_v z (Hkv
  heads); no bias and no QK-norm.
- rotary by the layer type's block of ``rope_parameters`` (`rotate`):
  ``sliding_attention`` the default rotary over the whole head,
  ``full_attention`` YaRN over its first ``partial_rotary_factor`` of the
  lanes (`rotary`: the inverse frequencies blended between interpolation
  and extrapolation by the linear ramp between the two correction dims, cos
  and sin scaled by ``attention_factor``), both in the half-split form
  within the rotated lanes; the other lanes pass as they are.
- causal softmax(q k^T / sqrt(head_dim)) v, query head h against key/value
  head h // (H_l / Hkv); in a ``sliding_attention`` layer a query sees the
  ``sliding_window`` keys up to its own, which `flash_attention` takes as its
  ``window`` and walks as a band.
- the per-head gate (``gating`` "per-head", Qiu et al. 2025,
  arXiv:2505.06708's headwise gate): o_h <- sigmoid(W_g z)_h o_h, W_g one
  output a head, under `jax.named_scope` `HEAD_GATE_SCOPE`; then W_o.

Its MLP is a dense SwiGLU in the layers ``mlp_layer_types`` calls dense
(``mlp_only_layers``) and `moe.DroplessMoE` in the others with
``scoring="softmax"``: the softmax over all ``router_width`` logits in
float32, the top ``num_experts_per_tok``, renormalised where
``norm_topk_prob`` and times ``moe_routed_scaling_factor``, plus one
ungated shared expert of ``shared_expert_intermediate_size`` (which is
``moe_intermediate_size``: the layer has one width for both), under the
family's floor of tiles (`EXPERT_FLOOR_SHARES`). The
configuration's keys are those of the model's public `config.json`; the
counts of query heads (``num_attention_heads_per_layer``), key/value heads,
routed experts and vocabulary rows are what is held *here* (one chip's
share of a layer), as is ``dense_columns_held`` of the dense MLP's
``intermediate_size``, while ``router_width`` stays the deployment's expert
count.

Two results, as `joyai_llm_flash` has them: per-token logits (B, T, V)
float32 over the vocabulary rows held, or with ``row_losses=True``
(`TpuLearner`'s ``loss="next_token"``) one loss a row, the mean over
t = 0..T-2 of -log softmax(W_head RMSNorm(h_t))[id_{t+1}], walked in chunks
of ``lm_loss_chunk`` positions (`joyai_llm_flash.chunked_token_losses`).

Not here: a learning-rate schedule, decode with a key/value cache (a
window's cache is a ring of ``sliding_window`` positions), packing with
segment ids, experts across chips with their all-to-all, the attention's
tensor-parallel all-reduce.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from fractions import Fraction
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .joyai_llm_flash import (_Leaf, _m_vocab_rows, _rms_norm,
                              chunked_token_losses)
from .kimi_linear import (_Block, _dense, block_mlp, causal_attention,
                          expert_step_stats, remat_block)
from .lfm2_moe import LM_STEP_STATS
from .moe import MOE_STEP_STATS

#: the scope the per-head output gate is traced under
HEAD_GATE_SCOPE = "head_gate"

#: the two kinds of attention layer the published ``layer_types`` name
LAYER_TYPES = ("full_attention", "sliding_attention")

#: the expert layers' floor of tiles, in uniform shares (`moe.DroplessMoE`'s
#: `floor_shares`; the field's default, `moe.GROUP_FLOOR_SHARES` = 2, is set
#: for sigmoid routers whose held experts swing 0.5-1.9 shares at a fresh
#: init). This family's softmax top 10 of 256 fills its held experts more
#: evenly: at 32,768 tokens a step, 1,280 assignments a held expert, the
#: fullest held expert read 1,268-2,136 (at most 1.67 shares) but a layer's
#: need only 39-50 tiles of 256 (3 of 384 layer-steps at 50, none past) over
#: 12 seeds x 8 batches x 4 layers at the fresh init
#: (`tools/count_tiles_needed.py`, TPU v5e; PERF.md section 6). 5/4 shares,
#: 50 tiles of 256 a layer, is the least floor no layer-step read passed, so
#: the walk's length stays the same from step to step; a step whose routing
#: passes it walks the tiles it needs. The result does not depend on it
EXPERT_FLOOR_SHARES = Fraction(5, 4)

_m_head_gates = telemetry.registry.counter(
    "mmlspark_attn_head_gates_total",
    "attention layers built with a per-head sigmoid output gate, by the "
    "layer's type (static in the configuration: counted at trace time)",
    labels=("layer_type",))
_m_rope_layers = telemetry.registry.counter(
    "mmlspark_rope_layers_total",
    "attention layers built with rotary position embedding, by its kind "
    "(default, yarn) and the lanes of a head it turns (static in the "
    "configuration: counted at trace time)", labels=("kind", "rotary_lanes"))


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One layer type's rotary: its kind, the lanes of a head it turns (the
    first ``lanes``), their pairs' inverse frequencies (lanes / 2, float32)
    and the factor cos and sin are scaled by."""
    kind: str
    lanes: int
    inv_freq: tuple
    scale: float = 1.0


def _float32s(a):
    """float64 values rounded to float32, as a tuple of Python floats."""
    return tuple(float(x) for x in np.asarray(a, np.float32))


def rotary(head_dim: int, params: dict) -> Rotary:
    """A ``rope_parameters`` block as `Rotary`. ``default``: pair i of the
    R = head_dim * ``partial_rotary_factor`` lanes turns at
    theta^(-2i / R). ``yarn`` (transformers' `_compute_yarn_parameters`):
    the extrapolated frequencies theta^(-2i / R) and the interpolated ones
    over ``factor`` are blended by the ramp r_i = clip((i - low) / (high -
    low), 0, 1) as interpolated r_i + extrapolated (1 - r_i), where low and
    high are the correction dims of ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position_embeddings``, R log(L / (2 pi
    beta)) / (2 log theta), floored and ceiled; cos and sin are scaled by
    ``attention_factor`` (0.1 ln(factor) + 1 where it is not given). The
    frequencies are computed in float64 and rounded to float32 once."""
    kind = params.get("rope_type", "default")
    theta = float(params["rope_theta"])
    lanes = int(head_dim * params.get("partial_rotary_factor", 1.0))
    if lanes % 2 or not 0 < lanes <= head_dim:
        raise ValueError(f"rotary over {lanes} of {head_dim} lanes")
    extrapolated = theta ** -(np.arange(0, lanes, 2, dtype=np.float64)
                              / lanes)
    if kind == "default":
        return Rotary(kind, lanes, _float32s(extrapolated))
    if kind != "yarn":
        raise ValueError(f"rope_type must be 'default' or 'yarn', got "
                         f"{kind!r}")
    factor = float(params["factor"])
    original = params["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (lanes * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(params.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(params.get("beta_slow", 1))),
               lanes - 1)
    ramp = np.clip((np.arange(lanes // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    inv = extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    scale = params.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return Rotary(kind, lanes, _float32s(inv), float(scale))


@functools.partial(jax.jit, static_argnums=1)
def rotate(x, rot: Rotary):
    """Rotary position embedding over the first ``rot.lanes`` lanes of the
    last dimension in the half-split form: x is (B, T, ..., D), position
    t = 0..T-1 along axis 1; with half = lanes / 2, lane i < half and lane
    i + half are the pair turned by the angle t * inv_freq[i]:
    x cos + rotate_half(x) sin over the rotated lanes, cos and sin scaled by
    ``rot.scale``; lanes from ``rot.lanes`` on pass unchanged. Angles, sines
    and the product are float32 over all D lanes at once (the unrotated
    lanes' cos is 1 and sin 0); the result has x's dtype. Under `jit`, so
    that an eager caller (`fitStream`'s init) compiles it as one program:
    the chip's compiler refuses the concatenation at half a head's lanes
    when it is compiled alone."""
    T, D = x.shape[1], x.shape[-1]
    R, half = rot.lanes, rot.lanes // 2
    inv = np.zeros(D, np.float32)
    inv[:R] = np.tile(np.asarray(rot.inv_freq, np.float32), 2)
    turned = (np.arange(D) < R).astype(np.float32)
    f32 = jnp.float32
    ang = (jnp.arange(T, dtype=f32)[:, None] * inv).reshape(
        (T,) + (1,) * (x.ndim - 3) + (D,))
    cos = jnp.cos(ang) * (turned * rot.scale) + (1.0 - turned)
    sin = jnp.sin(ang) * (turned * rot.scale)
    x32 = x.astype(f32)
    # rotate_half within the first R lanes, 0 past them (where sin is 0)
    partner = jnp.concatenate(
        [-x32[..., half:R], x32[..., :half], jnp.zeros_like(x32[..., R:])],
        axis=-1)
    return (x32 * cos + partner * sin).astype(x.dtype)


class GatedGQALayer(nn.Module):
    """Grouped-query attention with a per-head output gate over the heads a
    layer holds: (B, T, d) -> (B, T, d) (module docstring). `attention`
    takes q (B, T, H, D) and k, v (B, T, Hkv, D) as they are; a head of 128
    lanes is a lane block of the projection's (B, T, H*D) output, which the
    flash kernels read in place."""
    heads: int
    kv_heads: int
    head_dim: int
    attention: Any          # (q, k, v, scale) -> o (B, T, H, D)
    rotary: Rotary
    layer_type: str
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        H, Hkv, D = self.heads, self.kv_heads, self.head_dim
        if H % Hkv:
            raise ValueError(f"{H} query heads do not share {Hkv} key/value "
                             "heads evenly")
        _m_head_gates.labels(layer_type=self.layer_type).inc()
        _m_rope_layers.labels(kind=self.rotary.kind,
                              rotary_lanes=str(self.rotary.lanes)).inc()

        def heads(name, n):
            return _dense(n * D, self.dtype, f"{name}_proj")(x).reshape(
                B, T, n, D)

        q = rotate(heads("q", H), self.rotary)
        k = rotate(heads("k", Hkv), self.rotary)
        o = self.attention(q, k, heads("v", Hkv), D ** -0.5)
        with jax.named_scope(HEAD_GATE_SCOPE):
            g = jax.nn.sigmoid(
                _dense(H, self.dtype, "g_proj")(x).astype(jnp.float32))
            # a head's gate over its D lanes of the (B, T, H*D) view
            o = (o.reshape(B, T, H * D).astype(jnp.float32)
                 * jnp.repeat(g, D, axis=-1)).astype(self.dtype)
        return _dense(d, self.dtype, "o_proj")(o)


class LagunaModel(nn.Module):
    """Token ids (B, T) -> per-token logits (B, T, V) float32, or with
    ``row_losses=True`` the rows' losses (B,) (module docstring).
    ``step_stats=True`` also returns {name: scalar}: the expert layers'
    counts (`moe.MOE_STEP_STATS`) and, with ``row_losses``,
    `lfm2_moe.LM_STEP_STATS`."""
    vocab_size: int
    hidden_size: int
    layer_kinds: Sequence[str]       # full_attention | sliding_attention
    layer_heads: Sequence[int]       # query heads held, one a layer
    kv_heads: int
    dense: Sequence[bool]            # a dense MLP (True) or experts, a layer
    rotaries: Sequence[Any]          # ((layer type, Rotary), ...)
    head_dim: int = 128
    window: int = 512
    intermediate_size: int = 1536    # the dense MLP's columns held
    moe_intermediate_size: int = 1024
    num_experts: int = 8
    first_expert: int = 0
    router_width: int = 256
    top_k: int = 10
    num_shared: int = 1
    renormalize: bool = True
    routed_scale: float = 2.5
    eps: float = 1e-6
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

    def _mixer(self, kind, heads):
        if kind not in LAYER_TYPES:
            raise ValueError(f"layer type must be one of {LAYER_TYPES}, got "
                             f"{kind!r}")
        window = self.window if kind == "sliding_attention" else None
        return functools.partial(
            GatedGQALayer, heads, self.kv_heads, self.head_dim,
            causal_attention(self.attn_impl, self.block_size, window),
            dict(self.rotaries)[kind], kind, self.dtype)

    def _mlp(self, dense):
        mlp = block_mlp(self, dense)
        return mlp if dense else functools.partial(
            mlp, scoring="softmax", floor_shares=EXPERT_FLOOR_SHARES)

    @nn.compact
    def __call__(self, tokens, output_layer: Optional[str] = None,
                 row_mask=None, step_stats: bool = False,
                 row_losses: bool = False):
        from .modules import _LayerTap
        tap = _LayerTap(output_layer)
        B, T = tokens.shape
        V, d = self.vocab_size, self.hidden_size
        x = tap.tap("embed", nn.Embed(V, d, dtype=self.dtype,
                                      name="embed")(tokens))
        if tap.done:
            return tap.result.astype(jnp.float32)
        Block = remat_block() if self.remat else _Block
        stats = []
        for i, (kind, heads, dense) in enumerate(zip(
                self.layer_kinds, self.layer_heads, self.dense)):
            blk = Block(self._mixer(kind, heads), self._mlp(dense), self.eps,
                        self.dtype, name=f"block{i}")
            x, s = blk(x, row_mask)
            stats.append(s)
            x = tap.tap(f"block{i}", x)
            if tap.done:
                return tap.result.astype(jnp.float32)
        scale = _Leaf((d,), nn.initializers.ones, "scale", name="norm")()
        kernel = _Leaf((d, V), nn.initializers.lecun_normal(), "kernel",
                       name="head")()
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


def build(cfg: dict) -> LagunaModel:
    """The model from the keys of the public `config.json` (counts are what
    is held here; see the module's docstring)."""
    want = {"attention_bias": False, "tie_word_embeddings": False,
            "gating": "per-head", "moe_apply_router_weight_on_input": False,
            "moe_router_logit_softcapping": 0, "decoder_sparse_step": 1}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"laguna: {key} must be {value!r}, got "
                             f"{cfg[key]!r}")
    layers = cfg.get("num_hidden_layers", 2)
    kinds = tuple(cfg.get("layer_types", ("full_attention",) * layers))
    heads = tuple(cfg.get("num_attention_heads_per_layer",
                          (cfg.get("num_attention_heads", 4),) * layers))
    dense_layers = set(cfg.get("mlp_only_layers", (0,)))
    mlp_types = tuple(cfg.get("mlp_layer_types", [
        "dense" if i in dense_layers else "sparse" for i in range(layers)]))
    gates = tuple(cfg.get("gating_types", ("per_head",) * layers))
    for name, per_layer in (("layer_types", kinds),
                            ("num_attention_heads_per_layer", heads),
                            ("mlp_layer_types", mlp_types),
                            ("gating_types", gates)):
        if len(per_layer) != layers:
            raise ValueError(f"laguna: {name} names {len(per_layer)} layers, "
                             f"num_hidden_layers {layers}")
    if {i for i, t in enumerate(mlp_types) if t == "dense"} != dense_layers \
            or set(mlp_types) - {"dense", "sparse"}:
        raise ValueError(f"laguna: mlp_layer_types {mlp_types} and "
                         f"mlp_only_layers {sorted(dense_layers)} disagree")
    if set(gates) != {"per_head"}:
        raise ValueError(f"laguna: every layer gates per head, got {gates}")
    moe_width = cfg.get("moe_intermediate_size", 64)
    shared = cfg.get("shared_expert_intermediate_size", moe_width)
    if shared != moe_width:
        raise ValueError(f"laguna: shared_expert_intermediate_size {shared} "
                         f"must be moe_intermediate_size {moe_width} "
                         "(DroplessMoE has one width for both)")
    head_dim = cfg.get("head_dim", 32)
    rope = cfg.get("rope_parameters", {
        kind: {"rope_type": "default", "rope_theta": 10000.0}
        for kind in LAYER_TYPES})
    experts = cfg.get("num_experts", 4)
    intermediate = cfg.get("intermediate_size", 256)
    return LagunaModel(
        vocab_size=cfg.get("vocab_size", 1024),
        hidden_size=cfg.get("hidden_size", 64),
        layer_kinds=kinds,
        layer_heads=heads,
        kv_heads=cfg.get("num_key_value_heads", 1),
        dense=tuple(t == "dense" for t in mlp_types),
        rotaries=tuple((kind, rotary(head_dim, rope[kind]))
                       for kind in sorted(set(kinds))),
        head_dim=head_dim,
        window=cfg.get("sliding_window", 512),
        intermediate_size=cfg.get("dense_columns_held", intermediate),
        moe_intermediate_size=moe_width,
        num_experts=experts,
        first_expert=cfg.get("first_expert_held", 0),
        router_width=cfg.get("router_width", experts),
        top_k=cfg.get("num_experts_per_tok", 2),
        renormalize=cfg.get("norm_topk_prob", True),
        routed_scale=cfg.get("moe_routed_scaling_factor", 1.0),
        eps=cfg.get("rms_norm_eps", 1e-6),
        lm_loss_chunk=cfg.get("lm_loss_chunk", 512),
        remat=cfg.get("remat", False),
        attn_impl=cfg.get("attn_impl", "auto"),
        block_size=cfg.get("block_size", 512),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)))
