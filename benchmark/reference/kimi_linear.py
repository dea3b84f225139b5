"""Plain reference of the hybrid the `kimi_linear` configs run: one
expert-parallel rank's share of Kimi-Linear's layers.

Pre-RMSNorm residual blocks; no position embedding. Mixers, by the published
1-based lists of layer numbers:

- Kimi Delta Attention (arXiv:2510.26692), token by token: per head
  S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
  o_t = S_t^T q_t / sqrt(head_dim), with q, k, v a projection, a causal
  depthwise convolution (kernel 4) and SiLU each, q and k L2-normalised,
  a_t = exp(-exp(A_log) softplus(W_f2 W_f1 x_t + dt_bias)) per channel,
  b_t = sigmoid(W_b x_t) per head; o_t RMS-normalised per head, gated by
  sigmoid(W_g2 W_g1 x_t), projected by W_o.
- latent attention without rotary: q = W_q x; [c, k_r] = W_kva x, c
  RMS-normalised; [k_n, v] = W_kvb c; k = [k_n, k_r] (k_r shared by the
  heads, not rotated); the causal softmax written out in full over the
  (T, T) scores of a block of rows; W_o.

MLPs: SwiGLU in the leading dense layers; after them the expert layer: the
router scores every token against all `router_width` experts in float32
(sigmoid), takes the top k by score + selection_bias (ties to the lower
index), weighs by the chosen scores renormalised to sum 1 times
`routed_scaling_factor`; a loop over the experts *held here* adds each one's
SwiGLU for the tokens that chose it (a boolean mask); the shared expert adds
its own for every token. What the experts and heads held elsewhere would add
is left out, as in the program.

Departures from the published model, as the configuration file lists them:
a mean-pooled linear head on the final RMSNorm in place of the LM head; the
selection bias takes no gradient (its update rule is not in the config);
A_log per head, dt_bias per channel and the bottleneck rank head_dim follow
the family's public implementation; the output gate has no bias. The
parameter tree has the layout of the program's weights (names of the
leaves), nothing else of it. `precision` rounds the operands and the result
of every projection and of the attention products; the recurrent state, the
router and the norms stay float32 (the program keeps them so too).
"""

import functools

import jax
import jax.numpy as jnp

from . import common


def rmsnorm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * p["scale"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(p, x, mm):
    return mm(silu(mm(x, p["gate"]["kernel"])) * mm(x, p["up"]["kernel"]),
              p["down"]["kernel"])


def short_conv(x, w):
    """y_t = sum_i w_i x_{t-3+i} over time, per channel; then SiLU."""
    n, T = w.shape[0], x.shape[1]
    xp = jnp.concatenate([jnp.zeros_like(x[:, :n - 1]), x], axis=1)
    return silu(sum(xp[:, i:i + T] * w[i] for i in range(n)))


def kda(config, p, x, precision):
    lin = config["linear_attn_config"]
    B, T, _ = x.shape
    H, D = lin["num_heads"], lin["head_dim"]
    mm = functools.partial(common.matmul, precision=precision)

    def mixed(name):
        a = short_conv(mm(x, p[f"{name}_proj"]["kernel"]),
                       p[f"{name}_conv"]["kernel"])
        return a.reshape(B, T, H, D)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(mixed("q")), unit(mixed("k")), mixed("v")
    q, k, v = (common.lowp(a, precision) for a in (q, k, v))
    f = mm(mm(x, p["f_a_proj"]["kernel"]), p["f_b_proj"]["kernel"])
    a = jnp.exp(-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(B, T, H, D)))
    b = jax.nn.sigmoid(mm(x, p["b_proj"]["kernel"]))            # (B, T, H)

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[..., None] * S                                   # Diag(a) S
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, err)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S) * D ** -0.5

    by_token = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, b))
    _, o = jax.lax.scan(token, jnp.zeros((B, H, D, D), jnp.float32),
                        by_token)
    o = common.lowp(jnp.moveaxis(o, 0, 1), precision)           # (B, T, H, D)
    o = rmsnorm(o, p["o_norm"], config["rms_norm_eps"])
    gate = mm(mm(x, p["g_a_proj"]["kernel"]), p["g_b_proj"]["kernel"])
    o = o.reshape(B, T, H * D) * jax.nn.sigmoid(gate)
    return mm(o, p["o_proj"]["kernel"])


def mla(config, p, x, precision):
    B, T, _ = x.shape
    H, r = config["num_attention_heads"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    mm = functools.partial(common.matmul, precision=precision)
    q = mm(x, p["q_proj"]["kernel"]).reshape(B, T, H, dn + dr)
    kva = mm(x, p["kv_a_proj"]["kernel"])
    c = rmsnorm(kva[..., :r], p["kv_a_norm"], config["rms_norm_eps"])
    k_r = kva[..., r:]
    kv = mm(c, p["kv_b_proj"]["kernel"]).reshape(B, T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r[:, :, None], H, 2)],
                        axis=-1)
    v = kv[..., dn:]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))      # B, H, T, .
    s = mm(q, k.transpose(0, 1, 3, 2)) / (dn + dr) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v)
    return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * dv),
              p["o_proj"]["kernel"])


def experts(config, p, x, precision):
    B, T, d = x.shape
    k, first = config["num_experts_per_token"], config["first_expert_held"]
    mm = functools.partial(common.matmul, precision=precision)
    xf = x.reshape(B * T, d)
    scores = jax.nn.sigmoid(jnp.matmul(xf, p["router"],
                                       precision=common.HIGHEST))
    bias = jax.lax.stop_gradient(p["selection_bias"])
    chosen = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["moe_renormalize"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * config["routed_scaling_factor"]
    y = jnp.zeros_like(xf)
    for e in range(config["num_experts"]):                # the experts held
        mine = chosen == first + e                               # (N, k)
        w = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
        h = silu(mm(xf, p["expert_gate"][e])) * mm(xf, p["expert_up"][e])
        y = y + jnp.where(jnp.any(mine, axis=-1)[:, None],
                          mm(h, p["expert_down"][e]), 0.0) * w[:, None]
    for i in range(config["num_shared_experts"]):
        y = y + swiglu(p[f"shared{i}"], xf, mm)
    return y.reshape(B, T, d)


def block(config, kind, dense, p, x, precision):
    eps = config["rms_norm_eps"]
    mixer = {"kda": kda, "mla": mla}[kind]
    x = x + mixer(config, p["mixer"], rmsnorm(x, p["input_norm"], eps),
                  precision)
    h = rmsnorm(x, p["post_attention_norm"], eps)
    if dense:
        mm = functools.partial(common.matmul, precision=precision)
        return x + swiglu(p["mlp"], h, mm)
    return x + experts(config, p["mlp"], h, precision)


def forward(config, params, tokens, precision="f32"):
    """(B, T) int32 ids -> (B, num_classes) float32 logits."""
    P = params["params"]
    lin = config["linear_attn_config"]
    x = P["embed"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        kind = "kda" if i + 1 in lin["kda_layers"] else "mla"
        assert kind == "kda" or i + 1 in lin["full_attn_layers"], i
        x = jax.checkpoint(functools.partial(
            block, config, kind, i < config["first_k_dense_replace"],
            precision=precision))(P[f"block{i}"], x)
    x = rmsnorm(x, P["norm"], config["rms_norm_eps"])
    if config.get("pool", "mean") == "mean":
        x = jnp.mean(x, axis=1)
    return common.matmul(x, P["head"]["kernel"], precision) \
        + P["head"]["bias"]


train_steps = functools.partial(common.train_steps, forward)
