"""Plain reference of the GPT-2 style encoder the `transformer` configs run.

Pre-LayerNorm blocks, learned positions, fused QKV without biases, tanh-GELU
MLP with biases, causal softmax attention written out in full, a final
LayerNorm, the mean over positions and a linear head. The parameter tree has
the layout of the program's weights (names of the leaves), nothing else of it.
"""

import functools

import jax
import jax.numpy as jnp

from . import common

LN_EPS = 1e-6


def layernorm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(config, p, x, precision):
    B, T, d = x.shape
    H = config["heads"]
    D = d // H
    mm = functools.partial(common.matmul, precision=precision)
    h = layernorm(x, p["LayerNorm_0"])
    qkv = mm(h, p["Dense_0"]["kernel"]).reshape(B, T, 3 * H, D)
    q, k, v = qkv[:, :, :H], qkv[:, :, H:2 * H], qkv[:, :, 2 * H:]
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))     # B,H,T,D
    s = mm(q, k.transpose(0, 1, 3, 2)) / (D ** 0.5)
    if config.get("causal", False):
        keep = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(keep, s, -jnp.inf)
    a = mm(jax.nn.softmax(s, axis=-1), v)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + mm(a, p["Dense_1"]["kernel"])
    h = layernorm(x, p["LayerNorm_1"])
    h = gelu_tanh(mm(h, p["Dense_2"]["kernel"]) + p["Dense_2"]["bias"])
    return x + mm(h, p["Dense_3"]["kernel"]) + p["Dense_3"]["bias"]


def forward(config, params, tokens, precision="f32"):
    """(B, T) int32 ids -> (B, num_classes) float32 logits."""
    P = params["params"]
    T = tokens.shape[1]
    x = P["Embed_0"]["embedding"][tokens] + P["Embed_1"]["embedding"][:T][None]
    for i in range(config["layers"]):
        x = jax.checkpoint(functools.partial(block, config,
                                             precision=precision))(
            P[f"block{i}"], x)
    x = layernorm(x, P["LayerNorm_0"])
    if config.get("pool", "mean") == "mean":
        x = jnp.mean(x, axis=1)
    return common.matmul(x, P["Dense_0"]["kernel"], precision) \
        + P["Dense_0"]["bias"]


train_steps = functools.partial(common.train_steps, forward)
