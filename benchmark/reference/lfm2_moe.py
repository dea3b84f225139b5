"""Plain reference of what the `lfm2_moe` configs run: one chip's share of
LFM2-8B-A1B's layers (transformers' `Lfm2Moe*`), with its per-token loss over
the tied vocabulary head and its own loop over the checked steps.

Pre-RMSNorm residual blocks, x a token's hidden vector of width d:
h = x + Mixer_l(RMSNorm(x)), y = h + FFN_l(RMSNorm(h)).

- `layer_types[l] == "conv"`, the gated short convolution: [B, C, u] = the
  three thirds of W_in z (d -> 3d, in that order); v = B * u;
  c_t = sum_{i=0..L-1} w_i * v_{t-(L-1)+i} over L = `conv_L_cache` taps
  (depthwise, causal, positions before 0 read as 0, no bias, no activation),
  written out as a gather of the L taps of every position; out =
  W_out (C * c).
- `"full_attention"`, grouped-query attention: H query heads and Hkv
  key/value heads of `head_dim` D held here, g = H / Hkv; q = RMSNorm_q(W_q z)
  and k = RMSNorm_k(W_k z) per head (one scale of D each); q and k rotated
  at position t, lane i < D/2 paired with lane i + D/2 by the angle
  t * theta^(-2i / D), written out from that formula (`rotate`); the causal
  softmax(q . k / sqrt(D)) v of query head h against key/value head h // g,
  written out in full over the (rows, T) scores of a block of query rows at
  a time; W_o.
- FFN: a SwiGLU of `intermediate_size` in the `num_dense_layers` leading
  layers; after them the expert layer: s = sigmoid(W_r z) over all
  `router_width` experts in float32, the top k by s + selection_bias (ties
  to the lower index), weights the chosen s over (their sum + 1e-6) where
  `norm_topk_prob`, times `routed_scaling_factor`; a loop over the experts
  *held here* adds each one's SwiGLU of `moe_intermediate_size` for the
  tokens that chose it (a boolean mask). No shared expert.
- head and loss: z_t = E RMSNorm(h_t) over the embedding's own rows (tied),
  the whole (T, V) logits of one row at a time; the row's loss is the mean
  over t = 0..T-2 of -log softmax(z_t)[id_{t+1}]; the batch's loss is the
  mean over its rows.

What the experts, heads and vocabulary rows held elsewhere would add is left
out, as in the program; the labels that come with a batch are not read.
Departures from the published description, as the configuration file lists
them: the expert bias (`selection_bias`) takes no gradient and is never
updated; tied embeddings, the order B, C, u and the half-split rotation are
assumed from the family's public implementation. The parameter tree has the
layout of the program's weights (names of the leaves), nothing else of it.
`precision` rounds the operands and the result of every projection, of the
gate products and the convolution's sum, of the attention products and of
the vocabulary projection; the router, the norms, the rotation and the
log-sum-exp stay float32 (the program keeps them so too).
"""

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .joyai_llm_flash import rmsnorm, silu, swiglu, token_losses

#: query rows of one block of the written-out softmax (memory, not meaning)
QUERY_BLOCK = 1024
#: the family's constant in the renormalisation of the chosen scores
RENORM_EPS = 1e-6


def rotate(x, theta):
    """x (B, T, ..., D), position t along axis 1: the pair (x[i], x[i+D/2])
    turned by the angle t * theta^(-2i / D)."""
    T, D = x.shape[1], x.shape[-1]
    i = jnp.arange(D // 2, dtype=jnp.float32)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-2.0 * i / D)
    angle = angle.reshape((T,) + (1,) * (x.ndim - 3) + (D // 2,))
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)


def short_conv(config, p, x, precision):
    L, T = config["conv_L_cache"], x.shape[1]
    mm = functools.partial(common.matmul, precision=precision)
    low = functools.partial(common.lowp, precision=precision)
    gate_in, gate_out, u = jnp.split(mm(x, p["in_proj"]["kernel"]), 3,
                                     axis=-1)
    v = low(gate_in * u)
    # tap i of position t is position t - (L - 1) + i, nothing before 0
    at = jnp.arange(T)[:, None] - (L - 1) + jnp.arange(L)[None, :]
    taps = jnp.where((at >= 0)[None, :, :, None],
                     v[:, jnp.maximum(at, 0)], 0.0)            # B, T, L, d
    c = low(jnp.sum(taps * p["conv"]["kernel"], axis=2))
    return mm(gate_out * c, p["out_proj"]["kernel"])


def attention(config, p, x, precision):
    B, T, _ = x.shape
    H, Hkv, D = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    g, eps, theta = H // Hkv, config["norm_eps"], float(config["rope_theta"])
    mm = functools.partial(common.matmul, precision=precision)
    q = mm(x, p["q_proj"]["kernel"]).reshape(B, T, H, D)
    k = mm(x, p["k_proj"]["kernel"]).reshape(B, T, Hkv, D)
    v = mm(x, p["v_proj"]["kernel"]).reshape(B, T, Hkv, D)
    q = rotate(rmsnorm(q, p["q_norm"], eps), theta)
    k = rotate(rmsnorm(k, p["k_norm"], eps), theta)
    # query head h = kv * g + j reads key/value head kv: (B, Hkv, g, T, D)
    q = q.reshape(B, T, Hkv, g, D).transpose(0, 2, 3, 1, 4)
    k, v = (a.transpose(0, 2, 1, 3)[:, :, None] for a in (k, v))
    # the written-out softmax, a block of query rows at a time (the blocks
    # walked by `lax.map`, one compiled body for all); the last block's rows
    # past T are zeros masked as row T - 1 and are cut off
    blocks = -(-T // QUERY_BLOCK)
    size = min(QUERY_BLOCK, T)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, blocks * size - T), (0, 0)))

    @jax.checkpoint
    def query_block(start):
        rows = jnp.minimum(start + jnp.arange(size), T - 1)
        sc = mm(jax.lax.dynamic_slice_in_dim(q, start, size, axis=3),
                jnp.swapaxes(k, -1, -2)) / D ** 0.5
        sc = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(query_block, jnp.arange(blocks) * size)
    o = jnp.moveaxis(o, 0, 3)                          # B, Hkv, g, blocks, .
    o = o.reshape(B, H, blocks * size, D)[:, :, :T]
    return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * D),
              p["o_proj"]["kernel"])


def experts(config, p, x, precision):
    B, T, d = x.shape
    k, first = config["num_experts_per_tok"], config["first_expert_held"]
    mm = functools.partial(common.matmul, precision=precision)
    xf = x.reshape(B * T, d)
    scores = jax.nn.sigmoid(jnp.matmul(xf, p["router"],
                                       precision=common.HIGHEST))
    bias = jax.lax.stop_gradient(p["selection_bias"])
    chosen = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                           + RENORM_EPS)
    weight = weight * config["routed_scaling_factor"]

    @jax.checkpoint
    def add_expert(y, held):                    # one of the experts held
        e, w_gate, w_up, w_down = held
        mine = chosen == first + e                               # (N, k)
        w = jnp.sum(jnp.where(mine, weight, 0.0), axis=-1)
        h = silu(mm(xf, w_gate)) * mm(xf, w_up)
        return y + jnp.where(jnp.any(mine, axis=-1)[:, None],
                             mm(h, w_down), 0.0) * w[:, None], None

    # a loop over the experts held, one compiled body for all
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(xf),
        (jnp.arange(config["num_experts"]), p["expert_gate"],
         p["expert_up"], p["expert_down"]))
    return y.reshape(B, T, d)


def block(config, kind, dense, p, x, precision):
    eps = config["norm_eps"]
    mixer = {"conv": short_conv, "full_attention": attention}[kind]
    x = x + mixer(config, p["mixer"], rmsnorm(x, p["input_norm"], eps),
                  precision)
    h = rmsnorm(x, p["post_attention_norm"], eps)
    if dense:
        mm = functools.partial(common.matmul, precision=precision)
        return x + swiglu(p["mlp"], h, mm)
    return x + experts(config, p["mlp"], h, precision)


def hidden(config, params, tokens, precision="f32"):
    """(B, T) ids -> the last block's output before the final norm."""
    P = params["params"]
    x = P["embed"]["embedding"][tokens]
    for i, kind in enumerate(config["layer_types"]):
        # a layer at a time, rematerialised: what the backward pass keeps
        # between layers is one (B, T, d) array each
        layer = jax.checkpoint(functools.partial(
            block, config, kind, i < config["num_dense_layers"],
            precision=precision))
        x = layer(P[f"block{i}"], x)
    return x


def forward(config, params, tokens, precision="f32"):
    """(B, T) int32 ids -> (B, T, V) float32 logits over the embedding's own
    rows: z_t = E RMSNorm(h_t)."""
    P = params["params"]
    x = rmsnorm(hidden(config, params, tokens, precision), P["norm"],
                config["norm_eps"])
    return common.matmul(x, P["embed"]["embedding"].T, precision)


def row_losses(config, params, tokens, precision="f32"):
    """One loss a row: the mean over t = 0..T-2 of
    -log softmax(z_t)[id_{t+1}]."""
    z = forward(config, params, tokens, precision)
    return jnp.mean(token_losses(z[:, :-1], tokens[:, 1:]), axis=1)


def train_steps(config, params_init, batches, block_rows, precision="f32",
                fault=None):
    """Follow the checked steps from `params_init` over `batches`, as
    `common.train_steps` does for a per-row cross entropy on labels, with
    the rows' own per-token losses in its place (a batch's labels are not
    read). Returns what that returns: each step's loss; of the first step
    `first_input` (on the host), `grad_norms` and `block_rms` by leaf; and
    `params_after` the last (on the host)."""
    if fault not in common.FAULTS:
        raise ValueError(f"fault must be one of {common.FAULTS}")
    lp = config["learner"]
    if lp["optimizer"] != "adamw":
        raise ValueError(f"no reference for optimizer {lp['optimizer']!r}")
    tree_map = common.tree_map

    def block_loss(p, x):
        return jnp.sum(row_losses(config, p, x, precision))

    grad_block = jax.jit(jax.value_and_grad(block_loss))
    add = jax.jit(lambda a, b: tree_map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda a, c: tree_map(lambda v: v * c, a),
                    donate_argnums=0)
    # a state left unchanged keeps its parameters: they are not donated then
    donated = (1, 2) if fault == "state_unchanged" else (0, 1, 2)
    update = jax.jit(lambda p, g, s, t: common.adamw_update(
        p, g, s, t, lp["learningRate"], lp.get("weightDecay", 0.0)),
        donate_argnums=donated)

    # 12 bytes a parameter of state, a gradient and a block's gradient are
    # 10 GB here: nothing an earlier follower of these steps left on the
    # device (the control runs four in one process) may still be held
    gc.collect()
    with jax.default_matmul_precision("highest"):
        p = tree_map(lambda a: jnp.asarray(a, jnp.float32), params_init)
        state, losses, out = None, [], {}
        for t, (x, _) in enumerate(batches, start=1):
            if fault == "half_batch":
                x = x[:len(x) // 2]
            n, total, g, squares = len(x), 0.0, None, []
            for i in range(0, n, block_rows):
                xb = x[i:i + block_rows]
                lb, gb = grad_block(p, jnp.asarray(xb))
                total += float(lb)
                if t == 1:
                    squares.append((common.norms(gb) / len(xb)) ** 2)
                g = gb if g is None else add(g, gb)
            g = scale(g, 1.0 / n)
            losses.append(total / n)
            if t == 1:
                out["grad_norms"] = common.norms(g)
                out["block_rms"] = np.sqrt(np.mean(squares, axis=0))
            new_p, state, first_input = update(p, g, state, t)
            if t == 1:
                out["first_input"] = jax.device_get(first_input)
            del first_input, g
            if fault != "state_unchanged":
                p = new_p
        del state
        out["losses"] = losses
        out["params_after"] = jax.device_get(p)
    return out
