"""Plain reference of what the `joyai_llm_flash` configs run: one
expert-parallel rank's share of JoyAI-LLM-Flash's layers, which are those
DeepSeek-V3's report defines (arXiv:2412.19437), with its per-token loss and
its own loop over the checked steps.

Pre-RMSNorm residual blocks, x a token's hidden vector, H the heads held:

- latent attention, every layer (section 2.1.1): c_q = RMSNorm(W_qa x);
  [q_n, q_r] = W_qb c_q per head; [c_kv, k_r] = W_kva x, c_kv RMS-normalised,
  k_r one vector for all heads; [k_n, v] = W_kvb c_kv per head. q_r and k_r
  are rotated at position t: the pair (2i, 2i+1) by the angle
  t * theta^(-2i / rope width), written out from that formula (`rotate`). The
  causal softmax((q_n . k_n + q_r . k_r) / sqrt(nope + rope)) v is written
  out in full over the (rows, T) scores of a block of query rows at a time
  (the blocks and the experts below are walked by `lax.map` / `lax.scan`,
  for the compiler's sake: one body each, not one a block and an expert); W_o.
- MLPs: SwiGLU in the leading dense layers; after them the expert layer
  (section 2.1.2): the router scores every token against all `router_width`
  experts in float32 (sigmoid), takes the top k by score + selection_bias
  (ties to the lower index), weighs by the chosen scores renormalised to sum
  1 times `routed_scaling_factor`; a loop over the experts *held here* adds
  each one's SwiGLU for the tokens that chose it (a boolean mask); the shared
  expert adds its own for every token.
- head and main loss: z_t = W_head RMSNorm(h_t) over the whole (T, V) logits
  of one row at a time; the row's loss is the mean over t = 0..T-2 of
  -log softmax(z_t)[id_{t+1}].
- multi-token prediction, depth 1 (section 2.2): for t = 0..T-2,
  h'_t = W_eh [RMSNorm_e(Emb(id_{t+1})); RMSNorm_h(h_t)], h_t the last
  block's output before the final norm; one more block over those T-1
  positions; its own final RMSNorm; the shared W_head and embedding; the mean
  over t = 0..T-3 of -log softmax(z'_t)[id_{t+2}]. Row loss = main +
  `mtp_loss_weight` * prediction; the batch's loss is the mean over its rows.

What the experts, heads and vocabulary rows held elsewhere would add is left
out, as in the program; the labels that come with a batch are not read.

Departures from the published description, as the configuration file lists
them: the selection bias takes no gradient and is never updated (its rule is
not in the config); lambda 0.3, the order inside the concatenation and the
tap before the final norm are assumed. The parameter tree has the layout of
the program's weights (names of the leaves), nothing else of it. `precision`
rounds the operands and the result of every projection, of the attention
products and of the vocabulary projection; the router, the norms, the
rotation and the log-sum-exp stay float32 (the program keeps them so too).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

#: query rows of one block of the written-out softmax (memory, not meaning)
QUERY_BLOCK = 1024


def rmsnorm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * p["scale"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(p, x, mm):
    return mm(silu(mm(x, p["gate"]["kernel"])) * mm(x, p["up"]["kernel"]),
              p["down"]["kernel"])


def rotate(x, theta):
    """x (B, T, ..., D), position t along axis 1: the pair (x[2i], x[2i+1])
    turned by the angle t * theta^(-2i / D)."""
    T, D = x.shape[1], x.shape[-1]
    i = jnp.arange(D // 2, dtype=jnp.float32)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (-2.0 * i / D)
    angle = angle.reshape((T,) + (1,) * (x.ndim - 3) + (D // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      a * jnp.sin(angle) + b * jnp.cos(angle)],
                     axis=-1).reshape(x.shape)


def mla(config, p, x, precision):
    B, T, _ = x.shape
    H, r, eps = (config["num_attention_heads"], config["kv_lora_rank"],
                 config["rms_norm_eps"])
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    theta = float(config["rope_theta"])
    mm = functools.partial(common.matmul, precision=precision)
    c_q = rmsnorm(mm(x, p["q_a_proj"]["kernel"]), p["q_a_norm"], eps)
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(B, T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], theta)], axis=-1)
    kva = mm(x, p["kv_a_proj"]["kernel"])
    c = rmsnorm(kva[..., :r], p["kv_a_norm"], eps)
    k_r = rotate(kva[..., r:], theta)
    kv = mm(c, p["kv_b_proj"]["kernel"]).reshape(B, T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r[:, :, None], H, 2)],
                        axis=-1)
    v = kv[..., dn:]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))      # B, H, T, .
    # the written-out softmax, a block of query rows at a time (the blocks
    # walked by `lax.map`, one compiled body for all); the last block's rows
    # past T are zeros masked as row T - 1 and are cut off
    blocks = -(-T // QUERY_BLOCK)
    size = min(QUERY_BLOCK, T)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * size - T), (0, 0)))

    @jax.checkpoint
    def query_block(start):
        rows = jnp.minimum(start + jnp.arange(size), T - 1)
        sc = mm(jax.lax.dynamic_slice_in_dim(q, start, size, axis=2),
                k.transpose(0, 1, 3, 2)) / (dn + dr) ** 0.5
        sc = jnp.where(jnp.arange(T)[None, :] <= rows[:, None], sc, -jnp.inf)
        return mm(jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(query_block, jnp.arange(blocks) * size)
    o = jnp.moveaxis(o, 0, 2)                               # B, H, blocks, .
    o = o.reshape(B, H, blocks * size, dv)[:, :, :T]
    return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * dv),
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
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
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
        (jnp.arange(config["n_routed_experts"]), p["expert_gate"],
         p["expert_up"], p["expert_down"]))
    for i in range(config["n_shared_experts"]):
        y = y + swiglu(p[f"shared{i}"], xf, mm)
    return y.reshape(B, T, d)


def block(config, dense, p, x, precision):
    eps = config["rms_norm_eps"]
    x = x + mla(config, p["mixer"], rmsnorm(x, p["input_norm"], eps),
                precision)
    h = rmsnorm(x, p["post_attention_norm"], eps)
    if dense:
        mm = functools.partial(common.matmul, precision=precision)
        return x + swiglu(p["mlp"], h, mm)
    return x + experts(config, p["mlp"], h, precision)


def hidden(config, params, tokens, precision="f32"):
    """(B, T) ids -> (the last block's output before the final norm, the
    prediction module's before its own or None; it has T-1 positions)."""
    P = params["params"]
    eps = config["rms_norm_eps"]
    x = P["embed"]["embedding"][tokens]
    dense, layers = config["first_k_dense_replace"], config["num_hidden_layers"]

    def layer(is_dense):
        return jax.checkpoint(functools.partial(block, config, is_dense,
                                                precision=precision))
    for i in range(dense):
        x = layer(True)(P[f"block{i}"], x)
    if layers > dense:
        # the expert layers one after another: their parameters stacked and
        # walked by `lax.scan`, one compiled body for all
        stacked = common.tree_map(lambda *a: jnp.stack(a), *(
            P[f"block{i}"] for i in range(dense, layers)))
        x, _ = jax.lax.scan(lambda x, p: (layer(False)(p, x), None), x,
                            stacked)
    if not config["num_nextn_predict_layers"]:
        return x, None
    M = P["mtp"]
    pair = jnp.concatenate(
        [rmsnorm(P["embed"]["embedding"][tokens[:, 1:]], M["enorm"], eps),
         rmsnorm(x[:, :-1], M["hnorm"], eps)], axis=-1)
    x2 = common.matmul(pair, M["eh_proj"]["kernel"], precision)
    x2 = layer(False)(M["block"], x2)
    return x, x2


def logits(config, params, h, norm, precision="f32"):
    """z_t = W_head RMSNorm(h_t), whole."""
    return common.matmul(rmsnorm(h, norm, config["rms_norm_eps"]),
                         params["params"]["head"]["kernel"], precision)


def forward(config, params, tokens, precision="f32"):
    """(B, T) int32 ids -> (B, T, V) float32 logits of the main head."""
    x, _ = hidden(config, params, tokens, precision)
    return logits(config, params, x, params["params"]["norm"], precision)


def token_losses(z, targets):
    """-log softmax(z_t)[target_t]: (B, T, V), (B, T) -> (B, T)."""
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def row_losses(config, params, tokens, precision="f32", parts=False):
    """One loss a row: main + mtp_loss_weight * prediction (module
    docstring); with `parts` the two terms apart."""
    x, x2 = hidden(config, params, tokens, precision)
    P = params["params"]
    main = jnp.mean(token_losses(
        logits(config, params, x, P["norm"], precision)[:, :-1],
        tokens[:, 1:]), axis=1)
    extra = jnp.zeros_like(main)
    if x2 is not None:
        z2 = logits(config, params, x2, P["mtp"]["norm"], precision)
        extra = jnp.mean(token_losses(z2[:, :-1], tokens[:, 2:]), axis=1)
    if parts:
        return main, extra
    return main + config.get("mtp_loss_weight", 0.3) * extra


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
