"""Operations of the `joyai_llm_flash` family from its shapes, for the share
of it one rank holds (the configuration's counts of heads, routed experts
and vocabulary rows held).

2 operations a multiply-add; backward twice the forward; recomputation under
`remat` (the blocks' and the loss walk's own) is NOT counted; look-ups,
norms, the rotation, the softmax over the vocabulary and other element-wise
work count nothing.

Per token, forward, with d the hidden size, H the heads held, T the row:

- latent attention: the query bottleneck d q_rank + q_rank H (nope + rope);
  the compression d (kv_rank + rope); the expansion kv_rank H (nope + v);
  o H v d; the causal scores and values T (nope + rope + v) H / 2 a token,
  at the unpadded widths (the flash calls pad q, k and v to 256 lanes: the
  padding's work is not the model's).
- dense MLP 3 d f. Expert layer: router d W; shared experts 3 d f_e each;
  routed experts 3 d f_e times the *expected* assignments a token to the
  experts held, top_k * held / W (uniform routing; a run's real count moves
  with the seed, the operations counted here do not).
- the vocabulary head d V, once a use: the main head and the prediction
  module's.
- the prediction module: W_eh 2 d d, one latent + expert block, the head.
  It is counted over all T positions, as the program runs it.
"""


def mla_macs_per_token(config):
    d, H, T = (config["hidden_size"], config["num_attention_heads"],
               config["input"]["seq_len"])
    qr, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    proj = (d * qr + qr * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)
    return proj + T * (dn + dr + dv) * H / 2


def expected_assignments_per_token(config):
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_width"])


def moe_macs_per_token(config):
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    return (d * config["router_width"]
            + 3 * d * fe * (config["n_shared_experts"]
                            + expected_assignments_per_token(config)))


def head_macs_per_token(config):
    return config["hidden_size"] * config["vocab_size"]


def forward_macs_per_token(config):
    d = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    layers = config["num_hidden_layers"]
    total = layers * mla_macs_per_token(config) \
        + dense * 3 * d * config["intermediate_size"] \
        + (layers - dense) * moe_macs_per_token(config) \
        + head_macs_per_token(config)
    for _ in range(config["num_nextn_predict_layers"]):
        total += (2 * d * d + mla_macs_per_token(config)
                  + moe_macs_per_token(config) + head_macs_per_token(config))
    return total


def forward_flops_per_row(config):
    return 2.0 * forward_macs_per_token(config) * config["input"]["seq_len"]


def train_flops_per_row(config):
    return 3 * forward_flops_per_row(config)
