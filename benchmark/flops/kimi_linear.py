"""Operations of the `kimi_linear` hybrid from its shapes, for the share of
it one rank holds (the configuration's counts of heads and experts held).

2 operations a multiply-add; backward twice the forward; recomputation under
`remat` (and the chunked scan's own) is NOT counted; look-ups, norms,
convolutions of kernel 4 and element-wise work count nothing.

Per token, forward, with d the hidden size, H the heads held:

- KDA: q, k, v, o projections 4 d H K; the two bottlenecks (decay, output
  gate) 2 (d K + K H K); the step's projection d H; the state: per head the
  recurrence reads and writes a K x V state: k^T S, the rank-one update and
  S^T q are 3 K V multiply-adds (the token-by-token count; the chunked form
  spends more, on purpose uncounted).
- latent attention: q d H (nope + rope); the compression d (rank + rope);
  the expansion rank H (nope + v); o H v d; the causal scores and values
  T (nope + rope + v) H / 2 a token.
- dense MLP 3 d f. Expert layer: router d W; shared experts 3 d f_e each;
  routed experts 3 d f_e times the *expected* assignments a token to the
  experts held, top_k * held / W (uniform routing: 512 tokens an expert a
  step of 16,384 tokens at 8 of 256 held; a run's real count moves with the
  seed, the operations counted here do not).
"""


def layer_kinds(config):
    lin = config["linear_attn_config"]
    return ["kda" if i in lin["kda_layers"] else "mla"
            for i in range(1, config["num_hidden_layers"] + 1)]


def kda_macs_per_token(config):
    d, lin = config["hidden_size"], config["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    proj = 4 * d * H * K + 2 * (d * K + K * H * K) + d * H
    return proj + 3 * H * K * K


def mla_macs_per_token(config):
    d, H, T = (config["hidden_size"], config["num_attention_heads"],
               config["input"]["seq_len"])
    r, dn, dr, dv = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                     config["qk_rope_head_dim"], config["v_head_dim"])
    proj = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    return proj + T * (dn + dr + dv) * H / 2


def expected_assignments_per_token(config):
    return (config["num_experts_per_token"] * config["num_experts"]
            / config["router_width"])


def moe_macs_per_token(config):
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    return (d * config["router_width"]
            + 3 * d * fe * (config["num_shared_experts"]
                            + expected_assignments_per_token(config)))


def forward_macs_per_token(config):
    d = config["hidden_size"]
    total = 0.0
    for i, kind in enumerate(layer_kinds(config)):
        total += (kda_macs_per_token(config) if kind == "kda"
                  else mla_macs_per_token(config))
        total += (3 * d * config["intermediate_size"]
                  if i < config["first_k_dense_replace"]
                  else moe_macs_per_token(config))
    return total


def forward_flops_per_row(config):
    T = config["input"]["seq_len"]
    return (2.0 * forward_macs_per_token(config) * T
            + 2.0 * config["hidden_size"] * config["num_classes"])


def train_flops_per_row(config):
    return 3 * forward_flops_per_row(config)
