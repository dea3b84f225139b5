"""Operations and bytes of the `lfm2_moe` family from its shapes, for the
share of it one chip holds (the configuration's counts of heads, key/value
heads, routed experts and vocabulary rows held).

2 operations a multiply-add; backward twice the forward; recomputation under
`remat` (the blocks' and the loss walk's own) is NOT counted; look-ups,
norms, the rotation, the gates and the depthwise convolution's three taps,
the softmax over the vocabulary and other element-wise work count nothing.

Per token, forward, with d the hidden size, H / Hkv the query / key-value
heads held of width D, T the row:

- conv mixer: W_in d 3d and W_out d d.
- attention: W_q d H D, W_k and W_v d Hkv D each, W_o H D d; the causal
  scores and values T 2 D H / 2 a token (every query head computes its own,
  grouped or not).
- dense MLP 3 d f. Expert layer: router d W; routed experts 3 d f_e times
  the *expected* assignments a token to the experts held, top_k * held / W
  (uniform routing; a run's real count moves with the seed, the operations
  counted here do not, nor do the tiles the layer walks beyond its load).
- the tied vocabulary head d V, once.

The grouped flash calls: a forward call is two products of (T x D) by
(D x T) shapes for each of the H query heads, half under the causal mask,
and reads q and writes o at H heads but reads k and v at Hkv; the backward
pair (dq, dkv) is five such products and moves q, o, dO, dq at H heads and
k, v, dk, dv at Hkv. The conv mixer's gate, convolution and gate move 3d
lanes in and d out a token forward; backward those and dO in, 3d of
gradient out.
"""


def conv_macs_per_token(config):
    d = config["hidden_size"]
    return 4 * d * d


def attention_macs_per_token(config):
    d, H, Hkv, D = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    T = config["input"]["seq_len"]
    return 2 * d * H * D + 2 * d * Hkv * D + T * D * H


def expected_assignments_per_token(config):
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_width"])


def moe_macs_per_token(config):
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    return (d * config["router_width"]
            + 3 * d * fe * expected_assignments_per_token(config))


def head_macs_per_token(config):
    return config["hidden_size"] * config["vocab_size"]


def forward_macs_per_token(config):
    d, dense = config["hidden_size"], config["num_dense_layers"]
    kinds = config["layer_types"]
    mixers = {"conv": conv_macs_per_token,
              "full_attention": attention_macs_per_token}
    return (sum(mixers[kind](config) for kind in kinds)
            + dense * 3 * d * config["intermediate_size"]
            + (len(kinds) - dense) * moe_macs_per_token(config)
            + head_macs_per_token(config))


def forward_flops_per_row(config):
    return 2.0 * forward_macs_per_token(config) * config["input"]["seq_len"]


def train_flops_per_row(config):
    return 3 * forward_flops_per_row(config)


# ---------------------------------------------- the kernels, a call each

def _heads(config):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["input"]["seq_len"])


def gqa_flash_fwd(config, rows, dtype_bytes=2):
    """(operations, bytes) of one grouped forward call over `rows` rows."""
    H, Hkv, D, T = _heads(config)
    return (4.0 * rows * H * T * T * D * 0.5,
            2.0 * (H + Hkv) * rows * T * D * dtype_bytes)


def gqa_flash_bwd(config, rows, dtype_bytes=2):
    """(operations, bytes) of one dq and one dkv call together."""
    H, Hkv, D, T = _heads(config)
    ops, _ = gqa_flash_fwd(config, rows, dtype_bytes)
    return 2.5 * ops, 4.0 * (H + Hkv) * rows * T * D * dtype_bytes


def short_conv_bytes(config, rows, dtype_bytes=2):
    """(forward, backward) bytes of one conv mixer's gate, convolution and
    gate over `rows` rows: it computes next to nothing, so bytes are its
    whole roofline."""
    lanes = rows * config["input"]["seq_len"] * config["hidden_size"]
    return 4.0 * lanes * dtype_bytes, 8.0 * lanes * dtype_bytes
