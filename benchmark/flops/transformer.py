"""Operations of the GPT-2 style encoder from its shapes.

A block's products: fused QKV (3 d^2), the output projection (d^2), the MLP
(2 * mlp_ratio * d^2) per token, and attention's two products per head
(2 * T * d multiply-adds per token, half under a causal mask). 2 operations a
multiply-add; backward twice the forward; recomputation under `remat` is NOT
counted; embeddings are look-ups and count nothing.
"""

from . import attention


def block_params(config):
    """Weights of one block: 12 d^2 in its four kernels at mlp_ratio 4, and
    the MLP's biases and the two LayerNorms beside them."""
    d, r = config["d_model"], config["mlp_ratio"]
    return (4 + 2 * r) * d * d + (r * d + d) + 4 * d


def forward_flops_per_row(config):
    d, T, L = config["d_model"], config["input"]["seq_len"], config["layers"]
    H = config["heads"]
    dense = 2.0 * (4 + 2 * config["mlp_ratio"]) * d * d * T
    attn, _ = attention.flash_fwd(1, H, T, d // H, config.get("causal", False))
    return L * (dense + attn) + 2.0 * d * config["num_classes"]


def train_flops_per_row(config):
    return 3 * forward_flops_per_row(config)
