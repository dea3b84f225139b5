"""Operations of the bottleneck ResNet from its shapes.

Multiply-adds of every convolution and of the head; 2 operations a
multiply-add; backward is twice the forward (one product for the input's
gradient, one for the weight's). Normalisation, ReLU, pooling and the
optimizer are not counted, and nothing recomputed is.
"""


def _same(size, stride):
    return -(-size // stride)


def forward_macs(config):
    """Multiply-adds of one row's forward pass."""
    inp = config["input"]
    h, w, c = inp["height"], inp["width"], inp["channels"]
    widths = config["widths"]
    stem = widths[0] // 4
    h, w = _same(h, 2), _same(w, 2)
    macs = h * w * 7 * 7 * c * stem
    h, w, c = _same(h, 2), _same(w, 2), stem
    for stage, (width, depth) in enumerate(zip(widths,
                                               config["blocks_per_stage"])):
        inner = width // 4
        for b in range(depth):
            stride = 2 if (stage > 0 and b == 0) else 1
            macs += h * w * c * inner                      # 1x1 reduce
            ho, wo = _same(h, stride), _same(w, stride)
            macs += ho * wo * 3 * 3 * inner * inner        # 3x3
            macs += ho * wo * inner * width                # 1x1 expand
            if c != width or stride != 1:
                macs += ho * wo * c * width                # projection
            h, w, c = ho, wo, width
    return macs + c * config["num_classes"]


def train_flops_per_row(config):
    return 3 * 2 * forward_macs(config)
