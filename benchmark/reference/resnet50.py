"""Plain reference of the bottleneck ResNet the `resnet50` config runs.

He et al. 2015, table 1: a 7x7/2 stem, a 3x3/2 max-pool, four stages of
bottleneck blocks (1x1 reduce, 3x3, 1x1 expand; stride 2 on the 3x3 of a
stage's first block from the second stage on; a 1x1 projection where the
shape changes), the mean over positions and a linear head. The departures the
configuration file states are followed: one-group GroupNorm (eps 1e-6) for
BatchNorm, XLA's SAME padding, raw 0..255 pixels.
"""

import functools

import jax
import jax.numpy as jnp

from . import common

GN_EPS = 1e-6


def conv(x, kernel, stride, precision):
    return common.lowp(jax.lax.conv_general_dilated(
        common.lowp(x, precision), common.lowp(kernel, precision),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=common.HIGHEST), precision)


def groupnorm(x, p):
    """One group: statistics over height, width and channels of each row."""
    mean = jnp.mean(x, axis=(1, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(1, 2, 3), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + GN_EPS) * p["scale"] + p["bias"]


def bottleneck(p, x, stride, precision):
    y = conv(x, p["Conv_0"]["kernel"], 1, precision)
    y = jax.nn.relu(groupnorm(y, p["GroupNorm_0"]))
    y = conv(y, p["Conv_1"]["kernel"], stride, precision)
    y = jax.nn.relu(groupnorm(y, p["GroupNorm_1"]))
    y = conv(y, p["Conv_2"]["kernel"], 1, precision)
    y = groupnorm(y, p["GroupNorm_2"])
    if "Conv_3" in p:
        x = conv(x, p["Conv_3"]["kernel"], stride, precision)
    return jax.nn.relu(x + y)


def forward(config, params, images, precision="f32"):
    """(B, H, W, 3) uint8 or float rows -> (B, num_classes) float32 logits."""
    P = params["params"]
    x = images.astype(jnp.float32)
    x = conv(x, P["Conv_0"]["kernel"], 2, precision)
    x = jax.nn.relu(groupnorm(x, P["GroupNorm_0"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    n = 0
    for stage, depth in enumerate(config["blocks_per_stage"]):
        for b in range(depth):
            stride = 2 if (stage > 0 and b == 0) else 1
            x = jax.checkpoint(functools.partial(
                bottleneck, stride=stride, precision=precision))(
                P[f"_BottleneckBlock_{n}"], x)
            n += 1
    x = jnp.mean(x, axis=(1, 2))
    return common.matmul(x, P["Dense_0"]["kernel"], precision) \
        + P["Dense_0"]["bias"]


train_steps = functools.partial(common.train_steps, forward)
