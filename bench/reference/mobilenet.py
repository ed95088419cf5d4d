"""Plain MobileNetV1 forward (Howard et al. 2017, Table 1), from the
configuration file's published sizes and the seeded weights of
``bench.weights``, in float32 at the highest matmul precision.

A 3x3 stride-2 stem conv, then depthwise-separable blocks: a 3x3
depthwise conv (``feature_group_count`` = channels, filter ``(3, 3, 1,
C)``), batch norm, ReLU, a 1x1 pointwise conv, batch norm, ReLU; global
average pooling and a fully connected head.  Inference-mode batch norm;
convolutions pad "SAME" (the extra row and column at the bottom and
right), as the configuration states.

``precision="int8"`` or ``"fp8"`` (e4m3) is the control: every
convolution and the head take their weights quantised per output channel
and their inputs per pixel, with absmax scales, and compute on the
dequantised values.  A depthwise filter's output channel is its input
channel, so its weights are quantised per channel over the 9 taps.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.resnet import HIGHEST, QUANT
from bench.weights import leaf


@functools.partial(jax.jit, static_argnames=("stride", "groups", "eps",
                                             "precision"))
def _conv_bn_relu(x, w, scale, bias, mean, var, *, stride, groups, eps,
                  precision):
    if precision in QUANT:
        x = QUANT[precision](x, axis=-1)
        w = QUANT[precision](w, axis=(0, 1, 2))
    y = lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 feature_group_count=groups,
                                 precision=HIGHEST)
    return jax.nn.relu((y - mean) * lax.rsqrt(var + eps) * scale + bias)


def blocks(config: Dict, seed: int, images, precision: str = "f32"):
    """The activations after the stem and after each depthwise-separable
    block, in order."""
    eps = float(config["batchnorm_eps"])
    dt = jnp.dtype(config["dtype"])

    def conv(x, block, conv_name, k, cin, cout, stride, groups=1):
        def w(name, shape):
            return leaf(seed, f"{block}/{name}", shape, dt)
        bn = f"{conv_name}_bn"
        return _conv_bn_relu(
            x, w(f"{conv_name}_w", (k, k, cin // groups, cout)),
            w(f"{bn}_scale", (cout,)), w(f"{bn}_bias", (cout,)),
            w(f"{bn}_mean", (cout,)), w(f"{bn}_var", (cout,)),
            stride=stride, groups=groups, eps=eps, precision=precision)

    st = config["stem"]
    cin, k = st["channels"], config["depthwise_kernel"]
    x = conv(jnp.asarray(images, jnp.float32), "stem", "stem", st["conv"],
             config["image_channels"], cin, st["stride"])
    out = [x]
    for i, (cout, stride) in enumerate(config["blocks"]):
        x = conv(x, f"ds{i}", "dw", k, cin, cin, stride, groups=cin)
        x = conv(x, f"ds{i}", "pw", 1, cin, cout, 1)
        out.append(x)
        cin = cout
    return out


def forward(config: Dict, seed: int, images, precision: str = "f32"):
    """Logits ``(batch, num_classes)`` of ``images`` (NHWC)."""
    x = jnp.mean(blocks(config, seed, images, precision)[-1], axis=(1, 2))
    cin = config["blocks"][-1][0]
    n = config["num_classes"]
    dt = jnp.dtype(config["dtype"])
    fw = leaf(seed, "head/fc_w", (cin, n), dt)
    if precision in QUANT:
        x, fw = QUANT[precision](x, axis=-1), QUANT[precision](fw, axis=0)
    return (jnp.matmul(x, fw, precision=HIGHEST)
            + leaf(seed, "head/fc_b", (n,), dt))
