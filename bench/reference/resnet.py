"""Plain ResNet-34 forward (He et al. 2015, Table 1, 34-layer), from the
configuration file's published sizes and the seeded weights of
``bench.weights``, in float32 at the highest matmul precision.

Inference-mode batch norm after every convolution, basic blocks of two 3x3
convolutions, a 1x1 projection shortcut (with its own batch norm) where the
shape changes, ReLU after the stem and after each residual add, a 3x3/2 max
pool after the stem, global average pooling and a fully connected head.
Convolutions and pooling pad "SAME" (the extra row and column go at the
bottom and right), as the configuration states.

``precision="int8"`` or ``"fp8"`` (e4m3) is the control: every convolution
and the head take their weights quantised per output channel and their
inputs per pixel, with absmax scales, and compute on the dequantised
values.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.weights import leaf

HIGHEST = lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric int8 absmax quantise-dequantise along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _qf8(x, axis):
    """float8 (e4m3) quantise-dequantise along ``axis`` (absmax scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"int8": _q8, "fp8": _qf8}


@functools.partial(jax.jit, static_argnames=("stride", "eps", "precision"))
def _conv_bn(x, w, scale, bias, mean, var, *, stride, eps, precision):
    if precision in QUANT:
        x = QUANT[precision](x, axis=-1)
        w = QUANT[precision](w, axis=(0, 1, 2))
    y = lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=HIGHEST)
    return (y - mean) * lax.rsqrt(var + eps) * scale + bias


def _maxpool(x, window, stride):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), "SAME")


def forward(config: Dict, seed: int, images, precision: str = "f32"):
    """Logits ``(batch, num_classes)`` of ``images`` (NHWC)."""
    eps = float(config["batchnorm_eps"])
    dt = jnp.dtype(config["dtype"])

    def w(name, shape):
        return leaf(seed, name, shape, dt)

    def conv_bn(x, block, conv, bn, k, cin, cout, stride):
        return _conv_bn(x, w(f"{block}/{conv}_w", (k, k, cin, cout)),
                        w(f"{block}/{bn}_scale", (cout,)),
                        w(f"{block}/{bn}_bias", (cout,)),
                        w(f"{block}/{bn}_mean", (cout,)),
                        w(f"{block}/{bn}_var", (cout,)),
                        stride=stride, eps=eps, precision=precision)

    st = config["stem"]
    x = jnp.asarray(images, jnp.float32)
    x = jax.nn.relu(conv_bn(x, "stem", "stem", "stem_bn", st["conv"],
                            config["image_channels"], st["channels"],
                            st["stride"]))
    x = _maxpool(x, st["maxpool"], st["maxpool_stride"])
    cin, bi = st["channels"], 0
    for stage, (reps, cout) in enumerate(zip(config["stage_blocks"],
                                             config["stage_channels"])):
        for r in range(reps):
            s = 2 if (r == 0 and stage > 0) else 1
            blk = f"res{bi}"
            sc = x
            if s != 1 or cin != cout:
                sc = conv_bn(x, blk, "proj", "proj_bn", 1, cin, cout, s)
            h = jax.nn.relu(conv_bn(x, blk, "c1", "bn1", 3, cin, cout, s))
            h = conv_bn(h, blk, "c2", "bn2", 3, cout, cout, 1)
            x = jax.nn.relu(h + sc)
            cin, bi = cout, bi + 1
    x = jnp.mean(x, axis=(1, 2))
    fw = w("head/fc_w", (cin, config["num_classes"]))
    if precision in QUANT:
        x, fw = QUANT[precision](x, axis=-1), QUANT[precision](fw, axis=0)
    return jnp.matmul(x, fw, precision=HIGHEST) + w(
        "head/fc_b", (config["num_classes"],))
