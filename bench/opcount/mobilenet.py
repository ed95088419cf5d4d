"""The work counter of a MobileNetV1 configuration file (its ``opcount``
key): the dense convolutions of one image (``convs``, as the CNN runner
asks of every CNN configuration's counter), its depthwise convolutions
(``dwconvs``, read by ``bench/metrics/dwconv_roofline.py``), and the
model's FLOPs per image."""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.opcount.conv2d import conv2d
from bench.opcount.dwconv import dwconv
from bench.opcount.matmul import matmul


def _sizes(config: Dict):
    """(input side, channels in, channels out, depthwise stride) of each
    depthwise-separable block of one image."""
    st = config["stem"]
    s = -(-config["image_size"] // st["stride"])
    cin = st["channels"]
    for cout, stride in config["blocks"]:
        yield s, cin, cout, stride
        s, cin = -(-s // stride), cout


def convs(config: Dict) -> List[Tuple[int, int, int, int, int, int]]:
    """``(h, w, cin, cout, k, stride)`` of every dense convolution of one
    image, in order: the stem, then each block's 1x1 pointwise conv."""
    st = config["stem"]
    s = config["image_size"]
    out = [(s, s, config["image_channels"], st["channels"],
            st["conv"], st["stride"])]
    for side, cin, cout, stride in _sizes(config):
        so = -(-side // stride)
        out.append((so, so, cin, cout, 1, 1))
    return out


def dwconvs(config: Dict) -> List[Tuple[int, int, int, int]]:
    """``(h, w, c, stride)`` of every depthwise convolution of one image."""
    return [(side, side, cin, stride)
            for side, cin, _, stride in _sizes(config)]


def head_matmul(config: Dict) -> Tuple[int, int]:
    return config["blocks"][-1][0], config["num_classes"]


def flops_per_image(config: Dict) -> float:
    k = config["depthwise_kernel"]
    f = sum(conv2d(1, *c)[0] for c in convs(config))
    f += sum(dwconv(1, *d, k=k)[0] for d in dwconvs(config))
    return f + matmul(1, *head_matmul(config))[0]
