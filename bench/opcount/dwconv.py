"""Operations and bytes of one depthwise 2-D convolution (with its folded
batch norm and activation), counted from its shapes: the work of the op,
whichever kernel implements it."""
from __future__ import annotations

from typing import Tuple


def dwconv(n: int, h: int, w: int, c: int, stride: int, k: int = 3,
           itemsize: int = 2) -> Tuple[float, float]:
    """FLOPs and least HBM bytes of a "SAME" ``k``x``k`` depthwise
    convolution (one filter per channel) of ``n`` images of
    ``h``x``w``x``c``: read the input and the weights once, write the
    output once."""
    ho, wo = -(-h // stride), -(-w // stride)
    flops = 2.0 * n * ho * wo * k * k * c
    nbytes = float(itemsize) * (n * h * w * c + k * k * c + n * ho * wo * c)
    return flops, nbytes
