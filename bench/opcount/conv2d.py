"""Operations and bytes of one 2-D convolution (with its folded batch norm
and activation), counted from its shapes: the work of the op, whichever
kernel implements it."""
from __future__ import annotations

from typing import Tuple


def conv2d(n: int, h: int, w: int, cin: int, cout: int, k: int,
           stride: int, itemsize: int = 2) -> Tuple[float, float]:
    """FLOPs and least HBM bytes of a "SAME" ``k``x``k`` convolution of
    ``n`` images of ``h``x``w``x``cin`` to ``cout`` channels: read the input
    and the weights once, write the output once."""
    ho, wo = -(-h // stride), -(-w // stride)
    flops = 2.0 * n * ho * wo * k * k * cin * cout
    nbytes = float(itemsize) * (n * h * w * cin + k * k * cin * cout
                                + n * ho * wo * cout)
    return flops, nbytes
