"""Operations and bytes of one matrix multiplication, counted from its
shapes."""
from __future__ import annotations

from typing import Tuple


def matmul(m: int, k: int, n: int, itemsize: int = 2) -> Tuple[float, float]:
    """FLOPs and least HBM bytes of ``(m, k) @ (k, n)``: each operand read
    once, the product written once."""
    return 2.0 * m * k * n, float(itemsize) * (m * k + k * n + m * n)
