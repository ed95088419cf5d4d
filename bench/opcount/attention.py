"""Operations and bytes of decode attention, counted from its shapes: one
query token against the ``length`` cached keys and values of its
sequence."""
from __future__ import annotations

from typing import Iterable, Tuple


def decode_attention(lengths: Iterable[int], heads: int, kv_heads: int,
                     head_dim: int, itemsize: int = 2
                     ) -> Tuple[float, float]:
    """FLOPs and least HBM bytes of one layer's decode attention for rows
    attending over ``lengths`` keys each: scores and weighted values are
    ``2 * heads * head_dim`` FLOPs per key each; the bytes are the row's
    keys and values (shared by the heads of a group), its query and its
    output."""
    flops = nbytes = 0.0
    for L in lengths:
        flops += 4.0 * heads * head_dim * L
        nbytes += itemsize * (2.0 * kv_heads * head_dim * L
                              + 2.0 * heads * head_dim)
    return flops, nbytes
