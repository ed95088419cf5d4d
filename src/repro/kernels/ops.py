"""Jit'd wrappers routing the op layer onto the Pallas kernels.

``interpret=True`` executes kernel bodies on CPU for validation; on the TPU
target ``interpret=False`` compiles through Mosaic.  Tile parameters come
from the tiling pass (plan.tiles); ``None`` falls back to kernel defaults.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import matmul_fused as _mm
from repro.kernels import attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import conv2d as _cv
from repro.kernels import depthwise_conv2d as _dw


def matmul_fused(x, w, *, bias=None, w2=None, act=None, tile=None,
                 out_dtype=None, vmem_accum=True, interpret=False):
    return _mm.matmul_fused(
        x, w, bias=bias, w2=w2, act=act,
        tile=tile or (256, 512, 256), out_dtype=out_dtype,
        vmem_accum=vmem_accum, interpret=interpret)


def flash_attention(q, k, v, positions=None, *, causal=True, window=None,
                    softcap=None, tile=None, q_offset=0, interpret=False):
    return _fa.flash_attention(
        q, k, v, positions=positions, causal=causal, window=window,
        softcap=softcap, tile=tile or (256, 512), q_offset=q_offset,
        interpret=interpret)


def decode_attention(q, kc, vc, pos, qpos, *, window=None, softcap=None,
                     tile=None, interpret=False):
    return _da.decode_attention(
        q, kc, vc, pos, qpos, window=window, softcap=softcap,
        block_k=tile or 2048, interpret=interpret)


def paged_decode_attention(q, kp, vp, bt, lens, *, qpos=None, window=None,
                           softcap=None, tile=None, interpret=False):
    # the paged path has no free tile knob: the physical pool block is the
    # kernel's KV block (tile accepted for wrapper uniformity).  qpos (B, Sq)
    # unlocks the chunked catch-up mode (Sq = k > 1).
    return _da.paged_decode_attention(q, kp, vp, bt, lens, qpos=qpos,
                                      window=window, softcap=softcap,
                                      interpret=interpret)


def copy_block(pool, src, dst, *, interpret=False):
    return _da.copy_block(pool, src, dst, interpret=interpret)


def conv2d_fused(x, w, *, stride=1, padding="SAME", bn=None, act=None,
                 tile=None, interpret=False):
    # the tiling pass hands (block_h, block_c); a bare int means block_c only
    if isinstance(tile, tuple):
        block_h, block_c = tile
    else:
        block_h, block_c = None, (tile or 128)
    return _cv.conv2d_fused(x, w, stride=stride, padding=padding, bn=bn,
                            act=act, block_c=block_c, block_h=block_h,
                            interpret=interpret)


def depthwise_conv2d(x, w, *, stride=1, padding="SAME", bn=None, act=None,
                     tile=None, interpret=False):
    # the tiling pass hands (block_h, block_c)
    block_h, block_c = tile if tile is not None else (None, 512)
    return _dw.depthwise_conv2d(x, w, stride=stride, padding=padding, bn=bn,
                                act=act, block_h=block_h, block_c=block_c,
                                interpret=interpret)
