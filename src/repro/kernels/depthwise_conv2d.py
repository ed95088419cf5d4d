"""Depthwise 2-D convolution Pallas kernel with fused BN/activation epilogue.

A depthwise conv (MobileNetV1's 3x3 filters, one per channel) does
kh·kw multiply-adds per output element and reads each input element about
once: it is bound by memory and by the VPU, never by the MXU, so the
taps run as f32 multiply-adds on the vector unit, channels on lanes.

Grid: (batch, channel blocks, output row blocks).  At the first row block
of each (image, channel block) the kernel copies the image, in f32, into a
zero-bordered VMEM scratch of one plane per (row phase, column tap): plane
``(p, dw)`` holds the padded input's rows ``p::stride`` and columns
``dw % stride::stride``, moved ``dw // stride`` columns left, so that every
tap of every output row reads one contiguous, sublane-aligned ``(wo, bc)``
slice at column ``_OFF``; the misaligned shifts are paid once per input
element at the copy, not at each of the taps.  (The stride-2 column split
uses strided loads.)  No padded or space-to-depth copy of the activation is
made in HBM: the kernel reads the unpadded input once and writes the output
once.  The scratch's borders are zeroed once, at the first grid step, and
never written again, so every grid axis is sequential ("arbitrary").

Per output row, the taps accumulate in f32 from a bias-initialised
accumulator: the folded batch norm ``(y - mean) * scale / sqrt(var + eps)
+ bias`` is applied by scaling the taps' weights and starting from
``bias - mean * scale / sqrt(var + eps)``, then the activation, then one
write-back in the input's dtype (the LF + CW epilogue of ``conv2d_fused``).

The tiling pass hands ``(block_h, block_c)``: output rows per grid step
(the largest divisor of the output height up to it) and lanes per channel
block (a multiple of 128 dividing C, else all of C).  A channel count
below 128 fills only C of the 128 lanes: the activation arrives from HBM
in that lane-padded layout already (PERF.md §4), so the kernel does not
repack it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_OFF = 8                            # the column every tap reads from
_VMEM_DEFAULT = 16 * 2 ** 20        # Mosaic's default scoped VMEM limit
VMEM_CAP = 96 * 2 ** 20             # largest working set a grid step may take
_EPS = 1e-5


def geometry(h: int, w: int, kh: int, kw: int, stride: int, padding: str
             ) -> Tuple[int, int, int, int]:
    """``(ho, wo, pad_top, pad_left)``; "SAME" puts an odd total pad's
    extra row and column at the bottom and right, as ``lax.conv`` does."""
    if padding == "SAME":
        ho, wo = -(-h // stride), -(-w // stride)
        return (ho, wo, max((ho - 1) * stride + kh - h, 0) // 2,
                max((wo - 1) * stride + kw - w, 0) // 2)
    return (h - kh) // stride + 1, (w - kw) // stride + 1, 0, 0


def _phase(p: int, pad: int, n: int, s: int, extent: int
           ) -> Tuple[int, int, int]:
    """Phase ``p`` of one axis: ``(first input index, first phase index,
    count)`` of the input indices ``i`` with ``(i + pad) % s == p`` that
    fall inside the ``extent`` phase positions a tap can read."""
    first = (p - pad) % s
    at = (first + pad - p) // s
    count = max(0, min(-(-(n - first) // s), extent - at))
    return first, at, count


def _tiled_bytes(shape, itemsize: int) -> int:
    """Bytes of a VMEM buffer once its minor dims pad to the (8, 128) tile."""
    *lead, r, c = shape
    n = 1
    for d in lead:
        n *= d
    return n * (-(-r // 8) * 8) * (-(-c // 128) * 128) * itemsize


def _kernel(x_ref, w_ref, *rest, kh, kw, s, rows, cols, bh, wo, act,
            has_bn):
    from repro.core.ops_impl import _act
    bn_ref = rest[0] if has_bn else None
    o_ref, scr = rest[has_bn], rest[has_bn + 1]
    stage = rest[has_bn + 2] if s > 1 else None
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))

    @pl.when(first)
    def _():
        scr[...] = jnp.zeros(scr.shape, scr.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        for p, (r0, ra, nr) in enumerate(rows):
            def fill(a, carry, p=p, r0=r0, ra=ra):
                v = x_ref[0, r0 + a * s].astype(jnp.float32)
                # plane (p, dw) holds phase (p, dw % s) moved dw // s
                # columns left, so that every tap reads at column _OFF
                if s == 1:
                    c0, at, nc = cols[0]
                    for dw in range(kw):
                        scr[p * kw + dw, ra + a,
                            pl.ds(_OFF + at - dw, nc), :] = v[c0:c0 + nc]
                    return carry
                # Mosaic's strided loads take 32-bit rows of at most 128
                # lanes: stage the row in f32, 128 lanes at a time, then
                # split its columns into phases
                ln = stage.shape[-1]
                for k in range(stage.shape[0]):
                    stage[k] = v[:, k * ln:(k + 1) * ln]
                    for q, (c0, at, nc) in enumerate(cols):
                        if not nc:
                            continue
                        src = stage[k, pl.ds(c0, nc, stride=s), :]
                        for dw in range(q, kw, s):
                            scr[p * kw + dw, ra + a,
                                pl.ds(_OFF + at - dw // s, nc),
                                pl.ds(k * ln, ln)] = src
                return carry
            jax.lax.fori_loop(0, nr, fill, 0)

    w = w_ref[...]
    bc = w.shape[-1]
    if has_bn:
        scale, bias, mean, var = (bn_ref[i:i + 1, :] for i in range(4))
        a = scale * jax.lax.rsqrt(var + _EPS)
        w = w * a
        b0 = bias - mean * a
    else:
        b0 = jnp.zeros((1, bc), jnp.float32)
    taps = [w[k:k + 1, :] for k in range(kh * kw)]
    init = jnp.broadcast_to(b0, (wo, bc))
    i0 = pl.program_id(2) * bh

    def row(i, carry):
        acc = init
        for dh in range(kh):
            for dw in range(kw):
                xs = scr[(dh % s) * kw + dw, i0 + i + dh // s,
                         pl.ds(_OFF, wo), :]
                acc = acc + xs * taps[dh * kw + dw]
        if act:
            acc = _act(acc, act)
        o_ref[0, i] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bh, row, 0)


def _fit_rows(n: int, target: Optional[int]) -> int:
    """Largest divisor of ``n`` <= target (rule 2: even division)."""
    b = n if target is None else max(min(target, n), 1)
    while n % b:
        b -= 1
    return b


def _fit_lanes(c: int, target: int) -> int:
    """Largest multiple of 128 dividing ``c`` and <= target; all of ``c``
    where there is none (a lane-sparse or odd channel count)."""
    for b in range(min(target, c) // 128 * 128, 0, -128):
        if c % b == 0:
            return b
    return c


def working_set(h: int, w: int, kh: int, kw: int, stride: int,
                padding: str, bh: int, bc: int, itemsize: int = 2) -> int:
    """VMEM bytes of one grid step: the double-buffered input image and
    output rows of one channel block, the f32 planes and the f32 row a
    stride-2 copy stages."""
    ho, wo, _, _ = geometry(h, w, kh, kw, stride, padding)
    hq, wq = ho + (kh - 1) // stride, wo + (kw - 1) // stride
    return (2 * _tiled_bytes((h, w, bc), itemsize)
            + 2 * _tiled_bytes((bh, wo, bc), itemsize)
            + _tiled_bytes((stride * kw, hq, _OFF + wq, bc), 4)
            + (_tiled_bytes((w, bc), 4) if stride > 1 else 0))


def depthwise_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
                     padding: str = "SAME", bn=None,
                     act: Optional[str] = None, block_h: Optional[int] = None,
                     block_c: int = 512, interpret: bool = False
                     ) -> jax.Array:
    """x: (N, H, W, C) NHWC; w: (kh, kw, 1, C), the HWIO filter of
    ``lax.conv_general_dilated(..., feature_group_count=C)``."""
    N, H, W, C = x.shape
    kh, kw = w.shape[:2]
    s = stride
    ho, wo, pt, pleft = geometry(H, W, kh, kw, s, padding)
    hq, wq = ho + (kh - 1) // s, wo + (kw - 1) // s
    rows = tuple(_phase(p, pt, H, s, hq) for p in range(s))
    cols = tuple(_phase(q, pleft, W, s, wq) for q in range(s))
    bh = _fit_rows(ho, block_h)
    bc = _fit_lanes(C, block_c)
    isz = jnp.dtype(x.dtype).itemsize
    while (bc > 128 and bc % 256 == 0 and C % (bc // 2) == 0
           and working_set(H, W, kh, kw, s, padding, bh, bc, isz)
           > VMEM_CAP):
        bc //= 2
    ws = working_set(H, W, kh, kw, s, padding, bh, bc, isz)
    operands = [x, w.reshape(kh * kw, C).astype(jnp.float32)]
    in_specs = [pl.BlockSpec((1, H, W, bc), lambda n, j, i: (n, 0, 0, j)),
                pl.BlockSpec((kh * kw, bc), lambda n, j, i: (0, j))]
    if bn is not None:
        operands.append(jnp.stack([t.astype(jnp.float32).reshape(C)
                                   for t in bn]))
        in_specs.append(pl.BlockSpec((4, bc), lambda n, j, i: (0, j)))
    kern = functools.partial(_kernel, kh=kh, kw=kw, s=s, rows=rows,
                             cols=cols, bh=bh, wo=wo, act=act,
                             has_bn=bn is not None)
    return pl.pallas_call(
        kern, grid=(N, C // bc, ho // bh), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wo, bc),
                               lambda n, j, i: (n, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, ho, wo, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((s * kw, hq, _OFF + wq, bc), jnp.float32)]
        + ([pltpu.VMEM((-(-bc // 128), W, min(bc, 128)), jnp.float32)]
           if s > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=max(_VMEM_DEFAULT, ws + ws // 4)),
        interpret=interpret)(*operands)
