"""Direct 2-D convolution Pallas kernel with fused BN/activation epilogue.

The paper's workhorse op.  It has two paths, chosen from the shapes alone
(:func:`folds`); both end in the same kernel, f32 accumulation and
in-VMEM batch-norm and activation epilogue before the single write-back
(LF + CW).

Tap path (every conv whose taps do not fold).  Grid: (batch, C_out tiles,
H_out row blocks).  Each step keeps the full (padded, space-to-depth) input
feature map of one image in VMEM — the scoped limit is raised to the
working set where a lane-sparse map outgrows the default — and contracts
the kh×kw taps for one block of ``block_h`` output rows as shifted
(block_h·W_out, C_in)×(C_in, bc) matmuls on the MXU (the TPU-native
analogue of unrolling the filter loops: taps become statically unrolled
matmuls, not scalar MACCs).  The MXU streams every row once per tap
whatever C_in is, so a lane-sparse conv (the 3-channel stem) pays a full
pass for each of its taps.

Folded path (lane-sparse convs with more than one tap).  The wrapper
gathers the taps into one patch tensor (N, 1, H_out, W_out, C_in·kh·kw)
with a single XLA op (a one-hot patches convolution), and the kernel runs
it as a 1x1 stride-1 conv: one matmul with K = C_in·kh·kw per row block,
⌈K/128⌉ MXU passes where the tap path makes kh·kw.  A folded row block
needs no halo, so its input block is the output block's own rows, and the
grid is (batch, row blocks, C_out tiles) so each row block is read once.
A conv folds when its patch tensor, with minor dims padded to 128 lanes,
is no larger than the space-to-depth operand the tap path reads: folding
never adds MXU passes, and this keeps it out where it would move more
bytes.

The tiling pass hands ``(block_h, block_c)`` — the LU/LT row/channel tile
pair; both components are honoured (rule 2: blocks divide the output dims,
falling back to the largest divisor).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import METRICS

_VMEM_DEFAULT = 16 * 2 ** 20        # Mosaic's default scoped VMEM limit


def _kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int,
            bh: int, wo: int, act: Optional[str], has_bn: bool):
    from repro.core.ops_impl import _act
    if has_bn:
        scale_ref, bias_ref, mean_ref, var_ref = rest[:4]
    o_ref = rest[-1]
    # first output row of the block in the input block: a row-blocked
    # (folded) input holds only the block's own rows; a whole-image block
    # is indexed by the row-block grid axis
    r0 = 0 if x_ref.shape[2] == bh else pl.program_id(2) * bh
    ci = x_ref.shape[-1]
    bc = w_ref.shape[-1]
    acc = jnp.zeros((bh * wo, bc), jnp.float32)
    for dh in range(kh):
        for dw in range(kw):
            # tap (dh, dw) of a stride-s conv reads phase (dh % s, dw % s) of
            # the space-to-depth input at offset (dh // s, dw // s): every
            # load is a contiguous ref slice, no strided or value slicing
            ph = (dh % stride) * stride + dw % stride
            xs = x_ref[0, ph, pl.ds(r0 + dh // stride, bh),
                       pl.ds(dw // stride, wo), :]
            xs = xs.astype(jnp.float32).reshape(bh * wo, ci)
            acc += jnp.dot(xs, w_ref[dh, dw].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    if has_bn:
        inv = jax.lax.rsqrt(var_ref[...] + 1e-5)
        acc = (acc - mean_ref[...]) * (inv * scale_ref[...]) + bias_ref[...]
    if act:
        acc = _act(acc, act)
    o_ref[0] = acc.reshape(bh, wo, bc).astype(o_ref.dtype)


def _fit_block(n: int, target: Optional[int]) -> int:
    """Largest divisor of ``n`` <= target (rule 2: even division)."""
    if target is None or target >= n:
        return n
    b = max(min(target, n), 1)
    while n % b:
        b -= 1
    return b


def _lanes(c: int) -> int:
    return -(-c // 128) * 128


def folds(kh: int, kw: int, ci: int, stride: int, ho: int, wop: int) -> bool:
    """Whether a conv's taps fold into one contraction: it has more than
    one tap, and its patch tensor ``(ho, wop, kh*kw*ci)`` holds no more
    elements, once minor dims pad to 128 lanes, than the space-to-depth
    operand ``(s*s, hq, wq, ci)`` the tap path reads (per image; ``wop`` is
    the output width padded to the sublane tile)."""
    if kh * kw == 1:
        return False
    hq, wq = ho + (kh - 1) // stride, wop + (kw - 1) // stride
    return (ho * wop * _lanes(kh * kw * ci)
            <= stride * stride * hq * wq * _lanes(ci))


def conv2d_fused(x: jax.Array, w: jax.Array, *, stride: int = 1,
                 padding: str = "SAME", bn=None, act: Optional[str] = None,
                 block_c: int = 128, block_h: Optional[int] = None,
                 interpret: bool = False) -> jax.Array:
    """x: (N, H, W, CI) NHWC; w: (kh, kw, CI, CO) HWIO."""
    N, H, W, CI = x.shape
    kh, kw, _, CO = w.shape
    if padding == "SAME":
        ho = -(-H // stride)
        wo = -(-W // stride)
        ph = max((ho - 1) * stride + kh - H, 0)
        pw = max((wo - 1) * stride + kw - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    else:
        ho = (H - kh) // stride + 1
        wo = (W - kw) // stride + 1
    # output columns pad up to the sublane tile (8) so the in-kernel
    # (bh, wo, CI) -> (bh*wo, CI) merge is tile-aligned; the extra columns
    # read zero padding and are sliced off below
    wop = -(-wo // 8) * 8
    bc = _fit_block(CO, min(block_c, CO))
    bh = _fit_block(ho, block_h)
    if folds(kh, kw, CI, stride, ho, wop):
        # counted at trace time, once per call site, like the dispatch
        # counter kernels.dispatch.<backend>.conv2d
        METRICS.counter("kernels.conv2d.folded").inc()
        xin, w = _fold(x, w, stride, ho, wop)
        kh = kw = stride = 1
        grid = (N, ho // bh, CO // bc)
        x_spec = pl.BlockSpec((1, 1, bh, wop, xin.shape[-1]),
                              lambda n, i, j: (n, 0, i, 0, 0))
        index = lambda f: lambda n, i, j: f(n, j, i)
    else:
        xin = _space_to_depth(x, stride, ho + (kh - 1) // stride,
                              wop + (kw - 1) // stride)
        grid = (N, CO // bc, ho // bh)
        x_spec = pl.BlockSpec((1,) + xin.shape[1:],
                              lambda n, j, i: (n, 0, 0, 0, 0))
        index = lambda f: f
    K = w.shape[2]
    in_specs = [x_spec, pl.BlockSpec((kh, kw, K, bc),
                                     index(lambda n, j, i: (0, 0, 0, j)))]
    operands = [xin, w]
    if bn is not None:
        for t in bn:
            in_specs.append(pl.BlockSpec((1, bc),
                                         index(lambda n, j, i: (0, j))))
            operands.append(t.astype(jnp.float32).reshape(1, CO))
    kern = functools.partial(_kernel, kh=kh, kw=kw, stride=stride, bh=bh,
                             wo=wop, act=act, has_bn=bn is not None)
    # the tap path's whole space-to-depth image of one batch element sits
    # in VMEM; a lane-sparse input (the 3-channel stem) pads C to 128 lanes
    # and can outgrow the 16 MiB default scoped limit, so the limit follows
    # the double-buffered working set (v5e has 128 MiB of VMEM)
    isz = jnp.dtype(x.dtype).itemsize
    ws = 2 * (_tiled_bytes(x_spec.block_shape[1:], isz)
              + _tiled_bytes((kh, kw, K, bc), isz)
              + _tiled_bytes((bh, wop, bc), isz)) + bh * wop * bc * 4
    y = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, wop, bc),
                               index(lambda n, j, i: (n, i, 0, j))),
        out_shape=jax.ShapeDtypeStruct((N, ho, wop, CO), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_VMEM_DEFAULT, ws + ws // 4)),
        interpret=interpret)(*operands)
    return y[:, :, :wo] if wop != wo else y


def _tiled_bytes(shape, itemsize: int) -> int:
    """Bytes of a VMEM buffer once its minor dims pad to the (8, 128) tile."""
    *lead, r, c = shape
    n = 1
    for d in lead:
        n *= d
    return n * (-(-r // 8) * 8) * (-(-c // 128) * 128) * itemsize


def _fold(x: jax.Array, w: jax.Array, s: int, ho: int, wop: int
          ) -> Tuple[jax.Array, jax.Array]:
    """The padded input's taps as one patch tensor ``(N, 1, ho, wop,
    C*kh*kw)`` with ``out[n, 0, i, j, (c*kh + dh)*kw + dw] =
    x[n, i*s + dh, j*s + dw, c]`` (zero past the edge), and the weights
    reordered to match as ``(1, 1, K, CO)``.  One XLA convolution with
    one-hot filters builds it: exact (each output is one input times 1),
    and on the TPU it reads the lane-sparse input without the 128-lane
    copies that slicing it tap by tap makes."""
    kh, kw, C, CO = w.shape
    N, H, W, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, max((ho - 1) * s + kh - H, 0)),
                    (0, max((wop - 1) * s + kw - W, 0)), (0, 0)))
    p = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (s, s), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)[:, :ho, :wop]
    return (p[:, None],
            w.transpose(2, 0, 1, 3).reshape(1, 1, C * kh * kw, CO))


def _space_to_depth(x: jax.Array, s: int, hq: int, wq: int) -> jax.Array:
    """(N, H, W, C) -> (N, s*s, hq, wq, C) with
    ``out[n, p*s + q, i, j] = x[n, i*s + p, j*s + q]`` (zero past the edge):
    a stride-s conv becomes s*s stride-1 convs over contiguous phases."""
    N, H, W, C = x.shape
    x = jnp.pad(x, ((0, 0), (0, max(hq * s - H, 0)),
                    (0, max(wq * s - W, 0)), (0, 0)))[:, :hq * s, :wq * s]
    x = x.reshape(N, hq, s, wq, s, C).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(N, s * s, hq, wq, C)
